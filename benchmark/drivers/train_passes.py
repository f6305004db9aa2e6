"""Traffic driver `train_passes`: back-to-back `Workflow.train()` passes.

A pass is what a data scientist runs: a raw `Dataset` in, the feature
graph (transmogrify -> SanityChecker -> ModelSelector) built over it,
`Workflow.train()`, a fitted `WorkflowModel` out. Every pass trains on a
dataset the process has not trained on; all have the cell's shape. The
selector, its grid and the rows come from the configuration file, the
number of datasets and warm-up passes from the traffic file.

`check()` holds what the LAST timed pass produced against the plain
references in `benchmark/reference/` (see `train_check.py`).
"""

from __future__ import annotations

import gc
import time

import datagen


def _sizes(config: dict, rehearsal: bool) -> dict:
    return (config["rehearsal"] if rehearsal else config)["rows"]


def build_selector(spec: dict):
    """The configuration's selector block -> the program's ModelSelector."""
    import transmogrifai_tpu.models as models
    import transmogrifai_tpu.selector as sel
    families = [(getattr(models, f["estimator"])(**f["params"]),
                 [dict(g) for g in f["grid"]]) for f in spec["families"]]
    if spec["problem"] != "binary" \
            or spec["validator"]["kind"] != "cross_validation":
        # a cell that needs another selector brings it with its own
        # driver file; nothing here is kept for cells that do not exist
        raise ValueError("train_passes builds the binary cross-validated "
                         "selector only")
    sp = spec["splitter"]
    splitter = getattr(sel, sp["kind"])(
        reserve_test_fraction=sp["reserve_test_fraction"], seed=sp["seed"])
    val = spec["validator"]
    return sel.BinaryClassificationModelSelector.with_cross_validation(
        models=families, n_folds=val["folds"], seed=val["seed"],
        validation_metric=spec["metric"], splitter=splitter)


def make_dataset(schema: dict, n_rows: int, seed: int, stream: int):
    """(program Dataset, raw columns, label) for one stream of the seed."""
    import transmogrifai_tpu.types as t
    from transmogrifai_tpu.data import Dataset
    cols, y = datagen.make_table(schema, n_rows, seed, stream)
    types = {name: getattr(t, ty)
             for name, ty in datagen.column_names(schema)}
    types[schema["label"]] = t.Integral
    full = dict(cols)
    full[schema["label"]] = y
    return Dataset(full, types), cols, y


def train_once(ds, label_name: str, selector_spec: dict):
    """One pass. Returns (model, prediction feature, checked vector)."""
    from transmogrifai_tpu.automl import transmogrify
    from transmogrifai_tpu.automl.sanity_checker import SanityChecker
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.workflow import Workflow
    preds, label = FeatureBuilder.from_dataset(ds, response=label_name)
    checked = SanityChecker().set_input(
        label, transmogrify(preds)).get_output()
    pf = build_selector(selector_spec).set_input(label, checked).get_output()
    model = Workflow().set_result_features(pf, label) \
        .set_input_dataset(ds).train()
    return model, pf, checked


class Run:
    def __init__(self, cell, config, traffic, seed, rehearsal, fault,
                 control, say):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.rehearsal = seed, rehearsal
        self.fault, self.control, self.say = fault, control, say
        self.rows = int(_sizes(config, rehearsal)[traffic["rows_key"]])
        self.datasets = []
        self.last = None

    # -- set-up ------------------------------------------------------- #
    def setup(self):
        schema = self.config["schema"]
        n_data = int(self.traffic["datasets"])
        warm = int(self.traffic["warmup_passes"])
        t0 = time.perf_counter()
        self.datasets = [make_dataset(schema, self.rows, self.seed, s)
                         for s in range(warm + n_data)]
        self.say(f"[train] {len(self.datasets)} datasets of {self.rows} rows"
                 f" in {time.perf_counter() - t0:.1f}s")
        if self.fault:
            import faults
            faults.plant_train(self.fault)
        for s in range(warm):
            t0 = time.perf_counter()
            ds, _, _ = self.datasets[s]
            train_once(ds, schema["label"], self.config["selector"])
            self.say(f"[train] warm-up pass {s}: "
                     f"{time.perf_counter() - t0:.1f}s")
            self.datasets[s] = None
        gc.collect()

    # -- the window --------------------------------------------------- #
    def window(self, seconds: float, tracing) -> dict:
        from transmogrifai_tpu.obs.trace import TRACER
        from transmogrifai_tpu.parallel.sweep import SWEEP_STATS
        schema = self.config["schema"]
        warm = int(self.traffic["warmup_passes"])
        passes, failed, notes = [], 0, []
        stream = warm
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < seconds:
            if stream >= len(self.datasets):
                # a window longer than the traffic file foresaw: make the
                # next dataset now, inside the window, and say so
                notes.append(f"dataset {stream} generated inside the window")
                self.datasets.append(make_dataset(
                    schema, self.rows, self.seed, stream))
            ds, cols, y = self.datasets[stream]
            mark = max((sp.span_id for sp in TRACER.spans()), default=0)
            d0, s0 = SWEEP_STATS.dispatches, SWEEP_STATS.dispatch_s
            t0 = time.perf_counter()
            try:
                with tracing.span("train-pass"):
                    model, pf, checked = train_once(
                        ds, schema["label"], self.config["selector"])
            except Exception as e:   # a failed pass is counted, not hidden
                failed += 1
                notes.append(f"pass on stream {stream} failed: "
                             f"{type(e).__name__}: {e}")
                stream += 1
                continue
            wall = time.perf_counter() - t0
            spans = [(sp.name, sp.duration_s) for sp in TRACER.spans()
                     if sp.span_id > mark]
            passes.append({
                "stream": stream, "wall_s": wall, "spans": spans,
                "sweep_dispatches": SWEEP_STATS.dispatches - d0,
                "sweep_dispatch_s": SWEEP_STATS.dispatch_s - s0})
            self.say(f"[train] pass on stream {stream}: {wall:.2f}s; " + ", ".join(
                f"{name.split(':')[-1]} {d:.1f}" for name, d in spans
                if name.startswith(("sweep:family:", "stage:fit:"))))
            if self.last is not None:
                self.datasets[self.last["stream"]] = None
            self.last = {"stream": stream, "model": model, "pf": pf,
                         "checked": checked, "cols": cols, "y": y}
            stream += 1
        total = time.perf_counter() - t_open
        done = len(passes)
        # no pass completed: the window's wall stands in (never NaN in
        # the result line) and `check()` reports the run as not correct
        return {"metrics": {self.traffic["metric"]: total / max(done, 1)},
                "attempted": done + failed, "failed": failed,
                "passes": passes, "window_s": total, "notes": notes,
                "rows": self.rows}

    # -- after the window --------------------------------------------- #
    def release(self):
        """Take what the check needs to the host and free the rest."""
        import train_check
        if self.last is not None:
            self.last = train_check.extract(self.last)
        self.datasets = []
        gc.collect()

    def check(self, window: dict) -> list:
        import train_check
        if self.last is None:
            return [{"name": "passes_completed", "value": 1.0, "limit": 0.0}]
        return train_check.compare(
            self.last, self.config, self.seed, control=self.control,
            say=self.say)
