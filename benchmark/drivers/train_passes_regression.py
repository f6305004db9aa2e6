"""Traffic driver `train_passes_regression`: back-to-back
`Workflow.train()` passes over a typed table with a NUMERIC target,
through the regression selector.

The pass is `train_passes.py`'s (`FeatureBuilder.from_dataset` ->
`transmogrify` -> `SanityChecker` -> the configuration's selector ->
`Workflow.train()`) and the window's record is `train_passes_typed.py`'s
(walls, program spans, sweep dispatches, `counters`), so every reader of
those drivers' records reads this one. What is this driver's own: the
table comes from `datagen_airlines.py` and its label is `RealNN`;
`build_selector` builds `RegressionModelSelector.with_cross_validation`
from the configuration's selector block; a pass's `counters` also carry
`boost_rounds` (over the pass's `sweep:dispatch:gbt` spans, the rounds
of each times its real pairs) and what the program sets on
`selector:evaluate` (`on_device`) and `sanity:contingency`
(`categorical_label`); the last pass keeps what its `sweep:fetch:gbt`
spans say of every boosted (configuration, fold) chain's training rows
(`boost_folds_of`), for the check; the datasets are all made before the
window opens and go round in order if the window holds more passes than
there are datasets; and `check()` holds the LAST timed pass against
`train_check_regression.py`. Faults: those of `faults_regression.py`.
A pass's model is dropped before the next pass trains, as the typed
driver does.

This file calls the older drivers' functions and classes; it puts
nothing of its own under another module's names.
"""

from __future__ import annotations

import gc
import os
import sys
import time

import datagen_airlines

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import train_passes  # noqa: E402  (the driver this one is a case of)

# span name -> {attribute: (counter, how a pass's spans combine)}
COUNTERS = {"sanity:decide": {"encoded_width": ("encoded_width", max),
                              "selected_width": ("selected_width", max)},
            "sweep:bin": {"hist_slots": ("hist_slots", max),
                          "value_columns": ("value_columns", max),
                          "hist_reads": ("hist_reads", max)},
            "pivot:encode": {"cells": ("pivot_cells", sum)},
            "selector:evaluate": {"on_device": ("evaluate_on_device", min)},
            "sanity:contingency": {
                "categorical_label": ("categorical_label", max)}}


def build_selector(spec: dict):
    """The configuration's selector block -> the program's regression
    ModelSelector."""
    import transmogrifai_tpu.models as models
    import transmogrifai_tpu.selector as sel
    if spec["problem"] != "regression" \
            or spec["validator"]["kind"] != "cross_validation":
        raise ValueError("train_passes_regression builds the regression "
                         "cross-validated selector only")
    families = [(getattr(models, f["estimator"])(**f["params"]),
                 [dict(g) for g in f["grid"]]) for f in spec["families"]]
    sp = dict(spec["splitter"])
    splitter = getattr(sel, sp.pop("kind"))(**sp)
    val = spec["validator"]
    return sel.RegressionModelSelector.with_cross_validation(
        models=families, n_folds=val["folds"], seed=val["seed"],
        validation_metric=spec["metric"], splitter=splitter)


def make_dataset(schema: dict, n_rows: int, seed: int, stream: int):
    """(program Dataset, raw columns, label) for one stream of the seed."""
    import transmogrifai_tpu.types as t
    from transmogrifai_tpu.data import Dataset
    cols, y = datagen_airlines.make_table(schema, n_rows, seed, stream)
    types = {name: getattr(t, ty)
             for name, ty in datagen_airlines.column_names(schema)}
    types[schema["label"]] = t.RealNN
    full = dict(cols)
    full[schema["label"]] = y
    return Dataset(full, types), cols, y


def train_once(ds, label_name: str, selector_spec: dict):
    """One pass, as `train_passes.train_once` with this file's selector.
    Returns (model, prediction feature, checked vector)."""
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


def counters_of(spans) -> dict:
    seen = {}
    rounds = None
    for sp in spans:
        for attr, (name, how) in COUNTERS.get(sp.name, {}).items():
            if attr in sp.attributes:
                seen.setdefault((name, how), []).append(sp.attributes[attr])
        if sp.name == "sweep:dispatch:gbt" \
                and {"rounds", "pairs"} <= set(sp.attributes):
            rounds = (rounds or 0) + int(sp.attributes["rounds"]) \
                * int(sp.attributes["pairs"])
    out = {name: how(values) for (name, how), values in seen.items()}
    if rounds is not None:
        out["boost_rounds"] = rounds
    return out


def boost_folds_of(spans) -> dict:
    """{(grid, fold): {"train_loss", "train_weight"}}: what each boosted
    chain of a pass's sweep said of its training rows on its
    `sweep:fetch:gbt` span (`grid` counts the family's configurations).
    Empty for a program that sets none."""
    out = {}
    for sp in spans:
        at = sp.attributes
        if sp.name != "sweep:fetch:gbt" or not {
                "grids", "folds", "train_loss", "train_weight"} <= set(at):
            continue
        for t, key in enumerate(zip(at["grids"], at["folds"])):
            out[key] = {"train_loss": at["train_loss"][t],
                        "train_weight": at["train_weight"][t]}
    return out


class Run(train_passes.Run):
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
            import faults_regression
            faults_regression.plant(self.fault)
        for s in range(warm):
            t0 = time.perf_counter()
            train_once(self.datasets[s][0], schema["label"],
                       self.config["selector"])
            self.say(f"[train] warm-up pass {s}: "
                     f"{time.perf_counter() - t0:.1f}s")
            self.datasets[s] = None
        self.datasets = self.datasets[warm:]
        gc.collect()

    def window(self, seconds: float, tracing) -> dict:
        from transmogrifai_tpu.obs.trace import TRACER
        from transmogrifai_tpu.parallel.sweep import SWEEP_STATS
        schema = self.config["schema"]
        warm = int(self.traffic["warmup_passes"])
        passes, failed, notes = [], 0, []
        turn = 0
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < seconds:
            # a fitted model keeps every stage's output on the device:
            # the pass before goes before the next one trains
            self.last = None
            gc.collect()
            at = turn % len(self.datasets)
            if turn == len(self.datasets):
                notes.append(f"the window held more than {turn} passes: "
                             "the datasets go round")
            ds, cols, y = self.datasets[at]
            mark = max((sp.span_id for sp in TRACER.spans()), default=0)
            d0, s0 = SWEEP_STATS.dispatches, SWEEP_STATS.dispatch_s
            t0 = time.perf_counter()
            try:
                with tracing.span("train-pass"):
                    model, pf, checked = train_once(
                        ds, schema["label"], self.config["selector"])
            except Exception as e:   # a failed pass is counted, not hidden
                failed += 1
                notes.append(f"pass on stream {warm + at} failed: "
                             f"{type(e).__name__}: {e}")
                turn += 1
                continue
            wall = time.perf_counter() - t0
            new = [sp for sp in TRACER.spans() if sp.span_id > mark]
            spans = [(sp.name, sp.duration_s) for sp in new]
            passes.append({
                "stream": warm + at, "wall_s": wall, "spans": spans,
                "sweep_dispatches": SWEEP_STATS.dispatches - d0,
                "sweep_dispatch_s": SWEEP_STATS.dispatch_s - s0,
                "counters": counters_of(new)})
            self.say(f"[train] pass on stream {warm + at}: {wall:.2f}s; "
                     + ", ".join(f"{name.split(':')[-1]} {d:.1f}"
                                 for name, d in spans if name.startswith(
                                     ("sweep:family:", "stage:fit:")))
                     + f"; {passes[-1]['counters']}")
            self.last = {"stream": warm + at, "model": model, "pf": pf,
                         "checked": checked, "cols": cols, "y": y,
                         "boost_folds": boost_folds_of(new)}
            del model, pf, checked
            turn += 1
        total = time.perf_counter() - t_open
        done = len(passes)
        return {"metrics": {self.traffic["metric"]: total / max(done, 1)},
                "attempted": done + failed, "failed": failed,
                "passes": passes, "window_s": total, "notes": notes,
                "rows": self.rows}

    def release(self):
        """Take what the check needs to the host and free the rest."""
        import train_check_regression
        if self.last is not None:
            self.last = train_check_regression.extract(self.last)
        self.datasets = []
        gc.collect()

    def check(self, window: dict) -> list:
        import train_check_regression
        if self.last is None:
            return [{"name": "passes_completed", "value": 1.0, "limit": 0.0}]
        return train_check_regression.compare(
            self.last, self.config, self.seed, control=self.control,
            say=self.say)
