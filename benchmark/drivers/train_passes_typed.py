"""Traffic driver `train_passes_typed`: `train_passes` over a TYPED table.

The same pass (`train_passes.train_once`: `FeatureBuilder.from_dataset`
-> `transmogrify` -> `SanityChecker` -> the configuration's selector ->
`Workflow.train()`), the same selector builder and the same window
record, so every reader of `train_passes`'s record reads this one. What
differs: the table comes from `datagen_typed.py` (integer columns with
NaN holes, text columns of str and None, typed as the schema says),
each pass's record also carries `counters` (the attributes the program
sets on its `sanity:decide`, `sweep:bin` and `pivot:encode` spans:
`encoded_width`, `selected_width`, `hist_slots`, `pivot_cells`; a
program without those spans gives an empty dict), and `check()` holds
the LAST timed pass against `train_check_typed.py`. Faults: those of
`faults.py` under the fits, those of `faults_typed.py` under the
feature stages. Unlike `train_passes`, a pass's model is dropped before
the next pass trains (a fitted model holds every stage's output on the
device, 6.5 GB at 1,000,000 rows of 548 columns), so the chip holds one
pass at a time.
"""

from __future__ import annotations

import gc
import os
import sys
import time

import datagen_typed

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import train_passes  # noqa: E402  (the driver this one is a case of)

# span name -> {attribute: (counter, how a pass's spans of that name
# combine)}: a width is a width however many spans report it (the tree
# families may each bin the matrix), cells add up
COUNTERS = {"sanity:decide": {"encoded_width": ("encoded_width", max),
                              "selected_width": ("selected_width", max)},
            "sweep:bin": {"hist_slots": ("hist_slots", max)},
            "pivot:encode": {"cells": ("pivot_cells", sum)}}


def make_dataset(schema: dict, n_rows: int, seed: int, stream: int):
    """(program Dataset, raw columns, label) for one stream of the seed."""
    import transmogrifai_tpu.types as t
    from transmogrifai_tpu.data import Dataset
    cols, y = datagen_typed.make_table(schema, n_rows, seed, stream)
    types = {name: getattr(t, ty)
             for name, ty in datagen_typed.column_names(schema)}
    types[schema["label"]] = t.Integral
    full = dict(cols)
    full[schema["label"]] = y
    return Dataset(full, types), cols, y


def counters_of(spans) -> dict:
    seen = {}
    for sp in spans:
        for attr, (name, how) in COUNTERS.get(sp.name, {}).items():
            if attr in sp.attributes:
                seen.setdefault((name, how), []).append(sp.attributes[attr])
    return {name: how(values) for (name, how), values in seen.items()}


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
            import faults
            import faults_typed
            if self.fault in faults_typed.TYPED:
                faults_typed.plant(self.fault)
            else:
                faults.plant_train(self.fault)
        for s in range(warm):
            t0 = time.perf_counter()
            ds, _, _ = self.datasets[s]
            train_passes.train_once(ds, schema["label"],
                                    self.config["selector"])
            self.say(f"[train] warm-up pass {s}: "
                     f"{time.perf_counter() - t0:.1f}s")
            self.datasets[s] = None
        gc.collect()

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
                notes.append(f"dataset {stream} generated inside the window")
                self.datasets.append(make_dataset(
                    schema, self.rows, self.seed, stream))
            if self.last is not None:
                # a fitted model keeps every stage's output on the device
                # (6.5 GB at this width): the pass before goes before the
                # next one trains, so only the LAST pass is left to check
                self.datasets[self.last["stream"]] = None
                self.last = None
                gc.collect()
            ds, cols, y = self.datasets[stream]
            mark = max((sp.span_id for sp in TRACER.spans()), default=0)
            d0, s0 = SWEEP_STATS.dispatches, SWEEP_STATS.dispatch_s
            t0 = time.perf_counter()
            try:
                with tracing.span("train-pass"):
                    model, pf, checked = train_passes.train_once(
                        ds, schema["label"], self.config["selector"])
            except Exception as e:   # a failed pass is counted, not hidden
                failed += 1
                notes.append(f"pass on stream {stream} failed: "
                             f"{type(e).__name__}: {e}")
                stream += 1
                continue
            wall = time.perf_counter() - t0
            new = [sp for sp in TRACER.spans() if sp.span_id > mark]
            spans = [(sp.name, sp.duration_s) for sp in new]
            passes.append({
                "stream": stream, "wall_s": wall, "spans": spans,
                "sweep_dispatches": SWEEP_STATS.dispatches - d0,
                "sweep_dispatch_s": SWEEP_STATS.dispatch_s - s0,
                "counters": counters_of(new)})
            self.say(f"[train] pass on stream {stream}: {wall:.2f}s; "
                     + ", ".join(f"{name.split(':')[-1]} {d:.1f}"
                                 for name, d in spans if name.startswith(
                                     ("sweep:family:", "stage:fit:")))
                     + f"; {passes[-1]['counters']}")
            self.last = {"stream": stream, "model": model, "pf": pf,
                         "checked": checked, "cols": cols, "y": y}
            del model, pf, checked
            stream += 1
        total = time.perf_counter() - t_open
        done = len(passes)
        return {"metrics": {self.traffic["metric"]: total / max(done, 1)},
                "attempted": done + failed, "failed": failed,
                "passes": passes, "window_s": total, "notes": notes,
                "rows": self.rows}

    def release(self):
        """Take what the check needs to the host and free the rest."""
        import train_check_typed
        if self.last is not None:
            self.last = train_check_typed.extract(self.last)
        self.datasets = []
        gc.collect()

    def check(self, window: dict) -> list:
        import train_check_typed
        if self.last is None:
            return [{"name": "passes_completed", "value": 1.0, "limit": 0.0}]
        return train_check_typed.compare(
            self.last, self.config, self.seed, control=self.control,
            say=self.say)
