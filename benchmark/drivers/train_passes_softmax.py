"""Traffic driver `train_passes_softmax`: `train_passes_typed` over a
table of numeric columns whose label has HUNDREDS of classes, through
the multiclass selector with a softmax boosted family.

A case of `train_passes_typed` as `train_passes_multi` is: the same pass
(`train_passes.train_once`), the same window record, so every reader of
those drivers' records reads this one. What differs: the table comes
from `datagen_dionis.py` (Real columns, no holes, K classes at
near-uniform shares); the selector is `train_passes_multi.build_selector`
(the multiclass cross-validated selector, handed the configuration's
`n_classes`); a pass's `counters` also carry `boost_rounds` (over the
pass's `sweep:dispatch:gbt` spans, the rounds of each times its real
pairs, as the regression driver counts them); after the window the last
pass keeps what its `sweep:fetch:gbt` spans say of every boosted
(configuration, fold) chain's training rows
(`train_passes_regression.boost_folds_of`, over the pass's spans from
`obs.trace.train_passes()`); and `check()` holds the LAST timed pass
against `train_check_softmax.py`. Faults: those of `faults_softmax.py`.

The typed driver looks its dataset maker, selector builder, counter
table and counter function up by module name at call time; this driver
puts its own under those names when a `Run` is made (one cell a
process).
"""

from __future__ import annotations

import gc
import os
import sys

import datagen_dionis

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import train_passes  # noqa: E402
import train_passes_multi  # noqa: E402
import train_passes_regression  # noqa: E402
import train_passes_typed  # noqa: E402  (the driver this one is a case of)

COUNTERS = train_passes_multi.COUNTERS
_typed_counters_of = train_passes_typed.counters_of


def counters_of(spans) -> dict:
    out = _typed_counters_of(spans)
    rounds = [int(sp.attributes["rounds"]) * int(sp.attributes["pairs"])
              for sp in spans if sp.name == "sweep:dispatch:gbt"
              and {"rounds", "pairs"} <= set(sp.attributes)]
    if rounds:
        out["boost_rounds"] = sum(rounds)
    return out


def make_dataset(schema: dict, n_rows: int, seed: int, stream: int):
    """(program Dataset, raw columns, label) for one stream of the seed."""
    import transmogrifai_tpu.types as t
    from transmogrifai_tpu.data import Dataset
    cols, y = datagen_dionis.make_table(schema, n_rows, seed, stream)
    types = {name: getattr(t, ty)
             for name, ty in datagen_dionis.column_names(schema)}
    types[schema["label"]] = t.Integral
    full = dict(cols)
    full[schema["label"]] = y
    return Dataset(full, types), cols, y


def last_pass_spans() -> list:
    """The spans of the process's last finished training pass."""
    from transmogrifai_tpu.obs.trace import train_passes
    passes = train_passes()
    return passes[-1]["spans"] if passes else []


class Run(train_passes_typed.Run):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        train_passes.build_selector = train_passes_multi.build_selector
        train_passes_typed.make_dataset = make_dataset
        train_passes_typed.COUNTERS = COUNTERS
        train_passes_typed.counters_of = counters_of

    def setup(self):
        import faults_softmax
        if self.fault in faults_softmax.SOFTMAX:
            faults_softmax.plant(self.fault)
            fault, self.fault = self.fault, None
            try:
                super().setup()
            finally:
                self.fault = fault
        else:
            super().setup()

    def release(self):
        """Take what the check needs to the host and free the rest."""
        import train_check_softmax
        if self.last is not None:
            self.last["boost_folds"] = train_passes_regression.boost_folds_of(
                last_pass_spans())
            self.last = train_check_softmax.extract(self.last)
        self.datasets = []
        gc.collect()

    def check(self, window: dict) -> list:
        import train_check_softmax
        if self.last is None:
            return [{"name": "passes_completed", "value": 1.0, "limit": 0.0}]
        return train_check_softmax.compare(
            self.last, self.config, self.seed, control=self.control,
            say=self.say)
