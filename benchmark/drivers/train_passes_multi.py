"""Traffic driver `train_passes_multi`: `train_passes_typed` over a typed
table whose label has MANY classes, through the multiclass selector.

A case of `train_passes_typed` as that is of `train_passes`: the same
pass (`train_passes.train_once`), the same window record, so every
reader of those drivers' records reads this one. What differs: the
table comes from `datagen_kdd.py` (Integral, Real, Binary and PickList
columns, no holes, K labels at the source's shares); `build_selector`
builds `MultiClassificationModelSelector.with_cross_validation` from the
configuration's selector block and hands it the block's `n_classes`, so
the number of classes is the configuration's and not a table's largest
label (a table in which the rarest label does not fall runs the programs
the warm-up pass compiled); a pass's `counters` also carry what the
program sets on `sweep:bin` (`value_columns`, `hist_reads`) and on
`cutter:prepare` (`labels_seen`, `labels_kept`, `rows_dropped`); and
`check()` holds the LAST timed pass against `train_check_multi.py`.
Faults: those of `faults_multi.py`, beside the two files the typed
driver knows.

The two drivers above look their dataset maker, selector builder and
counter table up by module name at call time; this driver puts its own
under those names when a `Run` is made (one cell a process).
"""

from __future__ import annotations

import os
import sys

import datagen_kdd

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import train_passes  # noqa: E402
import train_passes_typed  # noqa: E402  (the driver this one is a case of)

COUNTERS = {**train_passes_typed.COUNTERS,
            "sweep:bin": {"hist_slots": ("hist_slots", max),
                          "value_columns": ("value_columns", max),
                          "hist_reads": ("hist_reads", max)},
            "cutter:prepare": {"labels_seen": ("labels_seen", max),
                               "labels_kept": ("labels_kept", max),
                               "rows_dropped": ("rows_dropped", sum)}}


def build_selector(spec: dict):
    """The configuration's selector block -> the program's multiclass
    ModelSelector."""
    import transmogrifai_tpu.models as models
    import transmogrifai_tpu.selector as sel
    if spec["problem"] != "multiclass" \
            or spec["validator"]["kind"] != "cross_validation":
        raise ValueError("train_passes_multi builds the multiclass "
                         "cross-validated selector only")
    families = [(getattr(models, f["estimator"])(**f["params"]),
                 [dict(g) for g in f["grid"]]) for f in spec["families"]]
    sp = dict(spec["splitter"])
    splitter = getattr(sel, sp.pop("kind"))(**sp)
    val = spec["validator"]
    return sel.MultiClassificationModelSelector.with_cross_validation(
        models=families, n_folds=val["folds"], seed=val["seed"],
        validation_metric=spec["metric"], splitter=splitter,
        n_classes=int(spec["n_classes"]))


def make_dataset(schema: dict, n_rows: int, seed: int, stream: int):
    """(program Dataset, raw columns, label) for one stream of the seed."""
    import transmogrifai_tpu.types as t
    from transmogrifai_tpu.data import Dataset
    cols, y = datagen_kdd.make_table(schema, n_rows, seed, stream)
    types = {name: getattr(t, ty)
             for name, ty in datagen_kdd.column_names(schema)}
    types[schema["label"]] = t.Integral
    full = dict(cols)
    full[schema["label"]] = y
    return Dataset(full, types), cols, y


class Run(train_passes_typed.Run):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        train_passes.build_selector = build_selector
        train_passes_typed.make_dataset = make_dataset
        train_passes_typed.COUNTERS = COUNTERS

    def setup(self):
        import faults_multi
        if self.fault in faults_multi.MULTI:
            faults_multi.plant(self.fault)
            fault, self.fault = self.fault, None
            try:
                super().setup()
            finally:
                self.fault = fault
        else:
            super().setup()

    def release(self):
        """Take what the check needs to the host and free the rest."""
        import gc

        import train_check_multi
        if self.last is not None:
            self.last = train_check_multi.extract(self.last)
        self.datasets = []
        gc.collect()

    def check(self, window: dict) -> list:
        import train_check_multi
        if self.last is None:
            return [{"name": "passes_completed", "value": 1.0, "limit": 0.0}]
        return train_check_multi.compare(
            self.last, self.config, self.seed, control=self.control,
            say=self.say)
