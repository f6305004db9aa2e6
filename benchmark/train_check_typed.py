"""What decides `correct` for a training pass over a TYPED table (text
and integer columns with holes): the feature stages against their own
references, then everything `train_check.py` compares, on the matrix the
checker kept.

- `encode_err`: widest |program - reference| over the matrix
  `transmogrify()` made (`reference/pivot.py`), and over the matrix the
  checker kept (as `train_check.compare` reads it);
- `levels_mismatch`: pivoted columns whose vocabulary (levels and their
  order) differs from the reference's;
- `kept_mismatch`: columns the checker kept and `reference/sanity.py`
  drops, or the other way round;
- `cramers_v_gap`: widest |program - reference| Cramér's V over the
  pivoted columns' groups;
- `holdout_rows_diff`, `edges_err`, `winner_mismatch`, `cv_metric_gap`,
  `tree_cv_metric_gap`, `split_gain_gap`, `leaf_gap`, `weights_gap`,
  `holdout_metric_gap`: `train_check.compare`, called as it is, with
  the reference's kept matrix standing as a table of that many real
  columns (its encoder then returns the matrix itself). The reference
  trees bin every column into the uniform `max_bins` slots, so these
  numbers also hold the program's per-column-bins histograms to the
  uniform layout's trees.

Two things `train_check.py` takes from `reference/trees.py` by name are
set here for the call: the edge rule (`reference/bins.py`: 0.5 for a
0/1 column; plain quantiles put a level rarer than 1/max_bins in one
bin with its absence) and the histogram product's row block (sized for
28 columns there; at 528 columns x 32 bins one block's one-hot operand
would be 8.9 GB).
"""

from __future__ import annotations

import contextlib

import numpy as np

import datagen_typed
import train_check
from reference import bins as ref_bins
from reference import pivot as ref_pivot
from reference import sanity as ref_sanity
from reference import trees as ref_trees

REFERENCE_ROW_BLOCK = 1 << 13


def extract(last: dict) -> dict:
    """`train_check.extract` plus what the feature stages produced: the
    encoded matrix, the pivot vocabularies, the checker's kept columns
    and its Cramér's V by group."""
    from transmogrifai_tpu.ops.categorical import OneHotModel
    model, checked = last["model"], last["checked"]
    out = train_check.extract(last)
    checker = checked.origin_stage
    vector = checker.input_features[1]
    out["encoded"] = np.asarray(
        model.train_columns[vector.uid].device_value(), np.float32)
    fitted = model.fitted[checker.uid]
    out["kept"] = [int(i) for i in fitted.indices]
    out["cramers_v"] = {g["group"]: float(g["cramersV"])
                        for g in fitted.summary["categoricalStats"]}
    out["vocabs"] = {}
    for stage in model.fitted.values():
        if isinstance(stage, OneHotModel):
            for f, vocab in zip(stage.input_features, stage.vocabs):
                out["vocabs"][f.name] = list(vocab)
    return out


@contextlib.contextmanager
def _typed_reference():
    plain = ref_trees.quantile_edges, ref_trees.BLOCK
    ref_trees.quantile_edges = ref_bins.typed_edges
    ref_trees.BLOCK = REFERENCE_ROW_BLOCK
    try:
        yield
    finally:
        ref_trees.quantile_edges, ref_trees.BLOCK = plain


def compare(last: dict, config: dict, seed: int, control=None,
            say=print) -> list:
    limits = config["limits"]["train"]
    schema = config["schema"]
    names_types = datagen_typed.column_names(schema)
    X_ref, _, vocabs, groups = ref_pivot.encode(last["cols"], names_types)
    y = np.asarray(last["y"], np.float64)
    numbers = {}
    enc = last["encoded"]
    numbers["encode_err"] = float(np.abs(enc - X_ref).max()) \
        if enc.shape == X_ref.shape else float("inf")
    numbers["levels_mismatch"] = float(sum(
        last["vocabs"].get(name) != vocab for name, vocab in vocabs.items()))
    kept_ref, v_ref = ref_sanity.check(X_ref, y, groups)
    numbers["kept_mismatch"] = float(len(set(kept_ref) ^ set(last["kept"])))
    # the program names a pivot's group `<parent>_<grouping>`
    pivoted = [name for name, ty in names_types
               if ty in ref_pivot.PIVOT_TYPES]
    numbers["cramers_v_gap"] = max(
        abs(v_ref[name] - last["cramers_v"].get(f"{name}_{name}", np.inf))
        for name in pivoted)
    say(f"[check] encoded {enc.shape[1]} columns, reference "
        f"{X_ref.shape[1]}; kept {len(last['kept'])}, reference "
        f"{len(kept_ref)}; largest Cramér's V "
        f"{max(v_ref[name] for name in pivoted):.4f}")
    out = [{"name": name, "value": value if np.isfinite(value) else 1e30,
            "limit": float(limits[name])} for name, value in numbers.items()]
    if numbers["kept_mismatch"]:
        return out          # the fits saw another matrix: nothing to hold
    X_kept = X_ref[:, kept_ref]
    del X_ref, enc
    width = X_kept.shape[1]
    as_reals = dict(config, schema={
        "classes": schema["classes"],
        "columns": [{"prefix": "kept", "count": width, "type": "Real"}]})
    kept_last = dict(last, cols={f"kept{j}": X_kept[:, j]
                                 for j in range(width)})
    with _typed_reference():
        rest = train_check.compare(kept_last, as_reals, seed,
                                   control=control, say=say)
    for c in rest:
        if c["name"] == "encode_err":
            out[0]["value"] = max(out[0]["value"], c["value"])
        else:
            out.append(c)
    return out
