"""A typed, categorical table through the normal path, against the plain
references the benchmark's typed cell uses (`benchmark/reference/
pivot.py`, `sanity.py`, loaded by path: they import nothing of the
program), and the tree histograms' per-column-bins layout against the
uniform layout it has to grow the same trees as.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu import types as T
from transmogrifai_tpu.automl import transmogrify
from transmogrifai_tpu.automl.sanity_checker import SanityChecker
from transmogrifai_tpu.data import Dataset
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.models import (
    OpGBTClassifier, OpRandomForestClassifier, OpXGBoostClassifier)
from transmogrifai_tpu.models import trees
from transmogrifai_tpu.ops.categorical import OneHotModel
from transmogrifai_tpu.stages.base import FitContext
from transmogrifai_tpu.workflow import Workflow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference(name):
    path = os.path.join(ROOT, "benchmark", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location(f"typed_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref_pivot, ref_sanity = _reference("pivot"), _reference("sanity")

N = 600


def _typed_table():
    """13 Integral + 26 PickList columns and a label. I2 and C1 never
    miss; C9 has 3 levels; C2 has a level seen exactly 10 times (min
    support: in) and one seen 9 times (folded into OTHER); C3 has more
    than 20 levels over the support; C4 is one level throughout (its
    level column is constant: the checker's variance floor); C5 repeats
    C6 (its columns duplicate C6's: the checker's feature correlation);
    I13 is the label over again (label correlation)."""
    rng = np.random.default_rng(5)
    y = (rng.uniform(size=N) < 0.3).astype(np.float64)
    cols, types = {}, {}
    for j in range(1, 14):
        v = np.floor(np.exp(rng.normal(1.0 + 0.2 * j, 1.0, N)))
        if j != 2:
            v[rng.uniform(size=N) < 0.05 * j] = np.nan
        cols[f"I{j}"], types[f"I{j}"] = v, T.Integral
    cols["I13"] = y * 3.0 + 1.0
    for j in range(1, 27):
        card = {9: 3, 3: 25}.get(j, 6 + j)
        p = np.arange(1, card + 1, dtype=np.float64) ** (
            0.0 if j == 3 else -1.1)
        lvl = rng.choice(card, size=N, p=p / p.sum())
        text = np.asarray([f"c{j}_{k:02d}" for k in range(card)],
                          object)[lvl]
        if j != 1:
            text[rng.uniform(size=N) < 0.02 * (j % 7)] = None
        cols[f"C{j}"], types[f"C{j}"] = text, T.PickList
    c2 = np.asarray(["c2_main"] * N, object)
    c2[:10], c2[10:19], c2[19:60] = "c2_ten", "c2_nine", "c2_more"
    cols["C2"] = c2[rng.permutation(N)]
    cols["C4"] = np.asarray(["only"] * N, object)
    cols["C5"] = cols["C6"].copy()
    # a level that follows the label closely, but not past Cramér's V 0.95
    c7 = np.where(y > 0, "pos", "neg").astype(object)
    flip = rng.uniform(size=N) < 0.1
    c7[flip] = np.where(y[flip] > 0, "neg", "pos")
    cols["C7"] = c7
    cols["label"], types["label"] = y, T.Integral
    return cols, types, y


@pytest.fixture(scope="module")
def typed_pass():
    """(program's encoded matrix, vocabularies, kept columns, Cramér's V
    by group; the reference's encode() and check() results)."""
    cols, types, y = _typed_table()
    ds = Dataset(dict(cols), types)
    preds, label = FeatureBuilder.from_dataset(ds, response="label")
    vector = transmogrify(preds)
    checked = SanityChecker().set_input(label, vector).get_output()
    model = Workflow().set_result_features(checked, label) \
        .set_input_dataset(ds).train()
    fitted = model.fitted[checked.origin_stage.uid]
    vocabs = {f.name: list(v) for stage in model.fitted.values()
              if isinstance(stage, OneHotModel)
              for f, v in zip(stage.input_features, stage.vocabs)}
    program = {
        "encoded": np.asarray(model.train_columns[vector.uid].device_value()),
        "vocabs": vocabs, "kept": list(fitted.indices),
        "v": {g["group"]: g["cramersV"]
              for g in fitted.summary["categoricalStats"]}}
    raw = {k: v for k, v in cols.items() if k != "label"}
    names_types = [(k, types[k].__name__) for k in raw]
    X, labels, ref_vocabs, groups = ref_pivot.encode(raw, names_types)
    kept, v = ref_sanity.check(X, y, groups)
    return program, {"X": X, "labels": labels, "vocabs": ref_vocabs,
                     "groups": groups, "kept": kept, "v": v}


def test_encoded_matrix_is_the_references(typed_pass):
    program, ref = typed_pass
    assert program["encoded"].shape == ref["X"].shape
    np.testing.assert_array_equal(program["encoded"], ref["X"])
    # Integral block first, in input order: value, null indicator
    assert ref["labels"][:4] == ["I1", "I1:null", "I2", "I2:null"]


def test_vocabularies_are_the_references(typed_pass):
    program, ref = typed_pass
    assert program["vocabs"] == ref["vocabs"]
    assert len(ref["vocabs"]["C9"]) == 3
    assert len(ref["vocabs"]["C3"]) == 20           # top K
    # exactly at min support stays, one under it folds into OTHER
    assert "c2_ten" in ref["vocabs"]["C2"]
    assert "c2_nine" not in ref["vocabs"]["C2"]
    at = ref["labels"].index("C2:OTHER")
    assert ref["X"][:, at].sum() == 9


def test_kept_columns_are_the_references(typed_pass):
    program, ref = typed_pass
    assert program["kept"] == ref["kept"]
    dropped = {ref["labels"][j] for j in range(len(ref["labels"]))
               if j not in set(ref["kept"])}
    # every rule fires: variance (a never-missing column's null
    # indicator, a one-level column), label correlation, duplicates
    assert {"I2:null", "C1:null", "C4=only", "C4:OTHER", "I13"} <= dropped
    assert all(lab in dropped for lab in ref["labels"]
               if lab.startswith("C6"))             # the later duplicate
    assert not any(lab in dropped for lab in ref["labels"]
                   if lab.startswith("C5=") and "00" in lab)


def test_cramers_v_is_the_references(typed_pass):
    program, ref = typed_pass
    for name, v in ref["v"].items():
        got = program["v"].get(f"{name}_{name}", program["v"].get(name))
        assert got == pytest.approx(v, abs=1e-12), name
    assert 0.5 < ref["v"]["C7"] < 0.95
    assert ref["v"]["I2"] == 0.0                    # one row: no test


# --------------------------------------------------------------------- #
# the histogram layout                                                  #
# --------------------------------------------------------------------- #

def _matrix(indicators: str, n=500, d=7, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    which = {"none": [], "some": [1, 2, 5], "all": list(range(d))}[indicators]
    share = np.asarray([0.5, 0.02, 0.3, 0.9, 0.1, 0.01, 0.6])
    X[:, which] = rng.uniform(size=(n, len(which))) < share[which]
    y = (X[:, 0] + 2 * X[:, 2] - X[:, 1] * X[:, 3] + X[:, 5]
         + rng.normal(0, 0.3, n) > 0.4).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y), which


def _fit_forest(Xb, y, layout):
    Y = jax.nn.one_hot(y.astype(jnp.int32), 2)
    return trees.fit_forest(Xb, Y, jnp.ones_like(y), 3, 5, 16, 2, 11,
                            min_gain=jnp.float32(0.001), layout=layout)


def _fit_gbt(Xb, y, layout):
    return trees.fit_gbt(Xb, y, jnp.ones_like(y), 4, 4, 16, 0.3, 1.0,
                         layout=layout)[0]


def _fit_xgboost(Xb, y, layout):
    return trees.fit_gbt_hosted(
        Xb, y, jnp.ones_like(y), 4, 5, 16, 0.1, 1.0, gamma=0.05,
        alpha=0.01, subsample=0.8, colsample=0.7, seed=5,
        min_child_weight=2.0, rounds_per_dispatch=2, layout=layout)[0]


ESTIMATORS = {
    "forest": (_fit_forest, lambda: OpRandomForestClassifier(
        n_trees=3, max_depth=5, max_bins=16, min_info_gain=0.001)),
    "gbt": (_fit_gbt, lambda: OpGBTClassifier(
        n_estimators=4, max_depth=4, max_bins=16, learning_rate=0.3)),
    "xgboost": (_fit_xgboost, None),
}


@pytest.mark.parametrize("indicators", ["none", "some", "all"])
@pytest.mark.parametrize("family", sorted(ESTIMATORS))
def test_per_column_bins_grow_the_uniform_layouts_trees(family, indicators):
    X, y, which = _matrix(indicators)
    fit, make_est = ESTIMATORS[family]
    indicator = trees.indicator_columns(X)
    assert list(np.flatnonzero(indicator)) == which
    edges = trees.quantile_bin_edges(X, 16, indicator)
    Xb = trees.bin_features(X, jnp.asarray(edges))
    layout = trees.hist_layout(indicator)
    assert (layout is None) == (not which)
    if which:
        assert list(np.asarray(layout["ind"])) == which
        assert trees.hist_slots(X.shape[1], 16, layout) \
            == 16 * (X.shape[1] - len(which)) + 2 * len(which)
    uniform, blocks = fit(Xb, y, None), fit(Xb, y, layout)
    for key in ("feat", "bin", "leaf"):
        np.testing.assert_array_equal(np.asarray(uniform[key]),
                                      np.asarray(blocks[key]), err_msg=key)
    assert (np.asarray(uniform["bin"]) < 16).any()      # it did split
    if make_est is not None:
        # and the estimator's own fit takes the layout: the same trees
        model = make_est().fit_arrays(X, y, jnp.ones_like(y),
                                      FitContext(n_rows=len(y), seed=11))
        ref = fit(Xb, y, None) if family == "forest" else uniform
        np.testing.assert_array_equal(model.edges, edges)
        if family == "forest":
            np.testing.assert_array_equal(model.trees["feat"],
                                          np.asarray(ref["feat"]))


def test_a_rare_indicator_is_splittable():
    """A level set in under 1/max_bins of the rows: a quantile edge put
    it in one bin with its absence; the 0.5 edge does not."""
    rng = np.random.default_rng(0)
    n = 2000
    rare = (rng.uniform(size=n) < 0.01).astype(np.float32)
    X = jnp.asarray(np.stack([rng.normal(size=n).astype(np.float32), rare], 1))
    y = jnp.asarray(rare)
    model = OpGBTClassifier(n_estimators=1, max_depth=1, max_bins=32) \
        .fit_arrays(X, y, jnp.ones(n), FitContext(n_rows=n, seed=0))
    assert model.trees["feat"][0, 0, 0] == 1
    assert model.trees["bin"][0, 0, 0] == 0
    assert sorted(np.unique(np.asarray(model._binned(X))[:, 1])) == [0, 31]


def test_quantile_edges_half_for_indicators_todays_for_the_rest():
    X, _, which = _matrix("some")
    X_np = np.asarray(X)
    edges = trees.quantile_bin_edges(X_np, 8)
    qs = np.linspace(0, 1, 9)[1:-1]
    today = np.quantile(X_np.astype(np.float64), qs, axis=0).T \
        .astype(np.float32)
    rest = [j for j in range(X_np.shape[1]) if j not in which]
    np.testing.assert_array_equal(edges[rest], today[rest])
    assert (edges[which] == 0.5).all()
    # a device matrix gives the same edges (only the wide columns cross)
    np.testing.assert_array_equal(trees.quantile_bin_edges(X, 8), edges)
    # no indicator column: today's edges throughout
    Xn = np.asarray(_matrix("none")[0])
    np.testing.assert_array_equal(
        trees.quantile_bin_edges(Xn, 8),
        np.quantile(Xn.astype(np.float64), qs, axis=0).T.astype(np.float32))


def test_pair_width_widens_when_columns_are_indicators():
    n, d, bins = 300_000, 528, 32
    indicator = np.arange(d) >= 13
    layout = trees.hist_layout(indicator)
    uniform = trees.hist_slots(d, bins, None)
    typed = trees.hist_slots(d, bins, layout)
    assert (uniform, typed) == (528 * 32, 13 * 32 + 515 * 2)
    def width(slots):           # depth 10, one learner: memory binds
        return trees.dispatch_plan(n, slots, 10, 1, n_pairs=64)[0]
    assert width(typed) > width(uniform)
    assert width(uniform) == 1
