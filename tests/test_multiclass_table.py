"""A many-label typed table through the multiclass selector, against the
plain references the benchmark's many-label cell uses (`benchmark/
reference/` and `benchmark/train_check_multi.py`, which import nothing
of the program but what they hold it to), and the pieces that path
brings: a classifier's composite class histograms against the
per-column form, the confusion matrix as a product, the label cutter,
one owner for the number of classes, `dispatch_plan` with value columns.
"""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.analysis import retrace
from transmogrifai_tpu.evaluators import MultiClassificationEvaluator
from transmogrifai_tpu.evaluators import device_metrics as dm
from transmogrifai_tpu.models import (
    OpLogisticRegression, OpRandomForestClassifier)
from transmogrifai_tpu.models import trees
from transmogrifai_tpu.models.base import n_classes_of
from transmogrifai_tpu.parallel import sweep as S
from transmogrifai_tpu.selector.splitters import DataCutter
from transmogrifai_tpu.selector.validators import OpCrossValidation
from transmogrifai_tpu.stages.base import FitContext
from transmogrifai_tpu.utils.compile_cache import COMPILE_STATS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's many-label modules (its directory on the path for
    this module's tests only)."""
    sys.path[:0] = [BENCH, os.path.join(BENCH, "drivers")]
    try:
        import train_check_multi
        import train_passes
        import train_passes_multi
        from reference import cutter, multiclass
        yield {"check": train_check_multi, "passes": train_passes,
               "driver": train_passes_multi, "cutter": cutter,
               "multiclass": multiclass}
    finally:
        sys.path.remove(BENCH)
        sys.path.remove(os.path.join(BENCH, "drivers"))


def _config(k: int) -> dict:
    """The many-label configuration cut to K labels and a size the CPU
    trains in seconds: the first K labels of the source, the last of them
    so rare that it does not fall in the table."""
    with open(os.path.join(BENCH, "configs", "kddcup99.json")) as fh:
        config = json.load(fh)
    config = copy.deepcopy(config)
    schema, spec = config["schema"], config["selector"]
    schema["classes"] = spec["n_classes"] = k
    schema["labels"] = schema["labels"][:k]
    counts = schema["label_counts"][:k]
    counts[-1] = 1              # 1 in a few million: does not fall
    schema["label_counts"] = counts
    schema["latent"]["large_labels"] = min(3, k - 1)
    spec["families"][0]["params"]["max_iter"] = 10
    spec["families"][1]["grid"] = [dict(
        spec["families"][1]["grid"][0], max_depth=5)]
    return config


@pytest.mark.parametrize("k", [3, 7, 23])
def test_whole_pass_against_the_references(bench, k):
    config = _config(k)
    schema = config["schema"]
    driver = bench["driver"]
    bench["passes"].build_selector = driver.build_selector
    ds, cols, y = driver.make_dataset(schema, 1500, seed=31, stream=1)
    assert y.max() < k - 1      # the top label did not fall
    model, pf, checked = bench["passes"].train_once(
        ds, schema["label"], config["selector"])
    last = bench["check"].extract(
        {"stream": 1, "model": model, "pf": pf, "checked": checked,
         "cols": cols, "y": y})
    compared = {c["name"]: c for c in bench["check"].compare(
        last, config, 31, say=lambda _: None)}
    for name in ("encode_err", "levels_mismatch", "kept_mismatch",
                 "holdout_rows_diff", "labels_kept_mismatch",
                 "winner_mismatch", "confusion_diff", "edges_err"):
        assert compared[name]["value"] == 0, (name, compared[name])
    for name, c in compared.items():
        assert c["value"] <= c["limit"], (name, c)
    assert {"cv_metric_gap", "tree_cv_metric_gap", "holdout_metric_gap",
            "cramers_v_gap"} <= set(compared)
    # the fits were K wide although the label's largest value is K - 2
    fitted = model.fitted[pf.origin_stage.uid]
    # the summary keeps the (K, K) tables its train and holdout metrics
    # came from: the check above read the holdout's
    for part in (fitted.summary.train_metrics,
                 fitted.summary.holdout_metrics):
        assert np.asarray(part["Confusion"]).shape == (k, k)
    width = (fitted.trees["leaf"].shape[-1] if hasattr(fitted, "trees")
             else fitted.W.shape[1])
    assert width == k


# --------------------------------------------------------------------- #
# a classifier's class histograms                                       #
# --------------------------------------------------------------------- #

def _hist_inputs(k, block, n=400, d=5, n_nodes=4, seed=0):
    rng = np.random.default_rng(seed)
    nb = 2 if block == "ind" else 8
    Xb = rng.integers(0, nb, size=(n, d)).astype(np.int8)
    B = trees.bins_onehot(jnp.asarray(Xb), nb)
    node = jnp.asarray(rng.integers(0, n_nodes, n), jnp.int32)
    cls = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    # a bootstrap's whole-number weights under a 0/1 fold mask
    H = jnp.asarray(rng.poisson(1.0, n) * (rng.random(n) < 0.7),
                    jnp.float32)
    return B, node, cls, H, n_nodes


@pytest.mark.parametrize("precision", ["bf16", "f32"])
@pytest.mark.parametrize("block", ["wide", "ind"])
@pytest.mark.parametrize("k", [2, 3, 7, 23])
def test_composite_class_histograms_are_the_per_column_ones(
        k, block, precision, monkeypatch):
    monkeypatch.setattr(trees, "HIST_PRECISION", precision)
    B, node, cls, H, n_nodes = _hist_inputs(k, block)
    G = jax.nn.one_hot(cls, k) * H[:, None]
    hg_col, hh_col = trees._histograms(B, node, G, H, n_nodes, block)
    hg, hh = trees._class_histograms(B, node, cls, H, n_nodes, k, block)
    assert hg.shape == hg_col.shape == (k, n_nodes) + B.shape[1:]
    # whole-number weights: both forms count exactly, in either precision
    np.testing.assert_array_equal(np.asarray(hg), np.asarray(hg_col))
    np.testing.assert_array_equal(np.asarray(hh), np.asarray(hh_col))
    # and against a plain count
    want = np.zeros(hg.shape, np.float64)
    Bn = np.asarray(B, np.float64)
    for r in range(Bn.shape[0]):
        want[int(cls[r]), int(node[r])] += float(H[r]) * Bn[r]
    np.testing.assert_array_equal(np.asarray(hg, np.float64), want)


@pytest.mark.parametrize("k", [2, 3, 23])
def test_a_classifiers_tree_is_the_tree_of_its_one_hot_targets(k):
    """`grow_tree` of labels (the composite form, leaves included)
    against `grow_tree` of the (n, K) targets the callers used to build;
    a label outside [0, K) counts nowhere."""
    rng = np.random.default_rng(3)
    n, d, nb = 500, 6, 8
    Xb = jnp.asarray(rng.integers(0, nb, size=(n, d)), jnp.int8)
    y = rng.integers(0, k, n)
    y[Xb[:, 0] > 4] = 0
    w = jnp.asarray(rng.poisson(1.0, n), jnp.float32)
    layout = trees.hist_layout(np.arange(d) >= 4)
    by_label = trees.grow_tree(Xb, jnp.asarray(y, jnp.int32), w, 4, nb,
                               reg_lambda=1e-6, layout=layout, n_classes=k)
    by_target = trees.grow_tree(
        Xb, jax.nn.one_hot(jnp.asarray(y), k) * w[:, None], w, 4, nb,
        reg_lambda=1e-6, layout=layout)
    for key in ("feat", "bin", "leaf"):
        np.testing.assert_array_equal(np.asarray(by_label[key]),
                                      np.asarray(by_target[key]))
    assert by_label["leaf"].shape == (16, k)
    stray = jnp.asarray(np.where(np.arange(n) < 5, k, y), jnp.int32)
    gone = trees.grow_tree(Xb, stray, w, 4, nb, reg_lambda=1e-6,
                           layout=layout, n_classes=k)
    kept = trees.grow_tree(Xb, jnp.asarray(y, jnp.int32),
                           w * (np.arange(n) >= 5), 4, nb, reg_lambda=1e-6,
                           layout=layout, n_classes=k)
    np.testing.assert_array_equal(np.asarray(gone["leaf"]),
                                  np.asarray(kept["leaf"]))


@pytest.mark.parametrize("n_classes,value_columns,want",
                         [(23, 1, 1), (2, 1, 1), (0, 1, 2), (0, 3, 4)])
def test_hist_reads(n_classes, value_columns, want):
    assert trees.hist_reads(n_classes, value_columns) == want


# --------------------------------------------------------------------- #
# the confusion matrix                                                  #
# --------------------------------------------------------------------- #

CONFUSION_CASES = {
    # id -> (K, labels present, predictions' range, mask)
    "all-rows": (5, 5, (0, 5), "ones"),
    "fold-mask": (5, 5, (0, 5), "fold"),
    "absent-classes": (23, 4, (0, 6), "fold"),
    "out-of-range-predictions": (7, 7, (-2, 10), "fold"),
    "empty-mask": (3, 3, (0, 3), "zeros"),
    "one-class": (3, 1, (0, 1), "ones"),
}


@pytest.mark.parametrize("case", list(CONFUSION_CASES))
def test_multiclass_dev_against_a_numpy_confusion_matrix(case, bench):
    k, present, (lo, hi), mask_kind = CONFUSION_CASES[case]
    rng = np.random.default_rng(9)
    n = 700
    y = rng.integers(0, present, n).astype(np.float32)
    pred = rng.integers(lo, hi, n).astype(np.float32)
    mask = {"ones": np.ones(n), "zeros": np.zeros(n),
            "fold": (rng.random(n) < 0.4)}[mask_kind].astype(np.float32)
    conf = np.asarray(dm.confusion_dev(jnp.asarray(y), jnp.asarray(pred),
                                       jnp.asarray(mask), k))
    on = mask > 0
    want = bench["multiclass"].confusion(y[on], pred[on], k)
    np.testing.assert_array_equal(conf, want)
    got = {name: float(v) for name, v in dm.multiclass_dev(
        jnp.asarray(y), jnp.asarray(pred), jnp.asarray(mask), k).items()}
    ref = bench["multiclass"].weighted_metrics(want)
    if not on.any():
        ref["Error"] = 1.0      # the program's: 1 - 0 / max(0, 1)
    for name in ("Precision", "Recall", "F1", "Error"):
        assert got[name] == pytest.approx(ref[name], abs=2e-6), name
    # no scatter and no loop over the rows in the program
    text = jax.jit(lambda a, b, c: dm.confusion_dev(a, b, c, k)).lower(
        jnp.asarray(y), jnp.asarray(pred), jnp.asarray(mask)).as_text()
    assert "dot_general" in text and "scatter" not in text


def test_evaluate_device_is_the_host_evaluators_metrics():
    from transmogrifai_tpu.evaluators.metrics import multiclass_metrics
    rng = np.random.default_rng(2)
    y = rng.integers(0, 6, 900)
    pred = np.where(rng.random(900) < 0.7, y, rng.integers(0, 6, 900))
    host = multiclass_metrics(y, pred).to_json()
    dev = MultiClassificationEvaluator().evaluate_device(
        jnp.asarray(y, jnp.float32),
        {"prediction": jnp.asarray(pred, jnp.float32)}, 8).to_json()
    for name in ("Precision", "Recall", "F1", "Error"):
        assert dev[name] == pytest.approx(host[name], abs=1e-12)


# --------------------------------------------------------------------- #
# the label cutter                                                      #
# --------------------------------------------------------------------- #

CUTS = {
    "defaults": (100, 0.0), "top-3": (3, 0.0), "top-1": (1, 0.0),
    "share-5pct": (100, 0.05), "top-4-share-20pct": (4, 0.2),
    "share-over-all": (100, 0.9),
}


@pytest.mark.parametrize("case", list(CUTS))
def test_data_cutter_against_its_reference(case, bench):
    max_labels, min_fraction = CUTS[case]
    rng = np.random.default_rng(4)
    # shares 40, 25, 15, 10, 4, 3, 3 (a tie), and two singletons
    y = np.repeat(np.arange(9.0), [400, 250, 150, 100, 40, 30, 30, 1, 1])
    y = y[rng.permutation(len(y))]
    train_idx = np.sort(rng.permutation(len(y))[:900])
    cutter = DataCutter(max_label_categories=max_labels,
                        min_label_fraction=min_fraction)
    kept_idx, details = cutter.prepare(y, train_idx)
    keep, labels = bench["cutter"].cut(y[train_idx], max_labels,
                                       min_fraction)
    np.testing.assert_array_equal(kept_idx, train_idx[keep])
    assert details["labels_kept"] == labels
    assert set(details["labels_dropped"]) == set(
        np.unique(y[train_idx])) - set(labels)


# --------------------------------------------------------------------- #
# one owner for the number of classes                                   #
# --------------------------------------------------------------------- #

def test_n_classes_of_takes_the_selectors_and_refuses_a_second_opinion():
    y = jnp.asarray([0.0, 1.0, 3.0])
    held = FitContext(n_rows=3, n_classes=9)
    assert n_classes_of(OpLogisticRegression(), y, held) == 9
    assert n_classes_of(OpLogisticRegression(n_classes=9), y, held) == 9
    with pytest.raises(ValueError, match="one place"):
        n_classes_of(OpLogisticRegression(n_classes=6), y, held)
    # a bare call: the estimator's own, else the label's largest value
    assert n_classes_of(OpLogisticRegression(n_classes=6), y, None) == 6
    assert n_classes_of(OpLogisticRegression(), y, None) == 4
    assert n_classes_of(OpLogisticRegression(), np.zeros(3), None) == 2


def test_a_scheduler_lane_fits_under_the_selectors_number_of_classes():
    from transmogrifai_tpu.parallel.scheduler import GridScheduler
    lane = GridScheduler(mesh=None)._worker_ctx(
        0, FitContext(n_rows=10, seed=3, n_classes=23))
    assert (lane.n_rows, lane.seed, lane.n_classes) == (10, 3, 23)


@pytest.mark.parametrize("stated", ["context", "estimator", "nobody"])
def test_a_sweep_takes_its_metric_kernel_from_the_stated_classes(stated):
    """The (K, K) table of a fold is counted on the device when somebody
    stated K; a bare call that states nothing scores on the host, to the
    same metric."""
    X, y, folds = _label_table(3, True)
    est = OpLogisticRegression(
        max_iter=5, n_classes=5 if stated == "estimator" else None)
    ctx = FitContext(n_rows=240, seed=7,
                     n_classes=5 if stated == "context" else None)
    seen = []
    real = S.make_device_metric

    def spy(evaluator, n_classes=None):
        seen.append(n_classes)
        return real(evaluator, n_classes=n_classes)
    S.make_device_metric = spy
    try:
        got = S.run_sweep(est, FAMILIES["logistic"][1], X, y, folds,
                          MultiClassificationEvaluator(), ctx)
    finally:
        S.make_device_metric = real
    assert seen == [None if stated == "nobody" else 5]
    want = S.run_sweep(OpLogisticRegression(max_iter=5),
                       FAMILIES["logistic"][1], X, y, folds,
                       MultiClassificationEvaluator(),
                       FitContext(n_rows=240, seed=7, n_classes=5))
    np.testing.assert_allclose(got, want, atol=1e-6)


def _label_table(seed, top_falls, k=5, n=240, d=6):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, k if top_falls else k - 1, n).astype(np.float32)
    folds = OpCrossValidation(n_folds=2, seed=1).splits(y)
    return jnp.asarray(X), jnp.asarray(y), folds


FAMILIES = {
    "logistic": (lambda: OpLogisticRegression(max_iter=5),
                 [{"reg_param": 0.01, "elastic_net_param": 0.1}]),
    "forest": (lambda: OpRandomForestClassifier(n_trees=2, max_bins=8),
               [{"max_depth": 2}]),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_tables_whose_top_labels_differ_share_every_sweep_program(family):
    make_est, grids = FAMILIES[family]
    for held in (S._block_program,):
        held.cache_clear()

    def sweep(seed, top_falls, n_classes):
        X, y, folds = _label_table(seed, top_falls)
        return S.run_sweep(make_est(), grids, X, y, folds,
                           MultiClassificationEvaluator(),
                           FitContext(n_rows=240, seed=7,
                                      n_classes=n_classes))

    sweep(1, True, 5)
    traces, compiles = retrace.MONITOR.snapshot(), dict(COMPILE_STATS)
    sweep(2, False, 5)          # label 4 does not fall: K is still 5
    assert not {label: n for label, n in
                retrace.MONITOR.delta(traces).items()
                if label.startswith("sweep:")}
    assert dict(COMPILE_STATS) == compiles
    # the guard can see what it guards against: K read off each table
    sweep(2, False, None)
    assert {label for label in retrace.MONITOR.delta(traces)
            if label.startswith("sweep:")}


def test_selector_reads_the_number_of_classes_once_and_states_it():
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.data.columns import Column
    from transmogrifai_tpu.obs.trace import TRACER
    from transmogrifai_tpu.selector import (
        MultiClassificationModelSelector)
    rng = np.random.default_rng(6)
    n = 300
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = rng.integers(0, 3, n).astype(np.float64)    # label 3 never falls
    selector = MultiClassificationModelSelector.with_cross_validation(
        models=[(OpLogisticRegression(max_iter=5),
                 [{"reg_param": 0.01, "elastic_net_param": 0.1}])],
        n_folds=2, n_classes=4)
    label = Column(T.RealNN, {"value": y, "mask": np.ones(n, bool)})
    vec = Column(T.OPVector, jnp.asarray(X))
    ctx = FitContext(n_rows=n, seed=3)
    mark = max((sp.span_id for sp in TRACER.spans()), default=0)
    model = selector.fit_model([label, vec], ctx)
    assert ctx.n_classes == 4 and model.W.shape == (4, 4)
    spans = {sp.name: sp for sp in TRACER.spans() if sp.span_id > mark}
    assert spans["selector:sweep"].attributes["classes"] == 4
    assert spans["cutter:prepare"].attributes == {
        "labels_seen": 3, "labels_kept": 3, "rows_dropped": 0}


# --------------------------------------------------------------------- #
# what goes into one dispatch, with value columns                       #
# --------------------------------------------------------------------- #

# id -> (n_rows, slots, pad_depth, learners, n_pairs, pad_tail, value
# columns), (width, rounds)
PLANS = {
    # the many-label cell's forest block: one pair, its one tree
    "kddcup99-forest": ((1_800_000, 1_138, 12, 1, 3, False, 23), (1, 1)),
    # a table the work budget lets 2 pairs of at one column...
    "one-column": ((100_000, 1_760, 10, 50, 64, False, 1), (2, 50)),
    # ...holds one pair and a tenth of its trees a dispatch at 23
    "work-binds-at-23": ((100_000, 1_760, 10, 50, 64, False, 23), (1, 5)),
    # a small table: neither budget binds, at any K
    "small-2": ((20_000, 256, 6, 4, 16, False, 2), (16, 4)),
    "small-23": ((20_000, 256, 6, 4, 16, False, 23), (16, 4)),
    "tiny-wide-23": ((5_000_000, 4_096, 12, 1, 8, False, 23), (1, 1)),
}


@pytest.mark.parametrize("case", list(PLANS))
def test_dispatch_plan_with_value_columns(case):
    args, want = PLANS[case]
    assert trees.dispatch_plan(*args[:6], value_columns=args[6]) == want
    width, rounds = want
    assert 1 <= width <= args[4] and 1 <= rounds <= args[3]


def test_dispatch_plan_never_widens_with_more_value_columns():
    for k in (1, 2, 7, 23, 100):
        w1, r1 = trees.dispatch_plan(200_000, 900, 10, 20, 32,
                                     value_columns=k)
        w2, r2 = trees.dispatch_plan(200_000, 900, 10, 20, 32,
                                     value_columns=k + 1)
        assert w2 <= w1 and r2 <= r1
    # one value column is the plan every caller had
    assert trees.dispatch_plan(200_000, 900, 10, 20, 32) \
        == trees.dispatch_plan(200_000, 900, 10, 20, 32, value_columns=1)
