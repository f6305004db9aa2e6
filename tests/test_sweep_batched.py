"""Batched sweep engine: device-metric parity + every-family coverage.

The contract under test (VERDICT r1 #1): every model family's grid×fold
block runs through the batched XLA path (`parallel/sweep.py` handlers) and
produces the same metric matrix as the eager host loop (`_sweep_generic`),
which itself matches the host evaluators used for final model metrics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import (
    BinaryClassificationEvaluator, MultiClassificationEvaluator,
    RegressionEvaluator)
from transmogrifai_tpu.evaluators.device_metrics import (
    aupr_dev, auroc_dev, binary_confusion_dev, multiclass_dev, regression_dev)
from transmogrifai_tpu.evaluators.metrics import (
    aupr_score, auroc_score, binary_metrics, multiclass_metrics,
    regression_metrics)
from transmogrifai_tpu.parallel import sweep as S
from transmogrifai_tpu.selector.validators import OpCrossValidation
from transmogrifai_tpu.stages.base import FitContext


# --------------------------------------------------------------------------- #
# device metric kernels vs host metrics                                       #
# --------------------------------------------------------------------------- #

def _masked_host(y, s, mask):
    idx = mask > 0.5
    return y[idx], s[idx]


@pytest.mark.parametrize("tied", [False, True])
def test_auroc_aupr_device_match_host(rng, tied):
    n = 400
    y = (rng.uniform(size=n) > 0.4).astype(np.float64)
    s = rng.uniform(size=n)
    if tied:
        s = np.round(s, 1)  # heavy ties
    mask = (rng.uniform(size=n) > 0.3).astype(np.float64)
    ym, sm = _masked_host(y, s, mask)
    got_roc = float(auroc_dev(jnp.asarray(y, jnp.float32),
                              jnp.asarray(s, jnp.float32),
                              jnp.asarray(mask, jnp.float32)))
    got_pr = float(aupr_dev(jnp.asarray(y, jnp.float32),
                            jnp.asarray(s, jnp.float32),
                            jnp.asarray(mask, jnp.float32)))
    assert got_roc == pytest.approx(auroc_score(ym, sm), abs=1e-5)
    assert got_pr == pytest.approx(aupr_score(ym, sm), abs=1e-5)


# The formulation `aupr_dev` / `auroc_dev` had until the tie-group ends
# were read by scan: argsort, gathers and `searchsorted`. It lives here
# only, as the oracle the scan form has to equal bit for bit.

def _auroc_searchsorted(y, scores, mask):
    wpos = mask * y
    wneg = mask * (1.0 - y)
    order = jnp.argsort(scores)
    s = scores[order]
    wp = wpos[order]
    wn = wneg[order]
    cumn = jnp.concatenate([jnp.zeros(1, s.dtype), jnp.cumsum(wn)])
    left = jnp.searchsorted(s, s, side="left")
    right = jnp.searchsorted(s, s, side="right")
    below = cumn[left]
    tied = cumn[right] - cumn[left]
    num = (wp * (below + 0.5 * tied)).sum()
    n_pos = wpos.sum()
    n_neg = wneg.sum()
    ok = (n_pos > 0) & (n_neg > 0)
    return jnp.where(ok, num / jnp.maximum(n_pos * n_neg, 1e-30), 0.0)


def _aupr_searchsorted(y, scores, mask):
    wpos = mask * y
    neg_s = -scores
    order = jnp.argsort(neg_s)
    s_asc = neg_s[order]
    wp = wpos[order]
    w = mask[order]
    cum_tp = jnp.cumsum(wp)
    cum_n = jnp.cumsum(w)
    right = jnp.searchsorted(s_asc, s_asc, side="right") - 1
    tp = cum_tp[right]
    n_at = cum_n[right]
    n_pos = wpos.sum()
    prec = jnp.where(n_at > 0, tp / jnp.maximum(n_at, 1e-30), 1.0)
    rec = tp / jnp.maximum(n_pos, 1e-30)
    r = jnp.concatenate([jnp.zeros(1, rec.dtype), rec])
    p = jnp.concatenate([jnp.ones(1, prec.dtype), prec])
    area = ((r[1:] - r[:-1]) * (p[1:] + p[:-1]) * 0.5).sum()
    return jnp.where(n_pos > 0, area, 0.0)


def _rank_case(name):
    """(y, scores, mask) float64 host arrays of one named case."""
    r = np.random.default_rng(27)
    n = {"n1": 1, "n2": 2, "n2_tied": 2, "non_pow2": 1237}.get(name, 600)
    y = (r.uniform(size=n) > 0.4).astype(np.float64)
    s = r.uniform(size=n)
    mask = (r.uniform(size=n) > 0.3).astype(np.float64)
    if name in ("n1", "n2", "n2_tied"):
        y[:] = [1.0, 0.0][:n]
        mask[:] = 1.0
    if name == "n2_tied":
        s[:] = 0.5
    elif name == "ties3":
        s = np.round(s * 2) / 2
    elif name == "ties50":
        s = np.round(s * 49) / 49
    elif name == "all_equal":
        s[:] = 0.25
    elif name == "ties_straddle_mask":
        # every tie group holds masked and unmasked rows, at its ends too
        s = np.repeat(np.linspace(0.05, 0.95, n // 6), 6)
        mask = np.tile([0.0, 1.0, 0.0, 1.0, 1.0, 0.0], n // 6)
        y = np.tile([1.0, 0.0, 0.0, 1.0, 0.0, 1.0], n // 6)
        perm = r.permutation(n)
        s, mask, y = s[perm], mask[perm], y[perm]
    elif name == "no_positive":
        mask = mask * (1.0 - y)
    elif name == "no_negative":
        mask = mask * y
    elif name == "mask_empty":
        mask[:] = 0.0
    elif name == "zero_and_negative_scores":
        s = np.round(s * 4 - 2) * 0.5          # -1.0 .. 1.0 in halves
        s[::7] = -0.0                          # one tie group with +0.0
    return y, s, mask


RANK_METRICS = {  # name -> (device kernel, searchsorted oracle, host metric)
    "aupr": (aupr_dev, _aupr_searchsorted, aupr_score),
    "auroc": (auroc_dev, _auroc_searchsorted, auroc_score)}

RANK_CASES = ["no_ties", "ties3", "ties50", "all_equal", "ties_straddle_mask",
              "no_positive", "no_negative", "mask_empty", "n1", "n2",
              "n2_tied", "non_pow2", "zero_and_negative_scores"]


@pytest.mark.parametrize("metric", sorted(RANK_METRICS))
@pytest.mark.parametrize("case", RANK_CASES)
def test_rank_metric_by_scan_equals_host_and_searchsorted_oracle(case, metric):
    dev, oracle, host = RANK_METRICS[metric]
    y, s, mask = _rank_case(case)
    # the device sees float32 scores: the host groups the same values
    ym, sm = _masked_host(y, s.astype(np.float32), mask)
    args = [jnp.asarray(a, jnp.float32) for a in (y, s, mask)]
    got = np.asarray(jax.jit(dev)(*args))
    assert got.tobytes() == np.asarray(jax.jit(oracle)(*args)).tobytes()
    assert float(got) == pytest.approx(host(ym, sm), abs=1e-5)


@pytest.mark.parametrize("metric", sorted(RANK_METRICS))
@pytest.mark.parametrize("case", ["no_ties", "ties3", "ties_straddle_mask",
                                  "non_pow2"])
def test_rank_metric_by_scan_under_the_sweeps_vmap(case, metric):
    # `_gbt_score_program`: one label vector, a batch of score vectors,
    # each under its own fold mask
    dev, oracle, host = RANK_METRICS[metric]
    y, s, mask = _rank_case(case)
    r = np.random.default_rng(3)
    S_ = np.stack([s, np.round(s, 1), s[::-1], r.permutation(s)])
    M_ = np.stack([mask, 1.0 - mask, mask, np.ones_like(mask)])
    args = (jnp.asarray(y, jnp.float32), jnp.asarray(S_, jnp.float32),
            jnp.asarray(M_, jnp.float32))
    got = np.asarray(jax.jit(jax.vmap(dev, in_axes=(None, 0, 0)))(*args))
    want = np.asarray(jax.jit(jax.vmap(oracle, in_axes=(None, 0, 0)))(*args))
    assert got.tobytes() == want.tobytes()
    for k in range(len(S_)):
        ym, sm = _masked_host(y, S_[k].astype(np.float32), M_[k])
        assert float(got[k]) == pytest.approx(host(ym, sm), abs=1e-5)


def test_binary_confusion_device_match_host(rng):
    n = 300
    y = (rng.uniform(size=n) > 0.5).astype(np.float64)
    s = rng.uniform(size=n)
    mask = (rng.uniform(size=n) > 0.25).astype(np.float64)
    ym, sm = _masked_host(y, s, mask)
    host = binary_metrics(ym, sm).to_json()
    dev = binary_confusion_dev(jnp.asarray(y, jnp.float32),
                               jnp.asarray(s, jnp.float32),
                               jnp.asarray(mask, jnp.float32))
    for k in ("Precision", "Recall", "F1", "Error", "TP", "TN", "FP", "FN"):
        assert float(dev[k]) == pytest.approx(host[k], abs=1e-5), k


def test_multiclass_device_match_host(rng):
    n, k = 500, 4
    y = rng.integers(k, size=n).astype(np.float64)
    p = rng.integers(k, size=n).astype(np.float64)
    mask = (rng.uniform(size=n) > 0.2).astype(np.float64)
    idx = mask > 0.5
    host = multiclass_metrics(y[idx], p[idx], n_classes=k).to_json()
    dev = multiclass_dev(jnp.asarray(y, jnp.float32),
                         jnp.asarray(p, jnp.float32),
                         jnp.asarray(mask, jnp.float32), k)
    for key in ("Precision", "Recall", "F1", "Error"):
        assert float(dev[key]) == pytest.approx(host[key], abs=1e-5), key


def test_regression_device_match_host(rng):
    n = 400
    y = rng.normal(size=n)
    p = y + rng.normal(size=n) * 0.3
    mask = (rng.uniform(size=n) > 0.3).astype(np.float64)
    idx = mask > 0.5
    host = regression_metrics(y[idx], p[idx]).to_json()
    dev = regression_dev(jnp.asarray(y, jnp.float32),
                         jnp.asarray(p, jnp.float32),
                         jnp.asarray(mask, jnp.float32))
    for key in ("RMSE", "MSE", "MAE", "R2"):
        assert float(dev[key]) == pytest.approx(host[key], abs=2e-4), key


# --------------------------------------------------------------------------- #
# full-family batched-vs-eager sweep parity                                   #
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def clf_data():
    rng = np.random.default_rng(3)
    n, d = 300, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-X @ w))).astype(np.float32)
    folds = OpCrossValidation(n_folds=3, seed=1).splits(y)
    return jnp.asarray(X), jnp.asarray(y), folds


@pytest.fixture(scope="module")
def reg_data():
    rng = np.random.default_rng(4)
    n, d = 300, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    y = (X @ w + rng.normal(size=n) * 0.3).astype(np.float32)
    folds = OpCrossValidation(n_folds=3, seed=1).splits(y)
    return jnp.asarray(X), jnp.asarray(y), folds


def _assert_parity(est, grids, X, y, folds, ev, tol=5e-3):
    ctx = FitContext(n_rows=int(X.shape[0]), seed=7)
    assert S._dispatch(est) is not None, \
        f"{type(est).__name__} has no batched sweep handler"
    batched = np.asarray(S.run_sweep(est, grids, X, y, folds, ev, ctx))
    eager = np.asarray(S._sweep_generic(est, grids, X, y, folds, ev, ctx))
    assert batched.shape == (len(grids), len(folds))
    np.testing.assert_allclose(batched, eager, atol=tol)


def test_sweep_logistic(clf_data):
    from transmogrifai_tpu.models import OpLogisticRegression
    X, y, folds = clf_data
    _assert_parity(OpLogisticRegression(max_iter=15),
                   [{"reg_param": r} for r in (0.001, 0.1)],
                   X, y, folds, BinaryClassificationEvaluator())


@pytest.mark.slow
def test_sweep_forest_classifier_mixed_depths(clf_data):
    from transmogrifai_tpu.models import OpRandomForestClassifier
    X, y, folds = clf_data
    _assert_parity(
        OpRandomForestClassifier(n_trees=4),
        [{"max_depth": d, "min_child_weight": m}
         for d in (2, 4) for m in (1.0, 10.0)],
        X, y, folds, BinaryClassificationEvaluator())


def test_sweep_xgb_classifier(clf_data):
    from transmogrifai_tpu.models import OpXGBoostClassifier
    X, y, folds = clf_data
    _assert_parity(OpXGBoostClassifier(n_estimators=8),
                   [{"eta": e, "max_depth": d} for e in (0.1, 0.3)
                    for d in (2, 4)],
                   X, y, folds, BinaryClassificationEvaluator())


@pytest.mark.slow
def test_sweep_svc_and_nb_and_mlp(clf_data):
    from transmogrifai_tpu.models import OpLinearSVC, OpNaiveBayes
    from transmogrifai_tpu.models.mlp import OpMultilayerPerceptronClassifier
    X, y, folds = clf_data
    ev = BinaryClassificationEvaluator()
    _assert_parity(OpLinearSVC(max_iter=15),
                   [{"reg_param": r} for r in (0.01, 0.1)], X, y, folds, ev)
    _assert_parity(OpNaiveBayes(), [{"smoothing": s} for s in (0.5, 1.0)],
                   jnp.abs(X), y, folds, ev)
    _assert_parity(OpMultilayerPerceptronClassifier(max_iter=20),
                   [{"learning_rate": l} for l in (0.01, 0.05)],
                   X, y, folds, ev)


@pytest.mark.slow
def test_sweep_multiclass_forest():
    from transmogrifai_tpu.models import OpRandomForestClassifier
    rng = np.random.default_rng(5)
    n, d, k = 300, 5, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    centers = rng.normal(size=(k, d)) * 2
    y = np.argmin(((X[:, None] - centers[None]) ** 2).sum(-1), axis=1)
    y = y.astype(np.float32)
    folds = OpCrossValidation(n_folds=2, seed=1).splits(y)
    _assert_parity(OpRandomForestClassifier(n_trees=4, n_classes=k),
                   [{"max_depth": d2} for d2 in (2, 4)],
                   jnp.asarray(X), jnp.asarray(y), folds,
                   MultiClassificationEvaluator())


@pytest.mark.slow
def test_sweep_regression_families(reg_data):
    from transmogrifai_tpu.models import (
        OpGBTRegressor, OpLinearRegression, OpRandomForestRegressor)
    from transmogrifai_tpu.models.glm import OpGeneralizedLinearRegression
    X, y, folds = reg_data
    ev = RegressionEvaluator()
    _assert_parity(OpLinearRegression(),
                   [{"reg_param": r} for r in (0.0, 0.1)], X, y, folds, ev)
    _assert_parity(OpRandomForestRegressor(n_trees=4),
                   [{"max_depth": d} for d in (2, 4)], X, y, folds, ev)
    _assert_parity(OpGBTRegressor(n_estimators=8),
                   [{"max_depth": d} for d in (2, 4)], X, y, folds, ev)
    _assert_parity(OpGeneralizedLinearRegression(max_iter=15),
                   [{"reg_param": r} for r in (0.0, 0.01)], X, y, folds, ev)


@pytest.mark.slow
def test_sweep_decision_tree_matches_deterministic_fit(clf_data):
    """DT sweeps must use the deterministic (no-bootstrap) tree the refit
    produces — metrics must match the eager fit_arrays path exactly."""
    from transmogrifai_tpu.models import OpDecisionTreeClassifier
    X, y, folds = clf_data
    _assert_parity(OpDecisionTreeClassifier(),
                   [{"max_depth": d} for d in (2, 4)],
                   X, y, folds, BinaryClassificationEvaluator(), tol=1e-5)


@pytest.mark.slow
def test_padded_depth_equals_exact_depth(clf_data):
    """A {2, 5} depth grid (padded to 5, traced active_depth) must match
    fitting each depth at its exact static shape."""
    from transmogrifai_tpu.models import OpRandomForestClassifier
    X, y, folds = clf_data
    ctx = FitContext(n_rows=int(X.shape[0]), seed=7)
    ev = BinaryClassificationEvaluator()
    grids = [{"max_depth": 2}, {"max_depth": 5}]
    mixed = np.asarray(S.run_sweep(OpRandomForestClassifier(n_trees=4),
                                   grids, X, y, folds, ev, ctx))
    for i, g in enumerate(grids):
        solo = np.asarray(S.run_sweep(OpRandomForestClassifier(n_trees=4),
                                      [g], X, y, folds, ev, ctx))
        np.testing.assert_allclose(mixed[i], solo[0], atol=1e-5)


def test_lambda_evaluator_uses_batched_fits_with_host_metrics(rng):
    """A LambdaEvaluator has no device kernel, but the sweep must still run
    the batched fit+predict program (HostMetricFallback), matching the fully
    eager host loop."""
    from transmogrifai_tpu.evaluators.evaluators import LambdaEvaluator
    from transmogrifai_tpu.evaluators.metrics import auroc_score
    from transmogrifai_tpu.models import OpLogisticRegression

    n, d = 200, 5
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y_np = (rng.uniform(size=n) > 0.5).astype(np.float64)
    y = jnp.asarray(y_np.astype(np.float32))
    folds = OpCrossValidation(n_folds=2, seed=0).splits(y_np)

    def custom(label, pred):
        yv = np.asarray(label.data["value"], dtype=np.float64)
        s = np.asarray(pred.data["probability"])[:, 1]
        return auroc_score(yv, s)

    ev = LambdaEvaluator("customAuROC", custom)
    est = OpLogisticRegression(max_iter=10)
    grids = [{"reg_param": r} for r in (0.001, 0.1)]
    ctx = FitContext(n_rows=n)

    got = S.run_sweep(est, grids, X, y, folds, ev, ctx)
    want = S._sweep_generic(est, grids, X, y, folds, ev, ctx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
