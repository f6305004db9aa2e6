"""Softmax boosting (`multi:softprob`) on the boosted family's shared
chain, against the plain reference the benchmark's `dionis` cell uses
(`benchmark/reference/softmax.py`, which imports nothing of the
program): K trees a round from the multinomial gradients, the sweep's
round-chunked loop against the mesh path's single program, early
stopping on the validation rows' multiclass log-loss, a chain's own
cross-entropy (`gbt_train_summary`), and the dispatch plan's count of K.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import MultiClassificationEvaluator
from transmogrifai_tpu.models import OpXGBoostClassifier
from transmogrifai_tpu.models import trees
from transmogrifai_tpu.parallel import sweep as S
from transmogrifai_tpu.parallel.mesh import make_mesh, sweep_sharding
from transmogrifai_tpu.selector.validators import OpCrossValidation
from transmogrifai_tpu.stages.base import FitContext

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

N_BINS, DEPTH, ROUNDS, ETA = 16, 4, 3, 0.3


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference modules (its directory on the path for
    this module's tests only)."""
    sys.path.insert(0, BENCH)
    try:
        from reference import softmax
        from reference import trees as ref_trees
        yield {"softmax": softmax, "trees": ref_trees}
    finally:
        sys.path.remove(BENCH)


def _table(k: int, n: int = 1500, d: int = 5, seed: int = 3):
    """(X, y): K classes, each with its own centre in d columns."""
    rng = np.random.default_rng(seed)
    centres = rng.normal(size=(k, d)) * 1.5
    y = rng.integers(k, size=n)
    y[:k] = np.arange(k)                     # every class present
    X = (centres[y] + rng.normal(size=(n, d))).astype(np.float32)
    return X, y.astype(np.float32)


def _binned(X):
    edges = trees.quantile_bin_edges(jnp.asarray(X), N_BINS)
    return trees.bin_features(jnp.asarray(X), jnp.asarray(edges))


@pytest.mark.parametrize("k", [7, 37])
def test_chain_against_the_reference(ref, k):
    """3 rounds at depth 4: every split of every class's tree equal, the
    leaves and the margins within float32 summation order."""
    X, y = _table(k)
    n = len(y)
    w = np.ones(n, np.float32)
    Xb = _binned(X)
    Xb_ref = ref["trees"].bin_matrix(
        X, ref["trees"].quantile_edges(X, N_BINS))
    np.testing.assert_array_equal(np.asarray(Xb), np.asarray(Xb_ref))
    got, margin = trees.fit_gbt(
        Xb, jnp.asarray(y), jnp.asarray(w), ROUNDS, DEPTH, N_BINS,
        jnp.float32(ETA), jnp.float32(1.0), "softmax",
        min_child_weight=1.0, n_classes=k)
    want, ref_margin = ref["softmax"].boost(
        Xb_ref, y, w, k, ROUNDS, DEPTH, N_BINS, ETA, lam=1.0, mcw=1.0,
        quant="bf16")
    assert np.asarray(got["feat"]).shape == (ROUNDS, k, DEPTH, 2 ** DEPTH)
    split = want["bin"] < N_BINS
    np.testing.assert_array_equal(np.asarray(got["bin"]), want["bin"])
    np.testing.assert_array_equal(np.asarray(got["feat"])[split],
                                  want["feat"][split])
    # leaves: float32 sums in another order, 1e-5 of the largest leaf
    leaf = np.asarray(got["leaf"])[..., 0]
    assert np.abs(leaf - want["leaf"]).max() \
        <= 1e-5 * np.abs(want["leaf"]).max()
    np.testing.assert_allclose(np.asarray(margin), np.asarray(ref_margin),
                               atol=1e-5)
    # the chain learned: the margin's argmax beats chance several-fold
    assert (np.asarray(margin).argmax(1) == y).mean() > 3.0 / k


def test_gbt_train_summary_is_the_chains_cross_entropy(ref):
    k = 7
    X, y = _table(k, n=600)
    rng = np.random.default_rng(5)
    w = (rng.uniform(size=len(y)) < 0.7).astype(np.float32)
    margin = rng.normal(size=(len(y), k)).astype(np.float32)
    said = trees.gbt_train_summary(jnp.asarray(margin), jnp.asarray(y),
                                   jnp.asarray(w), "softmax")
    want = ref["softmax"].mlogloss(margin, y, w)
    assert float(said["train_weight"]) == float(w.sum())
    assert abs(float(said["train_loss"]) - want) <= 1e-5 * want
    # a softmax chain starts at 0, every class
    assert float(trees.gbt_base_score(jnp.asarray(y), jnp.asarray(w),
                                      "softmax")) == 0.0


def test_early_stopping_at_the_round_the_reference_picks(ref):
    """A fast learning rate overfits noisy labels: the validation
    log-loss turns, and both chains stop before the same round."""
    k = 5
    X, y = _table(k, n=800, seed=11)
    rng = np.random.default_rng(2)
    y = np.where(rng.uniform(size=len(y)) < 0.5,
                 rng.integers(k, size=len(y)), y).astype(np.float32)
    val = (rng.uniform(size=len(y)) < 0.3).astype(np.float32)
    fit = 1.0 - val
    Xb = _binned(X)
    esr, rounds, eta = 2, 12, 1.0
    got, _ = trees.fit_gbt_hosted(
        Xb, jnp.asarray(y), jnp.asarray(fit), rounds, DEPTH, N_BINS,
        jnp.float32(eta), jnp.float32(1.0), "softmax", 1.0,
        val_w=jnp.asarray(val), early_stopping_rounds=esr,
        rounds_per_dispatch=1, n_classes=k)
    want, _ = ref["softmax"].boost(
        np.asarray(Xb), y, fit, k, rounds, DEPTH, N_BINS, eta, quant="bf16",
        val_w=val, early_stopping_rounds=esr)
    live = np.any(np.asarray(got["leaf"]) != 0, axis=(1, 2, 3))
    stopped = int(np.flatnonzero(live).max()) + 1
    assert stopped == want["feat"].shape[0] < rounds


def _sweep_table(k: int, n: int = 240, seed: int = 4):
    X, y = _table(k, n=n, d=6, seed=seed)
    folds = OpCrossValidation(n_folds=2, seed=1).splits(y)
    return jnp.asarray(X), jnp.asarray(y), folds


def test_chunked_sweep_equals_the_mesh_single_program():
    """The round-chunked loop (one round a dispatch) and the mesh path's
    one program per group grow the same chains: the same fold metrics."""
    k = 5
    X, y, folds = _sweep_table(k)
    est = OpXGBoostClassifier(n_estimators=3, max_bins=8,
                              early_stopping_rounds=0, n_classes=k)
    grids = [{"max_depth": 2, "min_child_weight": 1.0},
             {"max_depth": 3, "min_child_weight": 2.0}]
    ev = MultiClassificationEvaluator(metric="F1")
    chunked = S.run_sweep(est, grids, X, y, folds, ev,
                          FitContext(n_rows=len(y), seed=7, n_classes=k))
    mesh = make_mesh(4, sweep=2)
    single = S.run_sweep(est, grids, X, y, folds, ev,
                         FitContext(n_rows=len(y), seed=7, mesh=mesh,
                                    n_classes=k),
                         sharding=sweep_sharding(mesh))
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(single),
                               atol=1e-6)
    assert np.all(np.asarray(chunked) > 1.0 / k)


def test_chunked_sweep_spans_and_one_round_a_dispatch(monkeypatch):
    """A softmax chain in the chunked loop: `objective` softmax, `classes`
    K and its rounds on the dispatch spans, its cross-entropy on the
    fetch span; with a work budget of one round, a dispatch a round."""
    from transmogrifai_tpu.obs.trace import TRACER
    k = 4
    X, y, folds = _sweep_table(k)
    unit = len(y) * 2 ** 4 * trees.hist_slots(6, 8, None) * k
    monkeypatch.setattr(trees, "_DISPATCH_UNITS", 1.5 * unit)
    est = OpXGBoostClassifier(n_estimators=3, max_bins=8, max_depth=3,
                              early_stopping_rounds=0, n_classes=k)
    mark = max((sp.span_id for sp in TRACER.spans()), default=0)
    S.run_sweep(est, [{}], X, y, folds, MultiClassificationEvaluator(),
                FitContext(n_rows=len(y), seed=7, n_classes=k))
    spans = [sp for sp in TRACER.spans() if sp.span_id > mark]
    disp = [sp.attributes for sp in spans if sp.name == "sweep:dispatch:gbt"]
    assert disp and all(a["objective"] == "softmax" and a["classes"] == k
                        and a["rounds"] == 1 for a in disp)
    assert sum(a["rounds"] * a["pairs"] for a in disp) == 3 * len(folds)
    fetch = [sp.attributes for sp in spans if sp.name == "sweep:fetch:gbt"]
    losses = [v for a in fetch for v in a["train_loss"]]
    assert len(losses) == len(folds) and 0 < min(losses) < np.log(k)


def test_a_softmax_dispatch_subtracts_and_a_binary_one_does_not():
    """At depth 6 a softmax round's K = 5 trees batch each histogram
    product, so levels 3-5 come by sibling subtraction; a binary chain's
    one tree a round keeps every level direct in the default bf16 mode."""
    from transmogrifai_tpu.evaluators import BinaryClassificationEvaluator
    from transmogrifai_tpu.obs.trace import TRACER
    assert trees.HIST_PRECISION == "bf16"
    said = {}
    for k in (5, 2):
        X, y, folds = _sweep_table(k)
        est = OpXGBoostClassifier(n_estimators=2, max_bins=8, max_depth=6,
                                  early_stopping_rounds=0, n_classes=k)
        ev = (MultiClassificationEvaluator() if k > 2
              else BinaryClassificationEvaluator())
        mark = max((sp.span_id for sp in TRACER.spans()), default=0)
        S.run_sweep(est, [{}], X, y, folds, ev,
                    FitContext(n_rows=len(y), seed=7, n_classes=k))
        said[k] = {(sp.attributes["objective"],
                    sp.attributes["hist_subtract"])
                   for sp in TRACER.spans()
                   if sp.span_id > mark and sp.name == "sweep:dispatch:gbt"}
    assert said == {5: {("softmax", 3)}, 2: {("logistic", 0)}}
    assert trees.hist_subtract_levels(6, 0, 5) == (3, 4, 5)


def test_a_softmax_refits_own_binning_states_its_subtracted_levels():
    """The estimator's refit grows its K trees a round by the same rule
    as the sweep, and its `tree:edges` span says so."""
    from transmogrifai_tpu.obs.trace import TRACER
    k = 5
    X, y = _table(k, n=300)
    est = OpXGBoostClassifier(n_estimators=2, max_depth=6, max_bins=8,
                              n_classes=k)
    with TRACER.span("run:refit", new_trace=True) as root:
        est.fit_arrays(jnp.asarray(X), jnp.asarray(y),
                       jnp.ones(len(y), jnp.float32),
                       FitContext(n_rows=len(y), seed=3, n_classes=k))
    edges, = [sp for sp in TRACER.trace_spans(root.trace_id)
              if sp.name == "tree:edges"]
    assert edges.attributes["hist_subtract"] == 3


def test_refit_is_the_softmax_chain():
    """The estimator's refit grows the same chain as `fit_gbt` and its
    model scores the (n, K) margin as probabilities."""
    k = 6
    X, y = _table(k, n=400)
    est = OpXGBoostClassifier(n_estimators=3, max_depth=3, max_bins=16,
                              n_classes=k)
    model = est.fit_arrays(jnp.asarray(X), jnp.asarray(y),
                           jnp.ones(len(y), jnp.float32),
                           FitContext(n_rows=len(y), seed=3, n_classes=k))
    assert isinstance(model, trees.GBTMulticlassModel)
    got, margin = trees.fit_gbt(
        model._binned(jnp.asarray(X)), jnp.asarray(y),
        jnp.ones(len(y), jnp.float32), 3, 3, 16, jnp.float32(0.3),
        jnp.float32(1.0), "softmax", min_child_weight=1.0, seed=3,
        n_classes=k)
    for key in ("feat", "bin", "leaf"):
        np.testing.assert_array_equal(model.trees[key], np.asarray(got[key]))
    pred = model.predict_arrays(jnp.asarray(X))
    np.testing.assert_allclose(np.asarray(pred["rawPrediction"]),
                               np.asarray(margin), atol=1e-5)
    np.testing.assert_allclose(np.asarray(pred["probability"]).sum(1), 1.0,
                               rtol=1e-5)


def test_warm_refit_continues_the_softmax_margin():
    """A warm refit of a softmax chain appends rounds grown from the
    resident chain's (n, K) margin."""
    k = 4
    X, y = _table(k, n=300)
    ctx = FitContext(n_rows=len(y), seed=3, n_classes=k)
    est = OpXGBoostClassifier(n_estimators=4, max_depth=2, max_bins=8,
                              n_classes=k)
    first = est.fit_arrays(jnp.asarray(X), jnp.asarray(y),
                           jnp.ones(len(y), jnp.float32), ctx)
    est.init_params = dict(first.get_params(), n_new=2)
    warm = est.fit_arrays(jnp.asarray(X), jnp.asarray(y),
                          jnp.ones(len(y), jnp.float32), ctx)
    assert isinstance(warm, trees.GBTMulticlassModel)
    assert warm.trees["leaf"].shape[:2] == (6, k)
    np.testing.assert_array_equal(warm.trees["leaf"][:4],
                                  first.trees["leaf"])


# (width, rounds) of every cell's tree dispatches, as `sweep.py` plans
# them: (n_rows, slots, pad_depth, learners, n_pairs, pad_tail,
# value_columns)
CELL_PLANS = {
    "higgs-forest": ((2_160_000, 896, 12, 1, 3, False, 2), (1, 1)),
    "higgs-boosted": ((2_160_000, 896, 10, 2, 6, True, 1), (1, 2)),
    "criteo-forest": ((900_000, 1_446, 12, 1, 3, False, 2), (1, 1)),
    "criteo-boosted": ((900_000, 1_446, 10, 2, 6, True, 1), (1, 2)),
    "kddcup99-forest": ((1_800_000, 1_042, 12, 1, 3, False, 23), (1, 1)),
    "airlines-forest": ((4_500_000, 318, 12, 1, 3, False, 1), (1, 1)),
    "airlines-boosted": ((4_500_000, 318, 6, 10, 6, True, 1), (1, 10)),
}


@pytest.mark.parametrize("case", list(CELL_PLANS))
def test_dispatch_plan_of_the_cells_is_unchanged(case):
    args, want = CELL_PLANS[case]
    assert trees.dispatch_plan(*args[:6], value_columns=args[6]) == want
    assert trees.dispatch_plan(*args[:6], value_columns=args[6],
                               classes=0) == want


def test_dispatch_plan_counts_k_trees_a_round():
    # dionis: 374,569 rows x 60 columns of 32 bins, depth 6, 4 rounds,
    # 2 configurations x 3 folds: two pairs and one round a dispatch
    assert trees.dispatch_plan(374_569, 1_920, 6, 4, 6, True,
                               classes=355) == (2, 1)
    # without K the same chain would take all its rounds at once
    assert trees.dispatch_plan(374_569, 1_920, 6, 4, 6, True) == (2, 4)
    # a small table: every round of 16 pairs fits at K = 7 too
    assert trees.dispatch_plan(20_000, 256, 6, 4, 16, True,
                               classes=7) == (16, 4)
    # one more class never widens a dispatch nor lengthens it
    for k in (3, 10, 100, 355):
        w1, r1 = trees.dispatch_plan(374_569, 1_920, 6, 10, 6, True,
                                     classes=k)
        w2, r2 = trees.dispatch_plan(374_569, 1_920, 6, 10, 6, True,
                                     classes=k + 1)
        assert w2 <= w1 and r2 <= r1
    assert trees.dispatch_plan(1_000_000, 896, 6, 40, 6, True,
                               classes=8)[1] \
        < trees.dispatch_plan(1_000_000, 896, 6, 40, 6, True)[1]


def test_dispatch_plan_holds_a_wide_k_state_to_one_pair():
    # 500,000 rows x 1,000 classes: a pair's (n, K) margin, p, G and H
    # are 8 GB, so one pair a dispatch, where a one-tree chain of the
    # same shape would take two
    assert trees.dispatch_plan(500_000, 1_920, 6, 4, 6, True,
                               classes=1_000) == (1, 1)
    assert trees.dispatch_plan(500_000, 1_920, 6, 4, 6, True)[0] == 2
    # the shared bin one-hots are counted once: a narrow K widens
    assert trees.dispatch_plan(374_569, 1_920, 6, 10, 6, True,
                               classes=3)[0] == 4
