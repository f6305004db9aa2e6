"""The sweep's programs take the dataset as jit ARGUMENTS.

The contract under test: a sweep program's HLO depends on shapes, dtypes
and static hyper-parameters only — every array that depends on the
dataset reaches it as an argument, never as a closure constant — so a
second `run_sweep` on a same-shaped table compiles nothing — and what
goes into one dispatch (`dispatch_plan`: pairs wide, rounds long) is a
function of shapes alone, so it reads the same pass after pass and
process after process, in the sweep and in the refit.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.analysis import retrace
from transmogrifai_tpu.evaluators import (
    BinaryClassificationEvaluator, MultiClassificationEvaluator,
    RegressionEvaluator)
from transmogrifai_tpu.evaluators.evaluators import LambdaEvaluator
from transmogrifai_tpu.evaluators.metrics import auroc_score
from transmogrifai_tpu.models import (
    OpGeneralizedLinearRegression, OpLinearRegression, OpLinearSVC,
    OpLogisticRegression, OpMultilayerPerceptronClassifier, OpNaiveBayes,
    OpGBTRegressor, OpRandomForestClassifier, OpRandomForestRegressor,
    OpXGBoostClassifier)
from transmogrifai_tpu.models import trees
from transmogrifai_tpu.obs.trace import TRACER
from transmogrifai_tpu.parallel import sweep as S
from transmogrifai_tpu.parallel.mesh import make_mesh, sweep_sharding
from transmogrifai_tpu.selector.validators import OpCrossValidation
from transmogrifai_tpu.stages.base import FitContext
from transmogrifai_tpu.utils.compile_cache import COMPILE_STATS

N, D = 240, 6


def _table(kind: str, seed: int):
    """One of two different tables of one shape (per `seed`)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, D)).astype(np.float32)
    if kind == "typed":
        # half the columns 0/1 indicators, at other positions per seed:
        # the same two block widths, so the same programs
        ind = rng.permutation(D)[:D // 2]
        X[:, ind] = X[:, ind] > 0.3
    z = X @ rng.normal(size=D)
    if kind in ("binary", "typed"):
        y = (rng.uniform(size=N) < 1 / (1 + np.exp(-z))).astype(np.float32)
    elif kind == "multiclass":
        y = np.digitize(z + rng.normal(size=N) * 0.3,
                        np.quantile(z, [1 / 3, 2 / 3])).astype(np.float32)
        y[:3] = [0.0, 1.0, 2.0]      # every class present in both tables
    elif kind == "counts":           # NB wants non-negative features
        X = rng.poisson(2.0, size=(N, D)).astype(np.float32)
        y = (X[:, 0] + rng.normal(size=N) > 2.0).astype(np.float32)
    else:
        y = (z + rng.normal(size=N) * 0.3).astype(np.float32)
    folds = OpCrossValidation(n_folds=2, seed=1).splits(y)
    return jnp.asarray(X), jnp.asarray(y), folds


def _custom_auroc(label, pred):
    return auroc_score(np.asarray(label.data["value"], dtype=np.float64),
                       np.asarray(pred.data["probability"])[:, 1])


BINARY, MULTI, REG = (BinaryClassificationEvaluator,
                      MultiClassificationEvaluator, RegressionEvaluator)
LAMBDA = lambda: LambdaEvaluator("customAuROC", _custom_auroc)  # noqa: E731

# id -> (estimator, grids, table kind, evaluator factory); a `mesh-*` case
# sweeps on a {sweep: 2, data: 2} mesh of the suite's virtual devices
CASES = {
    "logistic-ridge": (
        lambda: OpLogisticRegression(max_iter=8),
        [{"reg_param": 0.001}, {"reg_param": 0.1}], "binary", BINARY),
    "logistic-enet": (
        lambda: OpLogisticRegression(max_iter=8),
        [{"reg_param": 0.01, "elastic_net_param": 0.1},
         {"reg_param": 0.1, "elastic_net_param": 0.5}], "binary", BINARY),
    "linreg": (
        lambda: OpLinearRegression(),
        [{"reg_param": 0.0}, {"reg_param": 0.1}], "regression", REG),
    "svc": (
        lambda: OpLinearSVC(max_iter=8),
        [{"reg_param": 0.01}, {"reg_param": 0.1}], "binary", BINARY),
    "glm": (
        lambda: OpGeneralizedLinearRegression(family="gaussian", max_iter=5),
        [{"reg_param": 0.0}, {"reg_param": 0.1}], "regression", REG),
    "naive-bayes": (
        lambda: OpNaiveBayes(),
        [{"smoothing": 0.5}, {"smoothing": 1.0}], "counts", BINARY),
    "mlp": (
        lambda: OpMultilayerPerceptronClassifier(
            hidden_layers=(4,), max_iter=5),
        [{"learning_rate": 0.01}, {"learning_rate": 0.05}], "binary", BINARY),
    "forest-classifier": (
        lambda: OpRandomForestClassifier(n_trees=3, max_bins=8),
        [{"max_depth": 2}, {"max_depth": 3}], "binary", BINARY),
    "forest-regressor": (
        lambda: OpRandomForestRegressor(n_trees=3, max_bins=8),
        [{"max_depth": 2}, {"max_depth": 3}], "regression", REG),
    "boosted-binary-chunked": (
        lambda: OpXGBoostClassifier(n_estimators=3, max_bins=8,
                                    early_stopping_rounds=0),
        [{"max_depth": 2}, {"max_depth": 3}], "binary", BINARY),
    "boosted-regressor-chunked": (
        lambda: OpGBTRegressor(n_estimators=3, max_bins=8),
        [{"max_depth": 2}, {"max_depth": 3}], "regression", REG),
    "linreg-enet": (
        lambda: OpLinearRegression(),
        [{"reg_param": 0.01, "elastic_net_param": 0.1},
         {"reg_param": 0.1, "elastic_net_param": 0.5}], "regression", REG),
    "boosted-multiclass": (
        lambda: OpXGBoostClassifier(n_estimators=2, max_bins=8,
                                    early_stopping_rounds=0),
        [{"max_depth": 2}], "multiclass", MULTI),
    "forest-classifier-typed": (
        lambda: OpRandomForestClassifier(n_trees=3, max_bins=8),
        [{"max_depth": 2}, {"max_depth": 3}], "typed", BINARY),
    "boosted-binary-chunked-typed": (
        lambda: OpXGBoostClassifier(n_estimators=3, max_bins=8,
                                    early_stopping_rounds=0),
        [{"max_depth": 2}, {"max_depth": 3}], "typed", BINARY),
    "lambda-evaluator-logistic": (
        lambda: OpLogisticRegression(max_iter=8),
        [{"reg_param": 0.001}, {"reg_param": 0.1}], "binary", LAMBDA),
    "lambda-evaluator-boosted": (
        lambda: OpXGBoostClassifier(n_estimators=3, max_bins=8,
                                    early_stopping_rounds=0),
        [{"max_depth": 2}], "binary", LAMBDA),
    "mesh-logistic-enet": (
        lambda: OpLogisticRegression(max_iter=8),
        [{"reg_param": 0.01, "elastic_net_param": 0.1},
         {"reg_param": 0.1, "elastic_net_param": 0.5}], "binary", BINARY),
    "mesh-forest-classifier": (
        lambda: OpRandomForestClassifier(n_trees=2, max_bins=8),
        [{"max_depth": 2}, {"max_depth": 3}], "binary", BINARY),
    "mesh-boosted-binary": (
        lambda: OpXGBoostClassifier(n_estimators=2, max_bins=8,
                                    early_stopping_rounds=0),
        [{"max_depth": 2}, {"max_depth": 3}], "binary", BINARY),
    "mesh-boosted-multiclass": (
        lambda: OpXGBoostClassifier(n_estimators=2, max_bins=8,
                                    early_stopping_rounds=0),
        [{"max_depth": 2}, {"max_depth": 3}], "multiclass", MULTI),
    "mesh-boosted-binary-typed": (
        lambda: OpXGBoostClassifier(n_estimators=2, max_bins=8,
                                    early_stopping_rounds=0),
        [{"max_depth": 2}, {"max_depth": 3}], "typed", BINARY),
}


def _clear_programs():
    for held in (S._block_program, S._gbt_rounds_program,
                 S._gbt_score_program):
        held.cache_clear()


@pytest.fixture
def fresh_sweep():
    """No held program (the sweep learns nothing else to reset)."""
    _clear_programs()
    yield
    _clear_programs()


def _sweep(case, seed):
    make_est, grids, kind, make_ev = CASES[case]
    X, y, folds = _table(kind, seed)
    mesh = make_mesh(4, sweep=2) if case.startswith("mesh-") else None
    return np.asarray(S.run_sweep(
        make_est(), grids, X, y, folds, make_ev(),
        FitContext(n_rows=N, seed=7, mesh=mesh),
        sharding=None if mesh is None else sweep_sharding(mesh)))


@pytest.mark.parametrize("case", list(CASES))
def test_second_table_compiles_nothing_and_lowers_the_same(
        case, fresh_sweep, monkeypatch):
    # (1) two tables of one shape, one process: the second sweep finds
    # every program — no trace of a `sweep:*` label, no compile request
    first = _sweep(case, seed=11)
    traces, compiles = retrace.MONITOR.snapshot(), dict(COMPILE_STATS)
    second = _sweep(case, seed=12)
    assert not {label: n for label, n in
                retrace.MONITOR.delta(traces).items()
                if label.startswith("sweep:")}
    assert dict(COMPILE_STATS) == compiles
    assert not np.array_equal(first, second)   # it did see the new table

    # (2) each program, built afresh for each table, lowers to the same
    # text: nothing of the table is in the module
    real_jit = retrace.instrumented_jit
    texts = {}

    def recording_jit(fn, label=None, **kw):
        prog = real_jit(fn, label=label, **kw)

        def call(*args):
            texts.setdefault(label, []).append(
                prog.lower(*args).as_text())
            return prog(*args)
        return call

    monkeypatch.setattr(retrace, "instrumented_jit", recording_jit)
    lowered = {}
    for seed in (11, 12):
        _clear_programs()
        texts.clear()
        again = _sweep(case, seed)
        lowered[seed] = {label: sorted(set(t)) for label, t in texts.items()}
    assert lowered[11] and all(
        label.startswith("sweep:") for label in lowered[11])
    assert lowered[11] == lowered[12]
    # and the held program of (1) answered for ITS arguments: the same
    # numbers as a program built for the second table alone
    np.testing.assert_array_equal(again, second)


def test_lowered_text_would_show_a_closed_over_table():
    """The comparison above can see what it guards against: a program
    that closes over its table lowers to a different text per table."""
    import jax

    def closing_over(X):
        return jax.jit(lambda w: (X @ w).sum())

    w = jnp.ones((D,), jnp.float32)
    a, b = (closing_over(_table("binary", s)[0]).lower(w).as_text()
            for s in (11, 12))
    assert a != b


# -- what goes into one dispatch ------------------------------------------ #

# id -> (n_rows, slots, pad_depth, learners, n_pairs, pad_tail), (width,
# rounds). The first four are the tree blocks of the benchmark's two cells
# (`higgs`: 2,160,000 rows x 28 x 32 slots; `criteo`: 900,000 x 1,446): the
# memory budget pins each to one pair a dispatch, and a forest's one tree
# or both boosting rounds go into it.
PLANS = {
    "higgs-forest": ((2_160_000, 896, 12, 1, 3, False), (1, 1)),
    "higgs-boosted": ((2_160_000, 896, 10, 2, 6, True), (1, 2)),
    "criteo-forest": ((900_000, 1_446, 12, 1, 3, False), (1, 1)),
    "criteo-boosted": ((900_000, 1_446, 10, 2, 6, True), (1, 2)),
    # a tiny table: every pair and every round in one dispatch, the
    # forest at the pair count itself, the boosted chunk at its
    # power-of-two floor (its last chunk is padded)
    "tiny-forest": ((240, 48, 4, 3, 6, False), (6, 3)),
    "tiny-boosted": ((240, 48, 4, 3, 6, True), (4, 3)),
    # memory allows 4 pairs, the work budget 2 of 50-tree forests...
    "work-binds-width": ((100_000, 1_760, 10, 50, 64, False), (2, 50)),
    # ...and, with one pair over it, 25 of 200 rounds at a time
    "work-binds-rounds": ((1_000_000, 896, 10, 200, 6, True), (1, 25)),
    # a prime round count has no divisor to chunk by: the ideal, and a tail
    "prime-rounds": ((1_000_000, 896, 10, 199, 6, True), (1, 27)),
}


@pytest.mark.parametrize("case", list(PLANS))
def test_dispatch_plan_from_shapes_alone(case):
    args, want = PLANS[case]
    assert trees.dispatch_plan(*args) == want
    assert trees.dispatch_plan(*args) == want     # nothing was learned
    width, rounds = want
    assert width & (width - 1) == 0 or width == args[4]
    assert 1 <= rounds <= args[3]


def test_sweep_and_refit_chunk_a_shape_by_the_same_rounds(
        fresh_sweep, monkeypatch):
    """One rule: the sweep's boosted chunk and `fit_gbt_hosted`'s default
    dispatch the same number of rounds for one shape."""
    X, y, folds = _table("binary", 11)
    est = OpXGBoostClassifier(n_estimators=4, max_depth=4, max_bins=8,
                              early_stopping_rounds=0)
    # a work budget of 2.5 rounds of this table at depth 4 (2^4 nodes)
    unit = N * 2 ** 4 * trees.hist_slots(D, 8, None)
    monkeypatch.setattr(trees, "_DISPATCH_UNITS", 2.5 * unit)
    seen = []
    real_chunk = trees.fit_gbt_chunk

    def recording_chunk(*args, **kw):
        seen.append(int(args[8]))            # n_rounds
        return real_chunk(*args, **kw)

    monkeypatch.setattr(trees, "fit_gbt_chunk", recording_chunk)
    ctx = FitContext(n_rows=N, seed=7)
    S.run_sweep(est, [{}], X, y, folds, BinaryClassificationEvaluator(), ctx)
    swept, seen[:] = set(seen), []
    est.fit_arrays(X, y, jnp.ones((N,), jnp.float32),
                   FitContext(n_rows=N, seed=7))
    assert swept == set(seen) == {2}


@pytest.mark.parametrize("family", ["gbt", "forest"])
def test_a_second_sweep_dispatches_the_same_and_leaves_no_state(
        family, fresh_sweep, monkeypatch, tmp_path):
    """Two sweeps of one table in one process: the same dispatches, no
    `recompile` event in the second, and nothing on disk but what the
    compile cache and the perf corpus keep."""
    from transmogrifai_tpu import perf
    monkeypatch.setenv("TRANSMOGRIFAI_STORE_DIR", str(tmp_path))
    monkeypatch.setenv("TRANSMOGRIFAI_PERF_MODEL", "1")
    perf.set_model(None)
    # one pair and one learner a dispatch, so a group makes several
    monkeypatch.setattr(trees, "_DISPATCH_UNITS", 1.0)
    case = ("boosted-binary-chunked" if family == "gbt"
            else "forest-classifier")
    made, events = [], []
    try:
        for _ in range(2):
            d0 = S.SWEEP_STATS.dispatches
            with TRACER.span("run:test-plan", category="run",
                             new_trace=True) as root:
                _sweep(case, seed=11)
            made.append(S.SWEEP_STATS.dispatches - d0)
            events.append([name for sp in TRACER.trace_spans(root.trace_id)
                           for name, _, _ in sp.events])
    finally:
        perf.set_model(None)
    assert made[0] == made[1] >= 3
    assert "recompile" in events[0] and "recompile" not in events[1]
    assert {p.name for p in tmp_path.iterdir()} <= {"xla-cache", "perf"}
    assert (tmp_path / "perf").is_dir()      # the store did see the run
