"""The sweep's programs take the dataset as jit ARGUMENTS.

The contract under test: a sweep program's HLO depends on shapes, dtypes
and static hyper-parameters only — every array that depends on the
dataset reaches it as an argument, never as a closure constant — so a
second `run_sweep` on a same-shaped table compiles nothing, and a
dispatch that did hold a compile never feeds the width calibration
(widths are compiled shapes: they have to read the same pass after pass).
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
    OpRandomForestClassifier, OpRandomForestRegressor, OpXGBoostClassifier)
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
def fresh_sweep(monkeypatch):
    """No held program, no learned calibration, nothing persisted."""
    monkeypatch.setattr(S, "_CALIB", {})
    monkeypatch.setattr(S, "_CALIB_LOADED", True)
    monkeypatch.setattr(S, "_save_calib", lambda: None)
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


@pytest.mark.parametrize("family", ["gbt", "forest"])
def test_a_dispatch_that_compiled_is_no_calibration_sample(
        family, fresh_sweep, monkeypatch):
    # one pair and one round a dispatch, so the group makes several
    monkeypatch.setattr(S, "_PAIR_EXEC_TARGET_S", 1e-9)
    seen = []   # _CALIB as each dispatch ends, before its own sample
    real_record = S.SWEEP_STATS.record
    monkeypatch.setattr(
        S.SWEEP_STATS, "record",
        lambda dt: (seen.append(dict(S._CALIB)), real_record(dt)))
    _sweep("boosted-binary-chunked" if family == "gbt"
           else "forest-classifier", seed=11)
    assert len(seen) >= 3
    # the first dispatch traced and compiled its program: clean of any
    # overlap (one thread), and still not a sample
    assert seen[1] == {} and S._sec_per_unit(family) != S._CALIB_INIT[family]
    # the second was execution only, and is one
    assert family in seen[2]
