"""Tree bin edges of a device matrix: `quantile_bin_edges` takes the
order statistics where the matrix lives and has to give numpy's float64
quantile edges bit for bit (the benchmark's cells hold `edges_err` to
exactly 0); the sweep's and the refit's binning sites agree, and neither
brings the table to the host.
"""

import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import OpRandomForestClassifier, trees
from transmogrifai_tpu.obs.trace import TRACER
from transmogrifai_tpu.parallel import sweep
from transmogrifai_tpu.stages.base import FitContext


def numpy_edges(X, max_bins, indicator=None):
    """The definition: `np.quantile` in float64 over the non-indicator
    columns, cast to float32; 0.5 for an indicator column."""
    X = np.asarray(X)
    if indicator is None:
        indicator = np.all((X == 0) | (X == 1), axis=0)
    edges = np.full((X.shape[1], max_bins - 1), 0.5, np.float32)
    wide = np.flatnonzero(~indicator)
    if wide.size:
        qs = np.linspace(0, 1, max_bins + 1)[1:-1]
        with warnings.catch_warnings():     # inf - inf inside numpy's lerp
            warnings.simplefilter("ignore", RuntimeWarning)
            edges[wide] = np.quantile(
                X[:, wide].astype(np.float64), qs, axis=0).T
    return edges


def _real(n, d=5, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _tied(n):
    X = _real(n, 4, seed=1)
    X[:, 0] = np.round(X[:, 0])                 # a handful of values
    X[:, 1] = np.round(X[:, 1] * 4) / 4
    return X


def _mostly_zero(n):
    X = _real(n, 3, seed=2)
    X[:, 0] = np.where(np.arange(n) % 10 == 0, X[:, 0], 0.0)
    X[:, 1] = np.where(np.arange(n) % 50 == 0, -np.abs(X[:, 1]), 0.0)
    return X


def _constant(n):
    X = _real(n, 3, seed=3)
    X[:, 1] = 7.25
    return X


def _infinite(n):
    X = _real(n, 4, seed=4)
    X[::7, 0] = np.inf
    X[::5, 1] = -np.inf
    X[::3, 2] = np.inf
    X[1::3, 2] = -np.inf
    X[:, 3] = np.inf
    return X


def _with_nan(n):
    X = _real(n, 4, seed=5)
    X[n // 2, 1] = np.nan
    X[:, 3] = np.nan
    return X


def _mixed(n, which):
    """Reals with the columns `which` made 0/1 indicators."""
    X = _real(n, 6, seed=6)
    rng = np.random.default_rng(7)
    for j in which:
        X[:, j] = rng.uniform(size=n) < 0.2
    X[0, list(which)] = 1.0
    X[1, list(which)] = 0.0
    return X


CASES = {
    "one_row": lambda: _real(1),
    "two_rows": lambda: _real(2),
    "three_rows": lambda: _real(3),
    "odd_rows": lambda: _real(1001),
    "even_rows": lambda: _real(1000),
    "fewer_rows_than_bins": lambda: _real(17),
    "tied": lambda: _tied(997),
    "mostly_zero": lambda: _mostly_zero(1000),
    "constant": lambda: _constant(500),
    "infinite": lambda: _infinite(211),
    "one_infinite_row": lambda: np.full((1, 2), np.inf, np.float32),
    "nan": lambda: _with_nan(300),
    "indicators_none": lambda: _mixed(400, ()),
    "indicators_some": lambda: _mixed(400, (1, 4)),
    "indicators_all": lambda: _mixed(400, range(6)),
}


@pytest.mark.parametrize("max_bins", [32, 255])
@pytest.mark.parametrize("case", sorted(CASES))
def test_device_edges_are_numpys_float64_quantiles_bit_for_bit(
        case, max_bins):
    X = CASES[case]()
    want = numpy_edges(X, max_bins)
    got = trees.quantile_bin_edges(jnp.asarray(X), max_bins)
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)        # NaN equals NaN here
    if case == "nan":           # numpy's: NaN edges for a column with one
        assert np.isnan(got[[1, 3]]).all() and np.isfinite(got[[0, 2]]).all()
    # the host path is the same definition
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        np.testing.assert_array_equal(
            trees.quantile_bin_edges(X, max_bins), want)


@pytest.mark.parametrize("given", ["some", "none", "all"])
def test_device_edges_with_the_indicator_given(given):
    # `indicator` from the caller (the sweep reads it once): the device
    # program then sorts the other columns only, picked by position
    X = _mixed(400, (1, 4))
    indicator = {"some": np.isin(np.arange(6), (1, 4)),
                 "none": np.zeros(6, bool),
                 "all": np.ones(6, bool)}[given]
    got = trees.quantile_bin_edges(jnp.asarray(X), 32, indicator)
    np.testing.assert_array_equal(got, numpy_edges(X, 32, indicator))
    assert (got[indicator] == 0.5).all()


def test_padded_rows_are_cut_before_the_edges():
    # the sweep's mesh padding appends zero rows; `X[:n]` is what the
    # edges come from
    X = _real(1001)
    padded = jnp.concatenate([jnp.asarray(X), jnp.zeros((23, 5))])
    np.testing.assert_array_equal(
        trees.quantile_bin_edges(padded[:1001], 32), numpy_edges(X, 32))
    assert not np.array_equal(
        trees.quantile_bin_edges(padded, 32), numpy_edges(X, 32))


def test_only_the_order_statistics_leave_the_device_program():
    # (d_wide, 2 (max_bins - 1)) values and a flag a column: nothing the
    # size of the table is there to pull
    n, d, max_bins = 4096, 6, 32
    X = jax.ShapeDtypeStruct((n, d), jnp.float32)
    idx = jax.ShapeDtypeStruct((2 * (max_bins - 1),), jnp.int32)
    for d_wide in (d, 2):
        cols = jax.ShapeDtypeStruct((d_wide,), jnp.int32)
        vals, has_nan = jax.eval_shape(trees._order_statistics, X, cols, idx)
        assert vals.shape == (d_wide, 2 * (max_bins - 1))
        assert has_nan.shape == (d_wide,)
    # a column at a time: the one sort is 1-D and unstable (no index
    # operand), and no table-sized gather or transpose feeds it
    text = trees._order_statistics.lower(X, cols, idx).as_text()
    sorts = re.findall(r'"stablehlo\.sort"\(.*?\) <\{(.*?)\}>', text)
    assert len(sorts) == 1 and "is_stable = false" in sorts[0]
    assert f"(tensor<{n}xf32>) -> tensor<{n}xf32>" in text
    assert "stablehlo.transpose" not in text
    assert not re.search(rf"stablehlo\.gather.*tensor<{n}x{d}xf32>", text)


def test_tables_of_the_same_counts_share_the_program():
    # the wide columns' positions are an argument, as `hist_layout`
    # passes them: another table with as many compiles nothing
    a, b = _mixed(400, (1, 4)), _mixed(400, (0, 5))
    trees.quantile_bin_edges(jnp.asarray(a), 32)
    before = trees._order_statistics._cache_size()
    trees.quantile_bin_edges(jnp.asarray(b), 32)
    assert trees._order_statistics._cache_size() == before


def _spans_since(mark, name):
    return [sp for sp in TRACER.spans()
            if sp.span_id > mark and sp.name == name]


@pytest.mark.parametrize("site", ["device", "host"])
def test_the_sweep_and_the_refit_bin_alike_and_say_where(site):
    X_np = _mixed(600, (2, 3))
    X = jnp.asarray(X_np) if site == "device" else X_np
    est = OpRandomForestClassifier(n_trees=1, max_depth=2, max_bins=16)
    mark = max((sp.span_id for sp in TRACER.spans()), default=0)
    out, layout, blocks = sweep._binned_cache(
        est, [{"max_bins": 16}], X, FitContext(n_rows=600, seed=0))
    edges, Xb, refit_layout = est._edges_binned(
        X, FitContext(n_rows=600, seed=0))
    np.testing.assert_array_equal(edges, numpy_edges(X_np, 16))
    np.testing.assert_array_equal(np.asarray(out[16]), np.asarray(Xb))
    np.testing.assert_array_equal(
        np.asarray(Xb), np.asarray(trees.bin_features(
            jnp.asarray(X_np), jnp.asarray(edges))))
    assert blocks == (4, 2)
    for block in ("wide", "ind"):
        np.testing.assert_array_equal(np.asarray(layout[block]),
                                      np.asarray(refit_layout[block]))
    (sweep_span,) = _spans_since(mark, "sweep:bin")
    (refit_span,) = _spans_since(mark, "tree:edges")
    assert sweep_span.attributes["edges"] == site
    assert refit_span.attributes["edges"] == site
    assert refit_span.attributes["max_bins"] == 16
    assert sweep_span.attributes["hist_slots"] == 4 * 16 + 2 * 2


def test_row_sharded_rows_give_the_unsharded_edges():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs the forced host mesh")
    mesh = Mesh(np.asarray(devices[:4]), ("data",))
    X = _mixed(1000, (1,))
    Xs = jax.device_put(jnp.asarray(X), NamedSharding(mesh, P("data", None)))
    assert len(Xs.sharding.device_set) == 4
    np.testing.assert_array_equal(
        trees.quantile_bin_edges(Xs, 32), numpy_edges(X, 32))
    # the sweep's padded rows, cut before the edges, sharded all the same
    padded = jax.device_put(
        jnp.concatenate([jnp.asarray(X), jnp.zeros((24, 6))]),
        NamedSharding(mesh, P("data", None)))
    ctx = FitContext(n_rows=1000, seed=0)
    ctx._sweep_n_rows = 1000
    est = OpRandomForestClassifier(n_trees=1, max_depth=2, max_bins=32)
    out, _, _ = sweep._binned_cache(est, [{"max_bins": 32}], padded, ctx)
    want = trees.bin_features(
        jnp.concatenate([jnp.asarray(X), jnp.zeros((24, 6))]),
        jnp.asarray(numpy_edges(X, 32)))
    np.testing.assert_array_equal(np.asarray(out[32]), np.asarray(want))
