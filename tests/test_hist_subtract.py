"""Sibling subtraction in `models/trees.py` (`hist_subtract_levels`,
`_subtract_siblings`): which levels of which trees take their histograms
as parent − right, that a classifier's trees are then the direct form's
bit for bit for whole-number weights, and how far fractional weights
move a subtracted histogram from a float64 sum."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import trees


# --------------------------------------------------------------------- #
# which levels                                                          #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("pad_depth,n_classes,precision,levels", [
    (12, 23, "bf16", range(1, 12)),     # kddcup99.train's forest
    (12, 2, "bf16", range(4, 12)),      # higgs.train's, criteo.train's
    (12, 0, "bf16", ()),                # airlines.train's regression forest
    (10, 0, "bf16", ()),                # higgs' and criteo's boosted rounds
    (6, 0, "bf16", ()),                 # airlines.train's boosted rounds
    (3, 2, "bf16", ()),                 # no level fills a tile
    (12, 7, "bf16", range(3, 12)),
    (12, 23, "f32", range(1, 12)),
    (12, 0, "f32", range(5, 12)),       # signed values subtract in f32
], ids=["kddcup99-forest", "binary-forest", "regression-forest",
        "boosted-depth10", "boosted-depth6", "shallow-binary",
        "k7-forest", "kddcup99-forest-f32", "regression-forest-f32"])
def test_hist_subtract_levels_at_the_cells_shapes(
        pad_depth, n_classes, precision, levels, monkeypatch):
    monkeypatch.setattr(trees, "HIST_PRECISION", precision)
    assert trees.hist_subtract_levels(pad_depth, n_classes) == tuple(levels)
    attrs = trees.tree_span_attrs(pad_depth, n_classes)
    assert attrs["hist_subtract"] == len(levels)
    assert attrs["leaf_sums"] == trees.leaf_sums_form(
        2 ** pad_depth, 1, n_classes)


def test_the_root_and_every_level_under_a_tile_stay_direct():
    for k in (1, 2, 3, 7, 23, 64, 200):
        levels = trees.hist_subtract_levels(14, k)
        assert 0 not in levels
        for level in range(1, 14):
            right_rows = k * 2 ** (level - 1)
            assert (level in levels) == (
                right_rows >= trees._SUBTRACT_MIN_ROWS), (k, level)


# --------------------------------------------------------------------- #
# whole-number weights: the trees of both forms, bit for bit            #
# --------------------------------------------------------------------- #

def _grow(Xb, y, w, depth, nb, k, layout, direct, monkeypatch):
    with monkeypatch.context() as m:
        if direct:
            m.setattr(trees, "hist_subtract_levels", lambda *a, **kw: ())
        return jax.jit(lambda a, b, c, lay: trees.grow_tree(
            a, b, c, depth, nb, reg_lambda=1e-6, layout=lay,
            n_classes=k))(Xb, y, w, layout)


def _table(k, layout_kind, n=3000, d=6, nb=8, seed=0):
    rng = np.random.default_rng(seed + k)
    Xb = rng.integers(0, nb, size=(n, d))
    layout = None
    if layout_kind == "two-block":      # two 0/1 columns: their own block
        Xb[:, 4:] = rng.integers(0, 2, size=(n, 2)) * (nb - 1)
        layout = trees.hist_layout(np.arange(d) >= 4)
    y = rng.integers(0, k, n)
    y[Xb[:, 0] > nb // 2] = 0           # a split worth finding
    return (jnp.asarray(Xb, jnp.int8), jnp.asarray(y, jnp.int32), layout,
            rng)


# the depth crosses the gate: one level at least comes by subtraction
@pytest.mark.parametrize("layout_kind", ["uniform", "two-block"])
@pytest.mark.parametrize("k,depth", [(2, 8), (7, 7), (23, 6)])
def test_whole_number_weights_grow_the_direct_forms_tree(
        k, depth, layout_kind, monkeypatch):
    monkeypatch.setattr(trees, "HIST_PRECISION", "bf16")
    levels = list(trees.hist_subtract_levels(depth, k))
    assert levels
    Xb, y, layout, rng = _table(k, layout_kind)
    # a bootstrap's Poisson counts under a 0/1 fold mask
    w = jnp.asarray(rng.poisson(1.0, Xb.shape[0])
                    * (rng.random(Xb.shape[0]) < 2 / 3), jnp.float32)
    sub = _grow(Xb, y, w, depth, 8, k, layout, False, monkeypatch)
    direct = _grow(Xb, y, w, depth, 8, k, layout, True, monkeypatch)
    for key in ("feat", "bin", "leaf"):
        assert np.array_equal(np.asarray(sub[key]), np.asarray(direct[key])), key
    # the nodes of the subtracted levels split: their histograms decided
    assert (np.asarray(sub["bin"])[levels] < 8).sum() >= 8


@pytest.mark.parametrize("precision", ["bf16", "f32"])
@pytest.mark.parametrize("k", [2, 23])
def test_subtracted_class_histograms_equal_the_direct_ones(k, precision,
                                                           monkeypatch):
    # one level, whole-number weights: parent − right is the direct left
    # histogram to the bit, and the weight histogram its class sum
    monkeypatch.setattr(trees, "HIST_PRECISION", precision)
    parent, right, direct, _ = _level(k, whole=True)
    hg, hh = trees._subtract_siblings(parent, right, True)
    assert hg.shape == direct[0].shape and hh.shape == direct[1].shape
    np.testing.assert_array_equal(np.asarray(hg), np.asarray(direct[0]))
    np.testing.assert_array_equal(np.asarray(hh), np.asarray(direct[1]))


# --------------------------------------------------------------------- #
# fractional weights: within float32 rounding of a float64 sum          #
# --------------------------------------------------------------------- #

def _level(k, whole, n=4000, d=5, nb=8, parents=4, seed=5):
    """The (hg, hh) of one level of `2 · parents` nodes: the parents',
    the right children's grouped by parent, and the level's direct ones;
    and the rows (Xb, node, cls, H, bins) for an oracle."""
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, nb, size=(n, d)).astype(np.int8)
    B = trees.bins_onehot(jnp.asarray(Xb), nb)
    node = rng.integers(0, 2 * parents, n)
    cls = rng.integers(0, k, n)
    H = (rng.poisson(1.0, n) * (rng.random(n) < 2 / 3) if whole
         else rng.uniform(0.0, 3.0, n)).astype(np.float32)
    right = (node & 1).astype(np.float32)
    node_j, cls_j, H_j = (jnp.asarray(node, jnp.int32),
                          jnp.asarray(cls, jnp.int32), jnp.asarray(H))
    parent = trees._class_histograms(B, node_j >> 1, cls_j, H_j, parents, k)
    right_h = trees._class_histograms(B, node_j >> 1, cls_j,
                                      H_j * jnp.asarray(right), parents, k)
    direct = trees._class_histograms(B, node_j, cls_j, H_j, 2 * parents, k)
    return parent, right_h, direct, (Xb, node, cls, H, nb)


def test_fractional_weights_subtract_within_float32_rounding(monkeypatch):
    monkeypatch.setattr(trees, "HIST_PRECISION", "bf16")
    k = 23
    parent, right, _, (Xb, node, cls, H, nb) = _level(k, whole=False)
    hg, hh = trees._subtract_siblings(parent, right, True)
    # the float64 oracle over the same bf16-narrowed weights
    Hq = np.asarray(jnp.asarray(H).astype(jnp.bfloat16), np.float64)
    want = np.zeros((k, int(node.max()) + 1, Xb.shape[1], nb))
    for r in range(Xb.shape[0]):
        want[cls[r], node[r], np.arange(Xb.shape[1]), Xb[r]] += Hq[r]
    # a left cell is the difference of two float32 sums, each within a
    # few ulps of its own float64 sum: at most a few ulps of the PARENT's
    # cell, whatever the cell itself holds
    parent64 = want.reshape(k, -1, 2, *want.shape[2:]).sum(2)
    tol = 4 * np.spacing(np.repeat(parent64, 2, axis=1).astype(np.float32))
    assert np.all(np.abs(np.asarray(hg, np.float64) - want) <= tol)
    # the weights' histogram is the class sum, as the direct form's is
    np.testing.assert_array_equal(np.asarray(hh), np.asarray(hg.sum(0)))
