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

@pytest.mark.parametrize("pad_depth,n_classes,batched,precision,levels", [
    (12, 23, None, "bf16", range(1, 12)),   # kddcup99.train's forest
    (12, 2, None, "bf16", range(4, 12)),    # higgs.train's, criteo.train's
    (12, 0, None, "bf16", ()),              # airlines.train's regression forest
    (10, 0, None, "bf16", ()),              # higgs' and criteo's boosted rounds
    (6, 0, None, "bf16", ()),               # airlines.train's boosted rounds
    (3, 2, None, "bf16", ()),               # no level fills a tile
    (12, 7, None, "bf16", range(3, 12)),
    (12, 23, None, "f32", range(1, 12)),
    (12, 0, None, "f32", range(5, 12)),     # signed values subtract in f32
    (6, 0, 355, "bf16", range(1, 6)),       # dionis.train's softmax round
    (6, 0, 1, "bf16", ()),                  # a one-tree chain at depth 6
    (10, 0, 1, "bf16", ()),                 # ... and at depth 10
    (6, 0, 7, "bf16", range(3, 6)),         # 7 · 2^(level − 1) >= 16
    (4, 0, 4, "bf16", (3,)),
    (6, 0, 355, "f32", range(1, 6)),
    (3, 0, 2, "bf16", ()),                  # no level fills a tile
], ids=["kddcup99-forest", "binary-forest", "regression-forest",
        "boosted-depth10", "boosted-depth6", "shallow-binary",
        "k7-forest", "kddcup99-forest-f32", "regression-forest-f32",
        "dionis-softmax", "one-tree-chain-depth6", "one-tree-chain-depth10",
        "k7-softmax", "k4-softmax-depth4", "dionis-softmax-f32",
        "shallow-softmax"])
def test_hist_subtract_levels_at_the_cells_shapes(
        pad_depth, n_classes, batched, precision, levels, monkeypatch):
    monkeypatch.setattr(trees, "HIST_PRECISION", precision)
    kw = {} if batched is None else {"batched_trees": batched}
    assert trees.hist_subtract_levels(pad_depth, n_classes, **kw) \
        == tuple(levels)
    attrs = trees.tree_span_attrs(pad_depth, n_classes, **kw)
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


# --------------------------------------------------------------------- #
# a softmax round's K trees: signed bf16 gradients by subtraction       #
# --------------------------------------------------------------------- #

def _softmax_round(k=7, n=8000, d=10, nb=32, seed=41):
    """The binned rows, the labels and a seeded margin part way along a
    softmax chain."""
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, nb, size=(n, d))
    y = (Xb[:, 0] * k // nb + (Xb[:, 1] > nb // 2)) % k
    y = np.where(rng.random(n) < 0.3, rng.integers(0, k, n), y)
    # confident rows too: p spans many binades, so no float32 sum of the
    # bfloat16 values is exact and the two forms round apart
    margin = (4.0 * rng.normal(size=(n, k))
              + 3.0 * np.eye(k)[(Xb[:, 2] * k) // nb]).astype(np.float32)
    return (jnp.asarray(Xb, jnp.int8), jnp.asarray(y, jnp.float32),
            jnp.asarray(margin))


def _grow_round(Xb, y, margin, depth, nb, direct, monkeypatch):
    """The round's negated gradients and hessians (n, K), its K trees as
    `_gbt_scan` grows them (one vmap over the classes, K batched trees;
    the values times the round's row sample, all rows here: the CPU
    backend runs no batched bfloat16 product of the raw values), and
    each level's (hg, hh) of each tree."""
    k = margin.shape[1]
    split = trees._split_from_blocks

    def one_tree(g, h, rows):
        seen = []

        def spy(B, hists, *a, **kw):
            seen.append(hists[0])           # the uniform layout's block
            return split(B, hists, *a, **kw)

        with monkeypatch.context() as m:
            m.setattr(trees, "_split_from_blocks", spy)
            if direct:
                m.setattr(trees, "hist_subtract_levels", lambda *a, **kw: ())
            tree = trees.grow_tree(Xb, (g * rows)[:, None], h * rows,
                                   depth, nb,
                                   reg_lambda=1.0, min_child_weight=1.0,
                                   batched_trees=k)
        return tree, seen

    def round_(margin):
        g, h = trees.gbt_grad_hess(margin, y, jnp.ones_like(y), "softmax")
        rows = (jax.random.uniform(jax.random.PRNGKey(0), y.shape)
                < 1.0).astype(jnp.float32)
        return -g, h, jax.vmap(one_tree, in_axes=(1, 1, None))(-g, h, rows)

    G, H, (tree, seen) = jax.jit(round_)(margin)
    return (G, H, {key: np.asarray(v) for key, v in tree.items()},
            [(np.asarray(hg, np.float64)[:, 0], np.asarray(hh, np.float64))
             for hg, hh in seen])


def _route(Xb, feat, bins, level):
    """Each row's node at `level` of one tree (feat, bins: (depth, nodes))."""
    node = np.zeros(Xb.shape[0], np.int64)
    rows = np.arange(Xb.shape[0])
    for lv in range(level):
        node = node * 2 + (Xb[rows, feat[lv, node]] > bins[lv, node])
    return node


def _hist64(Xb, node, v, n_nodes, nb):
    """(nodes, d, bins) float64 sums of v by (node, column, bin)."""
    n, d = Xb.shape
    idx = (node[:, None] * d + np.arange(d)) * nb + Xb
    return np.bincount(idx.ravel(), np.repeat(v, d),
                       minlength=n_nodes * d * nb).reshape(n_nodes, d, nb)


def _gains64(hg, hh, count, lam, mcw):
    """(nodes, d·bins) float64 gains of `_best_split`, -inf where invalid
    or where a bin holds no row of the node (its split is the previous
    bin's: each distinct partition once)."""
    cg, ch = np.cumsum(hg, -1), np.cumsum(hh, -1)
    tg, th = cg[..., -1:], ch[..., -1:]

    def score(g, h):
        return g ** 2 / (h + lam)

    gain = score(cg, ch) + score(tg - cg, th - ch) - score(tg, th)
    ok = (ch >= mcw) & (th - ch >= mcw) & (count > 0)
    return np.where(ok, gain, -np.inf).reshape(hg.shape[0], -1)


def test_a_softmax_rounds_subtracted_histograms_and_trees(monkeypatch):
    monkeypatch.setattr(trees, "HIST_PRECISION", "bf16")
    depth, nb, lam, mcw = 6, 32, 1.0, 1.0
    Xb, y, margin = _softmax_round()
    k = margin.shape[1]
    levels = trees.hist_subtract_levels(depth, 0, k)
    assert levels == (3, 4, 5)
    G, H, sub, sub_hists = _grow_round(Xb, y, margin, depth, nb, False,
                                       monkeypatch)
    _, _, direct, direct_hists = _grow_round(Xb, y, margin, depth, nb, True,
                                             monkeypatch)
    Xn = np.asarray(Xb, np.int64)
    # the float64 oracle sums the same bfloat16-rounded values
    Gq = np.asarray(G.astype(jnp.bfloat16), np.float64)
    Hq = np.asarray(H.astype(jnp.bfloat16), np.float64)
    compared = split_nodes = 0
    for c in range(k):
        for level in range(depth):
            n_nodes = 2 ** level
            want = {}
            for form, tree, hists in (("sub", sub, sub_hists),
                                      ("direct", direct, direct_hists)):
                node = _route(Xn, tree["feat"][c], tree["bin"][c], level)
                wg = _hist64(Xn, node, Gq[:, c], n_nodes, nb)
                wh = _hist64(Xn, node, Hq[:, c], n_nodes, nb)
                # a subtracted cell's float32 error is that of its
                # ancestor's sum at the last level taken direct (each
                # subtracted level is taken from the one before), whatever
                # the cell itself holds
                up = level - min(level, levels[0] - 1) if form == "sub" else 0

                def scale(v):
                    return np.repeat(_hist64(Xn, node >> up, v, n_nodes >> up,
                                             nb), 2 ** up, axis=0)

                gscale, hscale = scale(np.abs(Gq[:, c])), scale(Hq[:, c])
                hg, hh = hists[level][0][c], hists[level][1][c]
                assert np.all(np.abs(hg - wg) <= 1e-5 * gscale), (form, c,
                                                                  level)
                assert np.all(np.abs(hh - wh) <= 1e-5 * hscale)
                # the hessians do not cancel: non-negative to rounding
                assert np.all(hh >= -1e-5 * hscale), (form, c, level)
                want[form] = (node, wg, wh)
            # split for split, where both trees hold the node's rows (every
            # ancestor's split equal) and float64 puts the best clear
            node, wg, wh = want["sub"]
            same_rows = np.array_equal(node, want["direct"][0])
            count = _hist64(Xn, node, np.ones(len(node)), n_nodes, nb)
            gains = _gains64(wg, wh, count, lam, mcw)
            top2 = -np.sort(-gains, axis=1)[:, :2]
            for j in range(n_nodes):
                if not same_rows:
                    break
                best, second = top2[j]
                if not np.isfinite(best) or best <= 0:
                    continue
                if best - second <= 1e-6 * abs(best):
                    continue
                compared += 1
                split_nodes += level in levels
                got = (sub["feat"][c, level, j], sub["bin"][c, level, j])
                assert got == (direct["feat"][c, level, j],
                               direct["bin"][c, level, j]), (c, level, j)
                assert got == divmod(int(np.argmax(gains[j])), nb)
    # the subtracted levels were decided, and compared, in most trees
    assert split_nodes >= k * (8 + 16 + 32) // 2, (compared, split_nodes)
