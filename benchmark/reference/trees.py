"""Histogram trees in plain jax.numpy: quantile bins, exact float32
histograms, the second-order split gain, level-wise growth, the walk.

A tree is {"feat": (depth, 2^depth) int, "bin": (depth, 2^depth) int,
"leaf": (2^depth, m) float32}: node k of a level sends a row right when
its bin in feature `feat` is above `bin`; `bin == n_bins` means no split.
Targets G (n, m) and weights H (n,); gain = sum_m GL^2/(HL+lam) +
sum_m GR^2/(HR+lam) - sum_m G^2/(H+lam); leaf = soft(G, alpha)/(H+lam).

Histograms are a one-hot product at `highest` over blocks of rows, so a
table of any height fits; `quant` narrows the VALUES (never the 0/1
operands) to a lower precision first: `bf16` is what the configuration
states for the program's histograms, `fp8` is the control.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BLOCK = 1 << 17
MCW_SLACK = 2.0 ** -7       # one bfloat16 step
LEAF_ROWS = 4096            # verify()'s leaf sample: see there
QUANT = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def quantile_edges(X: np.ndarray, n_bins: int) -> np.ndarray:
    """(d, n_bins-1) float32: the interior n_bins-quantiles per column."""
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    return np.ascontiguousarray(
        np.quantile(np.asarray(X, np.float64), qs, axis=0).T, np.float32)


@jax.jit
def _bin_block(X, edges):
    return (X[:, :, None] >= edges[None]).sum(-1).astype(jnp.int8)


def bin_matrix(X, edges, dtype=None) -> jnp.ndarray:
    """(n, d) int8: how many edges each value reaches. `dtype` rounds
    the values first (the scoring control)."""
    edges = jnp.asarray(edges, jnp.float32)
    out = []
    for s in range(0, X.shape[0], BLOCK * 4):
        blk = jnp.asarray(X[s:s + BLOCK * 4], jnp.float32)
        if dtype is not None:
            blk = blk.astype(dtype).astype(jnp.float32)
        out.append(_bin_block(blk, edges))
    return jnp.concatenate(out)


def _quantize(v, quant: Optional[str]):
    if quant is None:
        return v
    return v.astype(QUANT[quant]).astype(jnp.float32)


@partial(jax.jit, static_argnames=("n_slots", "n_bins", "quant"))
def slot_histograms(Xb, slot, vals, n_slots: int, n_bins: int,
                    quant: Optional[str] = None):
    """(c, n_slots, d, n_bins) float32 sums of each value column over the
    rows of each slot; rows with slot -1 count nowhere."""
    n, d = Xb.shape
    c = vals.shape[1]
    n_blocks = -(-n // BLOCK)
    pad = n_blocks * BLOCK - n
    Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
    slot = jnp.pad(slot, (0, pad), constant_values=-1)
    vals = _quantize(jnp.pad(vals, ((0, pad), (0, 0))), quant)

    def body(i, acc):
        xb = jax.lax.dynamic_slice_in_dim(Xb, i * BLOCK, BLOCK)
        sl = jax.lax.dynamic_slice_in_dim(slot, i * BLOCK, BLOCK)
        vl = jax.lax.dynamic_slice_in_dim(vals, i * BLOCK, BLOCK)
        A = jax.nn.one_hot(sl, n_slots, dtype=jnp.float32)
        B = jax.nn.one_hot(xb, n_bins, dtype=jnp.float32).reshape(
            BLOCK, d * n_bins)
        parts = [jnp.matmul((A * vl[:, j:j + 1]).T, B, precision=HI)
                 for j in range(c)]
        return acc + jnp.stack(parts)

    acc = jax.lax.fori_loop(
        0, n_blocks, body, jnp.zeros((c, n_slots, d * n_bins), jnp.float32))
    return acc.reshape(c, n_slots, d, n_bins)


def gain_table(hg: np.ndarray, hh: np.ndarray, lam: float, mcw: float,
               fmask: Optional[np.ndarray]) -> np.ndarray:
    """(slots, d, bins) float64 gain of splitting after each bin; -inf
    where a child is under `mcw` or the feature is masked."""
    hg, hh = hg.astype(np.float64), hh.astype(np.float64)
    cg, ch = np.cumsum(hg, -1), np.cumsum(hh, -1)
    tg, th = cg[..., -1:], ch[..., -1:]

    def score(g, h):
        return (g ** 2).sum(0) / (h + lam)

    gain = score(cg, ch) + score(tg - cg, th - ch) - score(tg, th)
    ok = (ch >= mcw) & ((th - ch) >= mcw)
    if fmask is not None:
        ok = ok & np.asarray(fmask, bool)[None, :, None]
    return np.where(ok, gain, -np.inf)


@jax.jit
def _route(Xb, node, feat_l, bin_l):
    f = feat_l[node]
    b = bin_l[node]
    xb = jnp.take_along_axis(Xb, f[:, None].astype(jnp.int32), 1)[:, 0]
    return node * 2 + (xb.astype(jnp.int32) > b).astype(jnp.int32)


def walk(tree: Dict, Xb) -> jnp.ndarray:
    """(n,) leaf index of every row."""
    node = jnp.zeros(Xb.shape[0], jnp.int32)
    feat, bins = jnp.asarray(tree["feat"]), jnp.asarray(tree["bin"])
    for level in range(feat.shape[0]):
        node = _route(Xb, node, feat[level], bins[level])
    return node


def _soft(g, alpha):
    return np.sign(g) * np.maximum(np.abs(g) - alpha, 0.0)


def grow(Xb, G, H, depth: int, n_bins: int, lam: float, mcw: float,
         min_gain: float, min_gain_norm: float, fmask=None,
         alpha: float = 0.0, quant: Optional[str] = None,
         leaf_quant: Optional[str] = None) -> Dict:
    """Grow one tree level by level from whole-level histograms.
    `quant` narrows the histogram values, `leaf_quant` the leaf sums."""
    n, d = Xb.shape
    m = G.shape[1]
    width = 2 ** depth
    feats = np.zeros((depth, width), np.int32)
    bins = np.full((depth, width), n_bins, np.int32)
    vals = jnp.concatenate([G, H[:, None]], 1)
    node = jnp.zeros(n, jnp.int32)
    for level in range(depth):
        k = 2 ** level
        # two compiled widths serve every level of a depth-10 tree
        n_slots = next((s for s in (32, 512) if k <= s), k)
        h = np.asarray(slot_histograms(Xb, node, vals, n_slots, n_bins,
                                       quant))[:, :k]
        gain = gain_table(h[:m], h[m], lam, mcw, fmask).reshape(k, -1)
        best = gain.argmax(1)
        best_gain = gain[np.arange(k), best]
        thr = np.maximum(min_gain, min_gain_norm * h[m][:, 0, :].sum(-1))
        split = best_gain > thr
        feats[level, :k] = best // n_bins
        bins[level, :k] = np.where(split, best % n_bins, n_bins)
        node = _route(Xb, node, jnp.asarray(feats[level]),
                      jnp.asarray(bins[level]))
    sums = np.asarray(leaf_sums(node, vals, width, quant=leaf_quant))
    leaf = _soft(sums[:, :m], alpha) / (sums[:, m:] + lam)
    return {"feat": feats, "bin": bins, "leaf": leaf.astype(np.float32)}


@partial(jax.jit, static_argnames=("n_slots", "quant"))
def leaf_sums(slot, vals, n_slots: int, quant=None):
    """(n_slots, c) float32 column sums per slot (rows at -1 nowhere)."""
    n = slot.shape[0]
    n_blocks = -(-n // BLOCK)
    pad = n_blocks * BLOCK - n
    slot = jnp.pad(slot, (0, pad), constant_values=-1)
    vals = _quantize(jnp.pad(vals, ((0, pad), (0, 0))), quant)

    def body(i, acc):
        sl = jax.lax.dynamic_slice_in_dim(slot, i * BLOCK, BLOCK)
        vl = jax.lax.dynamic_slice_in_dim(vals, i * BLOCK, BLOCK)
        A = jax.nn.one_hot(sl, n_slots, dtype=jnp.float32)
        return acc + jnp.matmul(A.T, vl, precision=HI)

    return jax.lax.fori_loop(
        0, n_blocks, body, jnp.zeros((n_slots, vals.shape[1]), jnp.float32))


def verify(tree: Dict, Xb, G, H, n_bins: int, lam: float, mcw: float,
           min_gain: float, min_gain_norm: float, fmask, alpha: float,
           rng: np.random.Generator, per_level: int = 16
           ) -> Tuple[float, float, jnp.ndarray]:
    """Hold a tree somebody else grew against exact histograms of the
    same data, at every node of the shallow levels and a seeded sample
    of the deeper ones, and at a seeded sample of its leaves.

    Returns (split gap, leaf gap, leaf index per row). The split gap is
    the gain the tree gave away, as a share of the gain on offer, summed
    over the sampled nodes: at each, the best gain on offer less the gain
    of the split the tree took (all of it where the split is not on
    offer; for a node left unsplit, what the best gain clears its
    threshold by). Summed, because a single deep node's gain is a small
    difference of large sums and swings with the last bit of the stated
    histogram precision, while the sum is steady from seed to seed. The
    leaf gap is the widest |leaf - soft(G)/(H+lam)| over the sampled
    leaves, as a share of the largest reference leaf."""
    m = G.shape[1]
    feat, bins = np.asarray(tree["feat"]), np.asarray(tree["bin"])
    depth = feat.shape[0]
    vals = jnp.concatenate([G, H[:, None]], 1)
    node = jnp.zeros(Xb.shape[0], jnp.int32)
    lost = offered = 0.0
    for level in range(depth):
        k = 2 ** level
        rows = np.asarray(jnp.bincount(node, length=k))
        live = np.flatnonzero(rows > 0)
        sel = live if len(live) <= per_level else np.sort(
            rng.choice(live, per_level, replace=False))
        slot_of = np.full(k, -1, np.int32)
        slot_of[sel] = np.arange(len(sel), dtype=np.int32)
        h = np.asarray(slot_histograms(
            Xb, jnp.asarray(slot_of)[node], vals, per_level, n_bins))
        h = h[:, :len(sel)]
        best = gain_table(h[:m], h[m], lam, mcw, fmask).reshape(
            len(sel), -1).max(1)
        # the split taken is priced with the child-weight floor eased by
        # MCW_SLACK: a child that holds the floor exactly (four rows of
        # hessian 0.25 against a floor of 1) falls either side of it on
        # the last bit of the stated histogram precision
        gain = gain_table(h[:m], h[m], lam, mcw * (1 - MCW_SLACK), fmask)
        thr = np.maximum(min_gain, min_gain_norm * h[m][:, 0, :].sum(-1))
        for i, nd in enumerate(sel):
            f, b = int(feat[level, nd]), int(bins[level, nd])
            if not np.isfinite(best[i]) or best[i] <= thr[i]:
                # nothing on offer: the node has to stay whole; a split
                # taken all the same costs what it falls short of the bar
                if b < n_bins:
                    g = gain[i, f, b] if np.isfinite(gain[i, f, b]) else 0.0
                    lost += max(0.0, thr[i] - g)
                    offered += max(thr[i], 1e-12)
                continue
            offered += best[i]
            if b >= n_bins:                     # left whole with gain on offer
                lost += best[i] - thr[i]
            elif not np.isfinite(gain[i, f, b]) or gain[i, f, b] <= thr[i]:
                lost += best[i]                 # a split that is not on offer
            else:
                lost += max(0.0, best[i] - gain[i, f, b])
        node = _route(Xb, node, jnp.asarray(feat[level]),
                      jnp.asarray(bins[level]))
    width = 2 ** depth
    rows = np.asarray(jnp.bincount(node, length=width))
    # leaves of at most LEAF_ROWS rows, summed in float64 on the host: a
    # float32 running sum of r near-equal values is off by about
    # r * 2^-24 / 4 of itself, so a branch that stopped splitting early
    # (tens of thousands of rows) reads 1e-4 and more in a sound float32
    # program, and the reference must bring no such error of its own
    live = np.flatnonzero((rows > 0) & (rows <= LEAF_ROWS))
    if len(live) < 8:
        live = np.flatnonzero(rows > 0)
    n_leaf = 64
    sel = live if len(live) <= n_leaf else np.sort(
        rng.choice(live, n_leaf, replace=False))
    leaf_of = np.asarray(node)
    vals64 = np.asarray(vals, np.float64)
    sums = np.stack([np.bincount(leaf_of, vals64[:, c], width)
                     for c in range(m + 1)], 1)[sel]
    ref = _soft(sums[:, :m], alpha) / (sums[:, m:] + lam)
    got = np.asarray(tree["leaf"], np.float64)[sel]
    leaf_gap = float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-12))
    split_gap = lost / offered if offered > 0 else 0.0
    return float(split_gap), leaf_gap, node


def leaf_values(tree: Dict, leaf_idx) -> jnp.ndarray:
    """(n, m) the leaf rows a walk selected."""
    return jnp.asarray(tree["leaf"])[leaf_idx]


def gbt_grad_hess(margin, y, w):
    """Logistic second-order targets: G = -(p - y) w, H = max(p(1-p), 1e-6) w."""
    p = jax.nn.sigmoid(margin)
    return (-(p - y) * w)[:, None], jnp.maximum(p * (1 - p), 1e-6) * w


def gbt_margin(trees: Dict, Xb, learning_rate: float) -> jnp.ndarray:
    """(n,) boosted margin: learning_rate * sum of each tree's leaf."""
    n_trees = np.asarray(trees["feat"]).shape[0]
    margin = jnp.zeros(Xb.shape[0], jnp.float32)
    for t in range(n_trees):
        tree = {k: np.asarray(v)[t] for k, v in trees.items()}
        margin = margin + learning_rate * leaf_values(
            tree, walk(tree, Xb))[:, 0]
    return margin


def gbt_predict(trees: Dict, Xb, learning_rate: float) -> dict:
    p1 = jax.nn.sigmoid(gbt_margin(trees, Xb, learning_rate))
    return {"probability": jnp.stack([1 - p1, p1], 1),
            "prediction": (p1 >= 0.5).astype(jnp.int32)}


def forest_predict(trees: Dict, Xb) -> dict:
    """Mean of the trees' leaf class distributions."""
    n_trees = np.asarray(trees["feat"]).shape[0]
    acc = 0.0
    for t in range(n_trees):
        tree = {k: np.asarray(v)[t] for k, v in trees.items()}
        acc = acc + leaf_values(tree, walk(tree, Xb))
    prob = acc / n_trees
    prob = prob / jnp.maximum(prob.sum(-1, keepdims=True), 1e-12)
    return {"probability": prob, "prediction": jnp.argmax(prob, -1)}


def forest_bootstrap(seed: int, n_trees: int, tree: int, n: int, d: int,
                     subsample_features: bool = True):
    """The seed's row weights and feature mask for one tree of a forest:
    per-tree keys split from PRNGKey(seed); Poisson(1) row counts from
    the first half of the tree's key; the floor(sqrt(d)) smallest of d
    uniforms from the second half pick its features."""
    key = jax.random.split(jax.random.PRNGKey(seed), n_trees)[tree]
    k1, k2 = jax.random.split(key)
    boot = jax.random.poisson(k1, 1.0, (n,)).astype(jnp.float32)
    if not subsample_features:
        return boot, np.ones(d, bool)
    scores = np.asarray(jax.random.uniform(k2, (d,)))
    n_sub = max(int(np.sqrt(d)), 1)
    return boot, scores <= np.sort(scores)[n_sub - 1]
