"""Softmax boosting (XGBoost's `multi:softprob`) in plain jax.numpy and
numpy, from the published algorithm: no kernel of the program, no vmap.

A round of a chain over K classes, from the margin (n, K) (0 at the
start, XGBoost's `base_score` 0.5 being margin 0):

    p = softmax(margin),  G = (p - onehot(y)) w,  H = max(p (1 - p), 1e-6) w

then K trees, one a class, each grown level by level to `depth` from
exact histograms of its own class's (G, H) over the binned matrix: a
node's split is the (column, bin) of the largest XGBoost gain

    GL^2 / (HL + lam) + GR^2 / (HR + lam) - G^2 / (H + lam)

among those whose children both hold at least `mcw` of H (the first
largest in (column, bin) order), taken where it clears
max(gamma, min_gain_norm * H); a row goes right where its bin is above
the split bin; a leaf is -soft(sum G, alpha) / (sum H + lam). Every
class's margin then moves by eta times its tree's leaf. Early stopping
(`val_w`, `early_stopping_rounds`): after each round the weighted
multiclass log-loss of the validation rows (XGBoost's `mlogloss`); the
chain stops before the first round whose start has gone that many
rounds without improving by 1e-7.

Departures from XGBoost, each the program's too:
- bins: 32 quantile bins a column from `reference/trees.py`
  `quantile_edges` (`reference/bins.py` for a 0/1 column), not
  XGBoost's weighted quantile sketch of 256 bins;
- fixed-depth level-wise trees: a node that does not split keeps all
  its rows on the left, down to the last level (XGBoost prunes it);
- no row or column sampling (the configurations' rates are 1);
- the histogram and leaf sums are float32 sums of one-hot products, in
  blocks of rows, on whatever device JAX has: exact products (values
  narrowed to bfloat16 or fp8 enter a bfloat16 product exactly; float32
  values take `Precision.HIGHEST`). Exact float64 sums of K trees a
  round are 2 n K x slots x nodes multiply-adds, 64 TFLOP a round at
  374,569 rows x 1,920 slots x 355 classes x 63 nodes: minutes a round
  on a host. The gains and every reading are float64 on the host. Every
  lookup of a (row, class) is a one-hot select or product, no gather (a
  TPU serialises a gather of n K indices).

`quant` narrows the histogram VALUES first (`bf16` is the stated
precision of the program's histograms, `fp8` the control's) and
`leaf_quant` the leaf sums' values (the control's `bf16`).

A chain's trees: {"feat": (R, K, depth, 2^depth), "bin": the same,
"leaf": (R, K, 2^depth)}; `bin == n_bins` is no split.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.trees import HI, QUANT

BLOCK = 1 << 12             # rows a block of the histogram products
LEAF_ROWS = 4096            # leaves `verify_round` prices: see there


def _quantize(v, quant: Optional[str]):
    if quant is None:
        return v
    return v.astype(QUANT[quant]).astype(jnp.float32)


def _product(a, b, quant: Optional[str]):
    """a.T @ b, float32 sums of exact products: one bfloat16 pass where
    `a`'s values were narrowed to `quant` (a one-hot `b` is exact in
    bfloat16 too), `Precision.HIGHEST` for float32 values."""
    if quant is None:
        return jnp.matmul(a.T, b, precision=HI)
    return jnp.matmul(a.T.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


@jax.jit
def grad_hess(margin, y, w):
    """(G, H) (n, K) float32 of a margin (n, K), labels (n,), weights."""
    p = jax.nn.softmax(margin, axis=1)
    Y = jax.nn.one_hot(y.astype(jnp.int32), margin.shape[1],
                       dtype=jnp.float32)
    return (p - Y) * w[:, None], jnp.maximum(p * (1.0 - p), 1e-6) * w[:, None]


@partial(jax.jit, static_argnames=("n_nodes", "n_bins", "quant"))
def class_histograms(Xb, node, G, H, n_nodes: int, n_bins: int,
                     quant: Optional[str] = None):
    """(2, K, n_nodes, d, n_bins) float32: the sums of G and of H of each
    class over the rows of each of that class's tree's nodes, by column
    and bin. `node` (n, K) is each row's node in each class's tree."""
    n, d = Xb.shape
    k = G.shape[1]
    n_blocks = -(-n // BLOCK)
    pad = n_blocks * BLOCK - n
    Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
    node = jnp.pad(node, ((0, pad), (0, 0)))
    G = _quantize(jnp.pad(G, ((0, pad), (0, 0))), quant)
    H = _quantize(jnp.pad(H, ((0, pad), (0, 0))), quant)

    def body(i, acc):
        xb = jax.lax.dynamic_slice_in_dim(Xb, i * BLOCK, BLOCK)
        nd = jax.lax.dynamic_slice_in_dim(node, i * BLOCK, BLOCK)
        g = jax.lax.dynamic_slice_in_dim(G, i * BLOCK, BLOCK)
        h = jax.lax.dynamic_slice_in_dim(H, i * BLOCK, BLOCK)
        B = jax.nn.one_hot(xb, n_bins, dtype=jnp.float32).reshape(
            BLOCK, d * n_bins)
        A = jax.nn.one_hot(nd, n_nodes, dtype=jnp.float32)  # (B, K, nodes)
        parts = [_product((A * v[:, :, None]).reshape(BLOCK, k * n_nodes),
                          B, quant) for v in (g, h)]
        return acc + jnp.stack(parts)

    acc = jax.lax.fori_loop(
        0, n_blocks, body,
        jnp.zeros((2, k * n_nodes, d * n_bins), jnp.float32))
    return acc.reshape(2, k, n_nodes, d, n_bins)


def gains(hg: np.ndarray, hh: np.ndarray, lam: float, mcw: float
          ) -> np.ndarray:
    """(..., d, bins) float64 gain of splitting after each bin of one
    tree's node histograms; -inf where a child is under `mcw`."""
    hg, hh = hg.astype(np.float64), hh.astype(np.float64)
    cg, ch = np.cumsum(hg, -1), np.cumsum(hh, -1)
    tg, th = cg[..., -1:], ch[..., -1:]

    def score(g, h):
        return g ** 2 / (h + lam)

    gain = score(cg, ch) + score(tg - cg, th - ch) - score(tg, th)
    return np.where((ch >= mcw) & ((th - ch) >= mcw), gain, -np.inf)


@jax.jit
def _route(Xb, node, feat_l, bin_l):
    """Each row's child in each class's tree: (n, K) node ids of one
    level from its (K, nodes) split tables. Every node's comparison is
    made for every row (the split columns of all K x nodes nodes picked
    by one one-hot product, a block of rows at a time), and each row
    keeps its own node's."""
    k, nodes = feat_l.shape
    n, d = Xb.shape
    n_blocks = -(-n // BLOCK)
    pad = n_blocks * BLOCK - n
    pick = jax.nn.one_hot(feat_l.reshape(-1), d, dtype=jnp.bfloat16)
    thr = bin_l.astype(jnp.float32)[None]

    def block(args):
        xb, nd = args
        cols = jnp.matmul(xb.astype(jnp.bfloat16), pick.T,
                          preferred_element_type=jnp.float32)  # exact bins
        right = cols.reshape(-1, k, nodes) > thr
        mine = nd[:, :, None] == jnp.arange(nodes)[None, None, :]
        return nd * 2 + (right & mine).any(-1).astype(jnp.int32)

    out = jax.lax.map(block, (
        jnp.pad(Xb, ((0, pad), (0, 0))).reshape(n_blocks, BLOCK, d),
        jnp.pad(node, ((0, pad), (0, 0))).reshape(n_blocks, BLOCK, k)))
    return out.reshape(n_blocks * BLOCK, k)[:n]


@partial(jax.jit, static_argnames=("width", "quant"))
def leaf_sums(leaf_idx, G, H, width: int, quant: Optional[str] = None):
    """(2, K, width) float32 sums of G and H of each class over the rows
    of each leaf of that class's tree."""
    n, k = G.shape
    n_blocks = -(-n // BLOCK)
    pad = n_blocks * BLOCK - n
    leaf_idx = jnp.pad(leaf_idx, ((0, pad), (0, 0)))
    G = _quantize(jnp.pad(G, ((0, pad), (0, 0))), quant)
    H = _quantize(jnp.pad(H, ((0, pad), (0, 0))), quant)

    def body(i, acc):
        li = jax.lax.dynamic_slice_in_dim(leaf_idx, i * BLOCK, BLOCK)
        A = li[:, :, None] == jnp.arange(width)[None, None, :]  # (B, K, W)
        parts = [jnp.where(A, jax.lax.dynamic_slice_in_dim(
            v, i * BLOCK, BLOCK)[:, :, None], 0.0).sum(0) for v in (G, H)]
        return acc + jnp.stack(parts)

    return jax.lax.fori_loop(0, n_blocks, body,
                             jnp.zeros((2, k, width), jnp.float32))


def _soft(g, alpha: float):
    return np.sign(g) * np.maximum(np.abs(g) - alpha, 0.0)


def _leaves(sums: np.ndarray, lam: float, alpha: float) -> np.ndarray:
    """(K, width) leaf values from (2, K, width) gradient and hessian
    sums: -soft(G) / (H + lam)."""
    s = np.asarray(sums, np.float64)
    return -_soft(s[0], alpha) / (s[1] + lam)


def grow_round(Xb, G, H, depth: int, n_bins: int, lam: float, mcw: float,
               min_gain: float = 0.0, min_gain_norm: float = 0.0,
               alpha: float = 0.0, quant: Optional[str] = None,
               leaf_quant: Optional[str] = None) -> Tuple[Dict, jnp.ndarray]:
    """One round's K trees from its (G, H) (n, K): ({"feat", "bin":
    (K, depth, 2^depth), "leaf": (K, 2^depth)}, (n, K) leaf of every
    row in every class's tree)."""
    Xb = jnp.asarray(Xb)
    n, d = Xb.shape
    k = G.shape[1]
    width = 2 ** depth
    feats = np.zeros((k, depth, width), np.int32)
    bins = np.full((k, depth, width), n_bins, np.int32)
    node = jnp.zeros((n, k), jnp.int32)
    for level in range(depth):
        nodes = 2 ** level
        h = np.asarray(class_histograms(Xb, node, G, H, nodes, n_bins,
                                        quant), np.float64)
        gain = gains(h[0], h[1], lam, mcw).reshape(k, nodes, d * n_bins)
        best = gain.argmax(-1)
        best_gain = np.take_along_axis(gain, best[..., None], -1)[..., 0]
        thr = np.maximum(min_gain, min_gain_norm * h[1][:, :, 0, :].sum(-1))
        feats[:, level, :nodes] = best // n_bins
        bins[:, level, :nodes] = np.where(best_gain > thr, best % n_bins,
                                          n_bins)
        node = _route(Xb, node, jnp.asarray(feats[:, level]),
                      jnp.asarray(bins[:, level]))
    leaf = _leaves(leaf_sums(node, G, H, width, leaf_quant), lam, alpha)
    return {"feat": feats, "bin": bins, "leaf": leaf}, node


def walk_round(trees: Dict, Xb) -> jnp.ndarray:
    """(n, K) leaf of every row in every class's tree of one round."""
    Xb = jnp.asarray(Xb)
    feat, bins = np.asarray(trees["feat"]), np.asarray(trees["bin"])
    node = jnp.zeros((Xb.shape[0], feat.shape[0]), jnp.int32)
    for level in range(feat.shape[1]):
        node = _route(Xb, node, jnp.asarray(feat[:, level]),
                      jnp.asarray(bins[:, level]))
    return node


@jax.jit
def _step(margin, leaf, leaf_idx, eta):
    """margin + eta * each class's leaf (K, width) at each row's leaf."""
    mine = leaf_idx[:, :, None] == jnp.arange(leaf.shape[1])[None, None, :]
    return margin + eta * jnp.where(mine, leaf[None], 0.0).sum(-1)


def mlogloss(margin, y, w) -> float:
    """Weighted multiclass log-loss (float64) of a margin (n, K)."""
    m = np.asarray(margin, np.float64)
    m = m - m.max(1, keepdims=True)
    logp = m - np.log(np.exp(m).sum(1, keepdims=True))
    ll = -logp[np.arange(len(m)), np.asarray(y).astype(np.int64)]
    w = np.asarray(w, np.float64)
    return float((ll * w).sum() / max(w.sum(), 1.0))


def boost(Xb, y, w, n_classes: int, rounds: int, depth: int, n_bins: int,
          eta: float, lam: float = 1.0, mcw: float = 1.0,
          min_gain: float = 0.0, min_gain_norm: float = 0.0,
          alpha: float = 0.0, quant: Optional[str] = None,
          leaf_quant: Optional[str] = None, val_w=None,
          early_stopping_rounds: int = 0) -> Tuple[Dict, jnp.ndarray]:
    """A chain of up to `rounds` rounds: (trees, final (n, K) margin)."""
    Xb = jnp.asarray(Xb)
    yj = jnp.asarray(y, jnp.float32)
    wj = jnp.asarray(w, jnp.float32)
    margin = jnp.zeros((Xb.shape[0], n_classes), jnp.float32)
    grown = {"feat": [], "bin": [], "leaf": []}
    best, since = np.inf, 0
    for _ in range(rounds):
        if early_stopping_rounds and since >= early_stopping_rounds:
            break
        G, H = grad_hess(margin, yj, wj)
        tree, leaf_idx = grow_round(
            Xb, G, H, depth, n_bins, lam, mcw, min_gain, min_gain_norm,
            alpha, quant, leaf_quant)
        for key in grown:
            grown[key].append(tree[key])
        margin = _step(margin, jnp.asarray(tree["leaf"], jnp.float32),
                       leaf_idx, jnp.float32(eta))
        if early_stopping_rounds:
            m = mlogloss(margin, y, val_w)
            since = 0 if m < best - 1e-7 else since + 1
            best = min(best, m)
    return {key: np.stack(v) for key, v in grown.items()}, margin


def predict_margin(trees: Dict, Xb, eta: float) -> jnp.ndarray:
    """(n, K) margin of a chain's trees (leaves (R, K, 2^depth))."""
    Xb = jnp.asarray(Xb)
    feat = np.asarray(trees["feat"])
    margin = jnp.zeros((Xb.shape[0], feat.shape[1]), jnp.float32)
    for r in range(feat.shape[0]):
        one = {key: np.asarray(v)[r] for key, v in trees.items()}
        margin = _step(margin, jnp.asarray(one["leaf"], jnp.float32),
                       walk_round(one, Xb), jnp.float32(eta))
    return margin


def teacher_margin(trees: Dict, Xb, y, w, eta: float, lam: float,
                   alpha: float = 0.0, leaf_quant: Optional[str] = None
                   ) -> Tuple[np.ndarray, jnp.ndarray]:
    """((R, K, 2^depth) leaves, (n, K) margin) of a chain whose every
    tree keeps the SPLITS of `trees` (somebody else's chain) but takes
    its leaves from this module's own gradients: each round's (G, H)
    from the margin the rounds before left, summed over the rows each
    leaf holds."""
    Xb = jnp.asarray(Xb)
    yj = jnp.asarray(y, jnp.float32)
    wj = jnp.asarray(w, jnp.float32)
    feat = np.asarray(trees["feat"])
    width = 2 ** feat.shape[2]
    margin = jnp.zeros((Xb.shape[0], feat.shape[1]), jnp.float32)
    leaves = []
    for r in range(feat.shape[0]):
        one = {key: np.asarray(v)[r] for key, v in trees.items()}
        leaf_idx = walk_round(one, Xb)
        G, H = grad_hess(margin, yj, wj)
        leaves.append(_leaves(leaf_sums(leaf_idx, G, H, width, leaf_quant),
                              lam, alpha))
        margin = _step(margin, jnp.asarray(leaves[-1], jnp.float32),
                       leaf_idx, jnp.float32(eta))
    return np.stack(leaves), margin


def verify_round(trees: Dict, Xb, G, H, n_bins: int, lam: float, mcw: float,
                 min_gain: float = 0.0, min_gain_norm: float = 0.0,
                 alpha: float = 0.0, rng: Optional[np.random.Generator] = None,
                 n_leaves: int = 256) -> Tuple[float, float]:
    """Hold one round's K trees that somebody else grew from (G, H)
    against exact histograms of the same data, at every node of every
    class's tree, and at a seeded sample of their leaves.

    Returns (split gap, leaf gap), as `reference/trees.py` `verify`
    defines them: the gain the trees gave away as a share of the gain
    on offer, summed over the nodes (a split priced with the child
    floor eased by one bfloat16 step, a node left whole priced by what
    its best gain clears the bar by); the widest |leaf - reference leaf|
    over the sampled leaves of at most `LEAF_ROWS` rows, as a share of
    the largest reference leaf among them."""
    from reference.trees import MCW_SLACK
    Xb = jnp.asarray(Xb)
    n, d = Xb.shape
    feat, bins = np.asarray(trees["feat"]), np.asarray(trees["bin"])
    k, depth = feat.shape[:2]
    width = 2 ** depth
    node = jnp.zeros((n, k), jnp.int32)
    lost = offered = 0.0
    for level in range(depth):
        nodes = 2 ** level
        h = np.asarray(class_histograms(Xb, node, G, H, nodes, n_bins),
                       np.float64)
        best = gains(h[0], h[1], lam, mcw).reshape(k, nodes, -1).max(-1)
        gain = gains(h[0], h[1], lam, mcw * (1 - MCW_SLACK))
        thr = np.maximum(min_gain, min_gain_norm * h[1][:, :, 0, :].sum(-1))
        f = feat[:, level, :nodes]
        b = bins[:, level, :nodes]
        taken = np.take_along_axis(
            gain.reshape(k, nodes, -1),
            (f * n_bins + np.minimum(b, n_bins - 1))[..., None], -1)[..., 0]
        taken = np.where(np.isfinite(taken), taken, 0.0)
        split = b < n_bins
        on_offer = np.isfinite(best) & (best > thr)
        best = np.where(on_offer, best, 0.0)
        # nothing on offer: the node has to stay whole; a split taken all
        # the same costs what it falls short of the bar
        whole = ~on_offer & split
        lost += np.where(whole, np.maximum(0.0, thr - taken), 0.0).sum()
        offered += np.where(whole, np.maximum(thr, 1e-12), 0.0).sum()
        # gain on offer: left whole, a split not on offer, or one short
        offered += best.sum()
        lost += np.where(on_offer, np.where(
            ~split, best - thr, np.where(
                taken <= thr, best, np.maximum(0.0, best - taken))),
            0.0).sum()
        node = _route(Xb, node, jnp.asarray(feat[:, level]),
                      jnp.asarray(bins[:, level]))
    # leaves: float64 sums on the host of the rows of a seeded sample of
    # the leaves of at most LEAF_ROWS rows (a float32 running sum of r
    # near-equal values is off by about r 2^-24 / 4 of itself)
    idx = np.asarray(node)
    flat = (idx + np.arange(k)[None, :] * width).ravel()
    rows = np.bincount(flat, minlength=k * width)
    live = np.flatnonzero((rows > 0) & (rows <= LEAF_ROWS))
    if len(live) < 8:
        live = np.flatnonzero(rows > 0)
    rng = rng or np.random.default_rng(0)
    sel = live if len(live) <= n_leaves else np.sort(
        rng.choice(live, n_leaves, replace=False))
    keep = np.zeros(k * width, bool)
    keep[sel] = True
    pick = keep[flat]
    sums = np.stack([np.bincount(flat[pick], np.asarray(v).ravel()[pick]
                                 .astype(np.float64), k * width)
                     for v in (G, H)])
    ref = -_soft(sums[0][sel], alpha) / (sums[1][sel] + lam)
    got = np.asarray(trees["leaf"], np.float64).reshape(k * width)[sel]
    leaf_gap = float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-12))
    return (float(lost / offered) if offered > 0 else 0.0), leaf_gap
