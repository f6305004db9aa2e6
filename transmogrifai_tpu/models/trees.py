"""Decision trees, random forests, and gradient boosting as XLA programs.

Reference parity: `core/.../impl/classification/OpDecisionTreeClassifier.scala`,
`OpRandomForestClassifier.scala`, `OpGBTClassifier.scala`,
`OpXGBoostClassifier.scala` and the regression counterparts — all JNI/JVM
(Spark MLlib trees, libxgboost+Rabit) in the reference (SURVEY.md §2.9).

TPU-first design (SURVEY.md §7 "Trees on TPU"):
- features are pre-binned to `max_bins` quantile buckets (fit-time
  quantiles → static shapes); a tree never sees raw floats
- trees grow LEVEL-WISE with a fixed depth: every level builds
  (nodes × features × bins × outputs) gradient/weight histograms with one
  scatter-add over the batch — the data-parallel reduction (`psum` over a
  sharded batch axis), then picks argmax-gain splits — no data-dependent
  control flow, so the whole learner jits and vmaps
- a "tree" is three dense arrays (per-level split feature, split bin,
  leaf values); prediction is `depth` gathers — fusable into the scoring
  program
- RandomForest = vmap over per-tree bootstrap weights + feature masks;
  GBT/XGBoost = `lax.scan` over boosting rounds carrying the margin, using
  second-order (grad/hess) gains — the XGBoost formulation, with `psum`
  replacing Rabit allreduce when the batch axis is sharded

Unified learner: targets G (n, m) and weights H (n,); split gain =
Σ_m GL²/(HL+λ) + Σ_m GR²/(HR+λ) − Σ_m G²/(H+λ); leaf value = G/(H+λ).
With one-hot labels as G and counts as H this is exactly gini-style
variance reduction (RF/DT classification); with gradients/hessians it is
the XGBoost gain (GBT); with y and counts it is variance reduction (reg).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import logging
import os

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu.models.base import (
    PredictionModel, PredictorEstimator, n_classes_of)
from transmogrifai_tpu.obs.trace import TRACER, pull, upload
from transmogrifai_tpu.stages.base import FitContext

log = logging.getLogger(__name__)

DEFAULT_MAX_BINS = 32


# --------------------------------------------------------------------------- #
# binning                                                                     #
# --------------------------------------------------------------------------- #

@jax.jit
def _all_zero_or_one(X):
    return jnp.all((X == 0) | (X == 1), axis=0)


def indicator_columns(X) -> np.ndarray:
    """(d,) bool: the columns whose every value is 0 or 1 — pivot levels,
    null indicators, a hand-built 0/1 vector alike. Read off the data
    (one reduction, on the device when X lives there), never off
    metadata."""
    if isinstance(X, jax.Array):
        return pull("tree:indicator", _all_zero_or_one(X))
    X = np.asarray(X)
    return np.all((X == 0) | (X == 1), axis=0)


def edges_site(X) -> str:
    """Where `quantile_bin_edges` takes X's order statistics: "device"
    for a `jax.Array` (the rule of `indicator_columns`), else "host"."""
    return "device" if isinstance(X, jax.Array) else "host"


@jax.jit
@jax.named_scope("tree:edges")
def _order_statistics(X, cols, idx):
    """The values at the sorted positions `idx` (k,) int32 of the
    columns `cols` (d_wide,) int32 of X: (d_wide, k), and (d_wide,)
    bool, whether the column holds a NaN (a NaN sorts last).

    A column at a time, as a 1-D array, sorted unstably: equal keys are
    one value here, and a stable sort carries an index operand along.
    On the chip a column of 2.16 M rows takes 2.9 ms so (4.9 stable;
    3.6 and 7.2 as a row of one 2-D operand), and the loop's scratch
    is one column whatever the table's width, where gathering 13 of 528
    columns first held 1.3 GB."""
    def one(c):
        col = jax.lax.dynamic_index_in_dim(X, c, axis=1, keepdims=False)
        s = jnp.sort(col, stable=False)
        return s[idx], jnp.isnan(s[-1])
    return jax.lax.map(one, cols)


def _device_quantiles(X, qs: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """`np.quantile(X[:, cols] as float64, qs, axis=0).T`, bit for bit,
    with the order statistics taken where X lives: numpy's `linear`
    method reads two neighbours of the sorted column a quantile, a sort
    is a permutation, so the device hands over the very values numpy
    would pick and only (d_wide, 2·len(qs)) of them cross to the host,
    which interpolates in float64 as numpy's `_lerp` does."""
    n, m = int(X.shape[0]), len(qs)
    virtual = (n - 1) * qs
    lo = np.floor(virtual)
    hi = lo + 1
    top = virtual >= n - 1          # numpy: both neighbours the last row
    lo[top] = hi[top] = -1
    t = virtual - lo
    idx = np.concatenate([lo, hi]).astype(np.int32) % n
    vals, has_nan = pull(
        "tree:edges", _order_statistics(X, cols.astype(np.int32), idx))
    vals = vals.astype(np.float64)
    a, b = vals[:, :m], vals[:, m:]
    diff = b - a
    out = a + diff * t
    upper = t >= 0.5
    out[:, upper] = (b - diff * (1 - t))[:, upper]
    out[has_nan] = np.nan
    return out


def quantile_bin_edges(X, max_bins: int = DEFAULT_MAX_BINS,
                       indicator: Optional[np.ndarray] = None) -> np.ndarray:
    """(d, max_bins-1) ascending bin edges per feature (fit-time).

    An indicator column (`indicator_columns`) has the one threshold 0.5
    in every position, so it bins to 0 or max_bins-1 whatever share of
    its rows is set (a quantile edge cannot tell a level rarer than
    1/max_bins from a constant). Quantiles are taken over the OTHER
    columns only: `np.quantile` in float64 for a host matrix; for a
    `jax.Array` the same edges from a sort on the device
    (`_device_quantiles`), so the matrix never crosses to the host."""
    if indicator is None:
        indicator = indicator_columns(X)
    d = int(X.shape[1])
    edges = np.full((d, max_bins - 1), 0.5, dtype=np.float32)
    wide = np.flatnonzero(~indicator)
    if wide.size:
        qs = np.linspace(0, 1, max_bins + 1)[1:-1]
        if edges_site(X) == "device":
            edges[wide] = _device_quantiles(X, qs, wide)
        else:
            Xw = np.asarray(X if wide.size == d else X[:, wide],
                            dtype=np.float64)
            edges[wide] = np.quantile(Xw, qs, axis=0).T
    return edges


def hist_layout(indicator: np.ndarray) -> Optional[Dict[str, jnp.ndarray]]:
    """Where the histogram operand's two blocks take their columns from:
    {"wide": (d_wide,), "ind": (d_ind,)} ascending int32 positions in the
    binned matrix, or None when no column is an indicator (one block,
    the whole matrix). The widths are shapes of a compiled program, the
    positions its arguments: tables with the same two counts share it."""
    indicator = np.asarray(indicator, bool)
    if not indicator.any():
        return None
    return {"wide": jnp.asarray(np.flatnonzero(~indicator), jnp.int32),
            "ind": jnp.asarray(np.flatnonzero(indicator), jnp.int32)}


def hist_slots(d: int, n_bins: int, layout: Optional[Dict]) -> int:
    """Histogram operand slots a row: `n_bins` a wide column, 2 an
    indicator column."""
    if layout is None:
        return int(d) * int(n_bins)
    return (int(layout["wide"].shape[0]) * int(n_bins)
            + 2 * int(layout["ind"].shape[0]))


@jax.jit
@jax.named_scope("tree:bin")
def bin_features(X: jnp.ndarray, edges: jnp.ndarray) -> jnp.ndarray:
    """(n, d) int8 bin ids in [0, max_bins) (int32 above 127 bins).

    One program, so the compare and its sum fuse: called op by op (as
    the sweep's and the refit's binning did), the (n, d, max_bins-1)
    compare is a buffer of its own — 16 GB for a million rows of 528
    columns.

    Broadcast-compare + sum (== searchsorted side="right") instead of an
    actual per-column searchsorted: binary-search gathers serialize on TPU
    while the dense compare streams on the VPU and fuses with neighbours.
    int8 storage quarters the HBM slab the predict walk re-reads every
    level (the big-data path stages int8 too)."""
    b = (X[:, :, None] >= edges[None, :, :]).sum(-1, dtype=jnp.int32)
    n_bins = edges.shape[-1] + 1        # a shape: static under the jit
    return b.astype(jnp.int8) if n_bins <= 127 else b


def _select_bin(Xb: jnp.ndarray, feat_idx: jnp.ndarray) -> jnp.ndarray:
    """Per-row feature selection Xb[r, feat_idx[r]] as a masked reduction.
    `take_along_axis` lowers to a serialized row gather on TPU; the one-hot
    compare fuses into a single VPU pass over (n, d). Accepts int8 or
    int32 bins; the selected value widens to int32 for the split compare."""
    d = Xb.shape[-1]
    onehot = jnp.arange(d, dtype=jnp.int32)[None, :] == feat_idx[:, None]
    return jnp.where(onehot, Xb, jnp.zeros((), Xb.dtype)).sum(
        axis=1, dtype=jnp.int32)


# --------------------------------------------------------------------------- #
# the level-wise learner                                                      #
# --------------------------------------------------------------------------- #

def bins_onehot(Xb: jnp.ndarray, n_bins: int) -> jnp.ndarray:
    """(n, d, bins) bf16 one-hot of a binned block — one block of the
    histogram reduction operand (`hist_operand`), built ONCE per fit
    program and reused across every level, tree and round it grows (and
    every fold and grid config vmapped over it). The 0/1 operand is
    exact in bf16; the OTHER matmul operand (gradient/hessian values in
    `_histograms`) is bf16-quantized to ~0.4% relative error — a
    deliberate precision/throughput tradeoff (full MXU rate, f32
    accumulation): near-tie split choices may differ from an f32
    scatter-add histogram, which changes individual trees but not metric
    quality (split ties are statistically arbitrary anyway)."""
    return jax.nn.one_hot(Xb, n_bins, dtype=jnp.bfloat16)


def hist_operand(Xb: jnp.ndarray, n_bins: int,
                 layout: Optional[Dict] = None) -> List[Tuple]:
    """The histogram operand as dense blocks [(name, cols, B)], each
    sized by its columns' OWN number of bins: "wide" (n, d_wide, n_bins)
    over `layout["wide"]` and "ind" (n, d_ind, 2) over `layout["ind"]`,
    whose rows sit in bin 0 or above it (`quantile_bin_edges`). The
    slots a uniform (n, d, n_bins) operand spends on an indicator
    column's 30 empty bins carry no mass, so the blocks grow the same
    trees. `cols` is None for the one block of `layout` None (no
    indicator column: the whole matrix, no gather); a block with no
    column is left out."""
    if layout is None:
        return [("wide", None, bins_onehot(Xb, n_bins))]
    blocks = []
    for name in ("wide", "ind"):
        cols = layout[name]
        if not cols.shape[0]:
            continue
        xb = jnp.take(Xb, cols, axis=1)
        if name == "ind":           # bin 0, or above it
            xb, n_bins = (xb > 0).astype(jnp.int8), 2
        blocks.append((name, cols, bins_onehot(xb, n_bins)))
    return blocks


# Histogram precision (VERDICT r3 #8 — an explicit, documented choice):
#   "bf16" (default): G/H values quantize to bf16 before the histogram
#     matmul (~0.4% relative error; the one-hot operands are EXACT 0/1 in
#     bf16 and accumulation is f32). Near-tie splits can differ from an
#     exact f32 scatter-add histogram — individual trees change, metric
#     quality does not (ties are statistically arbitrary); in exchange the
#     matmul runs at full MXU bf16 rate.
#   "f32": exact single-precision histograms (Precision.HIGHEST forces
#     true f32 even where the platform runs plain f32 matmuls at bf16) —
#     the reference bar (MLlib/XGBoost exact f32/f64 scatter histograms)
#     at roughly 1/4-1/8 the MXU throughput.
# Sibling subtraction (`hist_subtract_levels`) keys on the values and the
# shapes, not on this switch alone. A classifier's class counts subtract
# in either mode (whole numbers, exact in bf16 and in f32 sums). Signed
# gradients subtract under "f32", and under "bf16" where one product
# batches K >= 2 trees (a softmax round's class trees): the right
# children's product rounds each row's g and h to bf16 exactly as the
# parent's does, so parent − right differs from the direct left child by
# float32 summation order alone, far under the bf16 rounding the direct
# form already has. A single tree's bf16 gradients stay direct.
# Process-level switch: TRANSMOGRIFAI_HIST_PRECISION=f32, read ONCE at
# import. jax.jit caches executables by shape/static-args only, so
# mutating this global (or the env var) after fit functions have traced
# silently keeps the OLD precision for already-compiled shapes — set the
# env var before importing this module and never mutate it mid-process
# (r4 advisor). test_models.py bounds the divergence of both modes
# against an f64 oracle on near-tie data.
HIST_PRECISION = os.environ.get("TRANSMOGRIFAI_HIST_PRECISION", "bf16")


@jax.named_scope("tree:hist")
def _histograms(B, node_idx, G, H, n_nodes: int, block: str = "wide"):
    """hist_G: (m, nodes, d, bins); hist_H: (nodes, d, bins) of one block
    of the operand (`hist_operand`), under the scope `tree:hist:<block>`.

    One-hot MATMUL histograms: hist[node, f, b] = Σ_r A[r,node]·B[r,f,b]·v[r]
    computed as (nodes, n) @ (n, d·bins) on the MXU — where the FLOPs live
    on TPU. A scatter-add formulation is 20-50× slower here (TPU scatters
    serialize) and its (n, d, m) update tensor tile-pads the tiny class
    axis to 128 lanes (the r2 152 GB OOM). Contraction over the row axis
    also means a mesh-sharded batch reduces via an XLA-inserted psum —
    the Rabit-allreduce analogue (SURVEY.md §2.9).

    Per-value-column matmuls (B read m+1 times) measured FASTER than
    stacking [G, H] into one ((m+1)·nodes, n) operand at m = 2 and the
    in-core shape that was timed before PR 21's first direct chip run
    (d ≈ 55 columns of 32 bins, 1,760 slots a row): the A-side
    (n, (m+1)·nodes) concatenation cost more than the saved B reads —
    the OPPOSITE tradeoff from the out-of-core path (d=500, B per-chunk
    rebuilt), where `parallel/bigdata.py` stacks. What still comes here
    is a regressor's one column and a boosted round's gradients (m = 1:
    the operand is read twice a level, `hist_reads` 2); a CLASSIFIER's
    K columns are one-hot times one weight and need no stacking at all:
    `_class_histograms`. The regression shape, timed on one TPU v5e:
    4,500,000 rows × (6 wide + 63 two-valued columns, 318 slots), one
    value column. A depth-6 squared-loss round is 0.065 s (my chip runs,
    PR 33: five rounds of `fit_gbt_chunk`, 0.3259 s): the histograms,
    splits and routing of its six levels (10.8 ms a level) and the walk
    of the finished tree; its leaf sums are 1.5 ms, a one-hot product
    like the histograms (`_leaf_sums`). As two float32 scatter-adds of
    4.5 M rows into 64 leaves (`.at[node_idx].add`, 39 ms each, until
    PR 33) they were 0.079 s of a 0.144 s round (PR 32, from the traced
    pass's device operations; 0.1428 s in PR 33's run): at this height
    the leaf sums, not the histograms, were over half of a boosted
    round. A depth-12 regression tree with its bootstrap is 1.8 s
    (PR 32).

    Value precision is governed by HIST_PRECISION (see above)."""
    n, d, nb = B.shape
    m = G.shape[1]
    exact = HIST_PRECISION == "f32"
    A = jax.nn.one_hot(node_idx, n_nodes,
                       dtype=jnp.float32 if exact else jnp.bfloat16)
    Bf = B.reshape(n, d * nb)
    if exact:
        Bf = Bf.astype(jnp.float32)

    def red(vec):  # (n,) weights → (nodes, d, bins) f32
        if exact:
            Ag = A * vec[:, None].astype(jnp.float32)
            out = jnp.matmul(Ag.T, Bf,
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
        else:
            Ag = A * vec[:, None].astype(jnp.bfloat16)
            out = jnp.matmul(Ag.T, Bf, preferred_element_type=jnp.float32)
        return out.reshape(n_nodes, d, nb)

    with jax.named_scope(f"tree:hist:{block}"):
        hh = red(H)
        hg = jnp.stack([red(G[:, c]) for c in range(m)])
    return hg, hh


def hist_reads(n_classes: int = 0, value_columns: int = 1) -> int:
    """Passes over the histogram operand a tree level: ONE for a
    classifier of `n_classes` (`_class_histograms`), else one a value
    column and one for the weights (`_histograms`)."""
    return 1 if n_classes else int(value_columns) + 1


@jax.named_scope("tree:hist")
def _class_histograms(B, node_idx, cls, H, n_nodes: int, n_classes: int,
                      block: str = "wide"):
    """`_histograms` of a CLASSIFIER, whose value columns are a one-hot
    label times one row weight: G[:, c] = (cls == c)·H. Then the K class
    histograms are ONE histogram over the composite index
    cls·nodes + node, a single (K·nodes, n) @ (n, d·bins) product that
    reads the operand once a level where the per-column form reads it
    K + 1 times, and the weights' histogram is the sum of the class
    histograms (every row is in exactly one class). Same value
    precision as `_histograms`: the weight narrows to bf16 (exact for
    the whole-number weights of a bootstrap under a 0/1 fold mask, so
    the two forms then give the same histograms bit for bit), float32
    accumulation; exact float32 under HIST_PRECISION = "f32". Scope
    `tree:hist:classes` inside `tree:hist:<block>`.

    Timed on one TPU v5e (PR 30), one depth-12 tree with its bootstrap,
    composite against per-column, the trees equal bit for bit in every
    case: 1,800,000 rows × (33 wide + 41 two-valued columns, 1,138
    slots) at K = 23, 2.89 s against 3.37 (compile 70 s against 105),
    at K = 7, 0.92 against 1.16; 2,160,000 × 28 wide (896 slots) at
    K = 2, 0.256 against 0.373; 900,000 × (13 + 515, 1,446 slots) at
    K = 2, 0.206 against 0.287. So every classifier takes this form,
    the binary ones too."""
    n, d, nb = B.shape
    exact = HIST_PRECISION == "f32"
    dt = jnp.float32 if exact else jnp.bfloat16
    A = jax.nn.one_hot(cls * n_nodes + node_idx, n_classes * n_nodes,
                       dtype=dt) * H[:, None].astype(dt)
    Bf = B.reshape(n, d * nb)
    with jax.named_scope(f"tree:hist:{block}"), \
            jax.named_scope("tree:hist:classes"):
        if exact:
            out = jnp.matmul(A.T, Bf.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
        else:
            out = jnp.matmul(A.T, Bf, preferred_element_type=jnp.float32)
        hg = out.reshape(n_classes, n_nodes, d, nb)
        return hg, hg.sum(0)


def _best_split(hg, hh, reg_lambda, min_child_weight, feature_mask):
    """Per node of one block: (gain, column, bin) of its best split — the
    FIRST maximum in (column, bin) order — and the node's total weight as
    the block's column 0 sums it."""
    n_nodes, _, n_bins = hh.shape
    cg = jnp.cumsum(hg, axis=-1)          # left sums at split-bin b
    ch = jnp.cumsum(hh, axis=-1)          # (nodes, d, bins)
    tg = cg[..., -1:]
    th = ch[..., -1:]
    score = lambda g, h: (g ** 2).sum(0) / (h + reg_lambda)  # noqa: E731
    gain = score(cg, ch) + score(tg - cg, th - ch) - score(tg, th)
    valid = (ch >= min_child_weight) & ((th - ch) >= min_child_weight)
    gain = jnp.where(valid, gain, -jnp.inf)
    if feature_mask is not None:
        gain = jnp.where(feature_mask[None, :, None], gain, -jnp.inf)
    flat = gain.reshape(n_nodes, -1)      # (nodes, d*bins)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
    return (best_gain, (best // n_bins).astype(jnp.int32),
            (best % n_bins).astype(jnp.int32), th[:, 0, 0])


def _split_or_not(best_gain, bb, total, n_bins: int, min_gain,
                  min_gain_norm, level: int, active_depth):
    """The split bin, or `n_bins` ("no split") where the best gain does
    not clear the thresholds or the level is past `active_depth`."""
    # a node with no usable gain "splits" at bin >= n_bins-1 → all left.
    # Two threshold scales coexist: `min_gain` compares raw (XGBoost
    # gamma), while `min_gain_norm` scales by the node's total weight —
    # with one-hot G / count H the unified score satisfies
    # (score_L + score_R − score_P)/h == Spark's gini/variance
    # impurity improvement, so the normalized threshold is EXACTLY
    # MLlib's minInfoGain scale ({0.001, 0.01, 0.1} in
    # DefaultSelectorParams.scala:39). Both may be traced grid values.
    splits = best_gain > jnp.maximum(min_gain, min_gain_norm * total)
    if active_depth is not None:
        splits = splits & (level < active_depth)
    return jnp.where(splits, bb, n_bins)


@jax.named_scope("tree:split")
def split_from_histograms(hg, hh, n_bins: int, reg_lambda,
                          min_child_weight, min_gain, min_gain_norm,
                          feature_mask, level: int, active_depth
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-node best (feature, bin) from (m, nodes, d, bins) gradient and
    (nodes, d, bins) weight histograms — shared by the in-core level loop
    and the chunked big-data path (`parallel/bigdata.py`)."""
    best_gain, bf, bb, total = _best_split(
        hg, hh, reg_lambda, min_child_weight, feature_mask)
    return bf, _split_or_not(best_gain, bb, total, n_bins, min_gain,
                             min_gain_norm, level, active_depth)


@jax.named_scope("tree:split")
def _split_from_blocks(B, hists, n_bins: int, reg_lambda, min_child_weight,
                       min_gain, min_gain_norm, feature_mask, level: int,
                       active_depth) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`split_from_histograms` over the operand's blocks: each block's
    best split, the better one taken in the order one argmax over the
    whole (column, bin) table has — higher gain, then lower column of
    the combined vector, then lower bin — and the node's weight from
    the block that holds column 0, so that the two-block layout grows
    the uniform layout's tree split for split."""
    best = None
    for (_, cols, _), (hg, hh) in zip(B, hists):
        mask = feature_mask
        if mask is not None and cols is not None:
            mask = mask[cols]
        gain, bf, bb, total = _best_split(
            hg, hh, reg_lambda, min_child_weight, mask)
        first = 0                   # the block's first column, combined
        if cols is not None:        # (None: the uniform layout's one block)
            bf, first = cols[bf], cols[0]
        if best is None:
            best = (gain, bf, bb, total, first)
            continue
        g0, f0, b0, t0, first0 = best
        take = (gain > g0) | ((gain == g0) & (bf < f0))
        best = (jnp.where(take, gain, g0), jnp.where(take, bf, f0),
                jnp.where(take, bb, b0),
                jnp.where(first < first0, total, t0),
                jnp.minimum(first, first0))
    gain, bf, bb, total, _ = best
    return bf, _split_or_not(gain, bb, total, n_bins, min_gain,
                             min_gain_norm, level, active_depth)


# How many 128 x 128 output tiles of the one-hot product a finished tree's
# leaf sums may take for each scatter-add of the rows the product saves;
# above it they are the scatter-adds (`leaf_sums_form`, where the chip
# times behind the number are).
_LEAF_PRODUCT_TILES_A_SCATTER = 32


def leaf_sums_form(max_nodes: int, value_columns: int = 1,
                   n_classes: int = 0) -> str:
    """How `_leaf_sums` adds a tree's rows into its `max_nodes` leaves:
    "product" or "scatter", from static shapes alone (the span
    attribute `leaf_sums` and the scope `tree:leaf:<form>` say which).

    The product's cost grows with its output tiles, leaves / 128 times
    (3 x summed columns) / 128 rounded up, and the scatter's does not; a
    classifier's composite sum is ONE scatter where value columns and
    weights are two. Timed on one TPU v5e (my chip runs, PR 33,
    `chiprun_out/leaf_bench2.jsonl`: skewed leaves, heavy-tailed values,
    best of seven; product / scatter-adds, ms, BOTH sums of a tree):

      rows x leaves, one value column + weights (regressor, boosted round)
        4,500,000 x     64     1.54 / 80.1    (`airlines`' boosted round)
        4,500,000 x    256     9.31 / 80.0
        4,500,000 x  1,024    11.14 / 80.0
        2,160,000 x  1,024     5.47 / 38.9    (`higgs`' boosted round)
          900,000 x  1,024     2.86 / 16.7    (`criteo`'s boosted round)
        4,500,000 x  4,096    22.22 / 61.6    (`airlines`' forest)
        4,500,000 x  8,192    36.87 / 61.6
        4,500,000 x 16,384    66.39 / 61.7    <- the scatter-adds
      rows x leaves x classes (a classifier's composite form)
        1,800,000 x 4,096 x 23   9.34 / 13.06 (`kddcup99`'s forest)
        2,160,000 x 4,096 x  2   9.87 / 15.38 (`higgs`' forest)
          900,000 x 4,096 x  2   5.18 /  7.02 (`criteo`'s forest)
        1,800,000 x 8,192 x 23  15.68 / 13.06 <- the scatter-add
        1,800,000 x 4,096 x 64  18.34 / 13.04 <- (3 x 64 columns: two tiles)
        1,800,000 x 2,048 x 64  10.21 / 13.05

    So: 32 tiles for each scatter saved, 64 where two are (8,192 leaves
    of up to 42 summed columns), 32 where one is (4,096 leaves of up to
    42 classes). A float32 product at `Precision.HIGHEST` is the same
    arithmetic in twice the passes: 5.05 ms at 2,160,000 x 1,024 but
    29.5 at 4,500,000 x 4,096 and 117 at 16,384, and its sums read 1.3 to
    14 times further from float64's than the three pieces' (same run)."""
    columns = n_classes or int(value_columns) + 1
    tiles = -(-int(max_nodes) // 128) * -(-3 * columns // 128)
    scatters = 1 if n_classes else 2
    return ("product" if tiles <= scatters * _LEAF_PRODUCT_TILES_A_SCATTER
            else "scatter")


def _bf16_pieces(v: jnp.ndarray) -> List[jnp.ndarray]:
    """A float32 array as three bfloat16 arrays whose float32 sum is the
    array again, exactly: 8 + 8 + 8 mantissa bits, each piece the
    rounding of what the ones before left over. The rounding is
    `reduce_precision`: a convert to bfloat16 and back is one XLA's TPU
    compiler may drop (excess precision allowed), which leaves one
    bfloat16 piece and two of zeros (read so on the chip, PR 33)."""
    pieces = []
    for _ in range(3):
        p = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        pieces.append(p.astype(jnp.bfloat16))
        v = v - p
    return pieces


def _onehot_sums(idx, V, width: int):
    """(width, c) float32 sums of the rows of V (n, c) float32 by their
    index: one (width, n) @ (n, 3c) product of an exact bfloat16
    indicator and V's three exact bfloat16 pieces, float32
    accumulation, the three partial sums added smallest first."""
    c = V.shape[1]
    P = jnp.concatenate(_bf16_pieces(V), axis=1)            # (n, 3c)
    A = jax.nn.one_hot(idx, width, dtype=jnp.bfloat16)      # (n, width)
    out = jnp.matmul(A.T, P, preferred_element_type=jnp.float32)
    return out[:, 2 * c:] + out[:, c:2 * c] + out[:, :c]


@jax.named_scope("tree:leaf")
def _leaf_sums(node_idx, G, H, max_nodes: int, cls=None, n_classes: int = 0):
    """(leaf_g (max_nodes, m), leaf_h (max_nodes,)) float32: the value
    columns and the weights of a finished tree's rows summed into its
    leaves, in the form `leaf_sums_form` names."""
    classes = cls is not None       # one weight a row, (leaf, class) sums
    m = n_classes if classes else G.shape[1]
    form = leaf_sums_form(max_nodes, m, m if classes else 0)
    with jax.named_scope(f"tree:leaf:{form}"):
        if classes and form == "product":
            leaf_g = _onehot_sums(
                node_idx, jax.nn.one_hot(cls, m, dtype=H.dtype) * H[:, None],
                max_nodes)
        elif classes:
            leaf_g = jnp.zeros((max_nodes * m,), H.dtype).at[
                node_idx * m + cls].add(H).reshape(max_nodes, m)
        elif form == "product":
            sums = _onehot_sums(
                node_idx, jnp.concatenate([G, H[:, None]], axis=1),
                max_nodes)
            return sums[:, :m], sums[:, m]
        else:
            return (jnp.zeros((max_nodes, m), G.dtype).at[node_idx].add(G),
                    jnp.zeros((max_nodes,), H.dtype).at[node_idx].add(H))
        return leaf_g, leaf_g.sum(1)


# How many A-side rows (classes x parent nodes) a level's right-children
# product has to reach before taking the level as parent - right pays:
# one bfloat16 tile of rows (`hist_subtract_levels`, where the chip times
# behind the number are).
_SUBTRACT_MIN_ROWS = 16


def hist_subtract_levels(pad_depth: int, n_classes: int = 0,
                         batched_trees: int = 1) -> Tuple[int, ...]:
    """The levels of a `pad_depth` tree whose histograms `grow_tree`
    takes by sibling subtraction, from static shapes alone (the span
    attribute `hist_subtract` counts them; the scope is
    `tree:hist:subtract`): each such level multiplies only the rows that
    went RIGHT, grouped by parent, and a left child's histograms are its
    parent's less its sibling's — XGBoost's and LightGBM's identity.

    Which values allow it. A classifier's composite form (`n_classes` >
    0) sums ONE non-negative weight a row; a bootstrap's Poisson count
    under a 0/1 fold mask is a whole number, exact in bfloat16, so every
    class-histogram cell is an integer below 2^24 in float32 and parent −
    right is the direct histogram bit for bit, in either precision mode
    (fractional weights: the same bfloat16 weights summed in another
    float32 order, a few ulps of the parent's cell). SIGNED gradients
    subtract under HIST_PRECISION "f32" (the cancellation sits at
    float32 rounding) and, under the default "bf16", where one product
    batches `batched_trees` ≥ 2 trees: a softmax round's K class trees,
    vmapped over one shared operand, so their (K · nodes, n) A side is
    one product. There the right children's product rounds each row's g
    and h to bfloat16 exactly as the parent's did, both sums accumulate
    in float32, and parent − right differs from the direct left child by
    float32 summation order alone: a few ulps of the cell at the last
    level taken direct (each subtracted level is taken from the one
    before), orders of magnitude under the bfloat16 rounding of the
    values both forms share (the hessians, ≥ 1e-6 a row, do not cancel
    at all). A single tree's bf16 gradients (a binary or squared-loss
    round, a regressor) stay direct: their padded depth-12 sweep lost
    ~0.005 CV AuPR to subtraction once and was not read again, and their
    products are not those cells' bottleneck.

    Which shapes make it pay. A level's product is (rows, n) @ (n,
    slots) with the bin one-hot generated inside it; the subtraction adds
    one pass over the level's float32 histograms. Timed on one TPU v5e,
    one level of 1,800,000 rows × 1,042 slots (30 columns of 32 bins, 41
    of 2) at K = 23, ms, best of five (level 11: the product of a
    depth-12 tree's deepest level, read off a traced training pass):

      level   A-side rows, direct / right   direct   by subtraction
        1            46 /     23             20.67        10.47
        2            92 /     46             23.90        10.62
        3           184 /     92             30.73        10.66
        4           368 /    184             44.46        12.25
        5           736 /    368             73.28        16.22
        6         1,472 /    736             75.69        23.37
       11        47,104 / 23,552            919          441

    The right-children product wins at every level, the smallest
    included, and takes under half the direct one's time where both
    hold the same rows (the direct product's 46 rows 20.67 ms, the
    right one's 46 rows 10.62). Under one bfloat16 tile of A-side rows
    a product pads its rows to the tile and halving them saves nothing,
    so a level subtracts where its right-children product holds at
    least `_SUBTRACT_MIN_ROWS` rows: classes × 2^(level − 1), or
    trees × 2^(level − 1) for each product of the per-value-column form
    (a binary forest from level 4, a softmax round of 355 trees from
    level 1). A whole depth-12 tree, same chip, best
    of three: 1,800,000 × 1,042 slots at K = 23 2.155 s direct, 1.152 s
    by subtraction (levels 1–11); 2,160,000 × 896 slots at K = 2 0.239 s
    and 0.156 s (levels 4–11); both forms grew the same trees bit for
    bit. The binary shapes were not timed level by level, nor 900,000 ×
    1,446 slots at all. A softmax round of K = 355 trees, 374,569 rows ×
    1,920 slots, two (configuration, fold) pairs a product, same chip,
    seconds over a training pass's 12 dispatches (the gradient product,
    then the hessian's), read off one traced pass of each form:

      level   A-side rows, direct / right   direct          by subtraction
        4      2 · 355 · 16 / 2 · 355 · 8   2.609, 2.609    1.520, 1.325
        5      2 · 355 · 32 / 2 · 355 · 16  5.219, 5.219    2.917, 2.917

    and the whole training pass 49.4–50.7 s direct, 39.2–39.9 s by
    subtraction (three seeds each)."""
    if not n_classes and batched_trees < 2 and HIST_PRECISION != "f32":
        return ()
    rows = int(n_classes) or int(batched_trees)
    return tuple(level for level in range(1, int(pad_depth))
                 if rows * 2 ** (level - 1) >= _SUBTRACT_MIN_ROWS)


def tree_span_attrs(pad_depth: int, n_classes: int = 0,
                    batched_trees: int = 1) -> Dict:
    """What a program of `pad_depth`-level trees does, as span attributes
    (`sweep:dispatch:forest`, `sweep:dispatch:gbt`, `tree:edges`):
    `leaf_sums`, the form of its leaf sums (`leaf_sums_form`), and
    `hist_subtract`, how many levels take their histograms by sibling
    subtraction (`hist_subtract_levels`). `n_classes`: a classifier
    forest's K, else 0; `batched_trees`: the trees one histogram product
    batches (a softmax round's K, else 1)."""
    return {"leaf_sums": leaf_sums_form(2 ** int(pad_depth), 1, n_classes),
            "hist_subtract": len(hist_subtract_levels(
                pad_depth, n_classes, batched_trees))}


@jax.named_scope("tree:hist:subtract")
def _subtract_siblings(parent, right, classes: bool):
    """A level's (hg, hh) of one operand block from its parents' and its
    right children's (grouped by parent): left 2k = parent k − right,
    right 2k + 1, interleaved. A classifier's hh is the class sum of hg,
    as `_class_histograms` returns it."""
    (hg, hh), (hg_r, hh_r) = parent, right
    m, n_parents = hg.shape[:2]
    hg = jnp.stack([hg - hg_r, hg_r], axis=2).reshape(
        m, 2 * n_parents, *hg.shape[2:])
    if classes:
        return hg, hg.sum(0)
    return hg, jnp.stack([hh - hh_r, hh_r], axis=1).reshape(
        2 * n_parents, *hh.shape[1:])


def grow_tree(Xb: jnp.ndarray, G: jnp.ndarray, H: jnp.ndarray,
              max_depth: int, n_bins: int, reg_lambda: float = 1.0,
              min_child_weight: float = 1.0, min_gain: float = 0.0,
              feature_mask: Optional[jnp.ndarray] = None,
              active_depth=None, alpha: float = 0.0,
              B: Optional[List[Tuple]] = None,
              min_gain_norm=0.0, layout: Optional[Dict] = None,
              n_classes: int = 0, batched_trees: int = 1) -> Dict:
    """Grow one fixed-depth tree. Returns dense arrays:

    {"feat": (depth, 2^depth) int32, "bin": (depth, 2^depth) int32,
     "leaf": (2^max_depth, m) float32}
    (per-level arrays are padded to 2^max_depth node slots)

    A CLASSIFIER passes its labels as a 1-D `G` with `n_classes`: the
    targets are then one_hot(G)·H and are never built as an (n, K)
    array (`_class_histograms`). A label outside [0, K) counts nowhere.

    The finished tree's leaf sums are float32 sums of float32 values in
    the form `leaf_sums_form` names from the leaf count and the summed
    columns (`_leaf_sums`, scope `tree:leaf:<form>`): a one-hot product
    whose values enter exactly as three bfloat16 pieces, a classifier's
    as (leaves, n) @ (n, 3·K) of one weight a row; past the crossover
    read on the chip, scatter-adds (a classifier's one weight a row into
    the composite (leaf, class) index).

    `active_depth`: optional TRACED effective depth ≤ max_depth. Levels at or
    beyond it never split (every sample routes left, partition unchanged), so
    the padded tree predicts exactly like a tree grown to that depth — this
    lets the sweep engine vmap a {max_depth: 3, 6, 12} grid in ONE compiled
    program padded to 12 instead of one compile per depth.

    `B`: the histogram operand (`hist_operand`), built here from `layout`
    when the caller does not share one across trees or rounds.

    Every level has its full histograms: the root and the levels before
    `hist_subtract_levels` as one direct product, the levels it names by
    sibling subtraction from the level before (a product over the rows
    that went right, half the A-side rows, and `_subtract_siblings`).
    A classifier's trees are then the direct form's bit for bit wherever
    its weights are whole numbers; the rule and the chip times behind it
    are in `hist_subtract_levels`. `batched_trees`: how many trees the
    caller vmaps over one shared operand, so that each histogram product
    batches them (a softmax round's K class trees; 1 otherwise).
    """
    n, d = Xb.shape
    cls = None
    if G.ndim == 1:                 # a classifier's labels
        cls = G.astype(jnp.int32)
        H = jnp.where((cls >= 0) & (cls < n_classes), H, 0.0)
        cls = jnp.clip(cls, 0, n_classes - 1)
    m = n_classes if cls is not None else G.shape[1]
    max_nodes = 2 ** max_depth
    node_idx = jnp.zeros(n, dtype=jnp.int32)
    feats = jnp.zeros((max_depth, max_nodes), jnp.int32)
    bins = jnp.full((max_depth, max_nodes), n_bins, jnp.int32)  # n_bins = "no split"
    if B is None:
        B = hist_operand(Xb, n_bins, layout)

    def histograms(node, Gv, Hv, n_nodes):
        if cls is not None:
            return [_class_histograms(Bk, node, cls, Hv, n_nodes, m, name)
                    for name, _, Bk in B]
        return [_histograms(Bk, node, Gv, Hv, n_nodes, name)
                for name, _, Bk in B]

    subtract = hist_subtract_levels(max_depth, 0 if cls is None else m,
                                    batched_trees)
    for level in range(max_depth):
        n_nodes = 2 ** level
        if level not in subtract:
            hists = histograms(node_idx, G, H, n_nodes)
        bf, bb = _split_from_blocks(
            B, hists, n_bins, reg_lambda, min_child_weight, min_gain,
            min_gain_norm, feature_mask, level, active_depth)
        feats = feats.at[level, :n_nodes].set(bf)
        bins = bins.at[level, :n_nodes].set(bb)
        with jax.named_scope("tree:route"):
            if n_nodes <= _ONEHOT_LOOKUP_MAX:
                sample_feat, split_bin = _table_lookup2(bf, bb, node_idx)
            else:
                sample_feat, split_bin = bf[node_idx], bb[node_idx]
            sample_bin = _select_bin(Xb, sample_feat)
            go_right = sample_bin > split_bin
            node_idx = node_idx * 2 + go_right.astype(jnp.int32)
        if level + 1 in subtract:
            right = go_right.astype(jnp.float32)
            hists = [_subtract_siblings(parent, right_hists, cls is not None)
                     for parent, right_hists in zip(hists, histograms(
                         node_idx >> 1,
                         None if cls is not None else G * right[:, None],
                         H * right, n_nodes))]

    leaf_g, leaf_h = _leaf_sums(node_idx, G, H, max_nodes, cls, n_classes)
    # L1 (alpha) soft-thresholds the leaf numerator (XGBoost leaf formula)
    leaf_g = jnp.sign(leaf_g) * jnp.maximum(jnp.abs(leaf_g) - alpha, 0.0)
    leaf = leaf_g / (leaf_h + reg_lambda)[:, None]
    return {"feat": feats, "bin": bins, "leaf": leaf}


# One-hot table lookups replace (n,)-indexed TPU gathers up to this
# table width: an (n,)-row gather serializes, while the generated (n, w)
# compare+select fuses into one VPU pass; above it the linear (n·w)
# one-hot pass loses to the constant-time gather again. The crossover
# predates PR 21 and has not been re-measured on a directly attached
# chip (ROADMAP.md, Speed 1).
_ONEHOT_LOOKUP_MAX = 2048


def _table_lookup2(ta: jnp.ndarray, tb: jnp.ndarray,
                   node: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(ta[node], tb[node]) for per-level tables: one fused one-hot
    pass instead of two serialized TPU gathers (the dominant cost of tree
    prediction at 100k rows was exactly these (n,)-indexed table reads)."""
    width = ta.shape[0]
    oh = jnp.arange(width, dtype=jnp.int32)[None, :] == node[:, None]
    return (jnp.where(oh, ta[None, :], 0).sum(1),
            jnp.where(oh, tb[None, :], 0).sum(1))


def _leaf_lookup(col: jnp.ndarray, node: jnp.ndarray) -> jnp.ndarray:
    """col[node] for one (width,) f32 leaf column as a fused one-hot
    masked sum — replacing the per-tree leaf gather with a generated
    VPU pass. Adding exact 0.0s keeps the
    selected value bit-identical to the gather. A single leaf pass
    amortizes its compare over one select (the walk's `_table_lookup2`
    amortizes over two), so its crossover sits a factor higher than
    `_ONEHOT_LOOKUP_MAX`; beyond that the linear (n·width) pass loses to
    the constant-time gather (pad depth 14 → 16384-wide leaf tables)."""
    width = col.shape[0]
    if width > 2 * _ONEHOT_LOOKUP_MAX:
        return col[node]
    oh = jnp.arange(width, dtype=jnp.int32)[None, :] == node[:, None]
    return jnp.where(oh, col[None, :], 0.0).sum(1)


@jax.named_scope("tree:predict")
def _tree_walk(tree: Dict, Xb: jnp.ndarray, select_fn=None) -> jnp.ndarray:
    """(n,) leaf index for binned samples — the shared routing walk.
    Gather-free at every level up to `_ONEHOT_LOOKUP_MAX`-wide tables.
    `select_fn(Xb, feat_idx)` defaults to `_select_bin` (the big-data
    path passes its own fused variant)."""
    select_fn = select_fn or _select_bin
    n = Xb.shape[0]
    node = jnp.zeros(n, dtype=jnp.int32)
    depth = tree["feat"].shape[0]
    for level in range(depth):
        n_nodes = 2 ** level
        if n_nodes <= _ONEHOT_LOOKUP_MAX:
            f, b = _table_lookup2(tree["feat"][level][:n_nodes],
                                  tree["bin"][level][:n_nodes], node)
        else:
            f = tree["feat"][level][node]
            b = tree["bin"][level][node]
        sample_bin = select_fn(Xb, f)
        node = node * 2 + (sample_bin > b).astype(jnp.int32)
    return node


def predict_tree(tree: Dict, Xb: jnp.ndarray, select_fn=None) -> jnp.ndarray:
    """(n, m) leaf values for binned samples."""
    node = _tree_walk(tree, Xb, select_fn)
    m = tree["leaf"].shape[-1]
    # per-class masked sums instead of one (n, m) row gather: the gather
    # serializes AND its m-minor output tile-pads to 128 lanes; the class
    # count is small and static, so m fused (n, width) passes win
    return jnp.stack([_leaf_lookup(tree["leaf"][:, c], node)
                      for c in range(m)], axis=-1)


def predict_tree_dense(tree: Dict, Xb: jnp.ndarray) -> jnp.ndarray:
    """(n, m) leaf values — a TENSORIZED alternative formulation.

    ALL node decisions compute as ONE MXU matmul: selected-bin values
    `S = Xb @ onehot(feat)` for every node at once (bin ids ≤ 256 are
    exact in bf16, accumulation f32), then `D = S > bin` and a
    level-by-level 0/1 path product routes probability mass to leaves —
    no gathers anywhere. Bit-identical to `predict_tree` (same
    comparisons, exact 0/1 products; `P @ leaf` selects one leaf row).

    The (n, 2^level) routing slabs are HBM-bound and at depth 10 outweigh
    the gathers they remove, so the level walk remains the default; this
    form is kept as the documented alternative (it wins only where
    gathers are pathologically slow or depth ≪ 10 slabs fit cache).
    Neither form has been timed on a directly attached chip."""
    n, d = Xb.shape
    depth = tree["feat"].shape[0]
    max_nodes = tree["leaf"].shape[0]
    # level-major flattened internal nodes: offset(level) = 2^level - 1
    feats = jnp.concatenate(
        [tree["feat"][lv][:2 ** lv] for lv in range(depth)])
    bins = jnp.concatenate(
        [tree["bin"][lv][:2 ** lv] for lv in range(depth)])
    F = jax.nn.one_hot(feats, d, dtype=jnp.bfloat16)        # (nodes, d)
    S = jnp.matmul(Xb.astype(jnp.bfloat16), F.T,
                   preferred_element_type=jnp.float32)       # (n, nodes)
    D = (S > bins[None, :].astype(jnp.float32)).astype(jnp.bfloat16)
    P = jnp.ones((n, 1), jnp.bfloat16)
    off = 0
    for lv in range(depth):
        w = 2 ** lv
        Dlv = D[:, off:off + w]                              # (n, w)
        # children interleave: node k -> (left 2k, right 2k+1)
        P = jnp.stack([P * (1 - Dlv), P * Dlv], axis=-1).reshape(n, 2 * w)
        off += w
    # grow_tree always emits (depth, 2^depth) levels with 2^depth leaves
    assert P.shape[1] == max_nodes, (P.shape, max_nodes)
    # leaf values stay f32 and the tiny final matmul runs at HIGHEST
    # precision: exactly one nonzero 0/1 weight per row selects the leaf,
    # so the result is the untouched f32 leaf value
    return jnp.matmul(P.astype(jnp.float32), tree["leaf"],
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------- #
# Random forest / decision tree                                               #
# --------------------------------------------------------------------------- #

_TREE_CHUNK_BUDGET = 1 << 26  # live per-tree working-set elements (bf16)


@partial(jax.jit, static_argnames=("n_trees", "max_depth", "n_bins",
                                   "n_outputs", "subsample_features",
                                   "bootstrap", "tree_budget_divisor"))
def fit_forest(Xb, Y, w, n_trees: int, max_depth: int, n_bins: int,
               n_outputs: int, seed, subsample_features: bool = True,
               min_child_weight: float = 1.0, active_depth=None,
               bootstrap: bool = True, tree_budget_divisor: int = 1,
               min_gain=0.0, layout: Optional[Dict] = None):
    """`Y`: (n, n_outputs) float targets, or a classifier's (n,) labels
    in [0, n_outputs) (`grow_tree`'s class form)."""
    n, d = Xb.shape
    classes = Y.ndim == 1
    keys = jax.random.split(jax.random.PRNGKey(seed), n_trees)
    n_sub = max(int(np.sqrt(d)), 1) if subsample_features else d
    B = hist_operand(Xb, n_bins, layout)  # shared across all trees

    def one_tree(key):
        k1, k2 = jax.random.split(key)
        if bootstrap:
            with jax.named_scope("tree:bootstrap"):
                boot = jax.random.poisson(
                    k1, 1.0, (n,)).astype(jnp.float32) * w
        else:  # deterministic single tree (OpDecisionTree* parity)
            boot = w
        if subsample_features:
            scores = jax.random.uniform(k2, (d,))
            thresh = jnp.sort(scores)[n_sub - 1]
            fmask = scores <= thresh
        else:
            fmask = jnp.ones((d,), bool)
        return grow_tree(Xb, Y if classes else Y * boot[:, None], boot,
                         max_depth, n_bins,
                         reg_lambda=1e-6, min_child_weight=min_child_weight,
                         min_gain_norm=min_gain,
                         feature_mask=fmask, active_depth=active_depth, B=B,
                         n_classes=n_outputs if classes else 0)

    # Bound simultaneous per-tree working set: each live instance holds the
    # (n, nodes) one-hot routing matrix at the deepest level plus O(n·d)
    # gather state — cap the vmapped width and lax.map over chunks
    # (sequential, still one compile). Callers that add further batch axes
    # (the sweep's grid×fold vmaps) shrink the budget via
    # `tree_budget_divisor` so the product of live axes stays bounded.
    budget = _TREE_CHUNK_BUDGET // max(int(tree_budget_divisor), 1)
    per_instance = n * (d + 2 ** min(max_depth, 14))
    chunk = max(1, min(n_trees, budget // max(per_instance, 1)))
    if chunk == n_trees:
        return jax.vmap(one_tree)(keys)
    # pad the key array to a chunk multiple (extra trees are grown and
    # sliced off) rather than shrinking to a divisor — a prime n_trees
    # must not collapse to fully sequential growth
    n_chunks = -(-n_trees // chunk)
    pad = n_chunks * chunk - n_trees
    if pad:
        keys = jnp.concatenate([keys, keys[:pad]])
    chunked = keys.reshape(n_chunks, chunk, *keys.shape[1:])
    trees = jax.lax.map(jax.vmap(one_tree), chunked)
    return jax.tree.map(
        lambda a: a.reshape(n_chunks * chunk, *a.shape[2:])[:n_trees], trees)


_PREDICT_TREE_CHUNK = 8


def _scan_tree_chunks(trees: Dict, per_tree, acc0, chunk: int):
    """Σ_t per_tree(t) over `chunk`-tree vmapped scan steps: pads the
    tree axis to a chunk multiple with ZEROED trees (all-zero leaves
    contribute nothing), so live memory is one chunk's generated
    passes while per-tree parallelism stays."""
    n_trees = jax.tree_util.tree_leaves(trees)[0].shape[0]
    c = min(max(1, int(chunk)), n_trees)
    n_chunks = -(-n_trees // c)
    pad = n_chunks * c - n_trees
    if pad:
        trees = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros_like(a[:pad])]), trees)
    chunked = jax.tree.map(
        lambda a: a.reshape(n_chunks, c, *a.shape[1:]), trees)

    def body(acc, tc):
        return acc + jax.vmap(per_tree)(tc).sum(axis=0), None

    acc, _ = jax.lax.scan(body, acc0, chunked)
    return acc


def _predict_trees_sum(trees: Dict, Xb: jnp.ndarray,
                       chunk: int = _PREDICT_TREE_CHUNK) -> jnp.ndarray:
    """Σ_t predict_tree(t, Xb) as a scan of vmapped tree chunks.

    Per-tree scores accumulate CLASS-MAJOR (m, n): with the big row axis
    minor, nothing tile-pads the tiny class axis to 128 lanes (a plain
    vmap-then-sum of (c, n, m) slabs padded m→128 was the r4 RF family
    drop: 8 pairs × 50 trees × 90k rows × pad-128 f32 = 18.4 GB). The
    single (m, n) → (n, m) transpose at the end materializes one
    lane-padded (n, m→128) output — the shape every caller consumes
    anyway."""
    m = trees["leaf"].shape[-1]

    def per_tree(t):  # (m, n) class-major leaf values
        node = _tree_walk(t, Xb)
        return jnp.stack([_leaf_lookup(t["leaf"][:, cl], node)
                          for cl in range(m)], axis=0)

    return _scan_tree_chunks(
        trees, per_tree, jnp.zeros((m, Xb.shape[0]), jnp.float32), chunk).T


def _predict_trees_margin(trees: Dict, Xb: jnp.ndarray,
                          chunk: int = 64) -> jnp.ndarray:
    """Σ_t leaf value of tree t, single-output specialization: the (n,)
    accumulator + gather-free walk is the streaming-scorer hot path
    (no per-tree (n,) row gathers)."""
    def per_tree(t):
        return _leaf_lookup(t["leaf"][:, 0], _tree_walk(t, Xb))

    return _scan_tree_chunks(
        trees, per_tree, jnp.zeros((Xb.shape[0],), jnp.float32), chunk)


@partial(jax.jit, static_argnames=("chunk",))
def predict_forest(trees: Dict, Xb: jnp.ndarray,
                   chunk: int = 64) -> jnp.ndarray:
    """Mean per-tree prediction (memory-bounded; `_predict_trees_sum`).

    `chunk` trades live memory (chunk × n × 128-padded f32) for per-tree
    parallelism: model scoring uses the big default; sweep dispatches —
    where a width-8 pair vmap multiplies the slab — pass a small one."""
    n_trees = jax.tree_util.tree_leaves(trees)[0].shape[0]
    return _predict_trees_sum(trees, Xb, chunk) / jnp.float32(n_trees)


# --------------------------------------------------------------------------- #
# Gradient boosting (XGBoost-style second order)                              #
# --------------------------------------------------------------------------- #

def _gbt_val_loss(margin, y, val_w, objective: str,
                  eval_metric: str = "logloss"):
    """Per-round early-stopping metric on the held-out rows, MINIMIZED.

    "logloss": weighted logloss (binary) / MSE (squared) — cheap and
    strictly proper. "aupr" (binary only): NEGATED sort-free binned AuPR
    over 512 sigmoid buckets via one one-hot matmul — the reference's
    default XGBoost eval is maximized aucpr
    (`DefaultSelectorParams.scala:71` BinaryClassXGBEvaluationMetric), so
    the stopping round matches reference semantics; an exact sorted AuPR
    would serialize on TPU every round, the binned histogram stays on
    the MXU (90k × 512 bf16 ≈ 0.1 GFLOP/round).

    "softmax" (an (n, K) margin) is the weighted multiclass log-loss
    whatever `eval_metric` says: XGBoost's `mlogloss`, the default eval
    of `multi:softprob` (a binned AuPR has no K-class form)."""
    vs = jnp.maximum(val_w.sum(), 1.0)
    if objective == "softmax":
        logp = jax.nn.log_softmax(margin, axis=1)
        ll = -(jax.nn.one_hot(y.astype(jnp.int32), margin.shape[1],
                              dtype=logp.dtype) * logp).sum(1)
        return (ll * val_w).sum() / vs
    if objective == "logistic" and eval_metric == "aupr":
        nb = 512
        p = jax.nn.sigmoid(margin)
        b = jnp.minimum((p * nb).astype(jnp.int32), nb - 1)
        B = jax.nn.one_hot(b, nb, dtype=jnp.bfloat16)
        h = jnp.matmul(jnp.stack([(val_w * y).astype(jnp.bfloat16),
                                  val_w.astype(jnp.bfloat16)]), B,
                       preferred_element_type=jnp.float32)  # (2, nb)
        tp = jnp.cumsum(h[0, ::-1])
        n_at = jnp.cumsum(h[1, ::-1])
        n_pos = jnp.maximum(tp[-1], 1e-9)
        prec = jnp.where(n_at > 0, tp / jnp.maximum(n_at, 1e-30), 1.0)
        rec = tp / n_pos
        r = jnp.concatenate([jnp.zeros(1), rec])
        pr = jnp.concatenate([jnp.ones(1), prec])
        aupr = ((r[1:] - r[:-1]) * (pr[1:] + pr[:-1]) * 0.5).sum()
        return -aupr  # maximize aucpr == minimize its negation
    if objective == "logistic":
        ll = jax.nn.softplus(margin) - y * margin  # -log p(y|margin)
        return (ll * val_w).sum() / vs
    return (((margin - y) ** 2) * val_w).sum() / vs


def gbt_base_score(y, w, objective: str):
    """Where a boosted chain starts: for squared loss the weighted mean
    of the target (the constant that minimises it, so the 20 rounds of a
    `learning_rate` 0.1 chain are not spent walking from 0 to a target
    whose mean is far from it), 0 for the logistic margin and for every
    class of a softmax one (XGBoost's `base_score` 0.5 is a probability:
    its margin is 0 for `multi:softprob`). One rule for
    the sweep's chains, the refit and the model's prediction
    (`GBTRegressionModel.base_score`)."""
    if objective != "squared":
        return jnp.float32(0.0)
    return ((y * w).sum() / jnp.maximum(w.sum(), 1e-12)).astype(jnp.float32)


def gbt_train_summary(margin, y, w, objective: str) -> Dict:
    """What a finished chain says of the rows it was fitted on:
    `train_loss`, the objective's own loss (mean squared error, the
    logistic loss, or a softmax chain's cross-entropy over its K classes)
    of its final margin under its training weights, and
    `train_weight`, their sum. The sweep puts both on a fold chain's
    `sweep:fetch:gbt` span: beside the validation metric they show what
    the chain fitted and how far it got (two scalars a chain; no row
    crosses to the host)."""
    return {"train_loss": _gbt_val_loss(margin, y, w, objective),
            "train_weight": w.sum()}


def gbt_grad_hess(margin, y, w, objective: str):
    """A round's gradients and hessians of the objective at `margin`,
    times the row weights: (n,) each, or (n, K) for "softmax"
    (p − onehot(y) and max(p(1 − p), 1e-6) of p = softmax(margin))."""
    if objective == "softmax":
        p = jax.nn.softmax(margin, axis=1)
        Y = jax.nn.one_hot(y.astype(jnp.int32), margin.shape[1],
                           dtype=p.dtype)
        return ((p - Y) * w[:, None],
                jnp.maximum(p * (1.0 - p), 1e-6) * w[:, None])
    if objective == "logistic":
        p = jax.nn.sigmoid(margin)
        return (p - y) * w, jnp.maximum(p * (1 - p), 1e-6) * w
    return (margin - y) * w, w  # squared error


def _gbt_scan(Xb, y, w, val_w, margin0, best0, since0, keys,
              max_depth: int, n_bins: int, learning_rate, reg_lambda,
              objective: str, min_child_weight, active_depth, gamma, alpha,
              subsample, colsample, early_stopping_rounds: int,
              min_gain_norm=0.0, eval_metric: str = "logloss",
              layout: Optional[Dict] = None):
    """Shared traced boosting loop. Carry = (margin, best_val, since);
    with `early_stopping_rounds` > 0, a round whose start state has
    `since >= early_stopping_rounds` grows a ZEROED tree (leaf *= 0), so
    the margin freezes and the trailing trees are exact no-ops — the model
    the scan returns is the early-stopped model even though the scan's
    length is static (XGBoost semantics: stop adding trees once the eval
    metric hasn't improved for N rounds,
    `XGBoostParams.scala numEarlyStoppingRounds`).

    `objective` "softmax" is XGBoost's `multi:softprob`: the margin is
    (n, K) (K from its shape), and a round grows K trees, one a class,
    from the multinomial gradients p − onehot(y) and hessians
    max(p(1 − p), 1e-6) of p = softmax(margin), all K from the round's
    one row sample and feature mask and the one histogram operand; the
    round's trees are stacked (K, ...) and a chain's (rounds, K, ...).
    Their histogram products batch the K trees, so their deep levels
    come by sibling subtraction (`hist_subtract_levels` of K)."""
    n, d = Xb.shape
    B = hist_operand(Xb, n_bins, layout)  # shared across all rounds
    esr = int(early_stopping_rounds)

    def round_(carry, key):
        margin, best, since = carry
        k1, k2 = jax.random.split(key)
        # uniform draws in [0,1): rate 1.0 keeps everything (no-op default)
        rows = (jax.random.uniform(k1, (n,)) < subsample).astype(jnp.float32)
        fmask = jax.random.uniform(k2, (d,)) < colsample
        g, h = gbt_grad_hess(margin, y, w, objective)
        # a softmax round's K trees share each histogram product
        batched = margin.shape[1] if objective == "softmax" else 1

        def one_tree(g, h):
            return grow_tree(Xb, (-g * rows)[:, None], h * rows, max_depth,
                             n_bins, reg_lambda=reg_lambda,
                             min_child_weight=min_child_weight,
                             min_gain=gamma, min_gain_norm=min_gain_norm,
                             feature_mask=fmask,
                             active_depth=active_depth, alpha=alpha, B=B,
                             batched_trees=batched)

        def update(tree):
            return _leaf_lookup(tree["leaf"][:, 0], _tree_walk(tree, Xb))

        if objective == "softmax":      # one tree a class
            tree = jax.vmap(one_tree, in_axes=(1, 1))(g, h)
            step = lambda t: jax.vmap(update)(t).T  # noqa: E731
        else:
            tree, step = one_tree(g, h), update
        if esr > 0:
            live = (since < esr).astype(jnp.float32)
            tree["leaf"] = tree["leaf"] * live
        margin = margin + learning_rate * step(tree)
        if esr > 0:
            m = _gbt_val_loss(margin, y, val_w, objective, eval_metric)
            improved = m < best - 1e-7
            since = jnp.where(since >= esr, since,
                              jnp.where(improved, 0, since + 1))
            best = jnp.minimum(best, m)
        return (margin, best, since), tree

    return jax.lax.scan(round_, (margin0, best0, since0), keys)


def gbt_margin0(y, w, objective: str, n_classes: int = 0):
    """Where a chain's margin starts: `gbt_base_score` at every row, an
    (n, `n_classes`) margin for "softmax"."""
    n = y.shape[0]
    if objective == "softmax":
        return jnp.zeros((n, n_classes), jnp.float32)
    return jnp.full(n, gbt_base_score(y, w, objective), jnp.float32)


@partial(jax.jit, static_argnames=("n_estimators", "max_depth", "n_bins",
                                   "objective", "early_stopping_rounds",
                                   "eval_metric", "n_classes"))
def fit_gbt(Xb, y, w, n_estimators: int, max_depth: int, n_bins: int,
            learning_rate, reg_lambda, objective: str = "logistic",
            min_child_weight: float = 1.0, active_depth=None,
            gamma=0.0, alpha=0.0, subsample=1.0, colsample=1.0, seed=0,
            val_w=None, early_stopping_rounds: int = 0, min_gain_norm=0.0,
            eval_metric: str = "logloss", layout: Optional[Dict] = None,
            n_classes: int = 0):
    """Returns (trees, final_margin): the scan carry already holds the full
    training-matrix margin, so sweep callers need not re-walk the forest.
    The chain starts at `gbt_base_score` (the margin includes it);
    "softmax" boosts `n_classes` margins (`_gbt_scan`).

    XGBoost param surface (OpXGBoostClassifier.scala / XGBoostParams.scala):
    `gamma` = min split gain, `alpha` = leaf L1, `subsample` = per-round
    row sampling, `colsample` = per-tree feature sampling; `val_w` +
    `early_stopping_rounds` = numEarlyStoppingRounds over a held-out row
    mask (trailing rounds after the stop are zeroed trees)."""
    n = Xb.shape[0]
    if val_w is None:
        val_w = jnp.zeros(n, jnp.float32)
        early_stopping_rounds = 0
    keys = jax.random.split(jax.random.PRNGKey(seed), n_estimators)
    (margin, _, _), trees = _gbt_scan(
        Xb, y, w, val_w, gbt_margin0(y, w, objective, n_classes),
        jnp.float32(jnp.inf),
        jnp.int32(0), keys, max_depth, n_bins, learning_rate, reg_lambda,
        objective, min_child_weight, active_depth, gamma, alpha, subsample,
        colsample, early_stopping_rounds, min_gain_norm, eval_metric,
        layout)
    return trees, margin


@partial(jax.jit, static_argnames=("n_rounds", "max_depth", "n_bins",
                                   "objective", "early_stopping_rounds",
                                   "eval_metric"))
def fit_gbt_chunk(Xb, y, w, val_w, margin, best, since, keys,
                  n_rounds: int, max_depth: int, n_bins: int,
                  learning_rate, reg_lambda, objective: str,
                  min_child_weight, active_depth, gamma, alpha,
                  subsample, colsample, early_stopping_rounds: int,
                  min_gain_norm=0.0, eval_metric: str = "logloss",
                  layout: Optional[Dict] = None):
    """One host-dispatched chunk of boosting rounds carrying the
    early-stopping state. The sweep engine calls this per
    `rounds_per_dispatch` slice of the key array and stops dispatching
    entirely once every vmapped pair reports `since >= early_stopping_
    rounds` — real compute savings on top of the in-scan masking, which
    one 200-round program could only zero out, not skip. A "softmax"
    chunk carries the (n, K) margin.
    Returns ((margin, best, since), trees_chunk)."""
    return _gbt_scan(Xb, y, w, val_w, margin, best, since, keys,
                     max_depth, n_bins, learning_rate, reg_lambda, objective,
                     min_child_weight, active_depth, gamma, alpha,
                     subsample, colsample, early_stopping_rounds,
                     min_gain_norm, eval_metric, layout)


def _pick_rounds_per_dispatch(n_estimators: int, ideal: int) -> int:
    """Largest divisor of `n_estimators` ≤ `ideal` — equal-size chunks mean
    ONE compiled chunk shape. A pathological divisor structure (prime
    round counts) falls back to `ideal` with a separately-compiled tail."""
    ideal = max(1, min(ideal, n_estimators))
    best = max(d for d in range(1, ideal + 1) if n_estimators % d == 0)
    return best if best * 2 >= ideal else ideal


# One dispatch's budgets. The memory budget caps the pairs alive at once;
# the work budget bounds a dispatch's wall (a boosted sweep checks early
# stopping only between dispatches): 25 s at the ~1e-12 s/unit of a
# 90k x 55 x 32-bin table at depth 10. Both predate PR 21's first direct
# chip run and have not been re-measured since (ROADMAP).
_PAIR_MEM_BYTES = 4 << 30
_DISPATCH_UNITS = 2.5e13
# A softmax dispatch's own bytes, counted without the over-count above:
# half of a v5e's 16 GiB, the rest left to the pass's other arrays.
_SOFTMAX_DISPATCH_BYTES = 8 << 30


def _pow2_floor(x: int) -> int:
    return 1 << max(0, int(x).bit_length() - 1)


def dispatch_plan(n_rows: int, slots: int, pad_depth: int, learners: int,
                  n_pairs: int = 1, pad_tail: bool = False,
                  value_columns: int = 1, classes: int = 0
                  ) -> Tuple[int, int]:
    """(width, rounds) of one tree dispatch, from shapes alone: how many
    grid×fold pairs it vmaps and how many of a pair's `learners`
    (boosting rounds; a forest grows all its trees in one) it runs.
    `slots` is the histogram operand's width a row (`hist_slots`),
    `value_columns` the learner's target columns (a classifier forest's
    K; 1 for a regressor and for a boosted round). The work unit is
    learners × rows × nodes × slots × value columns, the histogram
    matmuls' shape. The width is a power of two (a width is a compiled
    shape), capped by the pair count: at its power-of-two floor where
    the caller pads the last chunk (`pad_tail`), at the count itself
    otherwise. The sweep and the refit (`n_pairs` 1) both plan here, so
    a shape compiles the same rounds in either. What the cells' shapes
    give (each timed on the chip, none at a width over 1 beyond two
    value columns): at 1,800,000 rows × 1,042 slots either term pins
    the width to 1 for any K; at 4,500,000 rows × 318 slots and depth 6
    (PR 32) the memory term alone does (the bin one-hots of one pair
    are 3.4 GB of the 4 GiB), and the work term lets a pair's whole
    chain of 10 or 20 rounds into one dispatch, 1.4 or 2.9 s.

    `classes`: the trees a round of a SOFTMAX chain grows (its K; 0 for
    every other learner). Its round is K trees in the work term that
    sets the ROUNDS of a dispatch. Its memory term counts what a
    softmax dispatch holds, in `_SOFTMAX_DISPATCH_BYTES`: the bin
    one-hots ONCE (the pairs and a round's K trees share them), and for
    each pair its (n, K) float32 margin, p, G and H (16 · n · K bytes),
    its routing one-hot and its K trees' deepest histograms. Timed on
    one TPU v5e, `dionis`' chain, 374,569 rows × 1,920 slots (60
    columns of 32 bins), K = 355, depth 6, three folds × two
    configurations: 1.44 GB shared and 2.70 GB a pair give two pairs
    and one round a dispatch, a pair-round in 1.57 s (a pass of 49.2 s,
    peak 4.90 GB; one pair a dispatch 2.11 s, a pass of 65.1 s). At
    500,000 rows × 1,000 classes a pair's state alone is 8 GB: one pair
    a dispatch."""
    nodes = 2 ** min(pad_depth, 14)
    k = max(int(value_columns), 1)
    unit = max(n_rows * nodes * slots * k, 1)   # one tree of one pair
    if classes:
        trees_k = int(classes)
        pair = (n_rows * nodes * 2 + 16 * n_rows * trees_k
                + 6 * (k + 1) * nodes * slots * trees_k)
        w_mem = max(_SOFTMAX_DISPATCH_BYTES - n_rows * slots * 2, 0) // pair
    else:
        # bf16 bytes of the bin one-hots and the deepest level's routing
        # one-hot, as if every pair held its own: the bin one-hots are
        # built once a dispatch and shared by its pairs, so this
        # over-counts them. A pair's own float32 histograms of the
        # deepest level (nodes / 2), with their cumulative sums and the
        # gain table: (k + 1) value columns × nodes / 2 × slots × 4
        # bytes, three times
        w_mem = _PAIR_MEM_BYTES // max(
            n_rows * (slots + nodes) * 2 + 6 * (k + 1) * nodes * slots, 1)
    w_work = int(_DISPATCH_UNITS // (learners * unit))
    width = min(_pow2_floor(max(1, min(w_mem, w_work))),
                _pow2_floor(n_pairs) if pad_tail else n_pairs)
    rounds = _pick_rounds_per_dispatch(learners, max(1, int(
        _DISPATCH_UNITS // (width * unit * max(int(classes), 1)))))
    return width, rounds


def fit_gbt_hosted(Xb, y, w, n_estimators: int, max_depth: int, n_bins: int,
                   learning_rate, reg_lambda, objective: str = "logistic",
                   min_child_weight: float = 1.0, gamma=0.0, alpha=0.0,
                   subsample=1.0, colsample=1.0, seed=0, val_w=None,
                   early_stopping_rounds: int = 0,
                   rounds_per_dispatch: Optional[int] = None,
                   min_gain_norm=0.0, eval_metric: str = "logloss",
                   layout: Optional[Dict] = None, base_score=None,
                   n_classes: int = 0):
    """Host-chunked boosting: bitwise-identical trees/margin to `fit_gbt`
    (same key stream, same scan body) but dispatched `rounds_per_dispatch`
    rounds at a time so early stopping SKIPS the remaining dispatches
    instead of masking them. Used for refits whose full scan would be
    tens of seconds (200-round depth-10 at 100k rows). `base_score`:
    where the chain starts, `gbt_base_score` of (y, w) when None (a
    softmax chain of `n_classes` starts at 0)."""
    n, d = Xb.shape
    esr = int(early_stopping_rounds) if val_w is not None else 0
    if val_w is None:
        val_w = jnp.zeros(n, jnp.float32)
    softmax = objective == "softmax"
    if rounds_per_dispatch is None:
        _, rounds_per_dispatch = dispatch_plan(
            n, hist_slots(d, n_bins, layout), max_depth, n_estimators,
            classes=n_classes if softmax else 0)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_estimators)
    if softmax:
        margin = gbt_margin0(y, w, objective, n_classes)
    else:
        if base_score is None:
            base_score = gbt_base_score(y, w, objective)
        margin = jnp.full(n, base_score, jnp.float32)
    best = jnp.float32(jnp.inf)
    since = jnp.int32(0)
    chunks = []
    done = 0
    while done < n_estimators:
        ks = keys[done:done + rounds_per_dispatch]
        (margin, best, since), trees = fit_gbt_chunk(
            Xb, y, w, val_w, margin, best, since, ks, int(ks.shape[0]),
            max_depth, n_bins, learning_rate, reg_lambda, objective,
            min_child_weight, None, gamma, alpha, subsample, colsample, esr,
            min_gain_norm, eval_metric, layout)
        chunks.append(trees)
        done += int(ks.shape[0])
        if esr and int(since) >= esr:
            break  # remaining rounds would all be zeroed no-op trees
    trees = jax.tree.map(lambda *a: jnp.concatenate(a, 0), *chunks)
    return trees, margin


@partial(jax.jit, static_argnames=())
def predict_gbt_multiclass_margin(trees: Dict, Xb: jnp.ndarray,
                                  learning_rate) -> jnp.ndarray:
    """(n, K) margin from (T, K, ...) stacked round trees."""
    per_round = jax.vmap(         # over rounds
        jax.vmap(lambda t: _leaf_lookup(
            t["leaf"][:, 0], _tree_walk(t, Xb))))(trees)  # (T, K, n)
    return learning_rate * per_round.sum(axis=0).T


def gbt_multiclass_pred_from_margin(margin: jnp.ndarray) -> Dict:
    probs = jax.nn.softmax(margin, axis=1)
    return {"prediction": jnp.argmax(probs, 1).astype(jnp.float32),
            "rawPrediction": margin, "probability": probs}


@partial(jax.jit, static_argnames=("chunk",))
def predict_gbt_margin(trees: Dict, Xb: jnp.ndarray, learning_rate,
                       chunk: int = 64) -> jnp.ndarray:
    return learning_rate * _predict_trees_margin(trees, Xb, chunk)


# --------------------------------------------------------------------------- #
# shared prediction assembly (model classes AND the sweep engine use these,   #
# so sweep metrics always describe exactly what the refit model predicts)     #
# --------------------------------------------------------------------------- #

def forest_classification_pred(trees: Dict, Xb: jnp.ndarray,
                               chunk: int = 64) -> Dict:
    probs = predict_forest(trees, Xb, chunk)
    probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    return {"prediction": jnp.argmax(probs, -1).astype(jnp.float32),
            "rawPrediction": probs, "probability": probs}


def forest_regression_pred(trees: Dict, Xb: jnp.ndarray,
                           chunk: int = 64) -> Dict:
    pred = predict_forest(trees, Xb, chunk)[:, 0]
    return {"prediction": pred, "rawPrediction": pred[:, None],
            "probability": jnp.zeros((Xb.shape[0], 0), jnp.float32)}


def gbt_pred_from_margin(margin: jnp.ndarray, objective: str) -> Dict:
    if objective == "softmax":
        return gbt_multiclass_pred_from_margin(margin)
    if objective == "logistic":
        p1 = jax.nn.sigmoid(margin)
        return {"prediction": (margin > 0).astype(jnp.float32),
                "rawPrediction": jnp.stack([-margin, margin], 1),
                "probability": jnp.stack([1 - p1, p1], axis=1)}
    return {"prediction": margin, "rawPrediction": margin[:, None],
            "probability": jnp.zeros((margin.shape[0], 0), jnp.float32)}


# --------------------------------------------------------------------------- #
# Warm-start refits (continual training)                                      #
# --------------------------------------------------------------------------- #

def warm_tree_compatible(warm: Dict, X,
                         n_classes: Optional[int] = None,
                         max_bins: Optional[int] = None) -> bool:
    """Host-side validation of a tree warm-start payload against the
    incoming data — the `resolve_init_params` analogue for forests/GBT.
    The resident bin edges must match the feature width, the
    estimator's `max_bins` histogram must cover every resident bin id
    (rows binned past it would one-hot to all zeros and silently vanish
    from split decisions), and for classification the resident leaf
    width must cover every observed class: `one_hot` of an unseen class
    under the old width is all zeros, so a mismatched warm refit would
    silently mistrain instead of erroring. Returns False → the caller
    fits cold."""
    edges = np.asarray(warm["edges"])
    d = int(np.shape(X)[1])
    if int(edges.shape[0]) != d:
        log.info("tree warm refit: feature width changed (%d -> %d); "
                 "fitting cold", int(edges.shape[0]), d)
        return False
    if max_bins is not None and int(edges.shape[1]) + 1 > int(max_bins):
        log.info("tree warm refit: resident edges bin to %d buckets but "
                 "the estimator's max_bins is %d; fitting cold",
                 int(edges.shape[1]) + 1, int(max_bins))
        return False
    if n_classes is not None:
        leaf = np.asarray(warm["trees"]["leaf"])
        if int(leaf.shape[-1]) < int(n_classes):
            log.info("tree warm refit: resident leaves are %d-class but "
                     "the data has %d classes; fitting cold",
                     int(leaf.shape[-1]), int(n_classes))
            return False
    return True


def warm_refit_forest(est, warm: Dict, X, y, w, ctx,
                      classification: bool) -> Dict:
    """Forest warm refit: grow replacement trees on the DELTA rows and
    swap them in for the OLDEST trees of the resident ensemble, keeping
    the ensemble size (and therefore every compiled predict shape)
    fixed. `warm` is a fitted tree model's params ({"edges", "trees"})
    plus an optional "delta_rows" count of trailing new rows; without
    it the replacements grow on the full matrix.

    The resident bin edges are reused — re-binning under new quantiles
    would silently shift every surviving tree's split semantics.
    Returns the combined {"feat", "bin", "leaf"} pytree (host arrays)."""
    edges = jnp.asarray(np.asarray(warm["edges"], np.float32))
    old = {k: jnp.asarray(v) for k, v in warm["trees"].items()}
    n_trees = int(old["feat"].shape[0])
    delta = int(warm.get("delta_rows") or 0)
    if not (0 < delta <= X.shape[0]):
        delta = X.shape[0]
    n_new = int(warm.get("n_new") or 0)
    if n_new <= 0:
        # replacement count scales with how much of the data is new,
        # floored at one tree so a refit always learns something
        n_new = max(1, round(n_trees * delta / max(X.shape[0], 1)))
    n_new = min(n_new, n_trees)
    Xd = jnp.asarray(X)[-delta:]
    yd = jnp.asarray(y)[-delta:]
    wd = jnp.asarray(w)[-delta:]
    Xb = bin_features(Xd, edges)
    if classification:  # the labels themselves: `grow_tree`'s class form
        n_out = int(old["leaf"].shape[-1])
        Y = yd.astype(jnp.int32)
    else:
        n_out = 1
        Y = yd[:, None]
    seed = (ctx.seed if ctx is not None else 0) + n_trees  # fresh draws
    new = fit_forest(Xb, Y, wd, n_new, est.max_depth, est.max_bins,
                     n_out, seed, est.subsample_features,
                     est._effective_mcw(),
                     min_gain=jnp.float32(est.min_info_gain))
    combined = jax.tree.map(
        lambda o, nw: jnp.concatenate([o[n_new:], nw], axis=0), old, new)
    return pull("fit:trees", combined)


def warm_refit_gbt(est, warm: Dict, X, y, w, ctx,
                   objective: str) -> Dict:
    """GBT warm refit: CONTINUE boosting from the resident ensemble's
    margin instead of restarting from zero — the new rounds fit the
    residual the old trees leave on the refreshed data (appended rows
    included), and the grown trees append to the ensemble. A "softmax"
    ensemble's rounds are (rounds, K, ...) and its margin (n, K)."""
    edges = jnp.asarray(np.asarray(warm["edges"], np.float32))
    old = {k: jnp.asarray(v) for k, v in warm["trees"].items()}
    n_old = int(old["feat"].shape[0])
    lr = jnp.float32(warm.get("learning_rate", est.learning_rate))
    Xb = bin_features(jnp.asarray(X), edges)
    n = Xb.shape[0]
    if objective == "softmax":
        margin0 = predict_gbt_multiclass_margin(old, Xb, lr)
    else:
        margin0 = jnp.float32(warm.get("base_score", 0.0)) \
            + predict_gbt_margin(old, Xb, lr)
    n_extra = int(warm.get("n_new") or 0)
    if n_extra <= 0:
        n_extra = max(1, est.n_estimators // 4)
    # growth cap: an always-on loop must not boost the ensemble (and
    # every compiled predict shape, and HBM) without bound — the call
    # site falls back to a cold fit once the 2x ceiling is reached
    n_extra = min(n_extra,
                  max(1, 2 * int(est.n_estimators) - n_old))
    # key stream folded past the resident rounds: warm rounds draw fresh
    # subsample/colsample randomness, deterministically per (seed, round)
    seed = ctx.seed if ctx is not None else 0
    keys = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), n_old), n_extra)
    (_, _, _), new = fit_gbt_chunk(
        Xb, jnp.asarray(y), jnp.asarray(w), jnp.zeros(n, jnp.float32),
        margin0, jnp.float32(jnp.inf), jnp.int32(0), keys, n_extra,
        est.max_depth, est.max_bins, lr, jnp.float32(est.reg_lambda),
        objective, est._effective_mcw(), None, jnp.float32(est.gamma),
        jnp.float32(est.alpha), jnp.float32(est.subsample),
        jnp.float32(est.colsample_bytree), 0,
        jnp.float32(est.min_info_gain), est.eval_metric)
    combined = jax.tree.map(
        lambda o, nw: jnp.concatenate([o, nw], axis=0), old, new)
    return pull("fit:trees", combined)


# --------------------------------------------------------------------------- #
# Stage classes                                                               #
# --------------------------------------------------------------------------- #

class _TreeModelBase(PredictionModel):
    def __init__(self, edges=None, trees=None, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.edges = np.asarray(edges, dtype=np.float32)
        self.trees = {k: np.asarray(v) for k, v in trees.items()}

    def get_params(self):
        # ndarrays straight through: serialization offloads them to npz —
        # .tolist() would round-trip megabytes of leaves as PyObjects
        return {"edges": self.edges, "trees": dict(self.trees)}

    def _binned(self, X):
        return bin_features(jnp.asarray(X), jnp.asarray(self.edges))

    def _tree_pytree(self):
        return {"feat": jnp.asarray(self.trees["feat"], jnp.int32),
                "bin": jnp.asarray(self.trees["bin"], jnp.int32),
                "leaf": jnp.asarray(self.trees["leaf"], jnp.float32)}

    # megabyte-scale fitted arrays (a depth-12 forest is ~8MB) flow into
    # the compiled scorer as jit arguments, not closure constants
    def device_constants(self):
        return {"edges": jnp.asarray(self.edges),
                "trees": self._tree_pytree()}

    def narrow_device_constants(self, consts):
        """Quantized-inference dtypes for the tables the predict walk
        re-reads every level. Gates are SHAPE facts only (so every model
        sharing a scoring signature narrows to identical traced dtypes):
        split-feature ids fit int16 when d < 2^15, split-bin thresholds
        fit uint8 when there are at most 255 edges (bin ids <= n_edges),
        both lossless; threshold EDGES drop to f16 — lossy at f16 eps,
        inside the quantized mode's stated wire tolerance. Leaves stay
        f32 (tiny, and they carry the output precision)."""
        edges = consts["edges"]
        trees = dict(consts["trees"])
        d, n_edges = int(edges.shape[0]), int(edges.shape[1])
        if d < (1 << 15):
            trees["feat"] = trees["feat"].astype(jnp.int16)
        if n_edges <= 255:
            trees["bin"] = trees["bin"].astype(jnp.uint8)
        return {"edges": edges.astype(jnp.float16), "trees": trees}

    def device_apply_with(self, consts, enc, dev):
        return self._apply_arrays(consts["trees"],
                                  bin_features(jnp.asarray(dev[-1]),
                                               consts["edges"]))

    def predict_arrays(self, X):
        return self._apply_arrays(self._tree_pytree(), self._binned(X))

    def _apply_arrays(self, trees, Xb):
        raise NotImplementedError(type(self).__name__)


class ForestClassificationModel(_TreeModelBase):
    def _apply_arrays(self, trees, Xb):
        return forest_classification_pred(trees, Xb)


class ForestRegressionModel(_TreeModelBase):
    def _apply_arrays(self, trees, Xb):
        return forest_regression_pred(trees, Xb)


class GBTClassificationModel(_TreeModelBase):
    def __init__(self, edges=None, trees=None, learning_rate: float = 0.1,
                 uid: Optional[str] = None):
        super().__init__(edges=edges, trees=trees, uid=uid)
        self.learning_rate = learning_rate

    def get_params(self):
        d = super().get_params()
        d["learning_rate"] = self.learning_rate
        return d

    def _apply_arrays(self, trees, Xb):
        margin = predict_gbt_margin(trees, Xb,
                                    jnp.float32(self.learning_rate))
        return gbt_pred_from_margin(margin, "logistic")


class GBTRegressionModel(GBTClassificationModel):
    """`base_score`: where the chain started (`gbt_base_score`: the
    training target's weighted mean); a model saved before it existed
    loads with 0, which is where its chain started."""

    def __init__(self, edges=None, trees=None, learning_rate: float = 0.1,
                 base_score: float = 0.0, uid: Optional[str] = None):
        super().__init__(edges=edges, trees=trees,
                         learning_rate=learning_rate, uid=uid)
        self.base_score = float(base_score)

    def get_params(self):
        d = super().get_params()
        d["base_score"] = self.base_score
        return d

    def _apply_arrays(self, trees, Xb):
        margin = jnp.float32(self.base_score) + predict_gbt_margin(
            trees, Xb, jnp.float32(self.learning_rate))
        return gbt_pred_from_margin(margin, "squared")


class GBTMulticlassModel(GBTClassificationModel):
    """Softmax chain: trees stacked (rounds, classes, ...), an (n, K)
    margin scored by `gbt_pred_from_margin`."""

    def _apply_arrays(self, trees, Xb):
        margin = predict_gbt_multiclass_margin(
            trees, Xb, jnp.float32(self.learning_rate))
        return gbt_pred_from_margin(margin, "softmax")


class _TreeEstimatorBase(PredictorEstimator):
    # Optional shared binning cache (max_bins → (edges, Xb, layout)) used by the
    # sweep engine's HOST-loop fallback (`parallel/sweep.py:_sweep_generic`)
    # so repeated grid×fold fits bin the training matrix once. The batched
    # sweep path keeps its own per-family cache (`parallel/sweep.py:_binned`).
    _bin_cache: Optional[Dict] = None

    def _edges_binned(self, X, ctx, n_classes: int = 0,
                      batched_trees: int = 1):
        """(edges, binned matrix, histogram layout) of a training matrix;
        the matrix stays where it is (on the device in a workflow's
        refit: the span `tree:edges` says so). `n_classes`: a classifier
        forest's K; the span carries the fit's value columns (K, else 1)
        and the passes over the histogram operand a tree level.
        `batched_trees`: a softmax chain's K (`tree_span_attrs`)."""
        cache = self._bin_cache
        if cache is not None and self.max_bins in cache:
            return cache[self.max_bins]
        with TRACER.span("tree:edges", category="tree",
                         max_bins=self.max_bins, edges=edges_site(X),
                         value_columns=n_classes or 1,
                         hist_reads=hist_reads(n_classes),
                         **tree_span_attrs(self.max_depth, n_classes,
                                           batched_trees)):
            indicator = indicator_columns(X)
            edges = quantile_bin_edges(X, self.max_bins, indicator)
            out = (edges, bin_features(upload("tree:bin", X),
                                       upload("tree:bin", edges)),
                   hist_layout(indicator))
        if cache is not None:
            cache[self.max_bins] = out
        return out


class OpRandomForestClassifier(_TreeEstimatorBase):
    """Spark RandomForestClassifier param surface: `min_info_gain`
    (minInfoGain — gini-improvement threshold, normalized gain scale) and
    `min_instances_per_node` (minInstancesPerNode — with count weights this
    is the child-weight bound) are grid axes in the reference defaults
    (`DefaultSelectorParams.scala:38-39`)."""

    def __init__(self, n_trees: int = 20, max_depth: int = 5,
                 max_bins: int = DEFAULT_MAX_BINS, min_child_weight: float = 1.0,
                 subsample_features: bool = True, min_info_gain: float = 0.0,
                 min_instances_per_node: float = 1.0,
                 n_classes: Optional[int] = None, uid: Optional[str] = None):
        super().__init__(uid=uid, n_trees=n_trees, max_depth=max_depth,
                         max_bins=max_bins, min_child_weight=min_child_weight,
                         subsample_features=subsample_features,
                         min_info_gain=min_info_gain,
                         min_instances_per_node=min_instances_per_node,
                         n_classes=n_classes)
        self.n_trees = n_trees
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_child_weight = min_child_weight
        self.subsample_features = subsample_features
        self.min_info_gain = min_info_gain
        self.min_instances_per_node = min_instances_per_node
        self.n_classes = n_classes

    def _effective_mcw(self) -> float:
        return max(float(self.min_child_weight),
                   float(self.min_instances_per_node))

    def fit_arrays(self, X, y, w, ctx: FitContext):
        k = n_classes_of(self, y, ctx)
        warm = self.init_params
        if warm is not None and "trees" in warm and \
                warm_tree_compatible(warm, X, n_classes=k,
                                     max_bins=self.max_bins):
            trees = warm_refit_forest(self, warm, X, y, w, ctx,
                                      classification=True)
            return ForestClassificationModel(
                np.asarray(warm["edges"], np.float32), trees)
        edges, Xb, layout = self._edges_binned(X, ctx, n_classes=k)
        trees = fit_forest(Xb, y.astype(jnp.int32), w, self.n_trees,
                           self.max_depth,
                           self.max_bins, k, ctx.seed,
                           self.subsample_features, self._effective_mcw(),
                           min_gain=jnp.float32(self.min_info_gain),
                           layout=layout)
        return ForestClassificationModel(edges, pull("fit:trees", trees))


class OpRandomForestRegressor(OpRandomForestClassifier):
    def fit_arrays(self, X, y, w, ctx: FitContext):
        warm = self.init_params
        if warm is not None and "trees" in warm and \
                warm_tree_compatible(warm, X, max_bins=self.max_bins):
            trees = warm_refit_forest(self, warm, X, y, w, ctx,
                                      classification=False)
            return ForestRegressionModel(
                np.asarray(warm["edges"], np.float32), trees)
        edges, Xb, layout = self._edges_binned(X, ctx)
        trees = fit_forest(Xb, y[:, None], w, self.n_trees, self.max_depth,
                           self.max_bins, 1, ctx.seed,
                           self.subsample_features, self._effective_mcw(),
                           min_gain=jnp.float32(self.min_info_gain),
                           layout=layout)
        return ForestRegressionModel(edges, pull("fit:trees", trees))


class OpDecisionTreeClassifier(OpRandomForestClassifier):
    """Single deterministic tree (no bootstrap, all features)."""

    def __init__(self, max_depth: int = 5, max_bins: int = DEFAULT_MAX_BINS,
                 min_child_weight: float = 1.0, min_info_gain: float = 0.0,
                 min_instances_per_node: float = 1.0,
                 n_classes: Optional[int] = None,
                 uid: Optional[str] = None):
        super().__init__(n_trees=1, max_depth=max_depth, max_bins=max_bins,
                         min_child_weight=min_child_weight,
                         min_info_gain=min_info_gain,
                         min_instances_per_node=min_instances_per_node,
                         subsample_features=False, n_classes=n_classes, uid=uid)
        self.params = {"max_depth": max_depth, "max_bins": max_bins,
                       "min_child_weight": min_child_weight,
                       "min_info_gain": min_info_gain,
                       "min_instances_per_node": min_instances_per_node,
                       "n_classes": n_classes}

    def fit_arrays(self, X, y, w, ctx: FitContext):
        k = n_classes_of(self, y, ctx)
        edges, Xb, layout = self._edges_binned(X, ctx, n_classes=k)
        tree = grow_tree(Xb, y.astype(jnp.int32), w, self.max_depth,
                         self.max_bins, reg_lambda=1e-6,
                         min_child_weight=self._effective_mcw(),
                         min_gain_norm=jnp.float32(self.min_info_gain),
                         layout=layout, n_classes=k)
        trees = jax.tree.map(lambda a: a[None], tree)  # (1, ...) forest shape
        return ForestClassificationModel(edges, pull("fit:trees", trees))


class OpDecisionTreeRegressor(OpRandomForestRegressor):
    def __init__(self, max_depth: int = 5, max_bins: int = DEFAULT_MAX_BINS,
                 min_child_weight: float = 1.0, min_info_gain: float = 0.0,
                 min_instances_per_node: float = 1.0,
                 uid: Optional[str] = None):
        super().__init__(n_trees=1, max_depth=max_depth, max_bins=max_bins,
                         min_child_weight=min_child_weight,
                         min_info_gain=min_info_gain,
                         min_instances_per_node=min_instances_per_node,
                         subsample_features=False, uid=uid)
        self.params = {"max_depth": max_depth, "max_bins": max_bins,
                       "min_child_weight": min_child_weight,
                       "min_info_gain": min_info_gain,
                       "min_instances_per_node": min_instances_per_node}

    def fit_arrays(self, X, y, w, ctx: FitContext):
        edges, Xb, layout = self._edges_binned(X, ctx)
        tree = grow_tree(Xb, (y * w)[:, None], w, self.max_depth, self.max_bins,
                         reg_lambda=1e-6,
                         min_child_weight=self._effective_mcw(),
                         min_gain_norm=jnp.float32(self.min_info_gain),
                         layout=layout)
        trees = jax.tree.map(lambda a: a[None], tree)
        return ForestRegressionModel(edges, pull("fit:trees", trees))


class OpGBTClassifier(_TreeEstimatorBase):
    """Gradient-boosted classifier, XGBoost-style 2nd order: binary via
    sigmoid margin, multiclass via softmax boosting (K trees/round)."""

    def __init__(self, n_estimators: int = 20, max_depth: int = 3,
                 learning_rate: float = 0.1, reg_lambda: float = 1.0,
                 max_bins: int = DEFAULT_MAX_BINS, min_child_weight: float = 1.0,
                 gamma: float = 0.0, alpha: float = 0.0,
                 subsample: float = 1.0, colsample_bytree: float = 1.0,
                 early_stopping_rounds: int = 0, min_info_gain: float = 0.0,
                 min_instances_per_node: float = 1.0,
                 eval_metric: str = "logloss",
                 n_classes: Optional[int] = None, uid: Optional[str] = None):
        super().__init__(uid=uid, n_estimators=n_estimators, max_depth=max_depth,
                         learning_rate=learning_rate, reg_lambda=reg_lambda,
                         max_bins=max_bins, min_child_weight=min_child_weight,
                         gamma=gamma, alpha=alpha, subsample=subsample,
                         colsample_bytree=colsample_bytree,
                         early_stopping_rounds=early_stopping_rounds,
                         min_info_gain=min_info_gain,
                         min_instances_per_node=min_instances_per_node,
                         eval_metric=eval_metric,
                         n_classes=n_classes)
        self.n_estimators = n_estimators
        self.max_depth = max_depth
        self.learning_rate = learning_rate
        self.reg_lambda = reg_lambda
        self.max_bins = max_bins
        self.min_child_weight = min_child_weight
        self.gamma = gamma
        self.alpha = alpha
        self.subsample = subsample
        self.colsample_bytree = colsample_bytree
        self.early_stopping_rounds = early_stopping_rounds
        # Spark GBTClassifier/Regressor parity knobs (the regression
        # default grid sweeps them, DefaultSelectorParams.scala:38-39);
        # min_info_gain uses the NORMALIZED gain scale, XGBoost's `gamma`
        # stays raw
        self.min_info_gain = min_info_gain
        self.min_instances_per_node = min_instances_per_node
        # early-stopping eval: "logloss" (Spark-ish strictly-proper
        # default) or "aupr" (the reference's maximized XGBoost aucpr,
        # DefaultSelectorParams.scala:71 — OpXGBoostClassifier's default)
        self.eval_metric = eval_metric
        self.n_classes = n_classes

    def _effective_mcw(self) -> float:
        return max(float(self.min_child_weight),
                   float(self.min_instances_per_node))

    _objective = "logistic"
    _model_cls = GBTClassificationModel

    # refit early-stopping eval fraction: like the XGBoost sklearn
    # `eval_set` idiom, a seeded 20% of the training rows is held out of
    # the boosting gradients and drives numEarlyStoppingRounds when no
    # explicit eval split exists (the reference CV sweep evals on the
    # fold's validation rows; the refit has no fold)
    _ES_EVAL_FRACTION = 0.2

    def objective_of(self, n_classes: int) -> str:
        """The chain's objective: the estimator's, or "softmax" for a
        logistic one over more than two classes."""
        if self._objective == "logistic" and n_classes > 2:
            return "softmax"
        return self._objective

    def fit_arrays(self, X, y, w, ctx: FitContext):
        if self._objective == "logistic":
            k = n_classes_of(self, y, ctx)
        else:
            k = 2
        objective = self.objective_of(k)
        softmax = objective == "softmax"
        warm = self.init_params
        if warm is not None and "trees" in warm:
            resident = np.asarray(warm["trees"]["leaf"])
            n_resident = int(resident.shape[0])
            if softmax != (resident.ndim == 4) or (
                    softmax and int(resident.shape[1]) != k):
                log.info("GBT warm refit: resident chain is not a %s one "
                         "of %d classes; fitting cold", objective, k)
            elif n_resident >= 2 * self.n_estimators:
                log.info("GBT warm refit: resident ensemble at the 2x "
                         "growth cap (%d rounds vs n_estimators=%d); "
                         "fitting cold to reset the ensemble size",
                         n_resident, self.n_estimators)
            elif not warm_tree_compatible(warm, X,
                                          max_bins=self.max_bins):
                pass  # logged: shape drift falls back to a cold fit
            else:
                trees = warm_refit_gbt(self, warm, X, y, w, ctx, objective)
                return self._model(
                    np.asarray(warm["edges"], np.float32), trees,
                    float(warm.get("learning_rate", self.learning_rate)),
                    warm.get("base_score", 0.0), objective)
        classes = k if softmax else 0
        edges, Xb, layout = self._edges_binned(
            X, ctx, batched_trees=classes or 1)
        seed = ctx.seed if ctx is not None else 0
        esr = int(self.early_stopping_rounds or 0)
        n_rounds = self.n_estimators
        if esr > 0:
            # Pass 1 — round-count search: hold a seeded 20% of rows out
            # of the boosting gradients and let numEarlyStoppingRounds
            # pick the effective round count. The probe model is thrown
            # away: the reference's xgboost4j-spark refit trains on ALL
            # rows (trainTestRatio default 1.0), so shipping the
            # 80%-trained model silently changed default behavior
            # (r3 advisor, medium).
            rng = np.random.default_rng(seed)
            hold = jnp.asarray(
                rng.uniform(size=Xb.shape[0]) < self._ES_EVAL_FRACTION,
                dtype=jnp.float32)
            probe, _ = fit_gbt_hosted(
                Xb, y, (1.0 - hold) * w, self.n_estimators, self.max_depth,
                self.max_bins, jnp.float32(self.learning_rate),
                jnp.float32(self.reg_lambda), objective,
                self._effective_mcw(), gamma=jnp.float32(self.gamma),
                alpha=jnp.float32(self.alpha),
                subsample=jnp.float32(self.subsample),
                colsample=jnp.float32(self.colsample_bytree),
                seed=seed, val_w=hold * w, early_stopping_rounds=esr,
                min_gain_norm=jnp.float32(self.min_info_gain),
                eval_metric=self.eval_metric, layout=layout,
                n_classes=classes)
            # stopped rounds grow ZEROED trees, so the probe's stopping
            # round is the LAST live tree's index + 1 — counting live
            # trees instead would undercount when a mid-sequence tree is
            # fully pruned by gamma/min_info_gain (all-zero leaves while
            # boosting continued; r4 advisor)
            leaf = np.asarray(probe["leaf"])
            live = np.any(leaf != 0, axis=tuple(range(1, leaf.ndim)))
            n_live = int(np.flatnonzero(live).max()) + 1 if live.any() else 1
            # quantize UP to a multiple of the probe's dispatch chunk so
            # the refit reuses the already-compiled chunk program
            # instead of compiling a fresh XLA shape; the ≤R-1 extra
            # rounds match XGBoost's default of
            # predicting with post-best-iteration trees included
            _, rpd = dispatch_plan(
                Xb.shape[0],
                hist_slots(Xb.shape[1], self.max_bins, layout),
                self.max_depth, self.n_estimators, classes=classes)
            n_rounds = min(-(-n_live // rpd) * rpd, self.n_estimators)
            rpd_refit = rpd
        else:
            rpd_refit = None
        # Pass 2 (or the only pass) — the shipped model: full weights,
        # fixed round count, no holdout.
        base = gbt_base_score(y, w, objective)
        trees, _ = fit_gbt_hosted(
            Xb, y, w, n_rounds, self.max_depth,
            self.max_bins, jnp.float32(self.learning_rate),
            jnp.float32(self.reg_lambda), objective,
            self._effective_mcw(),
            gamma=jnp.float32(self.gamma),
            alpha=jnp.float32(self.alpha),
            subsample=jnp.float32(self.subsample),
            colsample=jnp.float32(self.colsample_bytree),
            seed=seed, rounds_per_dispatch=rpd_refit,
            min_gain_norm=jnp.float32(self.min_info_gain), layout=layout,
            base_score=base, n_classes=classes)
        return self._model(
            edges, pull("fit:trees", trees),
            self.learning_rate, base, objective)

    def _model(self, edges, trees, learning_rate, base_score, objective):
        """The fitted model; a squared-loss one keeps where its chain
        started."""
        if objective == "softmax":
            return GBTMulticlassModel(edges, trees, learning_rate)
        started = ({"base_score": float(base_score)}
                   if objective == "squared" else {})
        return self._model_cls(edges, trees, learning_rate, **started)


class OpGBTRegressor(OpGBTClassifier):
    _objective = "squared"
    _model_cls = GBTRegressionModel


class OpXGBoostClassifier(OpGBTClassifier):
    """XGBoost parameter surface (OpXGBoostClassifier.scala:47,
    XGBoostParams.scala:55-69): eta / gamma / alpha / lambda / subsample /
    colsample_bytree / min_child_weight, binary AND multiclass objectives.
    The in-tree GBT implements the XGBoost histogram + second-order
    algorithm natively; Rabit allreduce becomes a psum over the sharded
    batch axis."""

    def __init__(self, n_estimators: int = 50, max_depth: int = 6,
                 eta: float = 0.3, reg_lambda: float = 1.0,
                 max_bins: int = DEFAULT_MAX_BINS,
                 min_child_weight: float = 1.0, gamma: float = 0.0,
                 alpha: float = 0.0, subsample: float = 1.0,
                 colsample_bytree: float = 1.0,
                 early_stopping_rounds: int = 0, min_info_gain: float = 0.0,
                 min_instances_per_node: float = 1.0,
                 eval_metric: str = "aupr",
                 n_classes: Optional[int] = None, uid: Optional[str] = None):
        super().__init__(n_estimators=n_estimators, max_depth=max_depth,
                         learning_rate=eta, reg_lambda=reg_lambda,
                         max_bins=max_bins, min_child_weight=min_child_weight,
                         gamma=gamma, alpha=alpha, subsample=subsample,
                         colsample_bytree=colsample_bytree,
                         early_stopping_rounds=early_stopping_rounds,
                         min_info_gain=min_info_gain,
                         min_instances_per_node=min_instances_per_node,
                         eval_metric=eval_metric,
                         n_classes=n_classes, uid=uid)
        self.params["eta"] = eta
        self.params.pop("learning_rate", None)


class OpXGBoostRegressor(OpXGBoostClassifier):
    _objective = "squared"
    _model_cls = GBTRegressionModel
