"""Masked, jit-safe metric kernels for the batched sweep engine.

Reference parity: the metric *values* match `evaluators/metrics.py` (which
itself mirrors `core/.../evaluators/OpBinaryClassificationEvaluator.scala`
etc.), but these run ON DEVICE inside the fused sweep program: folds are
0/1 row masks over the fixed training matrix, so fit → predict → metric for
every grid×fold executes as one XLA computation with no host round-trip
(the reference evaluates each fit's metrics in a separate Spark job —
`OpValidator.scala:318-340`).

Masked-row semantics: a row with mask 0 contributes zero weight everywhere.
In the rank-based metrics (AuROC/AuPR) masked rows still occupy slots in
the sorted arrays but with zero weight they only create duplicated curve
points whose trapezoid contribution is exactly zero, so the result equals
the host metric computed on the unmasked subset (ties included).

The rank metrics hold no search and no table-sized gather (on a TPU each
is a serial pass over the rows): the weights ride the one multi-operand
sort, and a row reads its tie group's prefix sums by a running
minimum/maximum over the group's flagged end/start (`_at_group_end`,
`_at_group_start`). Weights are 0/1 and the sums integers below 2^24, so
the result is the same float in any order within a tie.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _masked_sum(x, mask):
    return (x * mask).sum()


def _tie_groups(s):
    """(is_start, is_end) row flags of the runs of equal values in sorted `s`."""
    change = s[1:] != s[:-1]
    edge = jnp.ones(1, bool)
    return jnp.concatenate([edge, change]), jnp.concatenate([change, edge])


def _at_group_end(cum, is_end):
    """Non-decreasing `cum` read at the LAST row of every row's tie group:
    a reverse running minimum over the group ends, no search and no gather."""
    return jax.lax.cummin(jnp.where(is_end, cum, jnp.inf), reverse=True)


def _at_group_start(cum, is_start):
    """Non-decreasing `cum` read at the FIRST row of every row's tie group."""
    return jax.lax.cummax(jnp.where(is_start, cum, -jnp.inf))


def auroc_dev(y: jnp.ndarray, scores: jnp.ndarray, mask: jnp.ndarray):
    """Tie-averaged Mann-Whitney AuROC over masked rows (auroc_score parity)."""
    wpos = mask * y
    wneg = mask * (1.0 - y)
    s, wp, wn = jax.lax.sort((scores, wpos, wneg), num_keys=1, is_stable=True)
    is_start, is_end = _tie_groups(s)
    cumn = jnp.cumsum(wn)
    below = _at_group_start(cumn - wn, is_start)   # negatives ranked lower
    tied = _at_group_end(cumn, is_end) - below
    num = (wp * (below + 0.5 * tied)).sum()
    n_pos = wpos.sum()
    n_neg = wneg.sum()
    ok = (n_pos > 0) & (n_neg > 0)
    return jnp.where(ok, num / jnp.maximum(n_pos * n_neg, 1e-30), 0.0)


def aupr_dev(y: jnp.ndarray, scores: jnp.ndarray, mask: jnp.ndarray):
    """Trapezoid area under the tie-grouped PR curve with the (r=0, p=1)
    start point (aupr_score / Spark BinaryClassificationMetrics parity)."""
    wpos = mask * y
    # ascending in -scores == scores descending; the weights ride the sort
    s_asc, wp, w = jax.lax.sort((-scores, wpos, mask), num_keys=1,
                                is_stable=True)
    _, is_end = _tie_groups(s_asc)
    # every row reads the prefix sums at its tie-group END
    tp = _at_group_end(jnp.cumsum(wp), is_end)
    n_at = _at_group_end(jnp.cumsum(w), is_end)
    n_pos = wpos.sum()
    prec = jnp.where(n_at > 0, tp / jnp.maximum(n_at, 1e-30), 1.0)
    rec = tp / jnp.maximum(n_pos, 1e-30)
    r = jnp.concatenate([jnp.zeros(1, rec.dtype), rec])
    p = jnp.concatenate([jnp.ones(1, prec.dtype), prec])
    area = ((r[1:] - r[:-1]) * (p[1:] + p[:-1]) * 0.5).sum()
    return jnp.where(n_pos > 0, area, 0.0)


def aupr_binned_dev(y: jnp.ndarray, scores: jnp.ndarray, mask: jnp.ndarray,
                    n_bins: int = 4096):
    """Sort-free, APPROXIMATE AuPR: scores quantize to `n_bins` buckets,
    positive/total weights histogram via one-hot matmuls (MXU), then the
    tie-grouped PR trapezoid runs over the 4096 bucket boundaries.
    Equivalent to `aupr_dev` with scores rounded to 1/n_bins.

    Not wired into any sweep: `aupr_dev` is exact and is not the slow
    path on a TPU (one v5e, 2.16 M rows: one metric 8.5 ms, six under a
    `vmap` 95 ms; `PERF.md` §6, PR 27). What the binned form is for: a
    table whose scores never sit in one device array (out-of-core: the
    histograms of row chunks add), and masked row counts past 2^24,
    where `aupr_dev`'s float32 prefix sums stop being exact integers.
    It answers a different question than `aupr_dev` (≈ 2e-4 away at
    10M rows), so it does not stand in for it where a configuration
    states the exact metric."""
    s = jnp.clip(scores, 0.0, 1.0)
    b = jnp.minimum((s * n_bins).astype(jnp.int32), n_bins - 1)
    n = b.shape[0]
    wpos = (mask * y).astype(jnp.bfloat16)
    wall = mask.astype(jnp.bfloat16)
    # chunked histogram: a full (n, bins) one-hot would be 84 GB at 10M
    # rows; scan row chunks, each chunk's one-hot contracted immediately
    chunk = 65_536
    pad = (-n) % chunk
    if pad:
        b = jnp.concatenate([b, jnp.zeros(pad, jnp.int32)])
        wpos = jnp.concatenate([wpos, jnp.zeros(pad, jnp.bfloat16)])
        wall = jnp.concatenate([wall, jnp.zeros(pad, jnp.bfloat16)])
    n_chunks = (n + pad) // chunk

    def body(acc, args):
        b_c, wp_c, wa_c = args
        B = jax.nn.one_hot(b_c, n_bins, dtype=jnp.bfloat16)
        h = jnp.matmul(jnp.stack([wp_c, wa_c]), B,
                       preferred_element_type=jnp.float32)  # (2, bins)
        return acc + h, None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((2, n_bins), jnp.float32),
        (b.reshape(n_chunks, chunk), wpos.reshape(n_chunks, chunk),
         wall.reshape(n_chunks, chunk)))
    hp, ha = acc[0], acc[1]
    # descending-score cumulative = reversed cumsum
    tp = jnp.cumsum(hp[::-1])
    n_at = jnp.cumsum(ha[::-1])
    n_pos = tp[-1]
    prec = jnp.where(n_at > 0, tp / jnp.maximum(n_at, 1e-30), 1.0)
    rec = tp / jnp.maximum(n_pos, 1e-30)
    r = jnp.concatenate([jnp.zeros(1, rec.dtype), rec])
    p = jnp.concatenate([jnp.ones(1, prec.dtype), prec])
    area = ((r[1:] - r[:-1]) * (p[1:] + p[:-1]) * 0.5).sum()
    return jnp.where(n_pos > 0, area, 0.0)


def binary_confusion_dev(y, scores, mask, threshold: float = 0.5):
    """Weighted TP/TN/FP/FN and the derived point metrics at `threshold`."""
    pred = (scores >= threshold).astype(scores.dtype)
    pos = (y > 0.5).astype(scores.dtype)
    tp = _masked_sum(pred * pos, mask)
    fp = _masked_sum(pred * (1 - pos), mask)
    fn = _masked_sum((1 - pred) * pos, mask)
    tn = _masked_sum((1 - pred) * (1 - pos), mask)
    n = jnp.maximum(mask.sum(), 1.0)
    precision = jnp.where(tp + fp > 0, tp / jnp.maximum(tp + fp, 1e-30), 0.0)
    recall = jnp.where(tp + fn > 0, tp / jnp.maximum(tp + fn, 1e-30), 0.0)
    f1 = jnp.where(precision + recall > 0,
                   2 * precision * recall
                   / jnp.maximum(precision + recall, 1e-30), 0.0)
    error = (fp + fn) / n
    return {"Precision": precision, "Recall": recall, "F1": f1,
            "Error": error, "TP": tp, "TN": tn, "FP": fp, "FN": fn}


def confusion_dev(y, pred, mask, n_classes: int):
    """(K, K) float32 masked confusion matrix, rows the label, columns
    the prediction, both clipped into [0, K): two one-hots and ONE
    (K, n) @ (n, K) product in true float32, exact for 0/1 masks while a
    cell stays under 2^24. The scatter-add `zeros((K, K)).at[y, p].add(mask)`
    it replaces is a serial pass over the rows on a TPU."""
    yi = jnp.clip(y.astype(jnp.int32), 0, n_classes - 1)
    pi = jnp.clip(pred.astype(jnp.int32), 0, n_classes - 1)
    Y = jax.nn.one_hot(yi, n_classes, dtype=jnp.float32) \
        * mask[:, None].astype(jnp.float32)
    P = jax.nn.one_hot(pi, n_classes, dtype=jnp.float32)
    return jnp.matmul(Y.T, P, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def multiclass_dev(y, pred, mask, n_classes: int):
    """Weighted-average Precision/Recall/F1 + Error over a masked confusion
    matrix (multiclass_metrics parity; `n_classes` static — extra empty
    classes carry zero support weight so any upper bound is exact). No
    scatter: `confusion_dev`. Timed on one TPU v5e (PR 30) at 1,800,000
    rows and K = 23, six fold masks under a `vmap`, the two tables
    equal: the scatter-add 14.6 ms, the product 2.2 ms."""
    conf = confusion_dev(y, pred, mask, n_classes)
    tp = jnp.diagonal(conf)
    support = conf.sum(axis=1)
    pred_count = conf.sum(axis=0)
    prec_c = jnp.where(pred_count > 0, tp / jnp.maximum(pred_count, 1e-30), 0.0)
    rec_c = jnp.where(support > 0, tp / jnp.maximum(support, 1e-30), 0.0)
    f1_c = jnp.where(prec_c + rec_c > 0,
                     2 * prec_c * rec_c / jnp.maximum(prec_c + rec_c, 1e-30), 0.0)
    w = support / jnp.maximum(support.sum(), 1.0)
    err = 1.0 - tp.sum() / jnp.maximum(mask.sum(), 1.0)
    return {"Precision": (prec_c * w).sum(), "Recall": (rec_c * w).sum(),
            "F1": (f1_c * w).sum(), "Error": err}


def regression_dev(y, pred, mask):
    """Weighted RMSE/MSE/MAE/R2 (regression_metrics parity)."""
    n = jnp.maximum(mask.sum(), 1.0)
    err = (pred - y) * mask
    mse = (err ** 2).sum() / n
    mae = jnp.abs(err).sum() / n
    y_mean = _masked_sum(y, mask) / n
    ss_tot = _masked_sum((y - y_mean) ** 2, mask)
    ss_res = (err ** 2).sum()
    r2 = jnp.where(ss_tot > 0, 1.0 - ss_res / jnp.maximum(ss_tot, 1e-30), 0.0)
    return {"RMSE": jnp.sqrt(mse), "MSE": mse, "MAE": mae, "R2": r2}


@functools.lru_cache(maxsize=None)
def regression_metrics_dev(metric: str = "RMSE"):
    """`prog(y, pred)`: `regression_dev` over every row as one held
    program, under the kernel name the sweep's regression metric has
    (`metric:<metric>`): a selector's train and holdout metrics, of
    which only the four scalars cross to the host."""
    @jax.jit
    @jax.named_scope(f"metric:{metric.lower()}")
    def prog(y, pred):
        return regression_dev(y, pred, jnp.ones(y.shape, jnp.float32))
    return prog


def _binary_scores(pred: dict) -> jnp.ndarray:
    prob = pred.get("probability")
    if prob is not None and prob.ndim == 2 and prob.shape[1] >= 2:
        return prob[:, 1]
    return pred["prediction"]


def device_metric_key(evaluator, n_classes: int | None = None):
    """Hashable description of `evaluator`'s device kernel — `(kind,
    metric, threshold or n_classes)` — or None when it has none
    (LambdaEvaluator etc. fall back to the host path in
    parallel/sweep.py). Everything a metric kernel depends on besides
    its arguments is in the key, so a sweep program built from the key
    can be held and reused for any evaluator that maps to it."""
    from transmogrifai_tpu.evaluators.evaluators import (
        BinaryClassificationEvaluator, MultiClassificationEvaluator,
        RegressionEvaluator)

    metric = evaluator.default_metric
    if isinstance(evaluator, BinaryClassificationEvaluator):
        return ("binary", metric, float(evaluator.threshold))
    if isinstance(evaluator, MultiClassificationEvaluator):
        return (None if n_classes is None
                else ("multiclass", metric, int(n_classes)))
    if isinstance(evaluator, RegressionEvaluator):
        return ("regression", metric, None)
    return None


def device_metric(key):
    """metric_fn(y, pred_dict, val_mask) -> scalar for the sweep program,
    from a `device_metric_key`; the function carries its key as `.key`."""
    kind, metric, param = key
    # a stable kernel name in the compiled program and the device trace:
    # `metric:aupr`, `metric:auroc`, `metric:f1`, `metric:rmse`, ...
    scope = jax.named_scope(f"metric:{metric.lower()}")

    if kind == "binary":
        @scope
        def fn(y, pred, mask):
            s = _binary_scores(pred)
            if metric == "AuPR":
                return aupr_dev(y, s, mask)
            if metric == "AuROC":
                return auroc_dev(y, s, mask)
            return binary_confusion_dev(y, s, mask, param)[metric]
    elif kind == "multiclass":
        @scope
        def fn(y, pred, mask):
            return multiclass_dev(y, pred["prediction"], mask, param)[metric]
    else:
        @scope
        def fn(y, pred, mask):
            return regression_dev(y, pred["prediction"], mask)[metric]
    fn.key = key
    return fn


def make_device_metric(evaluator, n_classes: int | None = None):
    """`device_metric` of the evaluator's key, or None when `evaluator`
    has no device kernel."""
    key = device_metric_key(evaluator, n_classes)
    return None if key is None else device_metric(key)
