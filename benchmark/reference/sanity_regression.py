"""The sanity checker's drop rules for a label that is NOT categorical
(a regression target: more integer values than the checker's
`categorical_label_max_card`, or fractional ones), in float64 numpy.
`sanity.py` holds the rules and their thresholds; what differs here is
that no contingency table is built (a label of a thousand values has no
Cramér's V worth the name, and the checker takes none), so every
decision is from the moments and the correlations:

- a column whose sample variance is under 1e-5 goes;
- a column whose |Pearson correlation with the label| is over 0.95 goes;
- a column whose |correlation| with an EARLIER column that is still kept
  is over 0.99 goes; a constant column correlates as 0.

`narrowed_label_correlations` is the control's: the same correlations
from float32 centred columns whose Gram product takes its operands
narrowed to `dtype` (what a chip's default precision does to a float32
product, or one step below it).
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from reference.sanity import (
    MAX_FEATURE_CORR, MAX_LABEL_CORR, MIN_VARIANCE, ROWS)


def correlations(X: np.ndarray, y: np.ndarray):
    """(variance (d,), correlation matrix (d+1, d+1) of [X | y]) in
    float64: one Gram product a block of rows, centred at the end."""
    n, d = X.shape
    S = np.zeros((d + 1, d + 1))
    total = np.zeros(d + 1)
    for a in range(0, n, ROWS):
        W = np.empty((min(ROWS, n - a), d + 1))
        W[:, :d] = X[a:a + ROWS]
        W[:, d] = y[a:a + ROWS]
        S += np.dot(W.T, W)
        total += W.sum(0)
    cov = (S - np.outer(total, total) / n) / max(n - 1, 1)
    var = np.maximum(np.diag(cov), 0.0)
    sd = np.sqrt(var)
    denom = np.outer(sd, sd)
    with np.errstate(divide="ignore", invalid="ignore"):
        corr = np.where(denom > 0, cov / denom, 0.0)
    return var[:d], corr


def check(X: np.ndarray, y: np.ndarray) -> Tuple[List[int], np.ndarray]:
    """(kept column positions, (d,) correlation of every column with
    the label)."""
    y = np.asarray(y, np.float64)
    d = X.shape[1]
    var, corr = correlations(X, y)
    kept, gone = [], set()
    for j in range(d):
        drop = var[j] < MIN_VARIANCE or abs(corr[j, d]) > MAX_LABEL_CORR
        hits = [i for i in range(j) if abs(corr[j, i]) > MAX_FEATURE_CORR]
        drop = drop or any(i not in gone for i in hits)
        if drop:
            gone.add(j)
        else:
            kept.append(j)
    return (kept or list(range(d))), corr[:d, d]


def narrowed_label_correlations(X: np.ndarray, y: np.ndarray, dtype,
                                clip: float) -> np.ndarray:
    """(d,) correlation of every column with the label from ONE float32
    Gram product of the centred [X | y] whose operands are narrowed to
    `dtype` (clipped into [-clip, clip] first: a narrowing that
    saturates), with float32 sums."""
    import jax.numpy as jnp
    from reference.linear import _mm
    Z = jnp.concatenate([jnp.asarray(X, jnp.float32),
                         jnp.asarray(y, jnp.float32)[:, None]], 1)
    Zc = jnp.clip(Z - Z.mean(0), -clip, clip)
    cov = np.asarray(_mm(Zc.T, Zc, dtype), np.float64) / max(len(y) - 1, 1)
    sd = np.sqrt(np.maximum(np.diag(cov), 0.0))
    denom = np.outer(sd, sd)
    with np.errstate(divide="ignore", invalid="ignore"):
        corr = np.where(denom > 0, cov / denom, 0.0)
    return corr[:-1, -1]
