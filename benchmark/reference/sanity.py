"""The sanity checker's drop rules in float64 numpy, from its documented
defaults (SanityChecker.scala:561-578, DerivedFeatureFilterUtils.scala:
355-385): over all rows (the checker samples only above 1,000,000),

- a column whose sample variance is under 1e-5 goes;
- a column whose |Pearson correlation with the label| is over 0.95 goes;
- a column whose |correlation| with an EARLIER column that is still kept
  is over 0.99 goes (the later of a duplicated pair); a constant column
  correlates as 0;
- every column of a categorical group (a pivot's level, OTHER and null
  columns; an integer's null indicator) goes when the group's Cramér's V
  against the label is over 0.95. V comes from the group's levels x
  labels count table with empty rows and columns left out
  (OpStatistics.scala:188); under two rows or columns it is 0.

Departure: the rule-confidence check is off at its defaults (confidence
and support both 1.0) and is left out.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

MIN_VARIANCE = 1e-5
MAX_LABEL_CORR = 0.95
MAX_FEATURE_CORR = 0.99
MAX_CRAMERS_V = 0.95
ROWS = 1 << 15


def moments(X: np.ndarray, y: np.ndarray, labels: np.ndarray):
    """(centred Gram (d+1, d+1) of [X | y], (d, labels) sums of each
    column over the rows of each label: for a 0/1 column, its count
    beside the label) in float64: one product a block of rows over
    [X | y | one-hot label], centred at the end."""
    n, d = X.shape
    k = len(labels)
    S = np.zeros((d + 1 + k, d + 1 + k))
    for a in range(0, n, ROWS):
        yb = y[a:a + ROWS]
        W = np.empty((len(yb), d + 1 + k))
        W[:, :d] = X[a:a + ROWS]
        W[:, d] = yb
        W[:, d + 1:] = yb[:, None] == labels[None, :]
        S += np.dot(W.T, W)
    total = S[:d + 1, d + 1:].sum(1)            # column sums of [X | y]
    return (S[:d + 1, :d + 1] - np.outer(total, total) / n,
            S[:d, d + 1:])


def cramers_v(table: np.ndarray) -> float:
    t = table[table.sum(1) > 0][:, table.sum(0) > 0]
    if t.shape[0] < 2 or t.shape[1] < 2:
        return 0.0
    n = t.sum()
    expected = t.sum(1, keepdims=True) @ t.sum(0, keepdims=True) / n
    chi2 = ((t - expected) ** 2 / expected).sum()
    return float(np.sqrt(chi2 / (n * (min(t.shape) - 1))))


def check(X: np.ndarray, y: np.ndarray, groups: Dict[str, List[int]]
          ) -> Tuple[List[int], Dict[str, float]]:
    """(kept column positions, Cramér's V by group)."""
    y = np.asarray(y, np.float64)
    n, d = X.shape
    labels = np.unique(y)
    G, counts = moments(X, y, labels)
    cov = G / max(n - 1, 1)
    var = np.maximum(np.diag(cov), 0.0)
    sd = np.sqrt(var)
    denom = np.outer(sd, sd)
    with np.errstate(divide="ignore", invalid="ignore"):
        corr = np.where(denom > 0, cov / denom, 0.0)
    v_of, group_of = {}, {}
    for name, idxs in groups.items():
        v_of[name] = cramers_v(counts[idxs])
        for j in idxs:
            group_of[j] = name
    kept, gone = [], set()
    for j in range(d):
        drop = var[j] < MIN_VARIANCE or abs(corr[j, d]) > MAX_LABEL_CORR
        hits = [i for i in range(j) if abs(corr[j, i]) > MAX_FEATURE_CORR]
        # the checker looks at the FIRST earlier column over the limit
        # that is still kept; one is enough
        drop = drop or any(i not in gone for i in hits)
        drop = drop or (j in group_of
                        and v_of[group_of[j]] > MAX_CRAMERS_V)
        if drop:
            gone.add(j)
        else:
            kept.append(j)
    return (kept or list(range(d))), v_of
