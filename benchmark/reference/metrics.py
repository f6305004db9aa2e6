"""Validation metrics in float64 numpy."""

from __future__ import annotations

import numpy as np


def aupr(y: np.ndarray, scores: np.ndarray) -> float:
    """Trapezoid area under the tie-grouped precision-recall curve from
    the point (recall 0, precision 1): Spark's convention."""
    y = np.asarray(y, np.float64)
    s = np.asarray(scores, np.float64)
    n_pos = y.sum()
    if n_pos == 0:
        return 0.0
    order = np.argsort(-s, kind="stable")
    ys, ss = y[order], s[order]
    ends = np.concatenate([np.nonzero(np.diff(ss))[0], [len(ss) - 1]])
    tp = np.cumsum(ys)[ends]
    prec = np.concatenate([[1.0], tp / (ends + 1.0)])
    rec = np.concatenate([[0.0], tp / n_pos])
    return float(np.sum((rec[1:] - rec[:-1]) * (prec[1:] + prec[:-1]) / 2))


def validation_metric(name: str, y, pred: dict, k: int) -> float:
    if name == "AuPR":
        return aupr(y, np.asarray(pred["probability"])[:, 1])
    raise ValueError(f"no reference for the metric {name!r}")
