"""Multiclass validation metrics in float64 numpy: the confusion matrix
and, from it, weighted precision, recall, F1 and the error
(OpMultiClassificationEvaluator.scala; Spark MulticlassMetrics'
weighted averages: each class's figure weighted by its share of the
true labels, a class nobody predicted has precision 0)."""

from __future__ import annotations

from typing import Dict

import numpy as np


def confusion(y: np.ndarray, pred: np.ndarray, k: int) -> np.ndarray:
    """(k, k) int64: rows the label, columns the prediction; a value
    outside [0, k) is clipped into it, as the program clips."""
    yi = np.clip(np.asarray(y).astype(np.int64), 0, k - 1)
    pi = np.clip(np.asarray(pred).astype(np.int64), 0, k - 1)
    return np.bincount(yi * k + pi, minlength=k * k).reshape(k, k)


def weighted_metrics(conf: np.ndarray) -> Dict[str, float]:
    conf = np.asarray(conf, np.float64)
    tp = np.diag(conf)
    support, predicted = conf.sum(1), conf.sum(0)
    with np.errstate(divide="ignore", invalid="ignore"):
        prec = np.where(predicted > 0, tp / predicted, 0.0)
        rec = np.where(support > 0, tp / support, 0.0)
        f1 = np.where(prec + rec > 0, 2 * prec * rec / (prec + rec), 0.0)
    total = max(conf.sum(), 1.0)
    w = support / total
    return {"Precision": float((prec * w).sum()),
            "Recall": float((rec * w).sum()), "F1": float((f1 * w).sum()),
            "Error": float(1.0 - tp.sum() / total)}


def validation_metric(name: str, y, pred: dict, k: int) -> float:
    if name not in ("Precision", "Recall", "F1", "Error"):
        raise ValueError(f"no multiclass reference for the metric {name!r}")
    return weighted_metrics(
        confusion(y, np.asarray(pred["prediction"]), k))[name]
