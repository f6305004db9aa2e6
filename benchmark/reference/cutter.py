"""Multiclass label pruning before validation, as DataCutter.scala
documents it: of the training rows' labels keep the
`max_label_categories` most frequent (count descending, a tie to the
smaller label) whose share of the training rows is at least
`min_label_fraction`; rows of any other label leave the training set.
The holdout is not cut."""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def cut(y_train: np.ndarray, max_label_categories: int = 100,
        min_label_fraction: float = 0.0) -> Tuple[np.ndarray, List[float]]:
    """((n,) bool rows kept, the labels kept in rank order)."""
    y = np.asarray(y_train, np.float64)
    labels, counts = np.unique(y, return_counts=True)
    ranked = sorted(zip(-counts, labels))[:int(max_label_categories)]
    kept = [float(lab) for neg, lab in ranked
            if -neg / len(y) >= min_label_fraction]
    return np.isin(y, kept), kept
