"""Bin edges of a TYPED feature matrix, as the tree estimators document
them: a column whose every value is 0 or 1 (a pivot's level, OTHER and
null columns, an integer's null indicator) has the one threshold 0.5 in
every position; every other column has the interior quantiles of
`reference/trees.py`."""

from __future__ import annotations

import numpy as np

from reference.trees import quantile_edges


def typed_edges(X: np.ndarray, n_bins: int) -> np.ndarray:
    """(d, n_bins-1) float32."""
    X = np.asarray(X)
    indicator = np.all((X == 0) | (X == 1), axis=0)
    edges = np.full((X.shape[1], n_bins - 1), 0.5, np.float32)
    if not indicator.all():
        edges[~indicator] = quantile_edges(X[:, ~indicator], n_bins)
    return edges
