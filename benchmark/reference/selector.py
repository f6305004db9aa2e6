"""Holdout, folds and the winner, as the selector documents them: a
seeded permutation reserves the holdout, a second seeded permutation
modulo k assigns folds, the best mean validation metric wins."""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def holdout_split(n: int, fraction: float, seed: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    perm = np.random.default_rng(seed).permutation(n)
    n_test = int(round(n * fraction))
    return np.sort(perm[n_test:]), np.sort(perm[:n_test])


def cv_masks(n: int, k: int, seed: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    fold = np.random.default_rng(seed).permutation(n) % k
    return [((fold != j).astype(np.float32), (fold == j).astype(np.float32))
            for j in range(k)]


def winner(mean_metrics: List[float], larger_is_better: bool = True) -> int:
    """Index of the first best finite mean."""
    sign = 1.0 if larger_is_better else -1.0
    best, arg = None, -1
    for i, m in enumerate(mean_metrics):
        if np.isfinite(m) and (best is None or sign * m > best):
            best, arg = sign * m, i
    return arg
