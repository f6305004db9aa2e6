"""Raw columns -> the feature matrix, as the workflow's default
vectorisation is documented: per feature type (in order of first
appearance) every column gives its value (a missing cell filled with the
column mean) and a null indicator; the combined vector then loses every
column whose variance is under 1e-5 (the sanity checker's floor)."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

MIN_VARIANCE = 1e-5


def encode(columns: Dict[str, np.ndarray], names_types: List[Tuple[str, str]]
           ) -> Tuple[np.ndarray, List[str]]:
    """((n, d) float32, kept column labels)."""
    order: List[str] = []
    for _, ty in names_types:
        if ty not in order:
            order.append(ty)
    out, labels = [], []
    for ty in order:
        for name, t in names_types:
            if t != ty:
                continue
            v = np.asarray(columns[name], np.float64)
            missing = np.isnan(v)
            if missing.any():
                v = np.where(missing, v[~missing].mean(), v)
            for lab, col in ((name, v), (name + ":null", missing * 1.0)):
                if np.var(col) >= MIN_VARIANCE:
                    out.append(col.astype(np.float32))
                    labels.append(lab)
    return np.stack(out, axis=1), labels
