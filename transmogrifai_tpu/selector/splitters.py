"""Data splitters: holdout reservation + label-balancing preparation.

Reference parity: `core/.../tuning/Splitter.scala:47-84` (reserve test
fraction), `DataSplitter.scala:65-128`, `DataBalancer.scala:73-393` (binary
up/down-sampling), `DataCutter.scala:78-308` (multiclass label pruning).

Host-side index computation (deterministic per seed); the device only ever
sees the resulting index arrays / weight masks.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from transmogrifai_tpu.obs.trace import TRACER


@dataclass
class SplitterSummary:
    splitter: str
    n_rows: int
    n_train: int
    n_test: int
    details: Dict = field(default_factory=dict)

    def to_json(self) -> Dict:
        return {"splitter": self.splitter, "n_rows": self.n_rows,
                "n_train": self.n_train, "n_test": self.n_test,
                "details": self.details}


class DataSplitter:
    """Random holdout reservation (DataSplitter.scala)."""

    def __init__(self, reserve_test_fraction: float = 0.1, seed: int = 42):
        self.reserve_test_fraction = reserve_test_fraction
        self.seed = seed

    def split(self, y: np.ndarray) -> Tuple[np.ndarray, np.ndarray, SplitterSummary]:
        n = len(y)
        rng = np.random.default_rng(self.seed)
        perm = rng.permutation(n)
        n_test = int(round(n * self.reserve_test_fraction))
        test, train = perm[:n_test], perm[n_test:]
        return np.sort(train), np.sort(test), SplitterSummary(
            splitter=type(self).__name__, n_rows=n,
            n_train=len(train), n_test=len(test))

    def prepare(self, y: np.ndarray, train_idx: np.ndarray
                ) -> Tuple[np.ndarray, Dict]:
        """Post-split training-set preparation (identity here)."""
        return train_idx, {}


class DataBalancer(DataSplitter):
    """Binary-label balancing: down-sample the majority class until the
    minority fraction reaches `sample_fraction` (DataBalancer.scala)."""

    def __init__(self, sample_fraction: float = 0.1,
                 max_training_sample: int = 1_000_000,
                 reserve_test_fraction: float = 0.1, seed: int = 42):
        super().__init__(reserve_test_fraction, seed)
        self.sample_fraction = sample_fraction
        self.max_training_sample = max_training_sample

    def prepare(self, y: np.ndarray, train_idx: np.ndarray
                ) -> Tuple[np.ndarray, Dict]:
        rng = np.random.default_rng(self.seed + 1)
        yt = y[train_idx]
        pos = train_idx[yt > 0.5]
        neg = train_idx[yt <= 0.5]
        n_pos, n_neg = len(pos), len(neg)
        details: Dict = {"n_pos": n_pos, "n_neg": n_neg, "balanced": False}
        if n_pos == 0 or n_neg == 0:
            return train_idx, details
        small, big = (pos, neg) if n_pos <= n_neg else (neg, pos)
        frac = len(small) / (len(small) + len(big))
        if frac < self.sample_fraction:
            # shrink the majority so the minority hits sample_fraction
            target_big = int(len(small) * (1 - self.sample_fraction)
                             / self.sample_fraction)
            big = rng.choice(big, size=min(target_big, len(big)), replace=False)
            details["balanced"] = True
        out = np.sort(np.concatenate([small, big]))
        if len(out) > self.max_training_sample:
            out = np.sort(rng.choice(out, self.max_training_sample, replace=False))
            details["downsampled_to_max"] = True
        details["n_after"] = int(len(out))
        return out, details


class DataCutter(DataSplitter):
    """Multiclass label pruning: keep the most frequent labels
    (DataCutter.scala: maxLabelCategories / minLabelFraction)."""

    def __init__(self, max_label_categories: int = 100,
                 min_label_fraction: float = 0.0,
                 reserve_test_fraction: float = 0.1, seed: int = 42):
        super().__init__(reserve_test_fraction, seed)
        self.max_label_categories = max_label_categories
        self.min_label_fraction = min_label_fraction

    def prepare(self, y: np.ndarray, train_idx: np.ndarray
                ) -> Tuple[np.ndarray, Dict]:
        """Rows of the `max_label_categories` most frequent training
        labels (count descending, a tie to the smaller label) whose share
        is at least `min_label_fraction`; the holdout is not cut. Under
        the span `cutter:prepare` (`labels_seen`, `labels_kept`,
        `rows_dropped`)."""
        with TRACER.span("cutter:prepare", category="selector") as sp:
            yt = y[train_idx]
            labels, counts = np.unique(yt, return_counts=True)
            order = np.argsort(-counts, kind="stable")
            keep = [labels[i] for i in order[: self.max_label_categories]
                    if counts[i] / len(yt) >= self.min_label_fraction]
            kept = set(keep)
            details = {"labels_kept": [float(v) for v in keep],
                       "labels_dropped": [float(v) for v in labels
                                          if v not in kept]}
            if len(keep) == len(labels):       # nothing to cut
                out = train_idx
            else:
                out = train_idx[np.isin(yt, np.asarray(keep))]
            sp.set(labels_seen=len(labels), labels_kept=len(keep),
                   rows_dropped=int(len(train_idx) - len(out)))
        return out, details
