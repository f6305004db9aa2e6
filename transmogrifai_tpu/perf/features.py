"""Feature engineering for the cost model: one dict of numeric features
per decision, shared by the RECORDING side (parallel/sweep.py journaling
measured block wall times) and the PREDICTION side (the scheduler,
bench extrapolations) — the two must agree on names or the
model silently predicts garbage for half its consumers.

The static-signature layouts mirrored here are the module-level
`_static_<family>` functions in `parallel/sweep.py` (the compile-group
keys the scheduler already cuts blocks along); this module is kept
import-light (numpy only) so `perf.params`/`workflow.params` never drag
jax in.
"""

from __future__ import annotations

from typing import Dict, Tuple

__all__ = ["block_features", "ingest_features", "parse_features",
           "serving_features"]


def block_features(family: str, static: Tuple, n_configs: int,
                   n_rows: int, n_cols: int, n_folds: int,
                   dtype_bytes: int = 4) -> Dict[str, float]:
    """Features of one sweep grid block (a compile group, or a scheduler
    sub-block of one): the family one-hot plus the static-signature
    facts that drive its runtime — iteration counts for linear-likes,
    learners × nodes × bins for trees — and the training-matrix shape.
    Unknown families degrade to the shape facts alone."""
    f: Dict[str, float] = {
        "n_configs": float(n_configs),
        "n_rows": float(n_rows),
        "n_cols": float(n_cols),
        "n_folds": float(n_folds),
        "dtype_bytes": float(dtype_bytes),
        f"fam_{family}": 1.0,
    }
    try:
        if family == "logistic":
            f["iters"] = float(static[0])
            f["enet"] = 1.0 if static[1] else 0.0
        elif family == "linreg":
            f["enet"] = 1.0 if static[0] else 0.0
        elif family == "svc":
            f["iters"] = float(static[0])
        elif family == "glm":
            f["iters"] = float(static[1])
        elif family == "mlp":
            hidden, iters = static[0], static[1]
            f["units"] = float(sum(int(h) for h in hidden))
            f["iters"] = float(iters)
        elif family in ("forest", "gbt"):
            learners, bins = int(static[0]), int(static[1])
            depth = int(static[3])
            f["learners"] = float(learners)
            f["bins"] = float(bins)
            f["depth"] = float(depth)
            f["nodes"] = float(2 ** min(depth, 14))
    except (IndexError, TypeError, ValueError):
        pass  # foreign static layout: shape facts still predict coarsely
    return f


def ingest_features(bytes_wire: float, workers: int, depth: int,
                    chunks: int, cache_hit: bool = False
                    ) -> Dict[str, float]:
    """Features of one pipelined upload (data/pipeline.py): wire bytes,
    pipeline shape, and whether the bytes came from a cache artifact
    (artifact replay has different read characteristics than a store
    sweep, so the model must be able to tell them apart)."""
    return {"bytes_wire": float(bytes_wire), "workers": float(workers),
            "depth": float(depth), "chunks": float(chunks),
            "cache_hit": 1.0 if cache_hit else 0.0}


def serving_features(bucket: int) -> Dict[str, float]:
    """Features of one serving device batch: the padded bucket size is
    the compiled shape, which is what drives the latency."""
    return {"bucket": float(bucket)}


def parse_features(n_rows: int, n_cols: int) -> Dict[str, float]:
    """Features of one host-side request parse (row codec / columnar
    convert): cost is ~affine in rows with a per-column fixed term, so
    rows, cols, and their product carry the fit."""
    return {"rows": float(n_rows), "cols": float(n_cols),
            "cells": float(n_rows * n_cols)}
