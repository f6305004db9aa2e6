"""What the ALGORITHM needs for a pass, counted from
the configuration's shapes and the grid actually run — never from the
implementation (the one-hot matmul spends far more), so `*_mfu_pct`
reads the same work whatever implements it and cannot pass 100.

Per tree level: one read of the binned matrix (n*d bytes), of the m
targets, the hessians and the node index (n*(m+1)*4 + n*4 bytes), and
n*d*(m+1) accumulations. Per logistic iteration: one read of X forward
and one back (2*n*d*4 bytes), 2*n*d*k operations forward and as many
back.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json (have {sorted(table)})")
    return table[device_kind]


def tree_fit(n: int, d: int, m: int, depth: int, n_trees: int) -> dict:
    levels = depth * n_trees
    return {"ops": float(levels) * n * d * (m + 1),
            "bytes": float(levels) * (n * d + n * (m + 1) * 4 + n * 4)}


def logistic_fit(n: int, d: int, k: int, iters: int) -> dict:
    return {"ops": float(iters) * 4 * n * d * k,
            "bytes": float(iters) * 2 * n * d * 4}


def enet_iters(max_iter: int) -> int:
    """The proximal-gradient budget the elastic-net fit documents."""
    return max(200, 4 * int(max_iter))


def _add(a: dict, b: dict, times: float = 1.0) -> dict:
    return {k: a[k] + times * b[k] for k in a}


def _targets(estimator: str, classes: int) -> int:
    # a forest histograms one column per class; binary boosting one
    # gradient column
    return classes if "Forest" in estimator else 1


def train_pass(config: dict, n_rows: int) -> dict:
    """One `Workflow.train()` pass: every (configuration, fold) fit of
    the grid on the training rows, plus the winner's refit counted as the
    costliest configuration (which family wins depends on the data)."""
    spec = config["selector"]
    classes = int(config["schema"]["classes"])
    d = sum(int(c["count"]) for c in config["schema"]["columns"])
    n = int(round(n_rows * (1 - spec["splitter"]["reserve_test_fraction"])))
    folds = int(spec["validator"].get("folds", 1))
    total = {"ops": 0.0, "bytes": 0.0}
    costliest = dict(total)
    for fam in spec["families"]:
        for grid in fam["grid"]:
            p = {**fam["params"], **grid}
            if fam["estimator"] == "OpLogisticRegression":
                one = logistic_fit(n, d, classes, enet_iters(p["max_iter"]))
            else:
                trees = p.get("n_trees", p.get("n_estimators", 1))
                one = tree_fit(n, d, _targets(fam["estimator"], classes),
                               int(p["max_depth"]), int(trees))
            total = _add(total, one, folds)
            if one["bytes"] > costliest["bytes"]:
                costliest = one
    return _add(total, costliest)


def least_seconds(work: dict, peaks: dict) -> tuple:
    """(seconds, which bound binds) on one chip."""
    by_ops = work["ops"] / peaks["flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_ops, "ops") if by_ops > by_bytes else (by_bytes, "bytes")
