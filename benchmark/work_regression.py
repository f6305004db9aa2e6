"""What the ALGORITHM needs for a pass over a typed table with a numeric
target, counted from the configuration's shapes — never from the
implementation, so the `train_reg_*mfu_pct` shares read the same work
whatever implements it.

A pass, as `work_multi.py` counts one: the fits see the matrix the
checker keeps, whose width follows from the schema by the references'
rules (`work_multi.widths`: an integer column gives its value, and no
null indicator where it has no hole; a level column gives
min(cardinality, top_k) levels and OTHER where the cardinality is over
top_k). A tree level reads the binned matrix ONCE and accumulates two
targets a cell, the gradient and the weight (`work.tree_fit` with one
value column): that the program's regressor reads its operand twice a
level is the program's (`train_hist_reads`), not the algorithm's. A
boosted chain is `n_estimators` such trees one after another. A
least-squares FISTA iteration is 4·n·d operations and two reads of the
matrix, and the linear family's lockstep sweep reads the matrix once
for all its fits (`work_typed.py`'s departure). A metric reads the
label, the prediction and the mask of every row once. On top, one write
of the encoded matrix.
"""

from __future__ import annotations

import work
import work_multi

LS_ITERS = 300      # the least-squares fit's documented budget


def ls_fit(n: int, d: int, iters: int = LS_ITERS) -> dict:
    """One elastic-net least-squares fit: a product forward and one
    back an iteration."""
    return {"ops": float(iters) * 4 * n * d,
            "bytes": float(iters) * 2 * n * d * 4}


def metric(n: int) -> dict:
    """RMSE, MAE and R2 of one prediction: error, square, absolute value
    and the label's two moments a row; label, prediction and mask read
    once (12 bytes a row)."""
    return {"ops": 6.0 * n, "bytes": 12.0 * n}


def train_pass(config: dict, n_rows: int) -> dict:
    """One `Workflow.train()` pass: every fit of the grid on the kept
    matrix's training rows with its fold metric, the winner's refit
    counted as the costliest single fit with its train and holdout
    metrics, and the encoded matrix's write."""
    spec = config["selector"]
    encoded, d = work_multi.widths(config["schema"])
    n = int(round(n_rows * (1 - spec["splitter"]["reserve_test_fraction"])))
    folds = int(spec["validator"].get("folds", 1))
    total = {"ops": 0.0, "bytes": float(n_rows) * encoded * 4}
    costliest = {"ops": 0.0, "bytes": 0.0}
    scored = metric(n)
    for fam in spec["families"]:
        shared_reads = 0.0          # the linear family's lockstep sweep
        for grid in fam["grid"]:
            p = {**fam["params"], **grid}
            if fam["estimator"] == "OpLinearRegression":
                one = ls_fit(n, d)
                total["ops"] += folds * one["ops"]
                shared_reads = max(shared_reads, one["bytes"])
            else:
                one = work.tree_fit(
                    n, d, 1, int(p["max_depth"]),
                    int(p.get("n_trees", p.get("n_estimators", 1))))
                total = {k: total[k] + folds * one[k] for k in total}
            total = {k: total[k] + folds * scored[k] for k in total}
            if one["bytes"] > costliest["bytes"]:
                costliest = one
        total["bytes"] += shared_reads
    held = metric(n_rows)           # the train and the holdout rows
    return {k: total[k] + costliest[k] + held[k] for k in total}


def least_seconds(config: dict, n_rows: int, peaks):
    """(seconds, which bound binds) on one chip; None off the chip."""
    if not peaks:
        return None
    return work.least_seconds(train_pass(config, n_rows), peaks)
