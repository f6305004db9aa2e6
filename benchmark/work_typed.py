"""What the ALGORITHM needs for a pass over a TYPED table, counted from
the configuration's shapes — never from the implementation, so the
`train_typed_*mfu_pct` shares read the same work whatever implements it.

`work.py` counts a pass from the raw column count, which is the model
matrix's width only where every column is a real. Here the fits see the
matrix the checker keeps, whose width follows from the schema by the
references' rules (`reference/pivot.py`, `reference/sanity.py`): an
integer column gives its value and, where it has holes, a null
indicator; a categorical column gives min(cardinality, top_k) levels,
OTHER where the cardinality is over top_k, and a null indicator where
it has holes (a constant column falls to the variance floor). Then, as
`work.py` counts a fit: one read of the binned n x width matrix a tree
level, two reads of n x width x 4 bytes a logistic iteration; on top,
one write of the encoded n x encoded-width float32 matrix.

One departure from `work.train_pass`: a logistic family's sweep is
counted as ONE run of iterations over the matrix, not one a (grid
point, fold). Its fits differ in penalties and row weights only and step
in lockstep, so one forward and one backward read of the matrix an
iteration serves them all (the operations are still counted a fit). At
528 columns the matrix's reads are nearly all of the count: a fit at a
time, the first traced run read 92 % of the chip's peak over its busy
seconds, because the program already shares those reads among its six
vmapped fits — a count the implementation beats is not the least work.
"""

from __future__ import annotations

import work


def widths(schema: dict) -> tuple:
    """(encoded width out of transmogrify, width the checker keeps)."""
    top_k = int(schema["encoding"]["top_k"])
    encoded = kept = 0
    for grp in schema["columns"]:
        for j in range(int(grp["count"])):
            holes = grp["missing"][j] > 0
            if grp["type"] == "Integral":
                encoded += 2
                kept += 1 + holes
            else:
                card = int(grp["cardinality"][j])
                encoded += min(card, top_k) + 2
                kept += min(card, top_k) + (card > top_k) + holes
    return encoded, kept


def train_pass(config: dict, n_rows: int) -> dict:
    """One `Workflow.train()` pass: every fit of the grid on the kept
    matrix's training rows, the winner's refit counted as the costliest
    single fit (as `work.train_pass` does), and the encoded matrix's
    write."""
    spec = config["selector"]
    classes = int(config["schema"]["classes"])
    encoded, d = widths(config["schema"])
    n = int(round(n_rows * (1 - spec["splitter"]["reserve_test_fraction"])))
    folds = int(spec["validator"].get("folds", 1))
    total = {"ops": 0.0, "bytes": float(n_rows) * encoded * 4}
    costliest = {"ops": 0.0, "bytes": 0.0}
    for fam in spec["families"]:
        shared_reads = 0.0          # a logistic family's lockstep sweep
        for grid in fam["grid"]:
            p = {**fam["params"], **grid}
            if fam["estimator"] == "OpLogisticRegression":
                one = work.logistic_fit(n, d, classes,
                                        work.enet_iters(p["max_iter"]))
                total["ops"] += folds * one["ops"]
                shared_reads = max(shared_reads, one["bytes"])
            else:
                trees = p.get("n_trees", p.get("n_estimators", 1))
                one = work.tree_fit(
                    n, d, classes if "Forest" in fam["estimator"] else 1,
                    int(p["max_depth"]), int(trees))
                total = {k: total[k] + folds * one[k] for k in total}
            if one["bytes"] > costliest["bytes"]:
                costliest = one
        total["bytes"] += shared_reads
    return {k: total[k] + costliest[k] for k in total}


def least_seconds(config: dict, n_rows: int, peaks):
    """(seconds, which bound binds) on one chip; None off the chip."""
    if not peaks:
        return None
    return work.least_seconds(train_pass(config, n_rows), peaks)
