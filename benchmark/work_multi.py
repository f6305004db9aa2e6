"""What the ALGORITHM needs for a pass over a typed table with a label
of K classes, counted from the configuration's shapes — never from the
implementation, so the `train_multi_*mfu_pct` shares read the same work
whatever implements it — and the operations and bytes of the two kernels
this configuration brings (their roofline shares are taken by hand from
a trace: `trace_reduce.py` keeps an operation's HLO line, not its scope).

A pass, as `work_typed.py` counts one: the fits see the matrix the
checker keeps, whose width follows from the schema by the references'
rules (a numeric column gives its value, and a null indicator only where
it has holes; a constant column falls to the variance floor; a level
column gives min(cardinality, top_k) levels and OTHER where the
cardinality is over top_k). A forest level reads the binned matrix once
and accumulates K + 1 targets a cell (`work.tree_fit`); a multinomial
FISTA iteration is 4·n·d·K operations and two reads of the matrix, and a
logistic family's lockstep sweep reads the matrix once for all its fits
(`work_typed.py`'s departure). On top, one write of the encoded matrix.
"""

from __future__ import annotations

import work


def widths(schema: dict) -> tuple:
    """(encoded width out of transmogrify, width the checker keeps)."""
    top_k = int(schema["encoding"]["top_k"])
    floor = 1e-5                # the checker's variance floor
    encoded = kept = 0
    for col in schema["columns"]:
        if col["type"] == "PickList":
            card = int(col["cardinality"])
            encoded += min(card, top_k) + 2
            kept += min(card, top_k) + (card > top_k)
            continue
        encoded += 2
        share = col.get("share")        # a flag's share of ones
        constant = col["kind"] == "constant" or (
            share is not None and share * (1 - share) < floor)
        kept += 0 if constant else 1
    return encoded, kept


def train_pass(config: dict, n_rows: int) -> dict:
    """One `Workflow.train()` pass: every fit of the grid on the kept
    matrix's training rows, the winner's refit counted as the costliest
    single fit, and the encoded matrix's write."""
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
                one = work.tree_fit(n, d, classes, int(p["max_depth"]),
                                    int(p.get("n_trees", 1)))
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


# -- the two kernels: operations and bytes of ONE call, for a roofline --- #

def class_histograms(n: int, nodes: int, classes: int, slots: int) -> dict:
    """One level's K-class histograms as the one-hot product computes
    them, (K·nodes, n) @ (n, slots): 2·n·K·nodes·slots operations; the
    bf16 bin operand read once (n·slots·2 bytes), the node index, the
    label and the weight read once (12 bytes a row) and the float32
    histograms written (K·nodes·slots·4)."""
    return {"ops": 2.0 * n * classes * nodes * slots,
            "bytes": float(n) * slots * 2 + 12.0 * n
            + 4.0 * classes * nodes * slots}


def confusion(n: int, classes: int) -> dict:
    """One masked (K, K) confusion matrix as the product computes it,
    (K, n) @ (n, K): 2·n·K² operations; label, prediction and mask read
    once (12 bytes a row)."""
    return {"ops": 2.0 * n * classes * classes, "bytes": 12.0 * n}
