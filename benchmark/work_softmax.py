"""What the ALGORITHM needs for a pass over a table of numeric columns
with a label of K classes through a softmax boosted chain, counted from
the configuration's shapes — never from the implementation, so the
`train_softmax_*mfu_pct` shares read the same work whatever implements
it — and the operations and bytes of the class-histogram products, for
their least seconds in the softmax dispatches' wall
(`train_softmax_hist_dispatch_pct`).

A pass: the fits see the matrix the checker keeps (as
`work_multi.widths` counts it: a Real column without holes gives its
value, its null indicator goes);
`n` training rows after the reserve, a fold chain fitted on
(folds − 1) / folds of them. A softmax round over `m` fitted rows:

- the K trees' histograms: at each of `depth` levels a row adds its
  class's gradient and hessian to one bin of every column, for every
  class: 2·m·d·K accumulations; the int8 binned matrix, the (m, K)
  gradients and hessians (float32) and the (m, K) node index read once
  a level;
- the softmax and the gradients: 8 operations a (row, class) — the
  row's largest margin, the difference, its exponent, the row's sum,
  the quotient, the gradient, the hessian and the margin's update —
  over ALL n rows (the validation rows' margins move too); the margin
  read and written, the gradients and hessians written (16 bytes a
  (row, class));
- the leaf sums: 2·m·K accumulations, reading the gradients, hessians
  and leaf index (12 bytes a (row, class));
- the routing: a compare a (row, class) a level over all n rows,
  reading the node index (4 bytes).

A chain is `n_estimators` rounds one after another. A multinomial FISTA
iteration is 4·n·d·K operations and two reads of the matrix, and the
logistic family's lockstep sweep reads the matrix once for all its fits
(`work_typed.py`'s departure). The winner's refit is counted as the
costliest single fit, on all n rows. On top, one write of the encoded
matrix.
"""

from __future__ import annotations

import work


def softmax_round(n: int, m: int, d: int, k: int, depth: int) -> dict:
    """One round of a K-class chain: `n` rows carried, `m` fitted."""
    hist = {"ops": 2.0 * depth * m * d * k,
            "bytes": float(depth) * (m * d + m * k * 12)}
    grads = {"ops": 8.0 * n * k, "bytes": 16.0 * n * k}
    leaves = {"ops": 2.0 * m * k, "bytes": 12.0 * m * k}
    route = {"ops": float(depth) * n * k, "bytes": 4.0 * depth * n * k}
    return {key: hist[key] + grads[key] + leaves[key] + route[key]
            for key in hist}


def _chain(p: dict, n: int, m: int, d: int, k: int) -> dict:
    one = softmax_round(n, m, d, k, int(p["max_depth"]))
    return {key: int(p["n_estimators"]) * v for key, v in one.items()}


def train_pass(config: dict, n_rows: int) -> dict:
    """One `Workflow.train()` pass: every fit of the grid on the kept
    matrix's training rows, the winner's refit counted as the costliest
    single fit, and the encoded matrix's write."""
    spec = config["selector"]
    k = int(config["schema"]["classes"])
    encoded = 2 * int(config["schema"]["columns"]["count"])
    d = int(config["schema"]["columns"]["count"])
    n = int(round(n_rows * (1 - spec["splitter"]["reserve_test_fraction"])))
    folds = int(spec["validator"].get("folds", 1))
    m = n * (folds - 1) // folds
    total = {"ops": 0.0, "bytes": float(n_rows) * encoded * 4}
    costliest = {"ops": 0.0, "bytes": 0.0}
    for fam in spec["families"]:
        shared_reads = 0.0          # the logistic family's lockstep sweep
        for grid in fam["grid"]:
            p = {**fam["params"], **grid}
            if fam["estimator"] == "OpLogisticRegression":
                one = work.logistic_fit(n, d, k,
                                        work.enet_iters(p["max_iter"]))
                total["ops"] += folds * one["ops"]
                shared_reads = max(shared_reads, one["bytes"])
            else:
                fold = _chain(p, n, m, d, k)
                total = {key: total[key] + folds * fold[key]
                         for key in total}
                one = _chain(p, n, n, d, k)
            if one["bytes"] > costliest["bytes"]:
                costliest = one
        total["bytes"] += shared_reads
    return {key: total[key] + costliest[key] for key in total}


def least_seconds(config: dict, n_rows: int, peaks):
    """(seconds, which bound binds) on one chip; None off the chip."""
    if not peaks:
        return None
    return work.least_seconds(train_pass(config, n_rows), peaks)


# -- the class-histogram products: one round's, for a roofline ---------- #

def class_hist_round(n: int, k: int, depth: int, slots: int) -> dict:
    """The histogram products of one softmax round as the one-hot
    formulation computes them, each level of each class's tree in the
    direct form: (nodes, n) @ (n, slots) for the gradient and for the
    hessian, 2·n·nodes·slots operations each, summed over the levels'
    nodes (2^depth − 1) and the K trees; the bf16 bin operand read once
    a level (n·slots·2 bytes), the gradients, hessians and node index
    read once a level (12 bytes a (row, class)) and the float32
    histograms written (2·K·nodes·slots·4 bytes)."""
    nodes = 2 ** depth - 1
    return {"ops": 4.0 * n * nodes * slots * k,
            "bytes": float(depth) * (n * slots * 2 + 12.0 * n * k)
            + 8.0 * k * nodes * slots}
