"""Synthetic tables to a public source's schema, made from the seed.

One general generator: a configuration's `schema` block says how many
columns of which kind, how many classes at which priors, and how strong
the planted signal is; nothing here knows a configuration by name. The
*structure* (which columns interact, with which coefficients) comes from
the schema's `structure_seed`, so every `--seed` draws rows from the same
distribution; the *rows* come from `(seed, stream)`.

numpy only: the program under test receives the finished columns.
"""

from __future__ import annotations

import json
from typing import Dict, List, Tuple

import numpy as np

_CALIBRATION_ROWS = 200_000


def _seed_seq(seed: int, stream: int) -> np.random.SeedSequence:
    # --seed may be a little over 2**31; SeedSequence takes any
    # non-negative integer, so fold a negative one into range
    return np.random.SeedSequence([int(seed) % (1 << 63), int(stream)])


def column_names(schema: Dict) -> List[Tuple[str, str]]:
    """[(name, type name)] in schema order; type names are the
    program's feature types (`Real`, `Binary`)."""
    out = []
    for grp in schema["columns"]:
        for j in range(int(grp["count"])):
            out.append((f"{grp['prefix']}{j}", grp["type"]))
    return out


def _features(schema: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """(d, n) float32, one contiguous row per column."""
    structure = np.random.default_rng(int(schema["structure_seed"]))
    blocks: List[np.ndarray] = []
    for grp in schema["columns"]:
        c, kind = int(grp["count"]), grp["kind"]
        if kind == "normal":
            blk = rng.standard_normal((c, n), dtype=np.float32)
        elif kind == "derived":
            # mass-like: root of a sum of squares of three earlier columns
            base = np.concatenate(blocks)
            blk = np.empty((c, n), np.float32)
            for j in range(c):
                pick = structure.choice(len(base), size=3, replace=False)
                blk[j] = np.sqrt((base[pick] ** 2).sum(0))
        else:
            raise ValueError(f"unknown column kind {kind!r}")
        blocks.append(blk)
    return np.concatenate(blocks)


def _scores(schema: Dict, F: np.ndarray) -> np.ndarray:
    """(k, n) class scores: linear + pairwise + |.| terms on the
    standardised columns, coefficients from the structure seed."""
    structure = np.random.default_rng(int(schema["structure_seed"]) + 1)
    d, n = F.shape
    k = int(schema["classes"])
    sig = schema["signal"]
    mu = F.mean(1, keepdims=True)
    sd = F.std(1, keepdims=True) + 1e-6
    Z = (F - mu) / sd
    n_pairs, n_abs = int(sig["pairs"]), int(sig["abs"])
    S = np.zeros((k, n), np.float32)
    for c in range(k):
        a = (structure.standard_normal(d) / np.sqrt(d)).astype(np.float32)
        S[c] += np.float32(sig["linear"]) * (a @ Z)
        for _ in range(n_pairs):
            i, j = structure.choice(d, size=2, replace=False)
            S[c] += np.float32(sig["pair"] * structure.choice([-1.0, 1.0])
                               / np.sqrt(n_pairs)) * Z[i] * Z[j]
        for _ in range(n_abs):
            i = structure.integers(d)
            S[c] += np.float32(sig["abs_scale"] / np.sqrt(max(n_abs, 1))) \
                * (np.abs(Z[i]) - np.float32(0.8))
    return S


def _class_bias(schema: Dict) -> np.ndarray:
    """Per-class additive bias that brings the label priors to the
    schema's, fitted once on a fixed calibration sample (structure seed),
    so it is the same for every run seed."""
    rng = np.random.default_rng(int(schema["structure_seed"]) + 2)
    F = _features(schema, _CALIBRATION_ROWS, rng)
    S = _scores(schema, F) + rng.gumbel(size=(int(schema["classes"]),
                                              _CALIBRATION_ROWS))
    target = np.asarray(schema["class_priors"], np.float64)
    target = target / target.sum()
    bias = np.log(target)
    for _ in range(60):
        got = np.bincount((S + bias[:, None]).argmax(0),
                          minlength=len(target)) / _CALIBRATION_ROWS
        bias += 0.5 * (np.log(target) - np.log(np.maximum(got, 1e-6)))
    return bias - bias.mean()


_BIAS_CACHE: Dict[str, np.ndarray] = {}


def make_table(schema: Dict, n_rows: int, seed: int, stream: int = 0
               ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """({column name: (n,) float64}, (n,) float64 label in 0..k-1).

    Values are drawn in float32 and widened, so the program's float32
    device cast of a column is exact. No missing values (neither source
    has any)."""
    key = json.dumps(schema, sort_keys=True)
    if key not in _BIAS_CACHE:
        _BIAS_CACHE[key] = _class_bias(schema)
    rng = np.random.default_rng(_seed_seq(seed, stream))
    F = _features(schema, n_rows, rng)
    S = _scores(schema, F) + rng.gumbel(
        size=(int(schema["classes"]), n_rows)).astype(np.float32)
    y = (S + _BIAS_CACHE[key][:, None].astype(np.float32)).argmax(0)
    cols = {name: F[j].astype(np.float64)
            for j, (name, _) in enumerate(column_names(schema))}
    return cols, y.astype(np.float64)
