"""Synthetic TYPED tables to a public source's schema, made from the seed:
integer count columns with holes and hashed categorical columns with
skewed levels, beside a binary label.

The configuration's `schema` block says everything: per column group its
prefix, count, feature type and kind, per column its missing share and
its law (`lognormal_count`: floor of a log-normal, capped so the float32
cast is exact; `zipf_level`: level r of `cardinality` drawn with
probability proportional to r^-exponent, so a cardinality above the row
count shows the levels that happen to fall), and the planted signal.
Nothing here knows a configuration by name. The *structure* (which
columns carry signal, every coefficient, every level's effect) comes
from `structure_seed`, so every `--seed` draws rows from one
distribution; the *rows* come from `(seed, stream)`. Missingness is
independent by column and of everything else.

A categorical cell is the level's rank through a fixed odd multiplier
modulo 2^32, written as eight hex digits (a bijection: distinct levels,
distinct strings; the source hashes its levels the same way), or None.
An integer cell is a float64 whole number or NaN, as the program's
`Dataset` stores numeric columns.

numpy only: the program under test receives the finished columns.
"""

from __future__ import annotations

import json
from typing import Dict, List, Tuple

import numpy as np

_CALIBRATION_ROWS = 200_000
_MIX = 2654435761          # odd: r -> r * _MIX mod 2^32 is a bijection
_TOP = 20                  # level ranks that carry an effect of their own


def _seed_seq(seed: int, stream: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) % (1 << 63), int(stream)])


def column_names(schema: Dict) -> List[Tuple[str, str]]:
    """[(name, type name)] in schema order, numbered from 1 as the source
    numbers them (I1..I13, C1..C26)."""
    return [(f"{grp['prefix']}{j + 1}", grp["type"])
            for grp in schema["columns"] for j in range(int(grp["count"]))]


_CDF_CACHE: Dict[Tuple[int, float], np.ndarray] = {}


def _zipf_cdf(cardinality: int, exponent: float) -> np.ndarray:
    key = (int(cardinality), float(exponent))
    if key not in _CDF_CACHE:
        p = np.arange(1, key[0] + 1, dtype=np.float64) ** -key[1]
        cdf = np.cumsum(p)
        _CDF_CACHE[key] = cdf / cdf[-1]
    return _CDF_CACHE[key]


def level_strings(ranks: np.ndarray, column: int) -> np.ndarray:
    """Object array of the ranks' level strings (shared str objects: one
    per distinct level)."""
    uniq, inv = np.unique(ranks, return_inverse=True)
    mixed = (uniq.astype(np.uint64) * np.uint64(_MIX)
             + np.uint64(column * 40503)) & np.uint64(0xFFFFFFFF)
    return np.char.mod("%08x", mixed).astype(object)[inv]


def _draw(schema: Dict, n: int, rng: np.random.Generator):
    """(ints (13, n) float64 with NaN, ranks (26, n) int64 with 0 for a
    missing cell)."""
    ints, ranks = [], []
    for grp in schema["columns"]:
        c = int(grp["count"])
        if grp["kind"] == "lognormal_count":
            for j in range(c):
                z = rng.standard_normal(n)
                v = np.floor(np.exp(grp["log_median"][j]
                                    + grp["log_sigma"][j] * z))
                v = np.minimum(v, float(grp["cap"]))
                v[rng.random(n) < grp["missing"][j]] = np.nan
                ints.append(v)
        elif grp["kind"] == "zipf_level":
            for j in range(c):
                cdf = _zipf_cdf(grp["cardinality"][j], grp["exponent"][j])
                r = np.searchsorted(cdf, rng.random(n), side="right") + 1
                r = np.minimum(r, int(grp["cardinality"][j]))
                r[rng.random(n) < grp["missing"][j]] = 0
                ranks.append(r.astype(np.int64))
        else:
            raise ValueError(f"unknown column kind {grp['kind']!r}")
    return np.stack(ints), np.stack(ranks)


def _score(schema: Dict, ints: np.ndarray, ranks: np.ndarray) -> np.ndarray:
    """(n,) float32 log-odds before the bias: |.| and pairwise terms on
    the standardised log counts (a hole contributes nothing), a small
    linear part, effects of the top levels of some categorical columns,
    and level-set x count crossings a linear model cannot follow."""
    st = np.random.default_rng(int(schema["structure_seed"]) + 1)
    sig = schema["signal"]
    d_i, n = ints.shape
    d_c = ranks.shape[0]
    groups = {g["kind"]: g for g in schema["columns"]}
    gi = groups["lognormal_count"]
    # standardise with the LAW's moments, not the sample's: the same
    # function of a row in every table
    mu = np.asarray(gi["log_median"])[:, None]
    sd = np.asarray(gi["log_sigma"])[:, None]
    Z = np.where(np.isnan(ints), 0.0,
                 (np.log1p(np.nan_to_num(ints)) - mu) / sd).astype(np.float32)
    s = np.zeros(n, np.float32)
    a = (st.standard_normal(d_i) / np.sqrt(d_i)).astype(np.float32)
    s += np.float32(sig["linear"]) * (a @ Z)
    for _ in range(int(sig["pairs"])):
        i, j = st.choice(d_i, size=2, replace=False)
        s += np.float32(sig["pair"] * st.choice([-1.0, 1.0])
                        / np.sqrt(sig["pairs"])) * Z[i] * Z[j]
    for _ in range(int(sig["abs"])):
        i = st.integers(d_i)
        s += np.float32(sig["abs_scale"] / np.sqrt(sig["abs"])) \
            * (np.abs(Z[i]) - np.float32(0.8))
    for _ in range(int(sig["levels"])):
        c = st.integers(d_c)
        effect = np.zeros(_TOP + 2, np.float32)     # rank 0 = missing
        effect[1:_TOP + 1] = st.standard_normal(_TOP) * sig["level_scale"]
        s += effect[np.minimum(ranks[c], _TOP + 1)]
    for _ in range(int(sig["cross"])):
        c, i = st.integers(d_c), st.integers(d_i)
        # a set of the column's top levels that half of its cells
        # fall in flips the sign of a count's effect
        inside = st.random(_TOP + 2) < 0.5
        flip = np.where(inside[np.minimum(ranks[c], _TOP + 1)], 1.0, -1.0)
        s += np.float32(sig["cross_scale"] / np.sqrt(sig["cross"])) \
            * flip.astype(np.float32) * Z[i]
    return s


def _bias(schema: Dict) -> float:
    """Additive bias that brings the positive share to the schema's,
    fitted once on a fixed calibration sample (structure seed)."""
    rng = np.random.default_rng(int(schema["structure_seed"]) + 2)
    ints, ranks = _draw(schema, _CALIBRATION_ROWS, rng)
    s = _score(schema, ints, ranks) + rng.logistic(size=_CALIBRATION_ROWS)
    return float(-np.quantile(s, 1.0 - float(schema["positive_share"])))


_BIAS_CACHE: Dict[str, float] = {}


def make_table(schema: Dict, n_rows: int, seed: int, stream: int = 0
               ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """({column name: (n,) float64 with NaN | object of str and None},
    (n,) float64 label in {0, 1})."""
    key = json.dumps(schema, sort_keys=True)
    if key not in _BIAS_CACHE:
        _BIAS_CACHE[key] = _bias(schema)
    rng = np.random.default_rng(_seed_seq(seed, stream))
    ints, ranks = _draw(schema, n_rows, rng)
    s = _score(schema, ints, ranks) + rng.logistic(size=n_rows)
    y = (s + _BIAS_CACHE[key] > 0).astype(np.float64)
    made: Dict[str, np.ndarray] = {}
    drawn = {"lognormal_count": 0, "zipf_level": 0}    # `_draw`'s order
    for grp in schema["columns"]:
        for j in range(int(grp["count"])):
            name, c = f"{grp['prefix']}{j + 1}", drawn[grp["kind"]]
            drawn[grp["kind"]] += 1
            if grp["kind"] == "lognormal_count":
                made[name] = ints[c]
            else:
                made[name] = level_strings(ranks[c], c)
                made[name][ranks[c] == 0] = None
    return made, y
