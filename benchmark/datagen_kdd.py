"""Synthetic MANY-LABEL typed tables to a public source's schema, made
from the seed: counters, rates, flags and a few level columns beside a
label of K classes whose shares are the source's (three large labels
and a long tail of rare ones).

The configuration's `schema` block says everything: the labels in
frequency-rank order with the source's counts (label index = rank, so
index 0 is the most frequent), and per column its name, feature type
and kind with that kind's law:

- `counter` (Integral): zero with the column's `zero_share` at the
  latent's centre, else 1 + floor of a log-normal (`log_median`,
  `log_sigma`), capped at `cap` so the float32 cast is exact;
- `rate` (Real): a value on the 0.01 grid in [0, 1], `centre` + `slope`
  times the latent, clipped, so 0 and 1 are heavy ties and about a
  hundred values appear;
- `flag` (Binary): 1 with the column's `share` at the latent's centre;
- `level` (PickList): level r of `cardinality` with probability
  proportional to r^-`exponent` (Zipf-like), the rank a monotone
  function of the latent;
- `constant` (Integral): every cell the same `value`.

No cell is missing (the source has none). Every column is a monotone
function of ONE latent number t = a . z + e of its own: z (`latent.dims`
numbers a row) is the row's label centre plus unit noise, a the column's
loading and e the column's own noise, so no two columns are copies of
each other. A column's `loading` (1 where it is not given) scales a: the
source's content counters and rare flags are zero in nearly every row of
every large label, so theirs is small and they carry little of the
label; 0 makes a column pure noise at its stated share. The three large
labels' centres lie `latent.separation` apart (the source is nearly
separable); a rare label's centre is one of theirs moved by
`latent.rare_shift` (the rare labels stay hard). The *structure*
(centres, loadings, which large label a rare one resembles) comes from
`structure_seed`, so every `--seed` draws rows from one distribution;
the *rows* come from `(seed, stream)`. Labels are drawn independently at
the source's shares, so a table's rarest labels may not fall at all.

A level cell is the level's rank through a fixed odd multiplier modulo
2^32, written as eight hex digits: `datagen_typed.py`'s `level_strings`,
whose seeding and Zipf table this file uses too. A
numeric cell is a float64, as the program's `Dataset` stores numeric
columns.

numpy only: the program under test receives the finished columns.
"""

from __future__ import annotations

from math import erf, sqrt
from typing import Dict, List, Tuple

import numpy as np

from datagen_typed import _seed_seq, _zipf_cdf, level_strings

def column_names(schema: Dict) -> List[Tuple[str, str]]:
    """[(name, type name)] in the source's column order."""
    return [(c["name"], c["type"]) for c in schema["columns"]]


def label_shares(schema: Dict) -> np.ndarray:
    counts = np.asarray(schema["label_counts"], np.float64)
    return counts / counts.sum()


def structure(schema: Dict) -> Dict[str, np.ndarray]:
    """{"centres": (K, q), "loadings": (columns, q)} from the structure
    seed."""
    st = np.random.default_rng(int(schema["structure_seed"]))
    lat = schema["latent"]
    k, q = int(schema["classes"]), int(lat["dims"])
    large = int(lat["large_labels"])
    centres = np.zeros((k, q))
    # the large labels: orthogonal directions, `separation` apart
    basis, _ = np.linalg.qr(st.standard_normal((q, q)))
    centres[:large] = basis[:large] * float(lat["separation"]) / sqrt(2.0)
    for g in range(large, k):
        centres[g] = centres[st.integers(large)] \
            + float(lat["rare_shift"]) * st.standard_normal(q)
    loadings = st.standard_normal((len(schema["columns"]), q)) \
        * float(lat["loading"])
    return {"centres": centres, "loadings": loadings}


def _phi(t: np.ndarray) -> np.ndarray:
    """Standard normal CDF by the logistic approximation (1.702 t): a
    monotone map to (0, 1) is all that is needed, and it is cheap."""
    return 1.0 / (1.0 + np.exp(-1.702 * t))


def _phi_inv(p: float) -> float:
    """Inverse of the exact normal CDF at one point, by bisection."""
    lo, hi = -10.0, 10.0
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        if 0.5 * (1.0 + erf(mid / sqrt(2.0))) < p:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def make_table(schema: Dict, n_rows: int, seed: int, stream: int = 0
               ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """({column name: (n,) float64 | object of str}, (n,) float64 label
    in [0, K))."""
    rng = np.random.default_rng(_seed_seq(seed, stream))
    struct = structure(schema)
    lat = schema["latent"]
    y = rng.choice(int(schema["classes"]), size=n_rows,
                   p=label_shares(schema))
    z = struct["centres"][y] + rng.standard_normal(
        (n_rows, int(lat["dims"])))
    # a column's own standard deviation of t at the latent's centre:
    # |a|^2 from the row's unit noise, `noise`^2 of its own
    noise = float(lat["noise"])
    made: Dict[str, np.ndarray] = {}
    for j, col in enumerate(schema["columns"]):
        a = struct["loadings"][j] * float(col.get("loading", 1.0))
        if col["kind"] == "constant":
            made[col["name"]] = np.full(n_rows, float(col["value"]))
            continue
        t = z @ a + noise * rng.standard_normal(n_rows)
        t /= sqrt(float(a @ a) + noise * noise)     # unit at the centre
        if col["kind"] == "counter":
            thr = _phi_inv(float(col["zero_share"]))
            v = 1.0 + np.floor(np.exp(
                float(col["log_median"])
                + float(col["log_sigma"]) * (t - thr)))
            v = np.where(t > thr, np.minimum(v, float(col["cap"])), 0.0)
        elif col["kind"] == "rate":
            v = np.round(np.clip(
                float(col["centre"]) + float(col["slope"]) * t, 0.0, 1.0), 2)
        elif col["kind"] == "flag":
            v = (t > _phi_inv(1.0 - float(col["share"]))).astype(np.float64)
        elif col["kind"] == "level":
            cdf = _zipf_cdf(col["cardinality"], col["exponent"])
            r = np.searchsorted(cdf, _phi(t), side="right") + 1
            v = level_strings(np.minimum(r, int(col["cardinality"])), j)
        else:
            raise ValueError(f"unknown column kind {col['kind']!r}")
        made[col["name"]] = v
    return made, y.astype(np.float64)
