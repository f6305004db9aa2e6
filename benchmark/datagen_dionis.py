"""Synthetic MANY-CLASS tables of numeric columns to a public source's
schema, made from the seed: `count` Real columns and a label of K
classes at near-uniform shares, each class told apart by a few columns
of its own.

The configuration's `schema` block says everything:

- `columns`: one group, `prefix` + number (V1..V60), type `Real`;
- `class_share`: the classes' shares are proportional to uniform draws
  in [`low`, `high`] (from `structure_seed`), so the largest class is at
  most high / low times the smallest;
- `background`: a cell that tells nothing is uniform on [`low`, `high`];
- `class_signal`: class k has `columns` columns of its own (a seeded
  draw of distinct columns) and a centre in each, uniform on
  [`centre_low`, `centre_high`]; in a row of class k those cells are
  the centre plus normal noise of standard deviation `sd`, every other
  cell is background. A class is then a narrow box in a few columns:
  a band in the MIDDLE of a column's range, which a tree cuts out with
  two splits and a linear model cannot hold apart from the rows on
  either side of it;
- `class_shift`: class k also moves every background cell of its rows
  by its own offset, a normal draw of standard deviation `sd` a column:
  a shift of the class's mean, which a linear model learns as well as a
  tree does.

No cell is missing (the source has none). Cells are rounded to 1e-6,
so every value is exact in float32 up to the rounding of the decimal.
The *structure* (shares, each class's columns and centres) comes from
`structure_seed`, so every `--seed` draws rows from one distribution;
the *rows* come from `(seed, stream)`. Labels are drawn independently at
the shares, so a table of this size holds every class.

numpy only: the program under test receives the finished columns.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from datagen_typed import _seed_seq


def column_names(schema: Dict) -> List[Tuple[str, str]]:
    """[(name, type name)] in column order, numbered from 1."""
    grp = schema["columns"]
    return [(f"{grp['prefix']}{j + 1}", grp["type"])
            for j in range(int(grp["count"]))]


def structure(schema: Dict) -> Dict[str, np.ndarray]:
    """{"shares": (K,), "columns": (K, c) int, "centres": (K, c),
    "shifts": (K, d)} from the structure seed."""
    st = np.random.default_rng(int(schema["structure_seed"]))
    k = int(schema["classes"])
    d = int(schema["columns"]["count"])
    sh, sig = schema["class_share"], schema["class_signal"]
    raw = st.uniform(float(sh["low"]), float(sh["high"]), k)
    c = int(sig["columns"])
    cols = np.stack([st.choice(d, c, replace=False) for _ in range(k)])
    centres = st.uniform(float(sig["centre_low"]), float(sig["centre_high"]),
                         (k, c))
    shifts = float(schema["class_shift"]["sd"]) * st.standard_normal((k, d))
    return {"shares": raw / raw.sum(), "columns": cols, "centres": centres,
            "shifts": shifts}


def make_table(schema: Dict, n_rows: int, seed: int, stream: int = 0
               ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """({column name: (n,) float64}, (n,) float64 label in [0, K))."""
    rng = np.random.default_rng(_seed_seq(seed, stream))
    struct = structure(schema)
    k = int(schema["classes"])
    d = int(schema["columns"]["count"])
    bg, sig = schema["background"], schema["class_signal"]
    y = rng.choice(k, size=n_rows, p=struct["shares"])
    X = rng.uniform(float(bg["low"]), float(bg["high"]), (n_rows, d)) \
        + struct["shifts"][y]
    rows = np.arange(n_rows)[:, None]
    X[rows, struct["columns"][y]] = struct["centres"][y] \
        + float(sig["sd"]) * rng.standard_normal(
            (n_rows, struct["columns"].shape[1]))
    X = np.round(X, 6)
    names = [name for name, _ in column_names(schema)]
    return ({name: np.ascontiguousarray(X[:, j])
             for j, name in enumerate(names)}, y.astype(np.float64))
