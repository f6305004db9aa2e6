"""Typed raw columns -> the feature matrix for a table of Real, Integral,
Binary and PickList columns, as `transmogrify()` documents its defaults
(Transmogrifier.scala:52-90, 116-344), in plain numpy. `pivot.py` has the
Integral and PickList encoders; this file adds the Real and Binary ones
and the block order of a table that holds all four.

Column order: one block per feature type in the order the dispatch lists
them (Real, Integral, Binary, then the pivoted text types), columns in
input order inside a block.

- `Real`: [value, null indicator] a column; a missing value is filled
  with the mean of the present ones (RealVectorizer.scala).
- `Integral`: [value, null indicator]; mode fill (`pivot.integral_block`).
- `Binary`: [value, null indicator]; a missing value is filled with
  false, 0 (BinaryVectorizer.scala).
- `PickList`: `pivot.vocabulary` and `pivot.pivot_block`.

The matrix is float32 (the reference's vectors are float64).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from reference import pivot

ORDER = ("Real", "Integral", "Binary")


def real_block(col: np.ndarray) -> np.ndarray:
    """(n, 2) float32: mean-filled value, null indicator."""
    v = np.asarray(col, np.float64)
    missing = np.isnan(v)
    fill = v[~missing].mean() if (~missing).any() else 0.0
    return np.stack([np.where(missing, fill, v), missing * 1.0],
                    1).astype(np.float32)


def binary_block(col: np.ndarray) -> np.ndarray:
    """(n, 2) float32: value with a missing cell as 0, null indicator."""
    v = np.asarray(col, np.float64)
    missing = np.isnan(v)
    return np.stack([np.where(missing, 0.0, v), missing * 1.0],
                    1).astype(np.float32)


_BLOCK = {"Real": real_block, "Integral": pivot.integral_block,
          "Binary": binary_block}


def encode(columns: Dict[str, np.ndarray],
           names_types: List[Tuple[str, str]], top_k: int = pivot.TOP_K,
           min_support: int = pivot.MIN_SUPPORT):
    """((n, d) float32, labels, vocabularies by column name, groups), as
    `pivot.encode` gives them: `groups` maps each column that has
    indicator columns to their positions (a numeric column's null
    indicator, a pivot's whole block)."""
    known = set(ORDER) | set(pivot.PIVOT_TYPES)
    if any(ty not in known for _, ty in names_types):
        raise ValueError(f"reference/encode_typed.py encodes {sorted(known)}"
                         " columns only")
    blocks, labels, vocabs, groups = [], [], {}, {}
    at = 0
    for kind in ORDER:
        for name, ty in names_types:
            if ty != kind:
                continue
            blocks.append(_BLOCK[kind](columns[name]))
            labels += [name, name + ":null"]
            groups[name] = [at + 1]
            at += 2
    for name, ty in names_types:
        if ty not in pivot.PIVOT_TYPES:
            continue
        vocabs[name] = pivot.vocabulary(columns[name], top_k, min_support)
        blocks.append(pivot.pivot_block(columns[name], vocabs[name]))
        labels += [f"{name}={lvl}" for lvl in vocabs[name]] \
            + [name + ":OTHER", name + ":null"]
        groups[name] = list(range(at, at + len(vocabs[name]) + 2))
        at += len(vocabs[name]) + 2
    return np.concatenate(blocks, 1), labels, vocabs, groups
