"""Typed raw columns -> the feature matrix, as `transmogrify()` documents
its defaults (Transmogrifier.scala:52-90: TopK 20, MinSupport 10, null
tracking on), in plain numpy.

Column order: one block per feature type in the order the dispatch lists
them (Integral before the pivoted text types), columns in input order
inside a block.

- `Integral`: [value, null indicator] a column; a missing value is
  filled with the column's mode, ties to the smallest value.
- `PickList`: the levels seen at least `min_support` times, the `top_k`
  most frequent of them by count descending then lexicographic, one 0/1
  column each, then OTHER (a present level outside the vocabulary), then
  the null indicator.

Departures from the reference implementation, as the program makes them:
the matrix is float32 (the reference's vectors are float64); a level's
text is used as it is (the reference cleans text only when asked to,
`cleanText`, which pivots of hashed ids never are).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

TOP_K = 20
MIN_SUPPORT = 10
PIVOT_TYPES = ("PickList",)


def vocabulary(col: np.ndarray, top_k: int = TOP_K,
               min_support: int = MIN_SUPPORT) -> List[str]:
    present = col[np.not_equal(col, None)]
    if not present.size:
        return []
    levels, counts = np.unique(present.astype("U"), return_counts=True)
    ok = counts >= min_support
    levels, counts = levels[ok], counts[ok]
    # np.unique sorted the levels; a stable sort by count keeps that
    # order inside a tie
    order = np.argsort(-counts, kind="stable")[:top_k]
    return [str(v) for v in levels[order]]


def pivot_block(col: np.ndarray, vocab: List[str]) -> np.ndarray:
    """(n, len(vocab) + 2) float32: levels, OTHER, null."""
    k = len(vocab)
    missing = np.equal(col, None)
    ids = np.full(len(col), k, np.int64)                    # OTHER
    if k:
        order = np.argsort(vocab)
        sorted_vocab = np.asarray(vocab, "U")[order]
        text = col[~missing].astype("U")
        pos = np.minimum(np.searchsorted(sorted_vocab, text), k - 1)
        ids[~missing] = np.where(sorted_vocab[pos] == text, order[pos], k)
    ids[missing] = k + 1
    out = np.zeros((len(col), k + 2), np.float32)
    out[np.arange(len(col)), ids] = 1.0
    return out


def integral_block(col: np.ndarray) -> np.ndarray:
    """(n, 2) float32: mode-filled value, null indicator."""
    v = np.asarray(col, np.float64)
    missing = np.isnan(v)
    fill = 0.0
    if (~missing).any():
        vals, counts = np.unique(v[~missing], return_counts=True)
        fill = vals[np.argmax(counts)]          # first maximum: smallest
    return np.stack([np.where(missing, fill, v), missing * 1.0],
                    1).astype(np.float32)


def encode(columns: Dict[str, np.ndarray],
           names_types: List[Tuple[str, str]]):
    """((n, d) float32, labels, vocabularies by column name, groups):
    `labels[j]` names column j (`<name>`, `<name>:null`,
    `<name>=<level>`, `<name>:OTHER`); `groups` maps each column that has
    indicator columns to their positions (a pivot's whole block, an
    integer's null indicator) — what the checker tests against the
    label."""
    blocks, labels, vocabs, groups = [], [], {}, {}
    at = 0
    for name, ty in names_types:
        if ty != "Integral":
            continue
        blocks.append(integral_block(columns[name]))
        labels += [name, name + ":null"]
        groups[name] = [at + 1]
        at += 2
    for name, ty in names_types:
        if ty not in PIVOT_TYPES:
            continue
        vocabs[name] = vocabulary(columns[name])
        blocks.append(pivot_block(columns[name], vocabs[name]))
        labels += [f"{name}={lvl}" for lvl in vocabs[name]] \
            + [name + ":OTHER", name + ":null"]
        groups[name] = list(range(at, at + len(vocabs[name]) + 2))
        at += len(vocabs[name]) + 2
    known = {"Integral", *PIVOT_TYPES}
    if any(ty not in known for _, ty in names_types):
        raise ValueError("reference/pivot.py encodes Integral and "
                         f"{PIVOT_TYPES} columns only")
    return np.concatenate(blocks, 1), labels, vocabs, groups
