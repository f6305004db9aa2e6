"""Categorical pivot (one-hot) vectorizers.

Reference parity: `core/.../feature/OpOneHotVectorizer.scala` /
`OpSetVectorizer` — top-K pivot with OTHER and null-indicator columns,
defaults TopK=20, MinSupport=10 (`Transmogrifier.scala:52-90`).

TPU-first: the vocabulary (data-dependent) is resolved at fit time on host;
the transform is a static-shape `one_hot` over integer ids. Neither host
pass walks the cells in python: both read the column's factorization
(`Column.factorization()`: integer codes into the distinct levels, made
once a column) — the fit is a `bincount` of the codes and a ranking of the
levels that reach `min_support`, host_prepare a take of the codes from a
level → id table; device_apply builds the dense pivot so XLA fuses it with
the downstream combine/model matmul.
"""

from __future__ import annotations

from collections import Counter
from itertools import repeat
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax.nn
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import types as T
from transmogrifai_tpu.data.columns import Column, factorize_text
from transmogrifai_tpu.data.metadata import (
    NULL_INDICATOR, OTHER_INDICATOR, VectorColumnMetadata, VectorMetadata)
from transmogrifai_tpu.obs.trace import TRACER, upload, uploading
from transmogrifai_tpu.stages.base import Estimator, FitContext, Transformer


def _factorization(values: Union[Column, Sequence]
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """(codes, levels) of a text column (kept on the column) or of raw
    cells (made here)."""
    if isinstance(values, Column):
        return values.factorization()
    return factorize_text(values)


def level_counts(values: Union[Column, Sequence]
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(levels, counts): the distinct present levels and the cells of
    each. The one counting pass of every text pivot's fit; a `take()`
    subset lists its parent's levels, some with no cell."""
    codes, levels = _factorization(values)
    return levels, np.bincount(codes[codes >= 0], minlength=len(levels))


def rank_levels(levels: Sequence, counts: np.ndarray, top_k: int,
                min_support: int) -> List[str]:
    """The `top_k` most frequent of the levels that reach `min_support`,
    count-desc then lexicographic for determinism. Python sees only the
    levels at or above the `top_k`-th count (ties included)."""
    counts = np.asarray(counts)
    eligible = np.flatnonzero(counts >= max(min_support, 1))
    if eligible.size > top_k > 0:
        cut = eligible.size - top_k
        eligible = eligible[
            counts[eligible] >= np.partition(counts[eligible], cut)[cut]]
    ranked = sorted((-int(counts[j]), levels[j]) for j in eligible)
    return [lvl for _, lvl in ranked[:top_k]]


def top_k_levels(counter: Counter, top_k: int, min_support: int) -> List[str]:
    """`rank_levels` of a Counter (the map pivots count per key)."""
    return rank_levels(list(counter),
                       np.fromiter(counter.values(), np.int64, len(counter)),
                       top_k, min_support)


def pivot_encode_ids(values: Union[Column, Sequence], lut: Dict[str, int],
                     k: int) -> np.ndarray:
    """Map level strings → ids with OTHER=k, NULL=k+1 (shared by OneHotModel
    and SmartTextModel so the two pivot encodings cannot drift). None and
    float NaN are both missing.

    Reads the factorization (a Column's own, kept on it; made here for raw
    cells): a level → id table, OTHER wherever the vocabulary has no such
    level, with one more slot at the end holding NULL, which is where a
    missing cell's code -1 lands; the ids are one take of the codes. The
    levels go through the vocabulary's dict in C, the cells through no
    python at all."""
    codes, levels = _factorization(values)
    table = np.empty(len(levels) + 1, dtype=np.int32)
    table[:-1] = np.fromiter(map(lut.get, levels, repeat(k)), np.int32,
                             len(levels))
    table[-1] = k + 1
    return table[codes]


def one_hot_np(ids: np.ndarray, k: int, track_nulls: bool) -> np.ndarray:
    """Host-side dense pivot block: k levels + OTHER (+ NULL if tracked)."""
    block = np.zeros((len(ids), k + 2), dtype=np.float32)
    block[np.arange(len(ids)), ids] = 1.0
    return block if track_nulls else block[:, : k + 1]


class OneHotModel(Transformer):
    """Fitted pivot: per feature K level columns + OTHER + null indicator."""

    out_type = T.OPVector

    def __init__(self, vocabs: Sequence[Sequence[str]], track_nulls: bool = True,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.vocabs = [list(v) for v in vocabs]
        self.track_nulls = track_nulls
        self._lookups = [
            {lvl: i for i, lvl in enumerate(v)} for v in self.vocabs]

    def _widths(self) -> List[int]:
        return [len(v) + 1 + (1 if self.track_nulls else 0) for v in self.vocabs]

    def host_prepare(self, cols: Sequence[Optional[Column]]):
        # the text cells' host pass, named in the timeline under the
        # stage's `stage:transform:*` span with the cells it read, the
        # columns that came with their codes made (`materialize` makes
        # them: in a training pass all) and the distinct levels
        with TRACER.span("pivot:encode", category="pivot",
                         cells=sum(len(c.data) for c in cols),
                         codes_reused=sum(c.factorized for c in cols)) as sp:
            ids = [
                pivot_encode_ids(c, self._lookups[i], len(self.vocabs[i]))
                for i, c in enumerate(cols)
            ]
            sp.set(levels=sum(len(c.factorization()[1]) for c in cols))
            return ids

    def device_apply(self, enc, dev):
        outs = []
        # the host ids become device arrays inside `one_hot`
        with uploading("pivot", enc):
            for i, ids in enumerate(enc):
                k = len(self.vocabs[i])
                n_classes = k + 2  # levels + OTHER + NULL
                oh = jax.nn.one_hot(ids, n_classes, dtype=jnp.float32)
                if not self.track_nulls:
                    oh = oh[:, : k + 1]
                outs.append(oh)
            return (jnp.concatenate(outs, axis=1) if outs
                    else jnp.zeros((0, 0)))

    def output_meta(self) -> VectorMetadata:
        cols: List[VectorColumnMetadata] = []
        for f, vocab in zip(self.input_features, self.vocabs):
            for lvl in vocab:
                cols.append(VectorColumnMetadata(
                    parent_name=f.name, parent_type=f.ftype.__name__,
                    grouping=f.name, indicator_value=lvl))
            cols.append(VectorColumnMetadata(
                parent_name=f.name, parent_type=f.ftype.__name__,
                grouping=f.name, indicator_value=OTHER_INDICATOR))
            if self.track_nulls:
                cols.append(VectorColumnMetadata(
                    parent_name=f.name, parent_type=f.ftype.__name__,
                    grouping=f.name, indicator_value=NULL_INDICATOR))
        return VectorMetadata(self.output_name(), tuple(cols)).with_indices()

    def get_params(self):
        return {"vocabs": self.vocabs, "track_nulls": self.track_nulls}


class OneHotVectorizer(Estimator):
    """N categorical text features → top-K pivot each (OpSetVectorizer)."""

    in_types = (T.Text, Ellipsis)
    out_type = T.OPVector

    def __init__(self, top_k: int = 20, min_support: int = 10,
                 track_nulls: bool = True, uid: Optional[str] = None):
        super().__init__(uid=uid, top_k=top_k, min_support=min_support,
                         track_nulls=track_nulls)
        self.top_k = top_k
        self.min_support = min_support
        self.track_nulls = track_nulls

    def fit_model(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        vocabs = []
        with TRACER.span("pivot:fit", category="pivot", columns=len(cols),
                         codes_reused=sum(c.factorized for c in cols)) as sp:
            n_levels = 0
            for c in cols:
                levels, counts = level_counts(c)
                n_levels += len(levels)
                vocabs.append(rank_levels(
                    levels, counts, self.top_k, self.min_support))
            sp.set(levels=n_levels)
        return OneHotModel(vocabs, self.track_nulls)


class MultiPickListModel(Transformer):
    """Fitted multi-hot pivot for set-valued categoricals."""

    out_type = T.OPVector

    def __init__(self, vocabs: Sequence[Sequence[str]], track_nulls: bool = True,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.vocabs = [list(v) for v in vocabs]
        self.track_nulls = track_nulls
        self._lookups = [{lvl: i for i, lvl in enumerate(v)} for v in self.vocabs]

    def host_prepare(self, cols: Sequence[Optional[Column]]):
        outs = []
        for i, c in enumerate(cols):
            lut, k = self._lookups[i], len(self.vocabs[i])
            width = k + 1 + (1 if self.track_nulls else 0)
            arr = np.zeros((len(c.data), width), dtype=np.float32)
            for r, val in enumerate(c.data):
                if val is None:
                    if self.track_nulls:
                        arr[r, k + 1] = 1.0
                    continue
                for s in val:
                    j = lut.get(s)
                    if j is None:
                        arr[r, k] = 1.0  # OTHER
                    else:
                        arr[r, j] = 1.0
            outs.append(arr)
        return outs

    def device_apply(self, enc, dev):
        return jnp.concatenate([upload("pivot", a) for a in enc], axis=1)

    def output_meta(self) -> VectorMetadata:
        cols: List[VectorColumnMetadata] = []
        for f, vocab in zip(self.input_features, self.vocabs):
            for lvl in vocab:
                cols.append(VectorColumnMetadata(
                    parent_name=f.name, parent_type=f.ftype.__name__,
                    grouping=f.name, indicator_value=lvl))
            cols.append(VectorColumnMetadata(
                parent_name=f.name, parent_type=f.ftype.__name__,
                grouping=f.name, indicator_value=OTHER_INDICATOR))
            if self.track_nulls:
                cols.append(VectorColumnMetadata(
                    parent_name=f.name, parent_type=f.ftype.__name__,
                    grouping=f.name, indicator_value=NULL_INDICATOR))
        return VectorMetadata(self.output_name(), tuple(cols)).with_indices()

    def get_params(self):
        return {"vocabs": self.vocabs, "track_nulls": self.track_nulls}


class MultiPickListVectorizer(Estimator):
    """N MultiPickList features → top-K multi-hot each."""

    in_types = (T.MultiPickList, Ellipsis)
    out_type = T.OPVector

    def __init__(self, top_k: int = 20, min_support: int = 10,
                 track_nulls: bool = True, uid: Optional[str] = None):
        super().__init__(uid=uid, top_k=top_k, min_support=min_support,
                         track_nulls=track_nulls)
        self.top_k = top_k
        self.min_support = min_support
        self.track_nulls = track_nulls

    def fit_model(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        vocabs = []
        for c in cols:
            counter: Counter = Counter()
            for val in c.data:
                if val is not None:
                    counter.update(val)
            vocabs.append(top_k_levels(counter, self.top_k, self.min_support))
        return MultiPickListModel(vocabs, self.track_nulls)
