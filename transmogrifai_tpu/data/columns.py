"""Columnar physical representation of typed feature data.

This is where the row-level type lattice (`transmogrifai_tpu.types`) meets
arrays. Each `Column` holds one feature's values for a whole batch in the
layout best suited to its kind:

- scalar (OPNumeric):  float64/int64 `value` + bool `mask` (True = present)
- text:                object ndarray of str|None, beside it (made at most
                       once a column, on first use) its factorization:
                       int32 `codes` (-1 = missing) into the distinct
                       `levels`, which the pivots read instead of cells
- list/set/geo:        object ndarray of list/frozenset
- map:                 object ndarray of dict
- vector (OPVector):   dense (n, d) float32 array + `VectorMetadata`
- prediction:          dict of arrays {prediction (n,), probability (n,k),
                       rawPrediction (n,k)}

The device contract: `Column.device_value()` returns the pytree of numeric
arrays a jitted stage consumes — strings and other host-only kinds return
None and must be encoded by a stage's `host_prepare` (see stages.base).
Reference analogue: `FeatureTypeSparkConverter` / DataFrame columns
(`features/.../FeatureSparkTypes.scala:54-96`), redesigned for XLA: static
dtypes, dense tiles, masks instead of in-band nulls.
"""

from __future__ import annotations

import math
import numbers
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from transmogrifai_tpu import types as T
from transmogrifai_tpu.data.metadata import VectorMetadata

SCALAR, TEXT, LIST, MAP, VECTOR, PREDICTION = (
    "scalar", "text", "list", "map", "vector", "prediction")


def kind_of(ftype: type) -> str:
    if not (isinstance(ftype, type) and issubclass(ftype, T.FeatureType)):
        raise TypeError(f"{ftype!r} is not a FeatureType class")
    if issubclass(ftype, T.Prediction):
        return PREDICTION
    if issubclass(ftype, T.OPMap):
        return MAP
    if issubclass(ftype, T.OPVector):
        return VECTOR
    if issubclass(ftype, (T.OPList, T.OPSet)):
        return LIST
    if issubclass(ftype, T.OPNumeric):
        return SCALAR
    if issubclass(ftype, T.Text):
        return TEXT
    raise TypeError(f"No columnar kind for {ftype.__name__}")


def _is_integral(ftype: type) -> bool:
    return issubclass(ftype, T.Integral)


def factorize_text(values) -> Tuple[np.ndarray, np.ndarray]:
    """(codes, levels) of a 1-D array of cells: `codes` int32 with -1 for a
    missing cell (None or float NaN), `levels` the object array of the
    distinct present cells in first-seen order. One hash pass in C, no
    sort and no stringification: a level keeps its python identity.
    Raises TypeError on an unhashable cell."""
    import pandas as pd
    codes, levels = pd.factorize(np.asarray(values, dtype=object))
    return codes.astype(np.int32), levels


@dataclass
class Column:
    """One feature's values for a batch, in columnar layout."""

    ftype: type
    data: Any
    meta: Optional[VectorMetadata] = None
    # (the `data` array it was made from, codes, levels): text kind only
    _fact: Optional[tuple] = field(default=None, repr=False, compare=False)

    @property
    def kind(self) -> str:
        return kind_of(self.ftype)

    def __len__(self) -> int:
        k = self.kind
        if k == SCALAR:
            return int(self.data["value"].shape[0])
        if k == VECTOR:
            return int(self.data.shape[0])
        if k == PREDICTION:
            return int(self.data["prediction"].shape[0])
        return int(self.data.shape[0])

    @property
    def width(self) -> int:
        """Vector width (vector kind) or probability width (prediction kind)."""
        k = self.kind
        if k == VECTOR:
            return int(self.data.shape[1])
        if k == PREDICTION:
            return int(self.data["probability"].shape[1])
        raise TypeError(f"width undefined for kind {k}")

    # ------------------------------------------------------------------ #
    # text factorization                                                 #
    # ------------------------------------------------------------------ #

    @property
    def factorized(self) -> bool:
        """Whether `factorization()` has been made for the `data` held."""
        return self._fact is not None and self._fact[0] is self.data

    def factorization(self) -> Tuple[np.ndarray, np.ndarray]:
        """`factorize_text(data)`, made at most once for a `data` array (a
        column whose `data` was replaced makes it again). A `take()` keeps
        its parent's levels, so there a level may have no cell."""
        if not self.factorized:
            self._fact = (self.data, *factorize_text(self.data))
        return self._fact[1], self._fact[2]

    # ------------------------------------------------------------------ #
    # construction                                                       #
    # ------------------------------------------------------------------ #

    @staticmethod
    def from_values(ftype: type, values: Sequence[Any]) -> "Column":
        """Build a column from raw python values (each may be a FeatureType
        instance or a plain value acceptable to `ftype`). Typed storage
        is not copied where it already is the column: a numeric array
        gives value/mask without a per-cell pass, and an object array of
        str|None IS the text column (aliasing the caller's array), with
        its factorization attached."""
        k = kind_of(ftype)
        n = len(values)

        def unwrap(v):
            if isinstance(v, T.FeatureType):
                return v.value
            return ftype(v).value  # validate via the type

        if k == SCALAR:
            dtype = np.int64 if _is_integral(ftype) else np.float64
            arr = np.asarray(values)
            if arr.dtype != object:
                # fast path: typed numeric storage (Dataset keeps numeric
                # columns as float arrays with NaN for missing)
                f = arr.astype(np.float64, copy=False)
                mask = ~np.isnan(f)
                if issubclass(ftype, T.NonNullable) and not mask.all():
                    raise T.FeatureTypeError(
                        f"{ftype.__name__} cannot be empty "
                        f"({int((~mask).sum())} missing values)")
                out = np.where(mask, f, 0.0).astype(dtype)
                return Column(ftype, {"value": out, "mask": mask})
            out = np.zeros(n, dtype=dtype)
            mask = np.zeros(n, dtype=bool)
            for i, v in enumerate(values):
                u = unwrap(v)
                if u is not None:
                    out[i] = u
                    mask[i] = True
            return Column(ftype, {"value": out, "mask": mask})
        if k == VECTOR:
            rows = [np.asarray(unwrap(v), dtype=np.float32) for v in values]
            if n == 0:
                return Column(ftype, np.zeros((0, 0), dtype=np.float32))
            width = max((r.size for r in rows), default=0)
            arr = np.zeros((n, width), dtype=np.float32)
            for i, r in enumerate(rows):
                arr[i, : r.size] = r
            return Column(ftype, arr)
        if k == PREDICTION:
            preds = [T.Prediction(unwrap(v)) for v in values]
            width = max((len(p.probability) for p in preds), default=0)
            rwidth = max((len(p.raw_prediction) for p in preds), default=0)
            data = {
                "prediction": np.array([p.prediction for p in preds], dtype=np.float64),
                "probability": np.zeros((n, width), dtype=np.float64),
                "rawPrediction": np.zeros((n, rwidth), dtype=np.float64),
            }
            for i, p in enumerate(preds):
                pr, rw = p.probability, p.raw_prediction
                data["probability"][i, : len(pr)] = pr
                data["rawPrediction"][i, : len(rw)] = rw
            return Column(ftype, data)
        # host-object kinds; str/None text cells skip FeatureType
        # construction — the per-value validation round-trip dominated
        # host encode at scale
        arr = np.empty(n, dtype=object)
        if k == TEXT:
            col = _text_column_of(ftype, values)
            if col is not None:
                return col
            for i, v in enumerate(values):
                arr[i] = v if (v is None or type(v) is str) else unwrap(v)
        else:
            for i, v in enumerate(values):
                u = unwrap(v)
                arr[i] = None if (u is None or len(u) == 0) else u
        return Column(ftype, arr)

    @staticmethod
    def vector(arr, meta: VectorMetadata) -> "Column":
        return Column(T.OPVector, arr, meta=meta)

    # ------------------------------------------------------------------ #
    # access                                                             #
    # ------------------------------------------------------------------ #

    def device_value(self):
        """Numeric pytree for jitted stages; None for host-only kinds."""
        k = self.kind
        if k == SCALAR:
            v = np.asarray(self.data["value"], dtype=np.float64)
            m = np.asarray(self.data["mask"])
            return {
                "value": np.where(m, v, 0.0).astype(np.float32),
                "mask": m.astype(np.float32),
            }
        if k == VECTOR:
            return self.data
        if k == PREDICTION:
            return self.data
        return None

    def to_values(self) -> List[T.FeatureType]:
        """Rehydrate row-level typed values (tests / local scoring)."""
        k = self.kind
        n = len(self)
        if k == SCALAR:
            val, mask = self.data["value"], self.data["mask"]
            return [
                self.ftype(val[i].item() if mask[i] else None) for i in range(n)
            ]
        if k == VECTOR:
            arr = np.asarray(self.data)
            return [T.OPVector(arr[i]) for i in range(n)]
        if k == PREDICTION:
            out = []
            for i in range(n):
                out.append(T.Prediction.build(
                    float(self.data["prediction"][i]),
                    raw_prediction=np.asarray(self.data["rawPrediction"][i]).tolist(),
                    probability=np.asarray(self.data["probability"][i]).tolist(),
                ))
            return out
        return [self.ftype(self.data[i]) for i in range(n)]

    def take(self, idx) -> "Column":
        """Row subset (numpy fancy index / bool mask)."""
        k = self.kind
        if k == SCALAR:
            return Column(self.ftype, {
                "value": np.asarray(self.data["value"])[idx],
                "mask": np.asarray(self.data["mask"])[idx]})
        if k == PREDICTION:
            return Column(self.ftype, {key: np.asarray(a)[idx] for key, a in self.data.items()})
        if k == VECTOR:
            return Column(self.ftype, np.asarray(self.data)[idx], meta=self.meta)
        out = Column(self.ftype, self.data[idx])
        if self.factorized:
            # a subset's levels are a subset: the codes stay valid
            out._fact = (out.data, self._fact[1][idx], self._fact[2])
        return out


def _text_column_of(ftype: type, values) -> Optional[Column]:
    """The text column that IS `values`, when `values` is a 1-D object
    array holding only str and None: decided from one factorization (the
    distinct levels' types, the missing cells' types), with no per-cell
    python. None where anything else is seen (a FeatureType instance, a
    number, NaN, an unhashable cell): the per-cell path's business."""
    if not (isinstance(values, np.ndarray) and values.dtype == object
            and values.ndim == 1):
        return None
    try:
        codes, levels = factorize_text(values)
    except TypeError:
        return None
    if not (set(map(type, levels)) <= {str}
            and set(map(type, values[codes < 0])) <= {type(None)}):
        return None
    return Column(ftype, values, _fact=(values, codes, levels))


def scalar_to_float(col: Column) -> np.ndarray:
    """Host helper: scalar column → float64 with NaN for missing."""
    v = np.asarray(col.data["value"], dtype=np.float64).copy()
    v[~np.asarray(col.data["mask"])] = np.nan
    return v
