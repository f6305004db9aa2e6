"""Numeric vectorizers: impute + null-indicator encoding.

Reference parity: `core/.../feature/RealVectorizer.scala` (mean impute),
`IntegralVectorizer.scala` (mode impute), `BinaryVectorizer.scala`,
`RealNNVectorizer.scala` — the per-type defaults applied by
`Transmogrifier.transmogrify` (`Transmogrifier.scala:116-344`).

TPU-first: each vectorizer is an N-ary sequence estimator whose fit is a
single masked reduction over the stacked (n, F) batch — shardable over the
data axis with a `psum` — and whose transform is a pure jnp map that XLA
fuses with everything downstream.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import types as T
from transmogrifai_tpu.data.columns import Column
from transmogrifai_tpu.data.metadata import (
    NULL_INDICATOR, VectorColumnMetadata, VectorMetadata)
from transmogrifai_tpu.obs.trace import pull, uploading
from transmogrifai_tpu.stages.base import Estimator, FitContext, Transformer


def stack_scalar_dev(dev: Sequence) -> tuple:
    """Stack N scalar device pytrees into (n, F) value / mask arrays."""
    value = jnp.stack([d["value"] for d in dev], axis=1)
    mask = jnp.stack([d["mask"] for d in dev], axis=1)
    return value, mask


def _interleave(cols_per_feature: Sequence[Sequence[jnp.ndarray]]) -> jnp.ndarray:
    """Concat per-feature column groups into one (n, sum(widths)) vector."""
    flat = [c for group in cols_per_feature for c in group]
    return jnp.stack(flat, axis=1) if flat else jnp.zeros((0, 0), jnp.float32)


class _NumericModelBase(Transformer):
    """Fitted numeric vectorizer: fill + optional null-indicator columns."""

    out_type = T.OPVector

    def __init__(self, fill_values: Sequence[float], track_nulls: bool = True,
                 descriptor: Optional[str] = None, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.fill_values = np.asarray(fill_values, dtype=np.float32)
        self.track_nulls = track_nulls
        self.descriptor = descriptor

    def device_apply(self, enc, dev):
        groups = []
        for i, d in enumerate(dev):
            v, m = d["value"], d["mask"]
            filled = v * m + self.fill_values[i] * (1.0 - m)
            cols = [filled]
            if self.track_nulls:
                cols.append(1.0 - m)
            groups.append(cols)
        # the columns are numpy up to here: the stack is their upload
        with uploading(f"stage:{self.operation_name}", groups):
            return _interleave(groups)

    def output_meta(self) -> VectorMetadata:
        cols: List[VectorColumnMetadata] = []
        for f in self.input_features:
            cols.append(VectorColumnMetadata(
                parent_name=f.name, parent_type=f.ftype.__name__,
                descriptor_value=self.descriptor))
            if self.track_nulls:
                cols.append(VectorColumnMetadata(
                    parent_name=f.name, parent_type=f.ftype.__name__,
                    indicator_value=NULL_INDICATOR))
        return VectorMetadata(self.output_name(), tuple(cols)).with_indices()

    def get_params(self):
        return {"fill_values": self.fill_values.tolist(),
                "track_nulls": self.track_nulls, "descriptor": self.descriptor}


class RealVectorizerModel(_NumericModelBase):
    pass


class RealVectorizer(Estimator):
    """N Real features → [imputed value, null indicator] per feature.

    fill_value: "mean" (default, RealVectorizer.scala) | "median" | float.
    """

    in_types = (T.Real, Ellipsis)
    out_type = T.OPVector

    def __init__(self, fill_value="mean", track_nulls: bool = True,
                 uid: Optional[str] = None):
        super().__init__(uid=uid, fill_value=fill_value, track_nulls=track_nulls)
        self.fill_value = fill_value
        self.track_nulls = track_nulls

    def fit_model(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        dev = [c.device_value() for c in cols]
        with uploading(f"stage:{self.operation_name}", dev):
            value, mask = stack_scalar_dev(dev)
        if self.fill_value == "mean":
            denom = jnp.maximum(mask.sum(axis=0), 1.0)
            # `wait_s`: what is left of the table's upload, and the reduction
            fills = pull("impute:fills", (value * mask).sum(axis=0) / denom)
        elif self.fill_value == "median":
            fills = []
            for c in cols:
                v = np.asarray(c.data["value"], dtype=np.float64)
                m = np.asarray(c.data["mask"])
                fills.append(float(np.median(v[m])) if m.any() else 0.0)
            fills = np.asarray(fills)
        else:
            fills = np.full(len(cols), float(self.fill_value))
        return RealVectorizerModel(fills, self.track_nulls)


class IntegralVectorizerModel(_NumericModelBase):
    pass


class IntegralVectorizer(Estimator):
    """N Integral features → [mode-imputed value, null indicator] each
    (IntegralVectorizer.scala fill-with-mode)."""

    in_types = (T.Integral, Ellipsis)
    out_type = T.OPVector

    def __init__(self, fill_value="mode", track_nulls: bool = True,
                 uid: Optional[str] = None):
        super().__init__(uid=uid, fill_value=fill_value, track_nulls=track_nulls)
        self.fill_value = fill_value
        self.track_nulls = track_nulls

    def fit_model(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        fills = []
        for c in cols:
            if self.fill_value == "mode":
                v = np.asarray(pull("impute:fills", c.data["value"]))[
                    np.asarray(pull("impute:fills", c.data["mask"]))]
                if v.size == 0:
                    fills.append(0.0)
                else:
                    vals, counts = np.unique(v, return_counts=True)
                    # ties broken by smallest value (np.unique sorts ascending)
                    fills.append(float(vals[np.argmax(counts)]))
            else:
                fills.append(float(self.fill_value))
        return IntegralVectorizerModel(np.asarray(fills), self.track_nulls)


class BinaryVectorizerModel(_NumericModelBase):
    pass


class BinaryVectorizer(Estimator):
    """N Binary features → [value (null→fill), null indicator] each
    (BinaryVectorizer.scala, fillValue default false)."""

    in_types = (T.Binary, Ellipsis)
    out_type = T.OPVector

    def __init__(self, fill_value: bool = False, track_nulls: bool = True,
                 uid: Optional[str] = None):
        super().__init__(uid=uid, fill_value=fill_value, track_nulls=track_nulls)
        self.fill_value = fill_value
        self.track_nulls = track_nulls

    def fit_model(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        fills = np.full(len(cols), 1.0 if self.fill_value else 0.0)
        return BinaryVectorizerModel(fills, self.track_nulls)


class RealNNVectorizer(Transformer):
    """N RealNN features → identity stack (RealNNVectorizer.scala) —
    stateless, no nulls possible."""

    in_types = (T.RealNN, Ellipsis)
    out_type = T.OPVector

    def device_apply(self, enc, dev):
        return jnp.stack([d["value"] for d in dev], axis=1)

    def output_meta(self) -> VectorMetadata:
        cols = tuple(
            VectorColumnMetadata(parent_name=f.name, parent_type=f.ftype.__name__)
            for f in self.input_features)
        return VectorMetadata(self.output_name(), cols).with_indices()
