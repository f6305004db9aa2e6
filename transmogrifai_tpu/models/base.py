"""Predictor base classes: Estimator2(RealNN, OPVector) → Prediction.

Reference parity: `core/.../sparkwrappers/specific/OpPredictorWrapper.scala:71-121`
and `OpPredictionModel` — but instead of wrapping Spark MLlib, every model
here is a pair of pure jnp functions:

    fit_fn(X, y, w, hyper)   -> params      (jit/vmap-able)
    predict_fn(params, X)    -> prediction pytree

`w` is a per-row weight vector — the single mechanism behind fold masking,
class balancing, and train/holdout splits in the sweep engine: k-fold CV
vmaps `fit_fn` over stacked weight masks so every fold×grid fit is one XLA
program on the mesh (SURVEY.md §3.3 north star).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import types as T
from transmogrifai_tpu.data.columns import Column
from transmogrifai_tpu.stages.base import Estimator, FitContext, Transformer


class PredictionModel(Transformer):
    """Fitted predictor: device_apply returns the Prediction pytree."""

    out_type = T.Prediction
    response_aware = True  # inputs are (label, features)

    def predict_arrays(self, X: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    def device_apply(self, enc, dev):
        X = dev[-1]  # inputs are (label, features); label unused at transform
        return self.predict_arrays(jnp.asarray(X))


class PredictorEstimator(Estimator):
    """Base for model estimators. Subclasses implement `fit_arrays`.

    `init_params` (attribute, or the `init_params=` kwarg the iterative
    families' `fit_arrays` accept) warm-starts the optimizer from an
    existing model's weights — the continual-refit path: a refit on
    appended data continues from the serving model instead of from
    zeros. Families where it is meaningless (closed-form solves) ignore
    it; the sweep engine never sets it (grid fits stay cold and
    comparable)."""

    in_types = (T.RealNN, T.OPVector)
    out_type = T.Prediction
    response_aware = True  # slot 0 is the label
    init_params: Optional[Dict[str, Any]] = None

    def fit_arrays(self, X: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                   ctx: FitContext) -> PredictionModel:
        raise NotImplementedError

    def fit_model(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        label, vec = cols
        y = jnp.asarray(np.asarray(label.data["value"], dtype=np.float32))
        X = jnp.asarray(vec.device_value())
        w = jnp.ones_like(y)
        return self.fit_arrays(X, y, w, ctx)


def infer_n_classes(y: np.ndarray) -> int:
    """Label cardinality for classification (labels must be 0..k-1)."""
    k = int(np.asarray(y).max(initial=0)) + 1
    return max(k, 2)


def stated_n_classes(est, ctx=None) -> Optional[int]:
    """The number of classes somebody STATED: the selector's
    (`FitContext.n_classes`, which `ModelSelector.fit_model` sets once a
    fit from its own `n_classes` or the host label) or, for a bare
    `fit_arrays` / `run_sweep` call, the estimator's own `n_classes`;
    None when neither says. The selector is the owner: an estimator that
    states another number than its selector is an error, not a silent
    winner."""
    own = getattr(est, "n_classes", None)
    held = getattr(ctx, "n_classes", None)
    if own and held and int(own) != int(held):
        raise ValueError(
            f"{type(est).__name__}(n_classes={int(own)}) under a selector "
            f"whose label has {int(held)} classes: state the number of "
            "classes in one place, the selector's `n_classes`")
    k = held or own
    return int(k) if k else None


def n_classes_of(est, y, ctx=None) -> int:
    """The number of classes a classifier fits: `stated_n_classes`, else
    the label's largest value + 1, which is what a bare `fit_arrays` or
    `run_sweep` call that states nothing falls back to (of a device label
    only the maximum crosses to the host, never the label). K is a
    compiled shape: taken from the data, a table in which the top label
    does not fall compiles every program anew."""
    k = stated_n_classes(est, ctx)
    if k:
        return k
    if isinstance(y, jax.Array):
        return max(int(jnp.max(y, initial=0)) + 1, 2)
    return infer_n_classes(y)


def resolve_init_params(est: PredictorEstimator,
                        explicit: Optional[Dict[str, Any]],
                        expect_shapes: Dict[str, tuple]
                        ) -> Optional[Dict[str, jnp.ndarray]]:
    """Warm-start weights for a fit: the explicit `init_params=` kwarg
    wins over the estimator's `init_params` attribute. Shapes are
    validated HERE, on host, against the incoming data — a refit whose
    feature width changed (an upstream vectorizer re-fit differently)
    must fail with a clear message, not a mid-trace XLA shape error."""
    warm = explicit if explicit is not None else est.init_params
    if warm is None:
        return None
    out: Dict[str, jnp.ndarray] = {}
    for name, shape in expect_shapes.items():
        if name not in warm:
            raise ValueError(
                f"{type(est).__name__}: init_params missing {name!r} "
                f"(have {sorted(warm)})")
        arr = jnp.asarray(warm[name], jnp.float32)
        if tuple(arr.shape) != tuple(shape):
            raise ValueError(
                f"{type(est).__name__}: init_params[{name!r}] shape "
                f"{tuple(arr.shape)} does not match the data "
                f"({tuple(shape)}) — warm start requires an unchanged "
                f"feature/class layout; refit cold instead")
        out[name] = arr
    return out
