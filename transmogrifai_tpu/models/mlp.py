"""Multilayer perceptron classifier.

Reference parity: `core/.../impl/classification/OpMultilayerPerceptronClassifier.scala`
(Spark MLP: sigmoid hidden layers, softmax output, full-batch L-BFGS).

TPU-first: fixed-epoch full-batch Adam inside a `lax.scan` (static shapes,
vmappable over hyperparams/folds); every layer is an MXU matmul.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from transmogrifai_tpu.models.base import (
    PredictionModel, PredictorEstimator, n_classes_of)
from transmogrifai_tpu.stages.base import FitContext


def _init_params(layers: Tuple[int, ...], key) -> List[Dict]:
    params = []
    for i in range(len(layers) - 1):
        key, sub = jax.random.split(key)
        fan_in = layers[i]
        params.append({
            "W": jax.random.normal(sub, (layers[i], layers[i + 1]),
                                   jnp.float32) / jnp.sqrt(fan_in),
            "b": jnp.zeros((layers[i + 1],), jnp.float32)})
    return params


def _forward(params: List[Dict], X: jnp.ndarray) -> jnp.ndarray:
    h = X
    for layer in params[:-1]:
        h = jax.nn.sigmoid(h @ layer["W"] + layer["b"])
    last = params[-1]
    return h @ last["W"] + last["b"]  # logits


@partial(jax.jit, static_argnames=("layers", "max_iter"))
def fit_mlp(X: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
            layers: Tuple[int, ...], max_iter: int = 200,
            learning_rate: float = 0.05, seed: int = 0) -> List[Dict]:
    k = layers[-1]
    oh = jax.nn.one_hot(y.astype(jnp.int32), k)
    params = _init_params(layers, jax.random.PRNGKey(seed))

    def loss_fn(p):
        logits = _forward(p, X)
        ll = optax.softmax_cross_entropy(logits, oh)
        return (ll * w).sum() / jnp.maximum(w.sum(), 1.0)

    opt = optax.adam(learning_rate)
    state = opt.init(params)

    def step(carry, _):
        p, s = carry
        v, g = jax.value_and_grad(loss_fn)(p)
        updates, s = opt.update(g, s)
        return (optax.apply_updates(p, updates), s), v

    (params, _), _ = jax.lax.scan(step, (params, state), None, length=max_iter)
    return params


def predict_mlp(params: List[Dict], X: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    logits = _forward(params, X)
    return {"prediction": jnp.argmax(logits, -1).astype(jnp.float32),
            "rawPrediction": logits,
            "probability": jax.nn.softmax(logits, -1)}


class MLPModel(PredictionModel):
    def __init__(self, weights=None, uid: Optional[str] = None):
        super().__init__(uid=uid)
        # weights: list of {"W": 2d list, "b": 1d list}
        self.weights = [
            {"W": np.asarray(l["W"], dtype=np.float32),
             "b": np.asarray(l["b"], dtype=np.float32)} for l in weights]

    def predict_arrays(self, X):
        params = [{"W": jnp.asarray(l["W"]), "b": jnp.asarray(l["b"])}
                  for l in self.weights]
        return predict_mlp(params, X)

    # parameter lifting: see LinearRegressionModel — the layer count and
    # widths key the program via the consts structure digest
    def device_constants(self):
        return {"layers": [
            {"W": jnp.asarray(l["W"]), "b": jnp.asarray(l["b"])}
            for l in self.weights]}

    def device_apply_with(self, consts, enc, dev):
        return predict_mlp(consts["layers"], jnp.asarray(dev[-1]))

    def signature_params(self):
        return {}

    def narrow_device_constants(self, consts):
        return {"layers": [
            {"W": l["W"].astype(jnp.bfloat16), "b": l["b"]}
            for l in consts["layers"]]}

    def get_params(self):
        return {"weights": [
            {"W": l["W"].tolist(), "b": l["b"].tolist()} for l in self.weights]}


class OpMultilayerPerceptronClassifier(PredictorEstimator):
    """hidden_layers e.g. (10, 10); input/output sizes are inferred."""

    def __init__(self, hidden_layers: Sequence[int] = (10,),
                 max_iter: int = 200, learning_rate: float = 0.05,
                 n_classes: Optional[int] = None, uid: Optional[str] = None):
        super().__init__(uid=uid, hidden_layers=list(hidden_layers),
                         max_iter=max_iter, learning_rate=learning_rate,
                         n_classes=n_classes)
        self.hidden_layers = tuple(hidden_layers)
        self.max_iter = max_iter
        self.learning_rate = learning_rate
        self.n_classes = n_classes

    def fit_arrays(self, X, y, w, ctx: FitContext) -> MLPModel:
        k = n_classes_of(self, y, ctx)
        layers = (int(X.shape[1]),) + self.hidden_layers + (k,)
        params = fit_mlp(X, y, w, layers, self.max_iter,
                         self.learning_rate, ctx.seed)
        return MLPModel([{"W": np.asarray(l["W"]), "b": np.asarray(l["b"])}
                         for l in params])
