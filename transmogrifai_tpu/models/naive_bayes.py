"""Multinomial Naive Bayes.

Reference parity: `core/.../impl/classification/OpNaiveBayes.scala` (Spark
MLlib NaiveBayes, multinomial, smoothing=1.0, non-negative features
required — negative features raise, and the selector's fault tolerance
drops the family, matching Spark behavior).

TPU-first: fit is one one-hot-label matmul (class-conditional feature sums)
— a single MXU pass, shardable over rows with a psum.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu.models.base import (
    PredictionModel, PredictorEstimator, n_classes_of)
from transmogrifai_tpu.stages.base import FitContext


def fit_naive_bayes(X: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                    smoothing, n_classes: int) -> Dict:
    oh = jax.nn.one_hot(y.astype(jnp.int32), n_classes) * w[:, None]
    class_counts = oh.sum(0)                      # (k,)
    feat_sums = oh.T @ X                          # (k, d) — MXU
    log_prior = jnp.log(class_counts + 1e-12) - jnp.log(
        jnp.maximum(class_counts.sum(), 1e-12))
    num = feat_sums + smoothing
    log_theta = jnp.log(num) - jnp.log(num.sum(1, keepdims=True))
    return {"log_prior": log_prior, "log_theta": log_theta}


def predict_naive_bayes(params: Dict, X: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    logits = X @ params["log_theta"].T + params["log_prior"]
    prob = jax.nn.softmax(logits, axis=-1)
    return {"prediction": jnp.argmax(logits, -1).astype(jnp.float32),
            "rawPrediction": logits, "probability": prob}


class NaiveBayesModel(PredictionModel):
    def __init__(self, log_prior=None, log_theta=None, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.log_prior = np.asarray(log_prior, dtype=np.float32)
        self.log_theta = np.asarray(log_theta, dtype=np.float32)

    def predict_arrays(self, X):
        return predict_naive_bayes(
            {"log_prior": jnp.asarray(self.log_prior),
             "log_theta": jnp.asarray(self.log_theta)}, X)

    # parameter lifting: see LinearRegressionModel
    def device_constants(self):
        return {"log_prior": jnp.asarray(self.log_prior),
                "log_theta": jnp.asarray(self.log_theta)}

    def device_apply_with(self, consts, enc, dev):
        return predict_naive_bayes(consts, jnp.asarray(dev[-1]))

    def signature_params(self):
        return {}

    def narrow_device_constants(self, consts):
        return {"log_prior": consts["log_prior"],
                "log_theta": consts["log_theta"].astype(jnp.bfloat16)}

    def get_params(self):
        return {"log_prior": self.log_prior.tolist(),
                "log_theta": self.log_theta.tolist()}


class OpNaiveBayes(PredictorEstimator):
    def __init__(self, smoothing: float = 1.0,
                 n_classes: Optional[int] = None, uid: Optional[str] = None):
        super().__init__(uid=uid, smoothing=smoothing, n_classes=n_classes)
        self.smoothing = smoothing
        self.n_classes = n_classes

    fit_fn = staticmethod(fit_naive_bayes)
    predict_fn = staticmethod(predict_naive_bayes)

    def fit_arrays(self, X, y, w, ctx: FitContext) -> NaiveBayesModel:
        if bool(jnp.any(X < 0)):
            raise ValueError(
                "NaiveBayes requires non-negative features (Spark parity)")
        k = n_classes_of(self, y, ctx)
        p = fit_naive_bayes(X, y, w, jnp.float32(self.smoothing), k)
        return NaiveBayesModel(np.asarray(p["log_prior"]),
                               np.asarray(p["log_theta"]))
