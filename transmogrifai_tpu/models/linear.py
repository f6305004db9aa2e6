"""Ridge / OLS linear regression via normal equations.

Reference parity: `core/.../impl/regression/OpLinearRegression.scala`
(Spark MLlib LinearRegression, "normal"/"l-bfgs" solvers).

TPU-first: closed-form (XᵀX + λI)β = Xᵀy with a Cholesky-backed solve —
XᵀX is one MXU matmul, shardable over the data axis with a `psum`, and the
whole fit vmaps over the λ grid and fold masks.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu.models.base import (
    PredictionModel, PredictorEstimator, resolve_init_params)
from transmogrifai_tpu.obs.trace import pull
from transmogrifai_tpu.stages.base import FitContext


@jax.jit
def fit_linreg(X: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray, l2) -> Dict:
    """Weighted ridge: returns {"beta": (d,), "intercept": ()}."""
    wsum = jnp.maximum(w.sum(), 1.0)
    x_mean = (X * w[:, None]).sum(0) / wsum
    y_mean = (y * w).sum() / wsum
    Xc = (X - x_mean) * jnp.sqrt(w)[:, None]
    yc = (y - y_mean) * jnp.sqrt(w)
    d = X.shape[1]
    gram = Xc.T @ Xc / wsum
    # adaptive jitter keeps the solve well-posed when columns are constant
    # (e.g. an all-zero null-indicator) and l2 == 0
    eps = 1e-6 * (jnp.trace(gram) / d + 1.0)
    gram = gram + (l2 + eps) * jnp.eye(d, dtype=X.dtype)
    rhs = Xc.T @ yc / wsum
    beta = jax.scipy.linalg.solve(gram, rhs, assume_a="pos")
    intercept = y_mean - x_mean @ beta
    return {"beta": beta, "intercept": intercept}


@partial(jax.jit, static_argnames=("max_iter",))
def fit_linreg_enet(X: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                    l1, l2, max_iter: int = 300,
                    init_params: Optional[Dict] = None) -> Dict:
    """Elastic-net weighted least squares via FISTA on centered data.

    Spark parity: MLlib LinearRegression with elasticNetParam > 0 (OWL-QN);
    callers pass `l1 = reg·α`, `l2 = reg·(1−α)`. Smooth part
    `0.5/wsum·Σ w(Xcβ − yc)² + 0.5·l2·||β||²` advances with a
    power-iteration Lipschitz step; the intercept comes from the centering
    identity (ȳ − x̄·β), exactly like `fit_linreg`. l1/l2 may be traced,
    so grids vmap."""
    from transmogrifai_tpu.models.logistic import _power_lipschitz
    wsum = jnp.maximum(w.sum(), 1.0)
    x_mean = (X * w[:, None]).sum(0) / wsum
    y_mean = (y * w).sum() / wsum
    Xc = X - x_mean
    yc = y - y_mean
    L = 1.05 * _power_lipschitz(Xc * jnp.sqrt(w)[:, None],
                                jnp.ones_like(w), wsum) + l2 + 1e-8
    step = 1.0 / L

    def fista_step(carry, _):
        b, bm, t = carry
        r = (Xc @ bm - yc) * w
        g = Xc.T @ r / wsum + l2 * bm
        b1 = bm - step * g
        b1 = jnp.sign(b1) * jnp.maximum(jnp.abs(b1) - step * l1, 0.0)
        t1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        return (b1, b1 + (t - 1.0) / t1 * (b1 - b), t1), None

    if init_params is None:
        b0 = jnp.zeros((X.shape[1],), jnp.float32)
    else:  # warm start from existing coefficients (continual refit)
        b0 = jnp.asarray(init_params["beta"], jnp.float32)
    with jax.named_scope("linear:fista"):
        (beta, _, _), _ = jax.lax.scan(
            fista_step, (b0, b0, jnp.float32(1.0)), None, length=max_iter)
    return {"beta": beta, "intercept": y_mean - x_mean @ beta}


def predict_linreg(params: Dict, X: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    pred = X @ params["beta"] + params["intercept"]
    return {
        "prediction": pred,
        "rawPrediction": pred[:, None],
        "probability": jnp.zeros((X.shape[0], 0), X.dtype),
    }


class LinearRegressionModel(PredictionModel):
    def __init__(self, beta=None, intercept: float = 0.0,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.beta = np.asarray(beta, dtype=np.float32)
        self.intercept = float(intercept)

    def predict_arrays(self, X):
        return predict_linreg(
            {"beta": jnp.asarray(self.beta),
             "intercept": jnp.float32(self.intercept)}, X)

    # parameter lifting (serving/fleet.py): fitted weights flow into the
    # compiled scorer as traced jit ARGUMENTS, so every same-shaped
    # linear tenant in a fleet shares ONE compiled program and a
    # tenant's resident HBM cost is its parameters, not a program copy
    def device_constants(self):
        return {"beta": jnp.asarray(self.beta),
                "intercept": jnp.float32(self.intercept)}

    def device_apply_with(self, consts, enc, dev):
        return predict_linreg(consts, jnp.asarray(dev[-1]))

    def signature_params(self):
        return {}  # all fitted state is lifted; shapes key via consts

    def narrow_device_constants(self, consts):
        # memory-bound predict: bf16 weights halve the table read; the
        # matmul accumulates in f32 (~0.4% relative weight error, the
        # same documented tradeoff as the GBT bf16 histograms)
        return {"beta": consts["beta"].astype(jnp.bfloat16),
                "intercept": consts["intercept"]}

    def get_params(self):
        return {"beta": self.beta.tolist(), "intercept": self.intercept}


class OpLinearRegression(PredictorEstimator):
    """elastic_net_param > 0 blends L1 into the penalty
    (Spark `LinearRegression.elasticNetParam`) and switches the closed-form
    ridge solve for the FISTA elastic-net fit."""

    def __init__(self, reg_param: float = 0.0,
                 elastic_net_param: float = 0.0, uid: Optional[str] = None):
        super().__init__(uid=uid, reg_param=reg_param,
                         elastic_net_param=elastic_net_param)
        self.reg_param = reg_param
        self.elastic_net_param = elastic_net_param

    fit_fn = staticmethod(fit_linreg)
    predict_fn = staticmethod(predict_linreg)

    def fit_arrays(self, X, y, w, ctx: FitContext,
                   init_params: Optional[Dict] = None
                   ) -> LinearRegressionModel:
        alpha = float(self.elastic_net_param)
        if alpha > 0.0:
            warm = resolve_init_params(self, init_params,
                                       {"beta": (X.shape[1],)})
            p = fit_linreg_enet(X, y, w,
                                jnp.float32(self.reg_param * alpha),
                                jnp.float32(self.reg_param * (1.0 - alpha)),
                                init_params=warm)
        else:
            # closed-form ridge: the solve is exact, so a warm start has
            # nothing to continue from — init_params is accepted (the
            # continual refitter treats every family uniformly) and
            # harmlessly ignored
            p = fit_linreg(X, y, w, jnp.float32(self.reg_param))
        p = pull("fit:params", {"beta": p["beta"],
                                "intercept": p["intercept"]})
        return LinearRegressionModel(np.asarray(p["beta"]),
                                     float(p["intercept"]))
