"""Multinomial logistic regression, vmappable: L-BFGS for the pure-L2
penalty (`fit_logreg`), FISTA for the elastic net (`fit_logreg_enet`),
which is the path every selector's default grid takes
(elasticNetParam 0.1 and 0.5).

Reference parity: `core/.../impl/classification/OpLogisticRegression.scala`
(wrapping Spark MLlib LogisticRegression, itself L-BFGS/OWL-QN).

TPU-first: either fit is a fixed-length `lax.scan` over the full batch —
optax L-BFGS steps, or proximal-gradient steps with a Lipschitz step
from power iteration — with static shapes and no data-dependent control
flow, so the sweep engine can `vmap` it over hyperparameters and fold
masks and `pjit` the batch dimension over the mesh. bfloat16 is
deliberately NOT used for the optimizer state (convergence); X enters as
f32 and the dominant cost (X @ W, (n, d) @ (d, K), and its transpose)
hits the MXU. Neither path scales the columns: on raw columns of very
different ranges the step is set by the widest one.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from transmogrifai_tpu.models.base import (
    PredictionModel, PredictorEstimator, n_classes_of,
    resolve_init_params)
from transmogrifai_tpu.obs.trace import pull
from transmogrifai_tpu.stages.base import FitContext


def logreg_loss(params: Dict, X: jnp.ndarray, y_onehot: jnp.ndarray,
                w: jnp.ndarray, l2: jnp.ndarray) -> jnp.ndarray:
    logits = X @ params["W"] + params["b"]
    ll = optax.softmax_cross_entropy(logits, y_onehot)
    wsum = jnp.maximum(w.sum(), 1.0)
    return (ll * w).sum() / wsum + 0.5 * l2 * (params["W"] ** 2).sum()


@partial(jax.jit, static_argnames=("n_classes", "max_iter"))
def fit_logreg(X: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
               l2, n_classes: int, max_iter: int = 100,
               init_params: Optional[Dict] = None) -> Dict:
    """Pure fit: (n,d), (n,), (n,), scalar l2 → {"W": (d,k), "b": (k,)}.

    vmap over `l2` and/or `w` to sweep grids × folds in one program.

    `init_params` ({"W", "b"}) warm-starts the optimizer from existing
    weights (the continual-refit path): on barely-shifted data L-BFGS
    starts inside the basin and converges in a fraction of the cold
    iteration budget. Passed as traced arrays, so repeated warm refits
    at fixed shapes reuse ONE compiled program (retrace-asserted in
    tests); the cold (None) form keeps its own cache entry.
    """
    d = X.shape[1]
    y_onehot = jax.nn.one_hot(y.astype(jnp.int32), n_classes, dtype=jnp.float32)
    if init_params is None:
        params = {"W": jnp.zeros((d, n_classes), jnp.float32),
                  "b": jnp.zeros((n_classes,), jnp.float32)}
    else:
        params = {"W": jnp.asarray(init_params["W"], jnp.float32),
                  "b": jnp.asarray(init_params["b"], jnp.float32)}
    loss_fn = lambda p: logreg_loss(p, X, y_onehot, w, l2)  # noqa: E731
    opt = optax.lbfgs()
    state = opt.init(params)
    value_and_grad = optax.value_and_grad_from_state(loss_fn)

    def step(carry, _):
        p, s = carry
        value, grad = value_and_grad(p, state=s)
        updates, s = opt.update(grad, s, p, value=value, grad=grad,
                                value_fn=loss_fn)
        p = optax.apply_updates(p, updates)
        return (p, s), value

    (params, _), _ = jax.lax.scan(step, (params, state), None, length=max_iter)
    return params


def _power_lipschitz(X: jnp.ndarray, w: jnp.ndarray, wsum: jnp.ndarray,
                     iters: int = 16) -> jnp.ndarray:
    """λmax(Xᵀ diag(w) X)/wsum via power iteration — two MXU matmuls per
    step, fully traceable (no eigendecomposition on device)."""
    d = X.shape[1]
    v = jnp.full((d,), 1.0 / jnp.sqrt(jnp.float32(d)), X.dtype)

    def step(v, _):
        u = X.T @ (w * (X @ v))
        nrm = jnp.linalg.norm(u)
        return u / jnp.maximum(nrm, 1e-12), nrm

    _, norms = jax.lax.scan(step, v, None, length=iters)
    return norms[-1] / wsum


@partial(jax.jit, static_argnames=("n_classes", "max_iter"))
def fit_logreg_enet(X: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                    l1, l2, n_classes: int, max_iter: int = 200,
                    init_params: Optional[Dict] = None) -> Dict:
    """Elastic-net multinomial logistic regression via FISTA.

    Spark parity: MLlib LR's penalty is
    `regParam * (α·||W||₁ + (1−α)/2·||W||₂²)` solved with OWL-QN
    (`DefaultSelectorParams.scala:48` sweeps elasticNetParam {0.1, 0.5});
    callers pass `l1 = reg·α`, `l2 = reg·(1−α)`. OWL-QN's orthant
    bookkeeping maps poorly to fixed-shape XLA, so the TPU build uses
    accelerated proximal gradient (FISTA): the smooth part (weighted CE +
    L2) advances with a Lipschitz step from power iteration, and the L1
    prox is a soft-threshold — every op is dense, so the whole fit vmaps
    over (l1, l2) grid vectors and fold-weight rows like `fit_logreg`.
    Bias is unpenalized. l1 and l2 may be traced scalars.
    """
    y_onehot = jax.nn.one_hot(y.astype(jnp.int32), n_classes,
                              dtype=jnp.float32)
    d = X.shape[1]
    wsum = jnp.maximum(w.sum(), 1.0)
    # softmax-CE Hessian ≼ 0.5·XᵀWX/wsum (+ l2) — diag(p) − ppᵀ has
    # eigenvalues ≤ 1/2 (the binary-sigmoid bound 0.25 under-estimates L
    # for the multinomial loss and voids FISTA's 1/L step guarantee);
    # 1.05 head-room for the power-iteration tail
    L = 0.5 * 1.05 * _power_lipschitz(X, w, wsum) + l2 + 1e-8
    step = 1.0 / L

    def smooth_grads(W, b):
        p = jax.nn.softmax(X @ W + b)
        R = (p - y_onehot) * w[:, None]        # (n, k) weighted residual
        return X.T @ R / wsum + l2 * W, R.sum(0) / wsum

    def fista_step(carry, _):
        W, b, Wm, bm, t = carry
        gW, gb = smooth_grads(Wm, bm)
        W1 = Wm - step * gW
        W1 = jnp.sign(W1) * jnp.maximum(jnp.abs(W1) - step * l1, 0.0)
        b1 = bm - step * gb
        t1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        beta = (t - 1.0) / t1
        return (W1, b1, W1 + beta * (W1 - W), b1 + beta * (b1 - b), t1), None

    if init_params is None:
        W0 = jnp.zeros((d, n_classes), jnp.float32)
        b0 = jnp.zeros((n_classes,), jnp.float32)
    else:  # warm start: FISTA momentum restarts from the given weights
        W0 = jnp.asarray(init_params["W"], jnp.float32)
        b0 = jnp.asarray(init_params["b"], jnp.float32)
    with jax.named_scope("linear:fista"):
        (W, b, _, _, _), _ = jax.lax.scan(
            fista_step, (W0, b0, W0, b0, jnp.float32(1.0)), None,
            length=max_iter)
    return {"W": W, "b": b}


def predict_logreg(params: Dict, X: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    logits = X @ params["W"] + params["b"]
    prob = jax.nn.softmax(logits, axis=-1)
    return {
        "prediction": jnp.argmax(logits, axis=-1).astype(jnp.float32),
        "rawPrediction": logits,
        "probability": prob,
    }


class LogisticRegressionModel(PredictionModel):
    def __init__(self, W=None, b=None, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.W = np.asarray(W, dtype=np.float32)
        self.b = np.asarray(b, dtype=np.float32)

    def predict_arrays(self, X):
        return predict_logreg({"W": jnp.asarray(self.W), "b": jnp.asarray(self.b)}, X)

    # parameter lifting: see LinearRegressionModel — weights are traced
    # jit arguments, so same-shaped LR tenants share one bucket program
    def device_constants(self):
        return {"W": jnp.asarray(self.W), "b": jnp.asarray(self.b)}

    def device_apply_with(self, consts, enc, dev):
        return predict_logreg(consts, jnp.asarray(dev[-1]))

    def signature_params(self):
        return {}

    def narrow_device_constants(self, consts):
        return {"W": consts["W"].astype(jnp.bfloat16), "b": consts["b"]}

    def get_params(self):
        return {"W": self.W.tolist(), "b": self.b.tolist()}


def enet_iters(max_iter: int) -> int:
    """FISTA iteration budget for an L-BFGS-equivalent `max_iter`: first-
    order prox steps need more iterations than quasi-Newton ones to reach
    the same region (O(1/k²) vs superlinear), so the elastic-net path runs
    4× the L-BFGS budget with a floor of 200."""
    return max(200, 4 * int(max_iter))


class OpLogisticRegression(PredictorEstimator):
    """Grid-sweepable hyperparams: reg_param, elastic_net_param, max_iter.

    Spark parity (`OpLogisticRegression.scala`, elasticNetParam): the
    penalty is `reg_param * (α·L1 + (1−α)/2·L2)`; α = 0 keeps the pure-L2
    L-BFGS path, α > 0 switches to the FISTA elastic-net fit."""

    def __init__(self, reg_param: float = 0.0, max_iter: int = 100,
                 elastic_net_param: float = 0.0,
                 n_classes: Optional[int] = None, uid: Optional[str] = None):
        super().__init__(uid=uid, reg_param=reg_param, max_iter=max_iter,
                         elastic_net_param=elastic_net_param,
                         n_classes=n_classes)
        self.reg_param = reg_param
        self.max_iter = max_iter
        self.elastic_net_param = elastic_net_param
        self.n_classes = n_classes

    # pure fns exposed for the sweep engine
    fit_fn = staticmethod(fit_logreg)
    predict_fn = staticmethod(predict_logreg)

    def fit_arrays(self, X, y, w, ctx: FitContext,
                   init_params: Optional[Dict] = None
                   ) -> LogisticRegressionModel:
        k = n_classes_of(self, y, ctx)
        warm = resolve_init_params(self, init_params,
                                   {"W": (X.shape[1], k), "b": (k,)})
        alpha = float(self.elastic_net_param)
        if alpha > 0.0:
            params = fit_logreg_enet(
                X, y, w, jnp.float32(self.reg_param * alpha),
                jnp.float32(self.reg_param * (1.0 - alpha)), k,
                enet_iters(self.max_iter), init_params=warm)
        else:
            params = fit_logreg(X, y, w, jnp.float32(self.reg_param), k,
                                self.max_iter, init_params=warm)
        params = pull("fit:params", {"W": params["W"], "b": params["b"]})
        return LogisticRegressionModel(np.asarray(params["W"]),
                                       np.asarray(params["b"]))
