"""The regression selector's three families and its metrics, plain: the
elastic-net least-squares fit, the regression tree of a forest, the
squared-loss boosted chain, and RMSE, MAE and R2 in float64. New beside
`linear.py`, `trees.py` and `metrics.py`, whose functions it calls
(`trees.grow`, `trees.forest_bootstrap`, `trees.walk`); it imports
nothing of the program.

- `fit_enet`: weighted least squares under reg*(alpha*|b|_1 +
  (1-alpha)/2*|b|_2^2) by accelerated proximal gradient (FISTA) on
  CENTRED data (weighted means of every column and of the target taken
  out; the intercept is mean(y) - mean(x).b). The step is 1/L with
  L = 1.05*lambda_max(Xc' diag(w) Xc)/sum(w) + l2 + 1e-8, the largest
  eigenvalue from 16 power iterations started at the uniform unit
  vector; `iters` iterations from zero. `dtype` narrows the operands of
  every matrix product (exact products of them, float32 sums): what a
  chip's default precision does to a float32 product, or the control.
- `forest_fold`: each tree grown from G = y*boot, H = boot under the
  documented bootstrap; the prediction is the mean of the trees' leaves.
- `boosted_fold`: the chain starts at the weighted mean of the target;
  a round's tree is grown from G = (y - margin)*w, H = w (the gradient
  of the squared loss is margin - y, the tree takes its negative) and
  the margin takes `learning_rate` times the tree's leaves, every round.

`quant` narrows a tree's histogram values and `leaf_quant` its leaf sums
(`trees.grow`). `clip` is for a narrowing that SATURATES: the values (a
product's operands in the linear fit) are
clipped into [-clip, clip] first (fp8 has no infinity, and a cast past
its largest finite number, 448, gives NaN: a NaN tree says nothing about
a precision step).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference import trees as ref_trees
from reference.linear import _mm

ENET_ITERS = 300        # the least-squares fit's documented budget


def _products(dtype, clip: Optional[float]):
    """`mm(a, b)`: the product with both operands narrowed to `dtype`
    (clipped into [-clip, clip] first where the narrowing saturates)."""
    def mm(a, b):
        if clip is not None:
            a, b = jnp.clip(a, -clip, clip), jnp.clip(b, -clip, clip)
        return _mm(a, b, dtype)
    return mm


def fit_enet(X, y, w, reg: float, alpha: float, iters: int = ENET_ITERS,
             dtype=None, clip: Optional[float] = None) -> Dict:
    """{"beta": (d,), "intercept": ()}."""
    mm = _products(dtype, clip)
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    l1, l2 = jnp.float32(reg * alpha), jnp.float32(reg * (1.0 - alpha))

    @jax.jit
    def run(X, y, w):
        d = X.shape[1]
        wsum = jnp.maximum(w.sum(), 1.0)
        x_mean = (X * w[:, None]).sum(0) / wsum
        y_mean = (y * w).sum() / wsum
        Xc, yc = X - x_mean, y - y_mean
        Xw = Xc * jnp.sqrt(w)[:, None]
        v = jnp.full((d,), 1.0 / jnp.sqrt(jnp.float32(d)), jnp.float32)

        def power(v, _):
            u = mm(Xw.T, mm(Xw, v))
            nrm = jnp.linalg.norm(u)
            return u / jnp.maximum(nrm, 1e-12), nrm

        _, norms = jax.lax.scan(power, v, None, length=16)
        step = 1.0 / (1.05 * norms[-1] / wsum + l2 + 1e-8)

        def fista(c, _):
            b, bm, t = c
            r = (mm(Xc, bm) - yc) * w
            g = mm(Xc.T, r) / wsum + l2 * bm
            b1 = bm - step * g
            b1 = jnp.sign(b1) * jnp.maximum(jnp.abs(b1) - step * l1, 0.0)
            t1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            return (b1, b1 + (t - 1.0) / t1 * (b1 - b), t1), None

        b0 = jnp.zeros((d,), jnp.float32)
        (beta, _, _), _ = jax.lax.scan(
            fista, (b0, b0, jnp.float32(1.0)), None, length=int(iters))
        return beta, y_mean - mm(x_mean, beta)

    beta, intercept = run(X, y, w)
    return {"beta": beta, "intercept": intercept}


def predict_linear(params: Dict, X, dtype=None,
                   clip: Optional[float] = None) -> jnp.ndarray:
    return _products(dtype, clip)(
        jnp.asarray(X, jnp.float32), params["beta"]) + params["intercept"]


def _clipped(G, clip: Optional[float]):
    return G if clip is None else jnp.clip(G, -clip, clip)


def forest_targets(y, boot):
    """(G (n, 1), H (n,)) of a regression tree: y*boot and boot."""
    return (y * boot)[:, None], boot


def forest_fold(Xb, y, w, n_trees: int, depth: int, n_bins: int,
                fit_seed: int, subsample_features: bool, args: Dict,
                quant: Optional[str] = None,
                leaf_quant: Optional[str] = None,
                clip: Optional[float] = None) -> Dict:
    """The trees of a regression forest under the row weights `w`:
    {"feat", "bin", "leaf"} stacked over the trees."""
    n, d = Xb.shape
    yj, wj = jnp.asarray(y, jnp.float32), jnp.asarray(w, jnp.float32)
    grown = {"feat": [], "bin": [], "leaf": []}
    for t in range(n_trees):
        boot, fmask = ref_trees.forest_bootstrap(
            fit_seed, n_trees, t, n, d, subsample_features)
        G, H = forest_targets(yj, boot * wj)
        tree = ref_trees.grow(Xb, _clipped(G, clip), H, depth, n_bins,
                              fmask=fmask, quant=quant,
                              leaf_quant=leaf_quant, **args)
        for key in grown:
            grown[key].append(tree[key])
    return {key: np.stack(v) for key, v in grown.items()}


def forest_predict(trees: Dict, Xb) -> jnp.ndarray:
    """(n,) mean of the trees' leaf values."""
    n_trees = np.asarray(trees["feat"]).shape[0]
    acc = jnp.zeros(Xb.shape[0], jnp.float32)
    for t in range(n_trees):
        tree = {k: np.asarray(v)[t] for k, v in trees.items()}
        acc = acc + ref_trees.leaf_values(
            tree, ref_trees.walk(tree, Xb))[:, 0]
    return acc / n_trees


def base_score(y, w) -> jnp.ndarray:
    """Where a squared-loss chain starts: the weighted mean of the target."""
    y, w = jnp.asarray(y, jnp.float32), jnp.asarray(w, jnp.float32)
    return (y * w).sum() / jnp.maximum(w.sum(), 1e-12)


def boosted_targets(margin, y, w):
    """(G (n, 1), H (n,)) of a squared-loss round: the negative gradient
    (y - margin)*w and the hessian w."""
    return ((y - margin) * w)[:, None], w


def boosted_fold(Xb, y, w, rounds: int, depth: int, n_bins: int,
                 learning_rate: float, args: Dict,
                 quant: Optional[str] = None,
                 leaf_quant: Optional[str] = None,
                 clip: Optional[float] = None):
    """(trees stacked over the rounds, (n,) final margin, base score)."""
    yj, wj = jnp.asarray(y, jnp.float32), jnp.asarray(w, jnp.float32)
    base = base_score(yj, wj)
    margin = jnp.full(Xb.shape[0], base, jnp.float32)
    grown = {"feat": [], "bin": [], "leaf": []}
    for _ in range(rounds):
        G, H = boosted_targets(margin, yj, wj)
        tree = ref_trees.grow(Xb, _clipped(G, clip), H, depth, n_bins,
                              quant=quant, leaf_quant=leaf_quant, **args)
        margin = margin + jnp.float32(learning_rate) * ref_trees.leaf_values(
            tree, ref_trees.walk(tree, Xb))[:, 0]
        for key in grown:
            grown[key].append(tree[key])
    return ({key: np.stack(v) for key, v in grown.items()}, margin,
            float(base))


def boosted_predict(trees: Dict, Xb, learning_rate: float,
                    base: float) -> jnp.ndarray:
    return jnp.float32(base) + ref_trees.gbt_margin(trees, Xb, learning_rate)


def metrics(y, pred) -> Dict[str, float]:
    """RMSE, MSE, MAE and R2 in float64 (OpRegressionEvaluator)."""
    y = np.asarray(y, np.float64).ravel()
    p = np.asarray(pred, np.float64).ravel()
    err = p - y
    mse = float(np.mean(err ** 2))
    ss_tot = float(((y - y.mean()) ** 2).sum())
    return {"RMSE": float(np.sqrt(mse)), "MSE": mse,
            "MAE": float(np.mean(np.abs(err))),
            "R2": 1.0 - float((err ** 2).sum()) / ss_tot if ss_tot > 0
            else 0.0}


def validation_metric(name: str, y, pred) -> float:
    if name not in ("RMSE", "MSE", "MAE", "R2"):
        raise ValueError(f"no regression reference for the metric {name!r}")
    return metrics(y, pred)[name]
