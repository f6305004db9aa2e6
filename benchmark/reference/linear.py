"""Elastic-net multinomial logistic regression by accelerated proximal
gradient (FISTA), float32 with every matrix product at `highest`.

Penalty reg*(alpha*|W|_1 + (1-alpha)/2*|W|_2^2), bias free. The step is
1/L with L = 0.5*1.05*lambda_max(X' diag(w) X)/sum(w) + l2 + 1e-8, the
largest eigenvalue from 16 power iterations started at the uniform unit
vector; max(200, 4*max_iter) iterations from zero."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(a, b, dtype):
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    return jnp.matmul(a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def fit_enet(X, y, w, reg: float, alpha: float, k: int, max_iter: int,
             dtype=None):
    """{"W": (d, k), "b": (k,)}. `dtype` narrows the matrix products'
    operands (the control)."""
    X = jnp.asarray(X, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    Y = jax.nn.one_hot(jnp.asarray(y).astype(jnp.int32), k, dtype=jnp.float32)
    l1, l2 = jnp.float32(reg * alpha), jnp.float32(reg * (1.0 - alpha))
    iters = max(200, 4 * int(max_iter))

    @jax.jit
    def run(X, Y, w):
        d = X.shape[1]
        wsum = jnp.maximum(w.sum(), 1.0)
        v = jnp.full((d,), 1.0 / jnp.sqrt(jnp.float32(d)), jnp.float32)

        def power(v, _):
            u = _mm(X.T, w * _mm(X, v, dtype), dtype)
            nrm = jnp.linalg.norm(u)
            return u / jnp.maximum(nrm, 1e-12), nrm

        _, norms = jax.lax.scan(power, v, None, length=16)
        L = 0.5 * 1.05 * norms[-1] / wsum + l2 + 1e-8
        step = 1.0 / L

        def grads(W, b):
            p = jax.nn.softmax(_mm(X, W, dtype) + b)
            R = (p - Y) * w[:, None]
            return _mm(X.T, R, dtype) / wsum + l2 * W, R.sum(0) / wsum

        def fista(c, _):
            W, b, Wm, bm, t = c
            gW, gb = grads(Wm, bm)
            W1 = Wm - step * gW
            W1 = jnp.sign(W1) * jnp.maximum(jnp.abs(W1) - step * l1, 0.0)
            b1 = bm - step * gb
            t1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            beta = (t - 1.0) / t1
            return (W1, b1, W1 + beta * (W1 - W), b1 + beta * (b1 - b),
                    t1), None

        W0 = jnp.zeros((d, k), jnp.float32)
        b0 = jnp.zeros((k,), jnp.float32)
        (W, b, _, _, _), _ = jax.lax.scan(
            fista, (W0, b0, W0, b0, jnp.float32(1.0)), None, length=iters)
        return W, b

    W, b = run(X, Y, w)
    return {"W": W, "b": b}


def predict(params, X) -> dict:
    logits = jnp.matmul(jnp.asarray(X, jnp.float32), params["W"],
                        precision=HI) + params["b"]
    return {"prediction": jnp.argmax(logits, -1),
            "probability": jax.nn.softmax(logits, -1)}
