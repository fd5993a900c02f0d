"""Centralized regularized kernel least-squares regression (paper Sec. 2.2).

The fusion-center baseline the paper compares against:

    min_{f in H_K}  sum_i (f(x_i) - y_i)^2 + lambda ||f||^2      (Eq. 4/10)
    c = (K + lambda I)^{-1} y                                    (Eq. 6)
    f(x) = sum_i c_i K(x, x_i)                                   (Eq. 5)

Solved with a Cholesky factorization (K + lambda I is SPD for lambda > 0).
Prediction can optionally route through the Pallas fused kernel-matvec
(`repro.kernels.ops.kernel_matvec`) — the testing-phase hot spot.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from .kernels_math import Kernel


@dataclasses.dataclass(frozen=True)
class KRRModel:
    """A fit regularized kernel least-squares model."""

    anchors: jax.Array  # (n, d) training inputs
    coef: jax.Array  # (n,)  representer coefficients c
    kernel: Kernel


@partial(jax.jit, static_argnames=("kernel",))
def _fit(kernel: Kernel, x: jax.Array, y: jax.Array, lam: jax.Array) -> jax.Array:
    n = x.shape[0]
    k = kernel(x, x)
    chol = jsl.cho_factor(k + lam * jnp.eye(n, dtype=k.dtype))
    return jsl.cho_solve(chol, y)


def fit_krr(
    x: jax.Array, y: jax.Array, kernel: Kernel, lam: float, *, dtype=jnp.float32
) -> KRRModel:
    """Train: compute c_lambda = (K + lambda I)^{-1} y (paper Eq. 6).

    Pass dtype=jnp.float64 (with x64 enabled) when lam is tiny relative to
    the Gram spectrum — same conditioning caveat as SN-Train.
    """
    x = jnp.atleast_2d(jnp.asarray(x, dtype))
    y = jnp.asarray(y, dtype)
    coef = _fit(kernel, x, y, jnp.asarray(lam, dtype))
    return KRRModel(anchors=x, coef=coef, kernel=kernel)


@partial(jax.jit, static_argnames=("kernel",))
def _predict(kernel: Kernel, anchors, coef, xq) -> jax.Array:
    return jnp.matmul(kernel(xq, anchors), coef, precision="highest")


def predict(model: KRRModel, xq: jax.Array, *, use_pallas: bool = False) -> jax.Array:
    """Test: f(x) = sum_i c_i K(x, x_i) for a batch of queries (Q, d)."""
    xq = jnp.atleast_2d(jnp.asarray(xq, model.anchors.dtype))
    if use_pallas and model.kernel.name == "rbf":
        from repro.kernels.ops import kernel_matvec

        return kernel_matvec(xq, model.anchors, model.coef, gamma=model.kernel.gamma)
    return _predict(model.kernel, model.anchors, model.coef, xq)


def mse(model: KRRModel, xq: jax.Array, yq: jax.Array, **kw) -> jax.Array:
    pred = predict(model, xq, **kw)
    return jnp.mean((pred - jnp.asarray(yq)) ** 2)
