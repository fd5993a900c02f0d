"""Positive (semi-)definite kernels and Gram-matrix helpers.

The paper (Sec. 2.2) anchors everything in an RKHS ``H_K`` induced by a
positive semi-definite kernel ``K``.  Its experiments use the linear kernel
(Case 1) and the Gaussian/RBF kernel (Case 2); we additionally provide
Matern-3/2 and polynomial kernels, which are common field-estimation choices.

All functions are pure jnp and jit/vmap-safe.  ``X`` arrays are ``(n, d)``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp


def pairwise_sq_dists(x1: jax.Array, x2: jax.Array) -> jax.Array:
    """Squared Euclidean distances, shape (n1, n2).

    Uses the expanded form so it lowers to two matmuls (MXU-friendly) rather
    than an (n1, n2, d) broadcast.  Every matmul in this module asks for
    ``precision="highest"``: on a TPU the default is one bf16 pass, whose
    rounding of the cross term is as large as a neighborhood's squared
    distances; on a CPU the flag changes nothing.
    """
    x1 = jnp.atleast_2d(x1)
    x2 = jnp.atleast_2d(x2)
    sq1 = jnp.sum(x1 * x1, axis=-1)[:, None]
    sq2 = jnp.sum(x2 * x2, axis=-1)[None, :]
    cross = jnp.matmul(x1, x2.T, precision="highest")
    return jnp.maximum(sq1 + sq2 - 2.0 * cross, 0.0)


def linear_kernel(x1: jax.Array, x2: jax.Array, *, bias: float = 1.0) -> jax.Array:
    """K(x, x') = x.x' + bias.

    The affine bias term lets the RKHS contain constant offsets, matching the
    paper's Case 1 target eta(x) = 5x + 5 (a pure linear kernel could not
    represent the intercept).
    """
    x1 = jnp.atleast_2d(x1)
    x2 = jnp.atleast_2d(x2)
    return jnp.matmul(x1, x2.T, precision="highest") + bias


def rbf_kernel(x1: jax.Array, x2: jax.Array, *, gamma: float = 1.0) -> jax.Array:
    """Gaussian kernel K(x, x') = exp(-gamma * ||x - x'||^2) (paper Example 2)."""
    return jnp.exp(-gamma * pairwise_sq_dists(x1, x2))


def matern32_kernel(x1: jax.Array, x2: jax.Array, *, length: float = 1.0) -> jax.Array:
    """Matern nu=3/2: (1 + sqrt(3) r / l) exp(-sqrt(3) r / l)."""
    r = jnp.sqrt(pairwise_sq_dists(x1, x2) + 1e-12)
    s = jnp.sqrt(3.0) * r / length
    return (1.0 + s) * jnp.exp(-s)


def poly_kernel(
    x1: jax.Array, x2: jax.Array, *, degree: int = 2, bias: float = 1.0
) -> jax.Array:
    x1 = jnp.atleast_2d(x1)
    x2 = jnp.atleast_2d(x2)
    return (jnp.matmul(x1, x2.T, precision="highest") + bias) ** degree


_REGISTRY: dict[str, Callable[..., jax.Array]] = {
    "linear": linear_kernel,
    "rbf": rbf_kernel,
    "matern32": matern32_kernel,
    "poly": poly_kernel,
}


@dataclasses.dataclass(frozen=True)
class Kernel:
    """A named kernel + hyperparameters; hashable so it is a static jit arg."""

    name: str = "rbf"
    gamma: float = 1.0  # rbf
    bias: float = 1.0  # linear / poly
    length: float = 1.0  # matern32
    degree: int = 2  # poly

    def __call__(self, x1: jax.Array, x2: jax.Array) -> jax.Array:
        fn = _REGISTRY[self.name]
        if self.name == "rbf":
            return fn(x1, x2, gamma=self.gamma)
        if self.name == "linear":
            return fn(x1, x2, bias=self.bias)
        if self.name == "matern32":
            return fn(x1, x2, length=self.length)
        if self.name == "poly":
            return fn(x1, x2, degree=self.degree, bias=self.bias)
        raise KeyError(self.name)

    def gram(self, x: jax.Array) -> jax.Array:
        """Full (n, n) Gram matrix K(x_i, x_j)."""
        return self(x, x)


@partial(jax.jit, static_argnames=("kernel",))
def gram_matrix(kernel: Kernel, x1: jax.Array, x2: jax.Array) -> jax.Array:
    return kernel(x1, x2)
