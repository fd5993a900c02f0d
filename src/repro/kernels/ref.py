"""Pure-jnp oracles for the Pallas kernels (ground truth in tests/benches).

Matmuls ask for ``precision="highest"`` so the oracles stay f32-exact on
a TPU, where the default is one bf16 pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _sq_dists(x1: jax.Array, x2: jax.Array) -> jax.Array:
    sq1 = jnp.sum(x1 * x1, axis=-1)[:, None]
    sq2 = jnp.sum(x2 * x2, axis=-1)[None, :]
    cross = jnp.matmul(x1, x2.T, precision="highest")
    return jnp.maximum(sq1 + sq2 - 2.0 * cross, 0.0)


def rbf_gram_ref(x1: jax.Array, x2: jax.Array, gamma: float) -> jax.Array:
    """(M, N) Gaussian Gram matrix K(x1_i, x2_j) = exp(-gamma ||.||^2)."""
    return jnp.exp(-gamma * _sq_dists(x1, x2))


def kernel_matvec_ref(
    xq: jax.Array, anchors: jax.Array, coef: jax.Array, gamma: float
) -> jax.Array:
    """f(xq_i) = sum_j coef_j exp(-gamma ||xq_i - anchors_j||^2), shape (Q,).

    Materializes the full (Q, N) Gram matrix — the thing the Pallas kernel
    avoids doing in HBM.
    """
    return jnp.matmul(rbf_gram_ref(xq, anchors, gamma), coef, precision="highest")


def kernel_matvec_batched_ref(
    xq: jax.Array, anchors: jax.Array, coef: jax.Array, gamma: float
) -> jax.Array:
    """Multi-field oracle: out[b, q] = sum_j coef[b, j] K(xq[q], anchors[b, j]).

    anchors: (B, N, d) per-field anchor sets; coef: (B, N).  Materializes the
    full (B, Q, N) Gram tensor the batched Pallas kernel streams through VMEM.
    """
    return jax.vmap(lambda an, c: kernel_matvec_ref(xq, an, c, gamma))(anchors, coef)


def local_batched_solve_ref(
    gram: jax.Array, lam: jax.Array, rhs: jax.Array, mask: jax.Array
) -> jax.Array:
    """Batched masked (K_s + lambda_s I)^{-1} rhs — SN-Train Eq. 18 oracle.

    gram: (B, D, D) masked local Gram blocks; lam: (B,); rhs: (B, D);
    mask: (B, D) neighborhood validity.
    """
    diag = jnp.where(mask, lam[:, None], 1.0)
    a = gram + jax.vmap(jnp.diag)(diag)
    rhs = jnp.where(mask, rhs, 0.0)
    return jnp.linalg.solve(a, rhs[..., None])[..., 0]
