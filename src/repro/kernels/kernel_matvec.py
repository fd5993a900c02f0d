"""Fused RBF kernel-matvec Pallas kernel — the paper's testing-phase hot spot.

Computes  out[b, q] = sum_j coef[b, j] * exp(-gamma * ||xq[q] - anchors[b, j]||^2)
for B kernel expansions against one shared query grid, without
materializing the (Q, N) Gram matrix in HBM.

FlashAttention-style streaming.  Per grid step (b, i, j) one (BQ, BN)
tile of squared distances is built on the VPU from the (BQ, d) query
block and the lane-dense (d, BN) anchor block (the direct sum of d
coordinate differences, exact in f32), exponentiated, and contracted
against the (1, BN) coefficient row on the MXU into the lane-dense
(1, BQ) output block, which stays resident in VMEM across the anchor
axis.  HBM traffic is O(Q + B*N) instead of O(B*Q*N).

Layout (what Mosaic accepts on v5e): the field axis is a squeezed block
dim and the anchor and query axes are the lane axes of their blocks —
anchors are stored (B, d, N), never (B, N, d), whose two-wide minor dim
would pad 64x in HBM.  BN and BQ are multiples of 128 or the whole
padded axis (``ops.kernel_matvec`` picks them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(xq_ref, an_ref, coef_ref, out_ref, *, gamma: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xq = xq_ref[...]  # (BQ, d)
    an = an_ref[...]  # (d, BN)
    d2 = jnp.zeros((xq.shape[0], an.shape[1]), jnp.float32)
    for c in range(an.shape[0]):
        diff = xq[:, c:c + 1] - an[c:c + 1, :]
        d2 = d2 + diff * diff
    k = jnp.exp(-gamma * d2)  # (BQ, BN)
    out_ref[...] += jax.lax.dot_general(
        coef_ref[...], k, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (1, BQ) on the MXU


@functools.partial(
    jax.jit, static_argnames=("gamma", "block_q", "block_n", "interpret")
)
def kernel_matvec_pallas(
    xq: jax.Array,
    anchors: jax.Array,
    coef: jax.Array,
    *,
    gamma: float = 1.0,
    block_q: int = 128,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """out (B, 1, Q) from xq (Q, d), anchors (B, d, N), coef (B, 1, N), all f32.

    Padded inputs required: Q % block_q == 0, N % block_n == 0.  Use
    ``repro.kernels.ops.kernel_matvec`` for the general-shape wrapper.
    """
    q, d = xq.shape
    b, _, n = anchors.shape
    assert coef.shape == (b, 1, n), (coef.shape, b, n)
    assert q % block_q == 0 and n % block_n == 0, (q, n, block_q, block_n)
    return pl.pallas_call(
        functools.partial(_kernel, gamma=gamma),
        grid=(b, q // block_q, n // block_n),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda b, i, j: (i, 0)),
            pl.BlockSpec((None, d, block_n), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((None, 1, block_n), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, 1, q), jnp.float32),
        interpret=interpret,
    )(xq, anchors, coef)
