"""Pallas color-step solve for the colored SN-Train engine.

One color step of the paper's Sec-3.3 parallel SOP sweep solves one
(D, D) SPD system per (field, member) lane and evaluates the fresh local
function at the member's neighborhood points:

  solve    (L L^T)^{-1} rhs by forward/back triangular substitution (the
           same substitution math as ``sn_train._tri_solve_spd``);
  GEMM     z_new = K_s @ coef_new — a local (D, D) @ (D,) contract.

Layout: the B * M independent systems sit on the LANE axis — ``chol`` and
``gram`` are (D, D, L) and ``rhs`` (D, L) with L = B*M padded to the lane
block — so every substitution step is one (D, BL) row operation across
128-lane tiles: load row i of L for all lanes, reduce over the sublane
axis, select the new row in.  The grid runs over lane blocks only, and
every block's last two dims are (D, BL): D is the whole axis, BL a
multiple of 128.

The gather of the color's messages / coefficients and the scatter of the
results stay in XLA around the kernel: the plan engine's
``sn_train._color_solve`` / ``_apply_plan`` (Mosaic on v5e lowers no
in-kernel vector gather or scatter).  dtype follows the inputs (f32, or
f64 under JAX_ENABLE_X64 in interpret mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ops import auto_interpret, fit_block

BLOCK_L = 256  # lanes per grid step: the two (D, D, 256) f32 blocks are 1.6 MiB at D = 25


def _solve_kernel(chol_ref, gram_ref, rhs_ref, coef_ref, z_ref):
    rhs = rhs_ref[...]  # (D, BL)
    d = rhs.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, rhs.shape, 0)

    # Forward substitution  L y = rhs, one row of L per step.
    y = jnp.zeros_like(rhs)
    for i in range(d):
        li = chol_ref[i]  # (D, BL) row i of every lane's L
        yi = (rhs[i:i + 1] - jnp.sum(li * y, axis=0, keepdims=True)) / li[i:i + 1]
        y = jnp.where(rows == i, yi, y)

    # Back substitution  L^T x = y, column-oriented so it reads rows of L:
    # once x_i is known, subtract its contribution L[i, :i] x_i from the
    # remaining residuals.
    res = y
    x = jnp.zeros_like(rhs)
    for i in reversed(range(d)):
        li = chol_ref[i]
        xi = res[i:i + 1] / li[i:i + 1]
        x = jnp.where(rows == i, xi, x)
        res = res - li * xi
    coef_ref[...] = x

    # f_s at the neighborhood points: z_i = sum_j K_s[i, j] x_j.
    z = jnp.zeros_like(rhs)
    for i in range(d):
        zi = jnp.sum(gram_ref[i] * x, axis=0, keepdims=True)
        z = jnp.where(rows == i, zi, z)
    z_ref[...] = z


@functools.partial(jax.jit, static_argnames=("block_l", "interpret"))
def color_step_pallas(
    chol_t: jax.Array,
    gram_t: jax.Array,
    rhs_t: jax.Array,
    *,
    block_l: int = BLOCK_L,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(coef_new, z_new), both (D, L), from lane-major chol_t / gram_t
    (D, D, L) and rhs_t (D, L).  Padded inputs required: L % block_l == 0.
    Use ``color_solve`` for the general-shape wrapper."""
    d, lanes = rhs_t.shape
    assert chol_t.shape == gram_t.shape == (d, d, lanes)
    assert lanes % block_l == 0, (lanes, block_l)
    mat = pl.BlockSpec((d, d, block_l), lambda j: (0, 0, j))
    vec = pl.BlockSpec((d, block_l), lambda j: (0, j))
    out = jax.ShapeDtypeStruct(rhs_t.shape, rhs_t.dtype)
    return pl.pallas_call(
        _solve_kernel,
        grid=(lanes // block_l,),
        in_specs=[mat, mat, vec],
        out_specs=[vec, vec],
        out_shape=[out, out],
        interpret=interpret,
    )(chol_t, gram_t, rhs_t)


def color_solve(
    chol_m: jax.Array,
    gram_m: jax.Array,
    rhs: jax.Array,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One color's local solves for all B fields: chol_m / gram_m
    (B, M, D, D), rhs (B, M, D) -> (coef_new, z_new), both (B, M, D).

    The B*M systems are moved onto the lane axis and padded to the lane
    block with inert lanes (identity factor, zero rhs), which solve to
    exact zeros and are sliced off.
    """
    b, m, d = rhs.shape
    lanes = b * m
    block_l, l_pad = fit_block(lanes, BLOCK_L)
    pad = l_pad - lanes

    def to_lanes(a):  # (B, M, ...) -> (..., L)
        a = jnp.moveaxis(a.reshape((lanes,) + a.shape[2:]), 0, -1)
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])

    chol_t = to_lanes(chol_m)
    if pad:
        inert = (jnp.arange(l_pad) >= lanes).astype(chol_t.dtype)
        chol_t = chol_t + jnp.eye(d, dtype=chol_t.dtype)[:, :, None] * inert
    coef_t, z_t = color_step_pallas(
        chol_t, to_lanes(gram_m), to_lanes(rhs),
        block_l=block_l, interpret=auto_interpret(interpret),
    )

    def from_lanes(a):  # (D, L) -> (B, M, D)
        return jnp.moveaxis(a[:, :lanes], 0, -1).reshape(b, m, d)

    return from_lanes(coef_t), from_lanes(z_t)
