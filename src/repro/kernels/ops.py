"""Jit'd general-shape wrappers around the Pallas kernels.

These pad to block multiples, pick block shapes Mosaic accepts, run the
kernels in interpret mode only where ``auto_interpret`` says so (the CPU
test path), and slice results back to the caller's shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .gram import rbf_gram_pallas
from .kernel_matvec import kernel_matvec_pallas


def auto_interpret(interpret: bool | None = None) -> bool:
    """The one interpret-mode switch of every kernel wrapper.

    ``None`` means: compiled on a TPU, interpreted anywhere else (the CPU
    test path).  On a TPU backend this is always False, so no kernel on
    the chip path runs interpreted.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def fit_block(size: int, block: int, align: int = 128) -> tuple[int, int]:
    """(block, padded size) for one blocked axis that Mosaic accepts.

    An axis that fits in one block is one whole-axis block; otherwise the
    block is rounded up to an ``align`` multiple (the lane width for the
    last block dim) and the axis is padded to a block multiple.
    """
    if size <= block:
        return size, size
    block = -(-block // align) * align
    return block, -(-size // block) * block


def _pad_dim(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    return _pad_dim(x, 0, mult)


def bucket_rows(q: int, min_rows: int = 8) -> int:
    """Round a row count up to its power-of-two bucket (min ``min_rows``).

    A serving process sees many distinct request sizes; padding each query
    grid to the next power of two means the padded shape — and therefore
    the lowered Pallas program — takes O(log Q) distinct values instead of
    one fresh compile per size (tests/test_serving.py counts the programs
    via the jit cache).  Padded rows are exact: they carry zeros and are
    sliced off by the callers.
    """
    return 1 << max(q - 1, min_rows - 1).bit_length()


def kernel_matvec(
    xq: jax.Array,
    anchors: jax.Array,
    coef: jax.Array,
    *,
    gamma: float = 1.0,
    block_q: int = 128,
    block_n: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """f(xq) = sum_j coef_j exp(-gamma ||xq - x_j||^2) for arbitrary shapes.

    Multi-field batching: pass coef as (B, N) — and optionally anchors as
    (B, N, d) for per-field anchor sets (streaming problems) — to evaluate B
    kernel expansions against one shared query grid in a single fused Pallas
    launch; returns (B, Q).  Single-field (N,) coef returns (Q,) through
    the same kernel with B = 1.

    Padding is exact: padded anchors carry coef 0 (zero contribution) and
    padded query rows are sliced off.  The query axis is padded to its
    power-of-two bucket (``bucket_rows``), so varied request sizes against
    one anchor set lower O(log Q) distinct programs, not O(#sizes).
    """
    q = xq.shape[0]
    coef = jnp.asarray(coef, jnp.float32)
    anchors = jnp.asarray(anchors, jnp.float32)
    single = coef.ndim == 1
    if single:
        coef, anchors = coef[None], anchors[None]
    b, n = coef.shape
    if anchors.ndim == 2:
        anchors = jnp.broadcast_to(anchors[None], (b,) + anchors.shape)
    block_q, q_pad = fit_block(bucket_rows(q), block_q)
    block_n, n_pad = fit_block(n, block_n)
    out = kernel_matvec_pallas(
        _pad_rows(jnp.asarray(xq, jnp.float32), q_pad),
        jnp.swapaxes(_pad_dim(anchors, 1, n_pad), 1, 2),
        _pad_dim(coef, 1, n_pad)[:, None, :],
        gamma=gamma,
        block_q=block_q,
        block_n=block_n,
        interpret=auto_interpret(interpret),
    )[:, 0, :q]
    return out[0] if single else out


def rbf_gram(
    x1: jax.Array,
    x2: jax.Array,
    *,
    gamma: float = 1.0,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    m, n = x1.shape[0], x2.shape[0]
    block_m = min(block_m, max(8, m))
    block_n = min(block_n, max(8, n))
    x1_p = _pad_rows(jnp.asarray(x1, jnp.float32), block_m)
    x2_p = _pad_rows(jnp.asarray(x2, jnp.float32), block_n)
    out = rbf_gram_pallas(
        x1_p,
        x2_p,
        gamma=gamma,
        block_m=block_m,
        block_n=block_n,
        interpret=auto_interpret(interpret),
    )
    return out[:m, :n]


def ssd_chunked_fused(
    x, dt, a, bmat, cmat, chunk: int, h0=None, *,
    block_h: int = 8, interpret: bool | None = None,
):
    """Drop-in replacement for `repro.models.ssm.ssd_chunked` whose
    intra-chunk term runs in the fused Pallas kernel (no O(S*cs*H) decay
    tensor in HBM).  The inter-chunk recurrence stays in jnp (tiny).

    Returns (y (B,S,H,P) f32, final_state (B,H,P,N) f32).
    """
    import jax

    from .ssd_intra import ssd_intra_pallas

    b, s, h, p = x.shape
    n = bmat.shape[-1]
    pad_s = (-s) % chunk
    pad_h = (-h) % block_h
    if pad_s:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_s), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad_s), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad_s), (0, 0)))
    if pad_h:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_h), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad_h)))
        a = jnp.pad(a, ((0, pad_h),))
    sp, hp = s + pad_s, h + pad_h
    nc = sp // chunk

    da = dt * a[None, None, :]
    da_c = da.reshape(b, nc, chunk, hp)
    da_cum = jnp.cumsum(da_c, axis=2)
    da_sum = da_cum[:, :, -1, :]

    y_intra = ssd_intra_pallas(
        x, dt, da_cum.reshape(b, sp, hp), bmat, cmat,
        chunk=chunk, block_h=block_h, interpret=auto_interpret(interpret),
    )

    # chunk boundary states + inter-chunk recurrence (same math as the ref)
    xc = x.reshape(b, nc, chunk, hp, p)
    dtc = dt.reshape(b, nc, chunk, hp)
    bc = bmat.reshape(b, nc, chunk, n)
    cc = cmat.reshape(b, nc, chunk, n)
    decay_to_end = jnp.exp(da_sum[:, :, None, :] - da_cum)
    states = jnp.einsum("bzmn,bzmh,bzmhp->bzhpn", bc, dtc * decay_to_end, xc)
    chunk_decay = jnp.exp(da_sum)
    if h0 is None:
        h0 = jnp.zeros((b, hp, p, n), jnp.float32)
    elif pad_h:
        h0 = jnp.pad(h0, ((0, 0), (0, pad_h), (0, 0), (0, 0)))

    def step(carry, inp):
        st, dec = inp
        new = carry * dec[:, :, None, None] + st
        return new, carry

    last, h_prev = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_prev = jnp.moveaxis(h_prev, 0, 1)
    y_inter = jnp.einsum("bzln,bzhpn,bzlh->bzlhp", cc, h_prev, jnp.exp(da_cum))
    y = y_intra.reshape(b, nc, chunk, hp, p) + y_inter
    y = y.reshape(b, sp, hp, p)[:, :s, :h]
    return y, last[:, :h]
