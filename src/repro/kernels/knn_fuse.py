"""kNN-fusion serving on the query plan — two Pallas kernels per launch.

Answers a query grid under the paper's kNN fusion rule (Eq. 19) for all
B fields without materializing the dense intermediates the oracle path
builds in HBM (the (n, Q) per-sensor predictions and the (Q, n) distance
matrix).  Queries sit on the lane axis throughout; one jitted program
(``knn_fuse_pallas``) runs:

  gather   (XLA) each query's cell candidate row from the static serving
           plan (``repro.core.serving.make_serving_plan``), its validity
           (plan mask & liveness) and the candidates' positions, laid out
           (d, K_max, Q);
  select   (Pallas, grid over query tiles) one (K_max, BQ) masked
           squared-distance tile and the top-k by a k-step masked
           selection network: min, first index at the min, disable,
           repeat — k is tiny (1..8), and ties break toward the lower
           candidate column (the lower sensor id) exactly like
           ``lax.top_k`` on the plan and dense paths;
  gather   (XLA) the k selected sensors' (D, d) neighborhood anchors and
           masked (D,) representer rows, laid out (B, k, d, D, Q) and
           (B, k, D, Q) — O(B*Q*k*D) elements, never O(Q*n);
  evaluate (Pallas, grid (B, Q / BQ)) f_s(x) = sum_j c_{s,j}
           exp(-gamma ||x - x_j||^2) for each selected sensor and the
           average over the valid selections into the (1, BQ) output.

Mosaic on v5e lowers no in-kernel vector gather, so both gathers run in
XLA around the kernels; every block's last two dims are whole axes
(d, K_max, k, D) or lane tiles of 128 queries.

Mixed precision (``compute_dtype=``): the neighborhood ANCHOR tables —
the dominant operand at O(B*n*D*d) elements, an order of magnitude above
the O(n*d) sensor-position table — are STORED in the compute dtype (bf16
for the quantized serving path), halving the bytes the anchor gather and
the evaluate kernel move, so the default query tile doubles
(``default_block_q``: 128 at f32, 256 at bf16).  Anchor tiles are
upconverted in registers and all arithmetic runs at (at least) f32 — the
same contract as a bf16-in/f32-out MXU contraction — while the
representer contraction and the running average ALWAYS accumulate in
the coefficient dtype (f32, or f64 under JAX_ENABLE_X64 — ``ecoef`` is
never downcast).  Selection stays EXACT: queries, sensor positions, the
distance tile, and the top-k network keep full precision, so the
quantized path selects the same sensors as the f32 path and the only
perturbation is the bf16 rounding of the anchors inside
exp(-gamma ||x - x_j||^2).  (Quantizing selection too was measured and
rejected: at n=1000 serving geometry, bf16 position rounding flips ~5% of
selected sets and costs ~2.3% field RMSE — over the quantized path's 1%
budget — while anchors-only costs ~0.1%; see BENCH_quant.json and
tests/test_quant_serving.py.)

The output dtype follows the COEFFICIENTS, not the queries — an f64
problem served with bf16 anchors still answers in f64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ops import auto_interpret, bucket_rows, fit_block


def default_block_q(compute_dtype=None) -> int:
    """Query-tile width (lanes) per evaluate-kernel step.

    The evaluate kernel's blocks are dominated by the k gathered (d, D)
    anchor tiles per query; halving their element width (f32 -> bf16)
    keeps the block bytes when the tile doubles, halving the number of
    grid steps per launch.
    """
    if compute_dtype is not None and jnp.dtype(compute_dtype).itemsize <= 2:
        return 256
    return 128


def _select_kernel(xq_ref, cpos_ref, cvalid_ref, best_ref, ok_ref, *, k: int):
    xq = xq_ref[...]  # (d, BQ)
    cvalid = cvalid_ref[...] != 0  # (K, BQ)
    d2 = jnp.zeros(cvalid.shape, xq.dtype)
    for c in range(xq.shape[0]):
        diff = xq[c:c + 1, :] - cpos_ref[c]
        d2 = d2 + diff * diff
    inf = jnp.asarray(jnp.inf, d2.dtype)
    d2 = jnp.where(cvalid, d2, inf)
    rows = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    kmax = d2.shape[0]
    for j in range(k):  # masked selection network, k unrolled steps
        low = jnp.min(d2, axis=0, keepdims=True)  # (1, BQ)
        best = jnp.min(
            jnp.where(d2 == low, rows, kmax), axis=0, keepdims=True
        )  # first column at the min == lowest sensor id
        best_ref[j:j + 1, :] = best
        # Fewer than k live candidates: the overflow picks +inf entries —
        # mark them invalid so the average counts live selections only.
        ok_ref[j:j + 1, :] = (low < inf).astype(jnp.int32)
        d2 = jnp.where(rows == best, inf, d2)  # disable the selected


def _eval_kernel(xq_ref, anc_ref, cf_ref, ok_ref, out_ref, *, gamma: float):
    xq = xq_ref[...]  # (d, BQ) arithmetic dtype (>= f32)
    acc_dt = out_ref.dtype  # the coefficient dtype — NEVER downcast
    k = ok_ref.shape[0]
    acc = jnp.zeros(out_ref.shape, acc_dt)  # (1, BQ)
    cnt = jnp.zeros(out_ref.shape, acc_dt)
    for j in range(k):
        dd = None
        for c in range(xq.shape[0]):
            # anchors may be stored narrower (bf16): upconvert in registers
            diff = xq[c:c + 1, :] - anc_ref[j, c].astype(xq.dtype)  # (D, BQ)
            dd = diff * diff if dd is None else dd + diff * diff
        kv = jnp.exp(-gamma * dd).astype(acc_dt)
        f = jnp.sum(kv * cf_ref[j], axis=0, keepdims=True)  # (1, BQ)
        ok = ok_ref[j:j + 1, :] != 0
        acc = acc + jnp.where(ok, f, 0.0)
        cnt = cnt + ok.astype(acc_dt)
    out_ref[...] = acc / jnp.maximum(cnt, 1.0)


@functools.partial(
    jax.jit, static_argnames=("gamma", "k", "block_q", "interpret")
)
def knn_fuse_pallas(
    xq: jax.Array,
    qcell: jax.Array,
    cells: jax.Array,
    cmask: jax.Array,
    alive: jax.Array,
    spos: jax.Array,
    nbr_pos: jax.Array,
    nbr_mask: jax.Array,
    coef: jax.Array,
    *,
    gamma: float = 1.0,
    k: int = 1,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Padded inputs required: Q % block_q == 0.  Use ``knn_fuse_fused``
    for the general-shape wrapper.

    xq (Q, d); qcell (Q,) int32 flattened cell ids; cells (C, K) int32;
    cmask (C, K) int8; alive (n+1,) int8 sensor-row liveness;
    spos (n+1, d) padded sensor positions; nbr_pos (B, n+1, D, d);
    nbr_mask (B, n+1, D) int8; coef (B, n+1, D).  Returns (B, Q) in the
    COEFFICIENT dtype.  ``nbr_pos`` may be stored in a narrower compute
    dtype (bf16) than the rest — it is gathered narrow, upconverted in
    registers, and the arithmetic runs at >= f32 while the contraction
    accumulates in coef.dtype.
    """
    q, d = xq.shape
    kmax = cells.shape[1]
    b, r, d_max, _ = nbr_pos.shape
    assert q % block_q == 0, (q, block_q)
    assert nbr_mask.shape == (b, r, d_max) and coef.shape == (b, r, d_max)
    assert alive.shape == (r,), (alive.shape, r)
    ar_dt = xq.dtype if xq.dtype.itemsize >= 4 else jnp.float32
    xq_t = xq.astype(ar_dt).T  # (d, Q)
    cand = cells[qcell]  # (Q, K) this grid's candidate rows
    # Candidate validity = plan mask & liveness: a removed (or pruned-out)
    # sensor drops out even before the serving plan's candidate lists are
    # repaired/compacted.
    cvalid = (cmask[qcell] != 0) & (alive[cand] != 0)
    cpos_t = jnp.transpose(spos[cand].astype(ar_dt), (2, 1, 0))  # (d, K, Q)
    best, ok = pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid=(q // block_q,),
        in_specs=[
            pl.BlockSpec((d, block_q), lambda i: (0, i)),
            pl.BlockSpec((d, kmax, block_q), lambda i: (0, 0, i)),
            pl.BlockSpec((kmax, block_q), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((k, block_q), lambda i: (0, i)),
            pl.BlockSpec((k, block_q), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, q), jnp.int32),
            jax.ShapeDtypeStruct((k, q), jnp.int32),
        ],
        interpret=interpret,
    )(xq_t, cpos_t, cvalid.T.astype(jnp.int32))
    sel = jnp.take_along_axis(cand.T, best, axis=0)  # (k, Q) sensor rows
    anc = jnp.transpose(nbr_pos[:, sel], (0, 1, 4, 3, 2))  # (B, k, d, D, Q)
    cf = jnp.where(nbr_mask[:, sel] != 0, coef[:, sel], 0.0)
    cf = jnp.swapaxes(cf, 2, 3)  # (B, k, D, Q) coefficient dtype
    out = pl.pallas_call(
        functools.partial(_eval_kernel, gamma=gamma),
        grid=(b, q // block_q),
        in_specs=[
            pl.BlockSpec((d, block_q), lambda f, i: (0, i)),
            pl.BlockSpec(
                (None, k, d, d_max, block_q), lambda f, i: (f, 0, 0, 0, i)
            ),
            pl.BlockSpec((None, k, d_max, block_q), lambda f, i: (f, 0, 0, i)),
            pl.BlockSpec((k, block_q), lambda f, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_q), lambda f, i: (f, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, 1, q), coef.dtype),
        interpret=interpret,
    )(xq_t, anc, cf, ok)
    return out[:, 0]


def knn_fuse_fused(
    xq: jax.Array,
    qcell: jax.Array,
    cells: jax.Array,
    cell_mask: jax.Array,
    spos: jax.Array,
    nbr_pos: jax.Array,
    nbr_mask: jax.Array,
    coef: jax.Array,
    *,
    alive: jax.Array | None = None,
    gamma: float = 1.0,
    k: int = 1,
    block_q: int | None = None,
    compute_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """General-shape wrapper: pad the query axis, launch, slice back.

    Queries are padded to the power-of-two bucket of Q (see
    ``kernels.ops.bucket_rows``) so a serving process with varied request
    sizes compiles O(log Q) programs; padded rows point at cell 0 and are
    sliced off.  ``alive`` is the (n+1,) sensor-row liveness mask (None =
    fully alive): dead candidates never get selected, independent of the
    serving plan's repair state.

    ``compute_dtype`` (e.g. ``jnp.bfloat16``) rounds the anchor tables
    (``nbr_pos``, the dominant operand) to the storage dtype the anchor
    gather and the evaluate kernel move; queries, sensor positions, and the top-k
    selection stay full-precision (selection-exact quantization),
    arithmetic upconverts to >= f32 in registers, ``coef`` is never cast,
    and the contraction accumulates — and the output returns — in
    ``coef.dtype``.  ``block_q`` defaults to
    ``default_block_q(compute_dtype)`` (128 f32 / 256 bf16).
    """
    if compute_dtype is not None:
        nbr_pos = nbr_pos.astype(jnp.dtype(compute_dtype))
    if block_q is None:
        block_q = default_block_q(compute_dtype)
    q = xq.shape[0]
    r = nbr_pos.shape[1]
    if alive is None:
        alive = jnp.ones((r,), jnp.int8)
    block_q, q_pad = fit_block(bucket_rows(q), block_q)
    if q_pad != q:
        xq = jnp.pad(xq, ((0, q_pad - q), (0, 0)))
        qcell = jnp.pad(qcell, ((0, q_pad - q),))
    return knn_fuse_pallas(
        xq, qcell.astype(jnp.int32),
        cells.astype(jnp.int32), cell_mask.astype(jnp.int8),
        alive.astype(jnp.int8), spos,
        nbr_pos, nbr_mask.astype(jnp.int8), coef,
        gamma=gamma, k=k, block_q=block_q,
        interpret=auto_interpret(interpret),
    )[:, :q]
