"""Streaming measurement absorption for batched SN-Train problems.

Sensor networks do not observe a field once: readings keep arriving.  The
recursive-least-squares line of work (Mateos & Giannakis, arXiv:1109.4627)
absorbs each arrival into the running estimator with an O(D^2) update rather
than refitting from scratch; this module is that idea instantiated for the
paper's SN-Train local systems.

An arrival ``(field b, sensor s, location x, value y)`` becomes one more
data point owned by sensor s: it occupies the next free padded slot ``k`` of
N_s (build the topology with ``d_max`` headroom for capacity), whose FIXED
reserved message slot ``nbr_idx[s, k]`` was assigned at problem build (see
sn_train's message-slot layout).  The local system of sensor s grows by one
row/column:

    A_s' = [[A_s, a], [a^T, K(x,x) + lambda_s]]

whose Cholesky factor differs from chol[s] in a single new row — computed
with one triangular solve and a scalar square root (the classic rank-1
"grow" update):

    w = L_s^{-1} a,    d = sqrt(K(x,x) + lambda_s - w^T w)

O(D^2) instead of the O(D^3) refactorization, and exact: after any number of
absorptions ``problem.chol`` equals ``rebuild_chol(problem)`` to float
precision (asserted in tests/test_multifield.py).  Because the padded free
slots of ``chol`` are identity rows and arrivals fill slots left-to-right,
the fixed-shape masked triangular solve below IS the textbook update.

Other sensors never reference the new point (it joins N_s only), so the SOP
sweep machinery — serial, colored, sharded — runs unchanged on the absorbed
problem; a few post-arrival sweeps propagate the new information through the
network.  All constraint sets remain subspaces containing 0, so Fejér
monotonicity of the weighted norm (Lemma 2.1) is preserved across arrivals.

``absorb`` handles one arrival per dispatch; ``absorb_many`` runs a whole
arrival window through the identical per-step update under one
``lax.scan`` (one compiled program, one host round-trip — the serving
stream loop's configuration; equals repeated ``absorb`` exactly, see
tests/test_serving.py).

Over-capacity policy: by default an arrival at a FULL sensor is dropped.
``evict_oldest`` frees a full sensor's oldest arrival instead — remaining
arrivals shift down one slot (preserving the left-to-right == chronological
invariant the grow-one update relies on) and the sensor's factor is
downdated by a masked rebuild of its (D, D) Cholesky, O(D^3) for ONE sensor.
``absorb(..., on_full="evict")`` applies it automatically, turning each
sensor's stream slots into a sliding window over its most recent arrivals.

Time-varying fields (exponential forgetting / EW-RLS, the arXiv:1109.4627
recursion): a problem built with ``beta < 1`` for a field decays that
field's OLD arrivals one beta step per absorb — each absorb at (field,
sensor) multiplies the sensor's occupied stream lanes' anchor weights
omega by sqrt(beta) (``problem.anchor_w``), rescales the cached Gram /
message slots in place, and patches the cached Cholesky factor by
scale-then-update: a sqrt(beta) row scale followed by one rank-1 update
per ticked lane restoring the UNDECAYED +lambda on the matrix diagonal
(``_chol_diag_update``) — O(D^2) per ticked lane, no refactorization.
Because lambda never decays, every factor-rebuild path (``rebuild_chol``,
evict's masked downdate, the lifecycle ``_refactor_rows``, robust
re-factorization) and every sweep engine consumes the forgetting state
unchanged, and each local solve becomes the w-weighted projection
min_f sum_j w_j (z_j - f(x_j))^2 + lambda_s ||f||^2 with w_j = omega_j^2
— old measurements fade instead of anchoring the fit to the time-average.
Sliding-window RLS is the composition that already exists: ``absorb(...,
on_full="evict")`` plus ``beta < 1`` gives an exponentially-weighted
window over each sensor's most recent arrivals.  With ``beta = 1.0``
every tick multiplies by exactly 1.0 and the factor restore is gated, so
the static path is BITWISE identical to no forgetting at all
(tests/test_streaming_beta.py pins this engine by engine).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from . import plans
from .sn_train import SNTrainProblem, SNTrainState


class JoinReceipt(NamedTuple):
    """Outcome of one symmetric join (``add_sensor``), all fixed shapes.

    ``joined``: () bool — False means the join was a bitwise no-op (no
    spare row, or the recolor pool was exhausted).
    ``slot``: () int32 — the claimed row (meaningful when ``joined``).
    ``adopted``/``adopted_mask``: (A,) int32 / bool — the neighbor rows
    that adopted a reciprocal anchor lane (sentinel ``n`` padded).
    ``skipped``/``skipped_mask``: (A,) int32 / bool — live IN-RADIUS
    neighbors that were NOT adopted because their rows have no free lane
    (``degrees == d_max``).  Each is a silently lost coupling relative to
    a from-scratch build; callers rebalance (rebuild with d_max headroom,
    or evict arrivals to free lanes) — see ``plans.degree_headroom``.
    ``dropped_newest``: (B, A) bool — fields whose adopter row was
    completely FULL: growing the reciprocal anchor lane dropped that
    field's newest absorbed arrival (its orphaned slot is zeroed).
    """

    joined: jax.Array
    slot: jax.Array
    adopted: jax.Array
    adopted_mask: jax.Array
    skipped: jax.Array
    skipped_mask: jax.Array
    dropped_newest: jax.Array

    def to_json(self) -> dict:
        """Plain-JSON receipt (schema-tagged; device syncs happen here,
        at the caller's chosen reporting point, never inside jit)."""
        return {
            "schema": "join_receipt/1",
            "joined": bool(self.joined),
            "slot": int(self.slot),
            "adopted": np.asarray(self.adopted).tolist(),
            "adopted_mask": np.asarray(self.adopted_mask).astype(bool).tolist(),
            "skipped": np.asarray(self.skipped).tolist(),
            "skipped_mask": np.asarray(self.skipped_mask).astype(bool).tolist(),
            "dropped_newest": np.asarray(self.dropped_newest)
            .astype(bool).tolist(),
        }


class AbsorbReceipt(NamedTuple):
    """Per-arrival outcome flags of ``absorb_many`` (both (A,) bool).

    ``absorbed``: the arrival was written (possibly after an eviction);
    ``evicted``: the ``on_full="evict"`` policy freed the sensor's oldest
    arrival first.  ``~absorbed`` arrivals were dropped (sensor full under
    the drop policy, zero-capacity window sensor, or dead sensor).
    """

    absorbed: jax.Array
    evicted: jax.Array

    def to_json(self) -> dict:
        """Plain-JSON receipt (schema-tagged; syncs at the call site)."""
        return {
            "schema": "absorb_receipt/1",
            "absorbed": np.asarray(self.absorbed).astype(bool).tolist(),
            "evicted": np.asarray(self.evicted).astype(bool).tolist(),
        }


def capacity_left(problem: SNTrainProblem) -> jnp.ndarray:
    """(B, n) free ABSORBABLE neighborhood slots per (field, sensor).

    Free lanes retired to the sentinel id (a base-neighbor removal that had
    no reserved id left to restore) back no message slot and do not count.
    """
    if not problem.batched:
        raise ValueError("streaming requires a batched problem (use B = 1)")
    absorbable = problem.nbr_idx[:-1] != problem.sentinel  # (n, D)
    return jnp.sum(~problem.nbr_mask[:, :-1, :] & absorbable[None], axis=-1)


def _chol_diag_update(chol_s: jax.Array, alpha: jax.Array) -> jax.Array:
    """chol(L L^T + diag(alpha^2)) via one classic rank-1 update per lane.

    The "update" half of the forgetting tick's scale-then-update: row
    scaling the cached factor by sqrt(beta) decays the ticked stream
    lanes' ENTIRE matrix diagonal, lambda included; this restores the
    undecayed regularizer (+(1 - beta) * lambda per ticked lane), keeping
    every local system >= lambda I and every full-lambda rebuild path
    consistent with the cached factor.  ``alpha`` is (D,) with zeros on
    untouched lanes; a zero entry is neutral only in exact arithmetic
    (sqrt(l*l) costs an ulp), so callers gate the whole call on beta < 1
    to keep the static path bitwise.  Fixed-shape fori_loops, O(D^2) per
    nonzero lane.
    """
    d = chol_s.shape[-1]
    ar = jnp.arange(d)

    def one_lane(j, L):
        x0 = jnp.zeros((d,), L.dtype).at[j].set(alpha[j])

        def one_row(i, carry):
            L, x = carry
            lii = L[i, i]
            xi = x[i]
            r = jnp.sqrt(lii * lii + xi * xi)
            c = r / lii
            s = xi / lii
            below = ar > i
            col = L[:, i]
            new_col = jnp.where(below, (col + s * x) / c, col).at[i].set(r)
            x = jnp.where(below, c * x - s * new_col, x)
            return L.at[:, i].set(new_col), x

        L, _ = jax.lax.fori_loop(0, d, one_row, (L, x0))
        return L

    return jax.lax.fori_loop(0, d, one_lane, chol_s)


def _absorb(
    problem: SNTrainProblem,
    state: SNTrainState,
    field: jax.Array,
    sensor: jax.Array,
    x: jax.Array,
    y: jax.Array,
) -> tuple[SNTrainProblem, SNTrainState, jax.Array]:
    n = problem.n
    field = jnp.asarray(field, jnp.int32)
    sensor = jnp.asarray(sensor, jnp.int32)
    dt = problem.nbr_pos.dtype
    x = jnp.asarray(x, dt).reshape(-1)  # (d,)
    y = jnp.asarray(y, state.z.dtype)

    mask_s = problem.nbr_mask[field, sensor]  # (D,)
    # A free RESERVED slot must exist (sentinel-retired lanes back no
    # message slot) and the sensor must be ALIVE; else DROP.
    free = ~mask_s & (problem.nbr_idx[sensor] != problem.sentinel)
    ok = jnp.any(free) & problem.alive[sensor]
    k = jnp.argmax(free)  # first free slot (arrivals fill left-to-right)
    zid = problem.nbr_idx[sensor, k]  # fixed reserved message slot
    pos_s = problem.nbr_pos[field, sensor]  # (D, d)
    lam_s = problem.lam_pad[sensor]

    # ---- forgetting tick (scale-then-update, module docstring) --------
    # The sensor's occupied STREAM lanes age one beta step: anchor weights
    # omega *= sqrt(beta), the Gram rows/cols and the lanes' message slots
    # rescale to match, and the cached factor is row-scaled then patched
    # with a rank-1-per-lane diagonal restore of the undecayed lambda.
    # Structural lanes never decay.  beta = 1.0 multiplies by exactly 1.0
    # everywhere and the restore is gated: bitwise-identical static path.
    gdt = problem.gram.dtype
    ids_s = problem.nbr_idx[sensor]  # (D,)
    beta_b = problem.beta[field].astype(gdt)
    is_stream = mask_s & (ids_s >= n) & (ids_s != problem.sentinel)
    root = jnp.sqrt(beta_b)
    s_vec = jnp.where(is_stream, root, jnp.ones((), gdt))  # (D,)
    aw_old = problem.anchor_w[field, sensor]  # (D,)
    aw_s = aw_old * s_vec.astype(aw_old.dtype)
    gram_s = problem.gram[field, sensor] * (s_vec[:, None] * s_vec[None, :])
    with jax.named_scope("chol_update"):
        chol_s = problem.chol[field, sensor] * s_vec[:, None].astype(
            problem.chol.dtype
        )
        alpha = jnp.where(
            is_stream, jnp.sqrt((1.0 - beta_b) * lam_s.astype(gdt)), 0.0
        )
        chol_s = jnp.where(
            beta_b < 1.0, _chol_diag_update(chol_s, alpha), chol_s
        )

    # The kernel vector is masked to the EFFECTIVE lanes (occupied & alive):
    # a removed neighbor's lane keeps its occupancy but is factored out of
    # the cached Cholesky, and must stay out of the grow-one update too.
    # Anchor weights ride along (gram row (new, j) = omega_j * K; the fresh
    # arrival enters at omega = 1).
    mask_eff = mask_s & problem.alive_z[problem.nbr_idx[sensor]]
    kvec = jnp.where(
        mask_eff,
        problem.kernel(x[None, :], pos_s)[0] * aw_s.astype(dt),
        0.0,
    )  # (D,)
    kself = problem.kernel(x[None, :], x[None, :])[0, 0]

    new_row = kvec.at[k].set(kself)
    gram_s = gram_s.at[k, :].set(new_row).at[:, k].set(new_row)

    # Grow-one Cholesky: rows >= k of chol[s] are identity (padded), so the
    # full-shape triangular solve returns w on the valid prefix and zeros
    # elsewhere; only row k of the factor changes.
    with jax.named_scope("chol_update"):
        w = jsl.solve_triangular(chol_s, kvec, lower=True)
        d_new = jnp.sqrt(jnp.maximum(kself + lam_s - jnp.sum(w * w), 1e-12))
        chol_s = chol_s.at[k, :].set(w.at[k].set(d_new))

    # Every write is gated on `ok`: absorbing into a FULL sensor (argmin of
    # an all-True mask would alias slot 0, a live neighbor) degrades to a
    # no-op drop instead of corrupting the problem.  Callers that must not
    # lose data check `capacity_left` first.
    sp_idx = jnp.where(ok, zid - n, 0)
    problem = dataclasses.replace(
        problem,
        nbr_pos=problem.nbr_pos.at[field, sensor, k].set(
            jnp.where(ok, x, problem.nbr_pos[field, sensor, k])
        ),
        # gated: at a full sensor the bit was already True, but a DEAD
        # sensor's free slot must stay free when the arrival is dropped
        nbr_mask=problem.nbr_mask.at[field, sensor, k].set(
            jnp.where(ok, True, problem.nbr_mask[field, sensor, k])
        ),
        gram=problem.gram.at[field, sensor].set(
            jnp.where(ok, gram_s, problem.gram[field, sensor])
        ),
        chol=problem.chol.at[field, sensor].set(
            jnp.where(ok, chol_s, problem.chol[field, sensor])
        ),
        stream_pos=problem.stream_pos.at[field, sp_idx].set(
            jnp.where(ok, x, problem.stream_pos[field, sp_idx])
        ),
        anchor_w=problem.anchor_w.at[field, sensor].set(
            jnp.where(ok, aw_s.at[k].set(1.0), aw_old)
        ),
    )
    # The ticked lanes' message slots decay with their anchors (the stored
    # z invariant is omega_j * value; x1.0 writes when beta = 1 / not ok),
    # then the arrival seeds its own slot (Table-1 init z_0 = y); the
    # sensor's coefficient for the new slot starts at 0.
    z_scale = jnp.where(
        is_stream & ok, root, jnp.ones((), gdt)
    ).astype(state.z.dtype)
    z = state.z.at[field, ids_s].multiply(z_scale)
    z_idx = jnp.where(ok, zid, problem.sentinel)
    state = SNTrainState(
        z=z.at[field, z_idx].set(jnp.where(ok, y, z[field, z_idx])),
        coef=state.coef,
    )
    return problem, state, ok


_absorb_copy = jax.jit(_absorb)
_absorb_donate = jax.jit(_absorb, donate_argnums=(0, 1))


def _absorb_evict(problem, state, field, sensor, x, y):
    """One fused program: evict the oldest arrival IF the sensor is full,
    then absorb — a single dispatch/copy per arrival, not two.  Returns
    ``(problem, state, absorbed, evicted)``."""
    full = jnp.all(
        problem.nbr_mask[field, sensor]
        | (problem.nbr_idx[sensor] == problem.sentinel)
    )
    problem, state, ev = _evict_core(problem, state, field, sensor, full)
    problem, state, ok = _absorb(problem, state, field, sensor, x, y)
    return problem, state, ok, ev


_absorb_evict_copy = jax.jit(_absorb_evict)
_absorb_evict_donate = jax.jit(_absorb_evict, donate_argnums=(0, 1))


def absorb(
    problem: SNTrainProblem,
    state: SNTrainState,
    field: jax.Array,
    sensor: jax.Array,
    x: jax.Array,
    y: jax.Array,
    *,
    donate: bool = False,
    on_full: str = "drop",
) -> tuple[SNTrainProblem, SNTrainState, jax.Array]:
    """Absorb one measurement (x, y) arriving at ``sensor`` of ``field``.

    Returns ``(problem, state, absorbed)``.  An arrival at a sensor with no
    free neighborhood slot is DROPPED (in-graph guard; no corruption) and
    ``absorbed`` — a traced scalar bool, inspectable without a device sync
    until the caller converts it — reports which happened.  Callers that
    must not lose data check ``capacity_left`` up front or accumulate the
    flags; capacity comes from building the topology with d_max headroom.
    jit-compiled; ``field`` and ``sensor`` may be traced ints, so one
    compiled program serves every arrival.

    on_full="evict" frees the sensor's OLDEST arrival first (see
    ``evict_oldest``) whenever the sensor is full, so its stream slots act
    as a sliding window over the most recent measurements.  The one fused
    program handles both cases (no extra dispatch when the sensor has
    room).  Note the window needs at least one stream slot: a sensor built
    with ZERO headroom (deg == d_max) holds no arrival to evict, so its
    arrivals are still dropped — check ``capacity_left`` at build time.

    donate=True hands the input buffers to XLA for in-place update — the
    per-arrival cost drops from a full copy of the per-field arrays to the
    touched rows.  The caller must not use the OLD problem/state afterwards
    (the serving/streaming hot loop rebinds them, so it can).
    """
    if not problem.batched:
        raise ValueError("streaming requires a batched problem (use B = 1)")
    if problem.n_stream == 0:
        raise ValueError(
            "problem has no streaming capacity — build the topology with "
            "d_max headroom (build_topology(pos, r, d_max=max_degree + k))"
        )
    if on_full not in ("drop", "evict"):
        raise ValueError(f"on_full must be 'drop' or 'evict', got {on_full!r}")
    if on_full == "evict":
        fn = _absorb_evict_donate if donate else _absorb_evict_copy
        problem, state, ok, _ = fn(problem, state, field, sensor, x, y)
        return problem, state, ok
    fn = _absorb_donate if donate else _absorb_copy
    return fn(problem, state, field, sensor, x, y)


def _absorb_many_core(problem, state, fields, sensors, xs, ys, evict):
    def body(carry, arrival):
        p, s = carry
        f, sn, x, y = arrival
        if evict:
            p, s, ok, ev = _absorb_evict(p, s, f, sn, x, y)
        else:
            p, s, ok = _absorb(p, s, f, sn, x, y)
            ev = jnp.zeros((), bool)
        return (p, s), AbsorbReceipt(absorbed=ok, evicted=ev)

    (problem, state), receipt = jax.lax.scan(
        body, (problem, state), (fields, sensors, xs, ys)
    )
    return problem, state, receipt


_absorb_many_drop_copy = jax.jit(
    partial(_absorb_many_core, evict=False))
_absorb_many_drop_donate = jax.jit(
    partial(_absorb_many_core, evict=False), donate_argnums=(0, 1))
_absorb_many_evict_copy = jax.jit(
    partial(_absorb_many_core, evict=True))
_absorb_many_evict_donate = jax.jit(
    partial(_absorb_many_core, evict=True), donate_argnums=(0, 1))


def absorb_many(
    problem: SNTrainProblem,
    state: SNTrainState,
    fields: jax.Array,
    sensors: jax.Array,
    xs: jax.Array,
    ys: jax.Array,
    *,
    donate: bool = False,
    on_full: str = "drop",
) -> tuple[SNTrainProblem, SNTrainState, AbsorbReceipt]:
    """Absorb a BATCH of A arrivals in one dispatch (lax.scan over them).

    ``fields``/``sensors`` are (A,) ints, ``xs`` (A, d), ``ys`` (A,);
    arrivals apply in order with exactly the per-step math of ``absorb``
    (same grow-one Cholesky update, same over-capacity ``on_full``
    policy), so the result equals A sequential ``absorb`` calls — but as
    ONE compiled program instead of A host round-trips, which is what the
    serving stream loop wants (see ``launch/serve.py``).  Returns an
    ``AbsorbReceipt`` of per-arrival (A,) ``absorbed``/``evicted`` flag
    vectors so callers can surface capacity pressure (drops, evictions)
    instead of silently losing data.

    The compiled program is specialized on A; serving processes that batch
    arrivals into fixed-size windows reuse one program.  ``donate`` has
    the ``absorb`` contract: the caller rebinds and drops the old buffers.
    """
    if not problem.batched:
        raise ValueError("streaming requires a batched problem (use B = 1)")
    if problem.n_stream == 0:
        raise ValueError(
            "problem has no streaming capacity — build the topology with "
            "d_max headroom (build_topology(pos, r, d_max=max_degree + k))"
        )
    if on_full not in ("drop", "evict"):
        raise ValueError(f"on_full must be 'drop' or 'evict', got {on_full!r}")
    fields = jnp.asarray(fields, jnp.int32)
    sensors = jnp.asarray(sensors, jnp.int32)
    xs = jnp.asarray(xs, problem.nbr_pos.dtype)
    ys = jnp.asarray(ys, state.z.dtype)
    a = fields.shape[0]
    if xs.ndim != 2 or xs.shape[0] != a:
        raise ValueError(f"xs must be (A={a}, d), got {xs.shape}")
    if sensors.shape != (a,) or ys.shape != (a,):
        raise ValueError(
            f"fields/sensors/ys must share length A={a}, got "
            f"{sensors.shape} / {ys.shape}"
        )
    if on_full == "evict":
        fn = _absorb_many_evict_donate if donate else _absorb_many_evict_copy
    else:
        fn = _absorb_many_drop_donate if donate else _absorb_many_drop_copy
    return fn(problem, state, fields, sensors, xs, ys)


def pad_arrivals(
    problem: SNTrainProblem,
    fields,
    sensors,
    xs,
    ys,
    a_pad: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, np.ndarray]:
    """Pad an arrival window to ``a_pad`` rows with guaranteed no-ops.

    ``absorb_many``'s compiled program is specialized on the window length
    A, so a long-lived serving process draining arbitrary arrival batches
    would compile one program per distinct size.  Padding each window to
    its power-of-two bucket (``kernels.ops.bucket_rows``) caps that at
    O(log A) programs — IF the padding rows provably change nothing.

    They do: padding arrivals target the SENTINEL row (``sensor ==
    problem.n``), which is permanently dead (``alive[n]`` is False by
    construction — retired lanes point at it).  ``_absorb`` gates every
    table write on ``ok = free-slot & alive[sensor]`` and ``_evict_core``
    on ``occupied & alive[sensor]``, so a sentinel-row arrival is a
    bitwise no-op under both ``on_full`` policies; its receipt row comes
    back ``absorbed=False`` (tests/test_daemon.py pins padded == unpadded
    bitwise).  Returns ``(fields, sensors, xs, ys, real)`` — ``real`` is
    the (a_pad,) bool mask of genuine arrivals for receipt accounting.
    """
    fields = jnp.asarray(fields, jnp.int32)
    sensors = jnp.asarray(sensors, jnp.int32)
    xs = jnp.atleast_2d(jnp.asarray(xs, problem.nbr_pos.dtype))
    ys = jnp.asarray(ys)
    a = int(fields.shape[0])
    if a > a_pad:
        raise ValueError(f"window of {a} arrivals exceeds a_pad={a_pad}")
    pad = a_pad - a
    real = np.arange(a_pad) < a
    if pad == 0:
        return fields, sensors, xs, ys, real
    return (
        jnp.concatenate([fields, jnp.zeros((pad,), jnp.int32)]),
        jnp.concatenate(
            [sensors, jnp.full((pad,), problem.n, jnp.int32)]
        ),
        jnp.concatenate([xs, jnp.zeros((pad, xs.shape[1]), xs.dtype)]),
        jnp.concatenate([ys, jnp.zeros((pad,), ys.dtype)]),
        real,
    )


def _absorb_wave_core(problem, state, xs, ys, amask, evict):
    """Batched arrival wave: one optional arrival per (field, sensor).

    The per-pair update of ``_absorb`` (and ``_evict_core`` under
    ``evict``) writes only (field, sensor)-local rows plus message/stream
    slots OWNED by that sensor, so a wave of arrivals at DISTINCT pairs
    — which the (B, n) operand layout enforces structurally — commutes:
    this computes every row's tick + evict + grow-one update as one
    batched tensor program (no scan), equal to absorbing the arrivals
    sequentially in any order.  O(B * n * D^3) fully parallel work; the
    serving configuration for dense per-round streams (every sensor
    measures every round — the drift-tracking regime), where the
    scan-based ``absorb_many`` would pay B*n sequential steps.
    """
    n = problem.n
    r_rows, d_max = problem.nbr_idx.shape  # R = n + 1 (sentinel row last)
    f = problem.batch_size
    s_cap = problem.n_stream
    dt = problem.nbr_pos.dtype
    gdt = problem.gram.dtype
    ar = jnp.arange(d_max)
    ids = problem.nbr_idx  # (R, D)
    sentinel_id = problem.sentinel
    absorbable = ids != sentinel_id  # (R, D)
    xs = jnp.asarray(xs, dt)  # (F, n, d)
    ys = jnp.asarray(ys, state.z.dtype)  # (F, n)
    amask = jnp.asarray(amask, bool)  # (F, n)
    # extend arrival operands to the R = n + 1 rows (sentinel row inert)
    pad_r = ((0, 0), (0, r_rows - xs.shape[1]), (0, 0))
    xs = jnp.pad(xs, pad_r)
    ys = jnp.pad(ys, pad_r[:2])
    amask = jnp.pad(amask, pad_r[:2])
    deg = jnp.pad(problem.topology.degrees, (0, r_rows - n))  # (R,)
    own_pos = jnp.pad(
        problem.topology.positions.astype(dt), pad_r[1:]
    )  # (R, d)
    lam_r = problem.lam_pad[None, :, None]  # (1, R, 1)
    lane_alive = problem.alive_z[ids]  # (R, D)
    chol2 = jax.vmap(jax.vmap(lambda m: jsl.cholesky(m, lower=True)))
    z = state.z
    coef = state.coef
    ev_ok = jnp.zeros((f, r_rows), bool)

    if evict:
        # ---- batched _evict_core, gated to FULL rows with an arrival --
        mask = problem.nbr_mask  # (F, R, D)
        full = jnp.all(mask | ~absorbable[None], axis=-1)  # (F, R)
        occ = mask & (ar[None, None] >= deg[None, :, None])
        ev_ok = (
            occ.any(-1) & full & amask & problem.alive[None]
        )  # (F, R)
        last = deg[None] + occ.sum(-1) - 1  # (F, R)
        above = ar[None, None] >= deg[None, :, None]  # lanes past structure
        perm = jnp.where(
            above & (ar[None, None] < last[..., None]),
            ar[None, None] + 1, ar[None, None],
        )  # (F, R, D)
        freed = ar[None, None] == last[..., None]  # (F, R, D)
        keep = ~freed

        pos_p = jnp.take_along_axis(
            problem.nbr_pos, perm[..., None], axis=2
        )
        new_pos = jnp.where(
            freed[..., None], own_pos[None, :, None, :], pos_p
        )
        new_mask = jnp.where(freed, False, jnp.take_along_axis(mask, perm, 2))
        g1 = jnp.take_along_axis(problem.gram, perm[..., None], axis=2)
        g2 = jnp.take_along_axis(g1, perm[..., None, :], axis=3)
        g2 = jnp.where(keep[..., None] & keep[..., None, :], g2, 0.0)
        aw_p = jnp.take_along_axis(problem.anchor_w, perm, axis=2)
        aw2 = jnp.where(freed, jnp.ones((), problem.anchor_w.dtype), aw_p)
        diag = jnp.where(
            new_mask & lane_alive[None], lam_r, jnp.ones((), gdt)
        )
        new_chol = chol2(g2 + diag[..., None] * jnp.eye(d_max, dtype=gdt))

        okB = ev_ok[..., None]
        problem = dataclasses.replace(
            problem,
            nbr_pos=jnp.where(okB[..., None], new_pos, problem.nbr_pos),
            nbr_mask=jnp.where(okB, new_mask, problem.nbr_mask),
            gram=jnp.where(okB[..., None], g2, problem.gram),
            chol=jnp.where(okB[..., None], new_chol, problem.chol),
            anchor_w=jnp.where(okB, aw2, problem.anchor_w),
        )
        # messages/coefficients/stream positions ride their slots; every
        # slot this writes is OWNED by its row (stream ids are unique to
        # one row; structural/sentinel lanes write their current values
        # back), so the flat scatter has no conflicting duplicates.
        zvals = z[:, ids.reshape(-1)].reshape(f, r_rows, d_max)
        tvals = jnp.where(freed, 0.0, jnp.take_along_axis(zvals, perm, 2))
        z_write = jnp.where(
            okB & above & absorbable[None], tvals, zvals
        )
        z = z.at[:, ids.reshape(-1)].set(z_write.reshape(f, -1))
        c_new = jnp.where(
            freed, 0.0, jnp.take_along_axis(coef, perm, 2)
        )
        coef = jnp.where(okB & above, c_new, coef)
        spv = jnp.pad(problem.stream_pos, ((0, 0), (0, 1), (0, 0)))
        sp_gather = jnp.where(
            ar[None, :] >= deg[:, None], jnp.clip(ids - n, 0, s_cap), s_cap
        )  # (R, D); sentinel-retired lanes land in the dump row
        cur_sp = spv[:, sp_gather.reshape(-1)].reshape(
            f, r_rows, d_max, -1
        )
        sp_vals = jnp.where(
            freed[..., None], 0.0,
            jnp.take_along_axis(cur_sp, perm[..., None], axis=2),
        )
        sp_idx = jnp.where(
            ev_ok[..., None] & above, jnp.clip(ids - n, 0, s_cap)[None],
            s_cap,
        )  # (F, R, D); everything not-ok dumps past the slice
        spv = spv.at[jnp.arange(f)[:, None, None], sp_idx].set(sp_vals)
        problem = dataclasses.replace(problem, stream_pos=spv[:, :s_cap])

    # ---- batched _absorb: tick + weighted grow-one per (field, row) ---
    mask = problem.nbr_mask  # (F, R, D)
    free = ~mask & absorbable[None]
    ok = free.any(-1) & problem.alive[None] & amask  # (F, R)
    k = jnp.argmax(free, axis=-1)  # (F, R) first free slot
    zid = jnp.take_along_axis(
        jnp.broadcast_to(ids[None], (f, r_rows, d_max)), k[..., None], 2
    )[..., 0]  # (F, R)
    at_k = ar[None, None] == k[..., None]  # (F, R, D)

    beta_b = problem.beta.astype(gdt)[:, None, None]  # (F, 1, 1)
    is_stream = mask & (ids >= n)[None] & absorbable[None]
    root = jnp.sqrt(beta_b)
    s_vec = jnp.where(is_stream, root, jnp.ones((), gdt))  # (F, R, D)
    aw_s = problem.anchor_w * s_vec.astype(problem.anchor_w.dtype)
    gram_s = problem.gram * (s_vec[..., :, None] * s_vec[..., None, :])
    chol_s = problem.chol * s_vec[..., :, None].astype(problem.chol.dtype)
    alpha = jnp.where(
        is_stream, jnp.sqrt((1.0 - beta_b) * lam_r.astype(gdt)), 0.0
    )
    chol_s = jnp.where(
        (beta_b < 1.0)[..., None],
        jax.vmap(jax.vmap(_chol_diag_update))(chol_s, alpha),
        chol_s,
    )

    mask_eff = mask & lane_alive[None]
    flat_x = xs.reshape(f * r_rows, -1)
    flat_p = problem.nbr_pos.reshape(f * r_rows, d_max, -1)
    kv = jax.vmap(lambda x, p: problem.kernel(x[None], p)[0])(
        flat_x, flat_p
    ).reshape(f, r_rows, d_max)
    kself = jax.vmap(lambda x: problem.kernel(x[None], x[None])[0, 0])(
        flat_x
    ).reshape(f, r_rows)
    kvec = jnp.where(mask_eff, kv * aw_s.astype(kv.dtype), 0.0)
    new_row = jnp.where(at_k, kself[..., None], kvec)
    gram_s = jnp.where(at_k[..., :, None], new_row[..., None, :], gram_s)
    gram_s = jnp.where(at_k[..., None, :], new_row[..., :, None], gram_s)

    w = jax.vmap(jax.vmap(
        lambda L, b: jsl.solve_triangular(L, b, lower=True)
    ))(chol_s, kvec)
    d_new = jnp.sqrt(jnp.maximum(
        kself + lam_r[..., 0] - jnp.sum(w * w, -1), 1e-12
    ))
    chol_row = jnp.where(at_k, d_new[..., None], w)
    chol_s = jnp.where(at_k[..., :, None], chol_row[..., None, :], chol_s)

    okB = ok[..., None]
    problem = dataclasses.replace(
        problem,
        nbr_pos=jnp.where(
            (okB & at_k)[..., None], xs[:, :, None, :], problem.nbr_pos
        ),
        nbr_mask=jnp.where(okB & at_k, True, problem.nbr_mask),
        gram=jnp.where(okB[..., None], gram_s, problem.gram),
        chol=jnp.where(okB[..., None], chol_s, problem.chol),
        anchor_w=jnp.where(
            okB, jnp.where(at_k, 1.0, aw_s), problem.anchor_w
        ),
    )
    sp_idx = jnp.where(ok, zid - n, s_cap)  # (F, R); dump past the slice
    spv = jnp.pad(problem.stream_pos, ((0, 0), (0, 1), (0, 0)))
    spv = spv.at[jnp.arange(f)[:, None], sp_idx].set(
        jnp.where(ok[..., None], xs, 0.0)
    )
    problem = dataclasses.replace(problem, stream_pos=spv[:, :s_cap])

    # z: decay the ticked lanes' message slots (owned by their rows), then
    # seed each arrival's slot (all writes owner-unique or value-neutral)
    z_scale = jnp.where(
        is_stream & okB, root, jnp.ones((), gdt)
    ).astype(z.dtype)
    z = z.at[:, ids.reshape(-1)].multiply(z_scale.reshape(f, -1))
    z_idx = jnp.where(ok, zid, sentinel_id)  # not-ok rows hit the sentinel
    cur = jnp.take_along_axis(z, z_idx, axis=1)
    z = z.at[jnp.arange(f)[:, None], z_idx].set(
        jnp.where(ok, ys, cur)
    )
    receipt = AbsorbReceipt(
        absorbed=ok[:, :n], evicted=ev_ok[:, :n]
    )
    return problem, SNTrainState(z=z, coef=coef), receipt


_absorb_wave_drop_copy = jax.jit(partial(_absorb_wave_core, evict=False))
_absorb_wave_drop_donate = jax.jit(
    partial(_absorb_wave_core, evict=False), donate_argnums=(0, 1))
_absorb_wave_evict_copy = jax.jit(partial(_absorb_wave_core, evict=True))
_absorb_wave_evict_donate = jax.jit(
    partial(_absorb_wave_core, evict=True), donate_argnums=(0, 1))


def absorb_wave(
    problem: SNTrainProblem,
    state: SNTrainState,
    xs: jax.Array,
    ys: jax.Array,
    *,
    mask: jax.Array | None = None,
    donate: bool = False,
    on_full: str = "drop",
) -> tuple[SNTrainProblem, SNTrainState, AbsorbReceipt]:
    """Absorb up to ONE arrival per (field, sensor) in one batched dispatch.

    ``xs`` is (B, n, d) arrival locations, ``ys`` (B, n) values, ``mask``
    an optional (B, n) bool selecting which pairs actually have an arrival
    (default: all).  Equal to absorbing the masked arrivals one
    ``absorb(..., on_full=...)`` at a time (every per-pair update touches
    only its own row and its own reserved message/stream slots, so the
    wave order cannot matter) — but as one O(B*n*D^3) data-parallel
    program instead of a B*n-step scan: the dense-stream configuration
    (every sensor measures every round) that drift tracking under
    ``beta < 1`` wants, where ``absorb_many`` would be quadratically
    slower.  Returns an ``AbsorbReceipt`` with (B, n) flag arrays.
    """
    if not problem.batched:
        raise ValueError("streaming requires a batched problem (use B = 1)")
    if problem.n_stream == 0:
        raise ValueError(
            "problem has no streaming capacity — build the topology with "
            "d_max headroom (build_topology(pos, r, d_max=max_degree + k))"
        )
    if on_full not in ("drop", "evict"):
        raise ValueError(f"on_full must be 'drop' or 'evict', got {on_full!r}")
    n, b = problem.n, problem.batch_size
    xs = jnp.asarray(xs, problem.nbr_pos.dtype)
    ys = jnp.asarray(ys, state.z.dtype)
    if xs.shape[:2] != (b, n) or ys.shape != (b, n):
        raise ValueError(
            f"xs must be (B={b}, n={n}, d) and ys (B, n), got "
            f"{xs.shape} / {ys.shape}"
        )
    if mask is None:
        mask = jnp.ones((b, n), bool)
    if on_full == "evict":
        fn = _absorb_wave_evict_donate if donate else _absorb_wave_evict_copy
    else:
        fn = _absorb_wave_drop_donate if donate else _absorb_wave_drop_copy
    return fn(problem, state, xs, ys, mask)


def _evict_core(
    problem: SNTrainProblem,
    state: SNTrainState,
    field: jax.Array,
    sensor: jax.Array,
    gate: jax.Array,
) -> tuple[SNTrainProblem, SNTrainState, jax.Array]:
    n = problem.n
    d_max = problem.nbr_idx.shape[-1]
    field = jnp.asarray(field, jnp.int32)
    sensor = jnp.asarray(sensor, jnp.int32)
    deg = problem.topology.degrees[sensor]  # structural |N_s| (self incl.)
    mask_s = problem.nbr_mask[field, sensor]  # (D,)
    ar = jnp.arange(d_max)
    occ = mask_s & (ar >= deg)  # occupied stream slots (contiguous from deg)
    ok = occ.any() & jnp.asarray(gate, bool) & problem.alive[sensor]
    last = deg + jnp.sum(occ) - 1  # last occupied stream slot (when ok)

    # Shift stream slots [deg+1, last] down one; slot `last` becomes free.
    # Every per-slot array is permuted the same way, so the left-to-right
    # chronological fill invariant (absorb's argmin and the grow-one update
    # both rely on it) is restored after the eviction.
    perm = jnp.where((ar >= deg) & (ar < last), ar + 1, ar)
    freed = ar == last

    pos_s = problem.nbr_pos[field, sensor]  # (D, d)
    own = problem.topology.positions[sensor].astype(pos_s.dtype)  # (d,)
    new_pos = jnp.where(freed[:, None], own[None, :], pos_s[perm])
    new_mask = jnp.where(freed, False, mask_s[perm])

    # Gram: permute rows/cols (exact — the kept entries are the very floats
    # the original absorptions computed), then zero the freed row/col.
    # Anchor weights ride the same permutation (forgetting state survives
    # the window slide); the freed lane resets to the fresh weight 1.
    g = problem.gram[field, sensor]
    keep = ~freed
    g2 = jnp.where(keep[:, None] & keep[None, :], g[perm][:, perm], 0.0)
    aw = problem.anchor_w[field, sensor]
    aw2 = jnp.where(freed, jnp.ones((), aw.dtype), aw[perm])

    # Downdate = masked rebuild of this ONE sensor's factor, O(D^3): padded
    # AND lifecycle-dead lanes get unit diagonal (matching the effective
    # occupied & alive mask of the cached factors) so the factor stays SPD
    # and the grow-one update keeps working on the evicted problem.
    lam_s = problem.lam_pad[sensor]
    lane_alive = problem.alive_z[problem.nbr_idx[sensor]]  # (D,)
    diag = jnp.where(new_mask & lane_alive, lam_s, jnp.ones((), lam_s.dtype))
    new_chol = jsl.cholesky(g2 + jnp.diag(diag), lower=True)

    # Messages and coefficients ride along with their slots; the freed
    # slot's message/coefficient reset to 0 (the unoccupied convention).
    zids = problem.nbr_idx[sensor]  # (D,) fixed slot ids
    zvals = state.z[field, zids]
    tvals = jnp.where(freed, 0.0, zvals[perm])
    z_write = jnp.where(ok & (ar >= deg), tvals, zvals)
    z = state.z.at[field, zids].set(z_write)

    coef_s = state.coef[field, sensor]
    c_new = jnp.where(freed, 0.0, coef_s[perm])
    c_write = jnp.where(ok & (ar >= deg), c_new, coef_s)
    coef = state.coef.at[field, sensor].set(c_write)

    # stream_pos entries of this sensor shift the same way (dump writes for
    # non-stream lanes and the not-ok case into a scratch row).
    s_cap = problem.n_stream
    spv = jnp.pad(problem.stream_pos[field], ((0, 1), (0, 0)))
    sp_gather = jnp.where(ar >= deg, jnp.clip(zids - n, 0, s_cap), s_cap)
    cur_sp = spv[sp_gather]  # (D, d); zeros for non-stream lanes
    sp_vals = jnp.where(freed[:, None], 0.0, cur_sp[perm])
    sp_idx = jnp.where(ok & (ar >= deg), zids - n, s_cap)
    new_sp = spv.at[sp_idx].set(sp_vals)[:s_cap]

    problem = dataclasses.replace(
        problem,
        nbr_pos=problem.nbr_pos.at[field, sensor].set(
            jnp.where(ok, new_pos, pos_s)
        ),
        nbr_mask=problem.nbr_mask.at[field, sensor].set(
            jnp.where(ok, new_mask, mask_s)
        ),
        gram=problem.gram.at[field, sensor].set(jnp.where(ok, g2, g)),
        chol=problem.chol.at[field, sensor].set(
            jnp.where(ok, new_chol, problem.chol[field, sensor])
        ),
        stream_pos=problem.stream_pos.at[field].set(new_sp),
        anchor_w=problem.anchor_w.at[field, sensor].set(
            jnp.where(ok, aw2, aw)
        ),
    )
    return problem, SNTrainState(z=z, coef=coef), ok


_evict_jit = jax.jit(_evict_core)
_evict_donate = jax.jit(_evict_core, donate_argnums=(0, 1))


def evict_oldest(
    problem: SNTrainProblem,
    state: SNTrainState,
    field: jax.Array,
    sensor: jax.Array,
    *,
    donate: bool = False,
) -> tuple[SNTrainProblem, SNTrainState, jax.Array]:
    """Free the OLDEST occupied reserved slot of ``sensor`` in ``field``.

    Returns ``(problem, state, evicted)``; ``evicted`` is False (and the
    call is a no-op) when the sensor holds no absorbed arrival.  The
    remaining arrivals shift down one slot so absorb's left-to-right fill
    invariant survives, the sensor's Gram is permuted accordingly, and its
    Cholesky factor is downdated by a masked rebuild (O(D^3) for the one
    sensor; everything else is untouched).  After evict, an ``absorb`` at
    the same sensor reuses the freed slot — the round-trip equals building
    the window's problem from scratch (tests/test_multifield.py).

    donate=True hands the buffers to XLA in place, same contract as
    ``absorb``: the caller must rebind and drop the old problem/state.
    """
    if not problem.batched:
        raise ValueError("streaming requires a batched problem (use B = 1)")
    if problem.n_stream == 0:
        raise ValueError(
            "problem has no streaming capacity — build the topology with "
            "d_max headroom (build_topology(pos, r, d_max=max_degree + k))"
        )
    fn = _evict_donate if donate else _evict_jit
    return fn(problem, state, field, sensor, True)


def rebuild_chol(problem: SNTrainProblem) -> jnp.ndarray:
    """From-scratch Cholesky of every local system — the O(D^3) reference
    the streaming and lifecycle updates are tested against.  Factors over
    the EFFECTIVE lane mask (occupied & alive): lanes of removed neighbors
    keep their occupancy but drop out of the system, exactly as the event
    repairs patch the cached factors."""
    lam_pad = problem.lam_pad
    lane_alive = problem.alive_z[problem.nbr_idx] & problem.alive[:, None]

    def per_sensor(gram_s, mask_s, lam_s):
        diag = jnp.where(mask_s, lam_s, 1.0)
        return jsl.cholesky(gram_s + jnp.diag(diag), lower=True)

    per_field = jax.vmap(per_sensor, in_axes=(0, 0, 0))
    if problem.batched:
        return jax.vmap(lambda g, m: per_field(g, m, lam_pad))(
            problem.gram, problem.nbr_mask & lane_alive[None]
        )
    return per_field(problem.gram, problem.nbr_mask & lane_alive, lam_pad)


# ---------------------------------------------------------------------------
# Network lifecycle: sensor join / leave at fixed shapes (paper Sec. 3.3
# "Robustness" made persistent).  Siblings of absorb/evict_oldest: one
# jitted program each, every operand traced, so an arbitrary churn trace
# compiles a constant number of programs (tests/test_lifecycle.py counts).
#
# Joins are SYMMETRIC: the newcomer adopts its neighbors AND each adopter
# grows a reciprocal anchor lane at the new position (with on-device
# conflict-aware recoloring when two same-color adopters would now share
# the newcomer's slot), so the post-join problem is the problem a fresh
# make_problem on the post-join topology would build.  Removal is the
# exact inverse (lane deletion + reserved-id restore).  Both events
# gather, repair and refactorize only the O(degree) affected rows.
# ---------------------------------------------------------------------------


def _refactor_rows(problem, alive_new, rows, idx_rows, mask_rows, gram_rows):
    """Masked Cholesky refactorization of O(degree) gathered rows.

    THE shared effective-lane convention of both event repairs (the
    row-gathered form of ``sn_train._masked_factors``): a lane is active
    iff occupied AND its slot and row are alive; live diagonal entries get
    lambda, everything else 1, so padded/dead blocks factor to identity.
    ``rows`` (R,) sensor ids (sentinel-padded), ``idx_rows`` (R, D) their
    post-event slot tables, ``mask_rows`` (B, R, D) occupancy,
    ``gram_rows`` (B, R, D, D).  Returns the (B, R, D, D) lower factors.
    """
    lane_alive = (
        plans.alive_slots(alive_new, problem.layout.slot_owner)[idx_rows]
        & alive_new[rows][:, None]
    )  # (R, D)
    mask_eff = mask_rows & lane_alive[None]  # (B, R, D)
    diag = jnp.where(mask_eff, problem.lam_pad[rows][None, :, None], 1.0)
    outer = mask_eff[..., :, None] & mask_eff[..., None, :]
    eye = jnp.eye(idx_rows.shape[-1], dtype=gram_rows.dtype)
    a = jnp.where(outer, gram_rows, 0.0) + diag[..., None] * eye
    return jax.vmap(jax.vmap(lambda m: jsl.cholesky(m, lower=True)))(a)


def _add_sensor_core(problem, state, x, ys, lam, repair, kappa):
    n = problem.n
    n_rows, d_max = problem.nbr_idx.shape
    dt = problem.nbr_pos.dtype
    lay = problem.layout
    topo = problem.topology
    n_base = lay.n_base
    b = problem.batch_size
    x = jnp.asarray(x, dt).reshape(-1)  # (d,)
    ys = jnp.asarray(ys, state.z.dtype).reshape(-1)  # (B,)
    lam = jnp.asarray(lam, problem.lam_pad.dtype)
    repair = jnp.asarray(repair, bool)
    kappa = jnp.asarray(kappa, problem.lam_pad.dtype)

    # 1. Claim the first dead SPARE row (spares carry reserved singleton
    # colors, so the NEWCOMER never invalidates the frozen distance-2
    # coloring; removed spare rows are recycled).  No free spare => DROP.
    spare_alive = problem.alive[n_base:n]
    have_spare = jnp.any(~spare_alive)
    slot = jnp.int32(n_base) + jnp.argmin(spare_alive).astype(jnp.int32)

    # 2. Adopt the nearest live in-radius sensors (up to D-1 of them plus
    # self; a denser-than-capacity neighborhood truncates to the nearest).
    # The join is SYMMETRIC: every adopted neighbor grows a reciprocal
    # anchor lane at x, so candidates must have a lane to spare —
    # capacity-exhausted rows are not adopted in either direction, keeping
    # the realized edge set symmetric.
    pos = topo.positions.astype(dt)  # (n, d)
    d2 = jnp.sum((pos - x[None, :]) ** 2, axis=-1)  # (n,)
    radius = jnp.asarray(topo.radius, dt)
    in_radius = problem.alive[:n] & (d2 < radius * radius)
    cand = in_radius & (topo.degrees < d_max)
    neg = jnp.where(cand, -d2, -jnp.inf)
    k_n = min(d_max - 1, n)  # static lane budget for adopted neighbors
    vals, ids = jax.lax.top_k(neg, k_n)  # nearest live first
    valid0 = jnp.isfinite(vals)  # (k_n,)
    c = 1 + jnp.sum(valid0)  # occupied lane count (self included)
    lam = jnp.where(lam >= 0, lam, kappa / c.astype(lam.dtype) ** 2)

    # Lane-exhausted in-radius sensors are NOT adopted in either direction
    # (the symmetric coupling would need a reciprocal lane they don't
    # have): each is a lost coupling relative to a from-scratch build on
    # the post-join positions.  Reported in the JoinReceipt so callers can
    # rebalance (plans.degree_headroom) instead of silently losing edges.
    exhausted = in_radius & (topo.degrees >= d_max)
    sk_vals, sk_ids = jax.lax.top_k(jnp.where(exhausted, -d2, -jnp.inf), k_n)
    sk_valid = jnp.isfinite(sk_vals)  # (k_n,)

    # 3. Conflict-aware recoloring: adopters all gain the newcomer's slot
    # as a shared neighbor, so same-color adopter pairs now violate the
    # distance-2 rule — move all but the first of each color into empty
    # reserved recolor classes.  Pool exhausted => DROP the join whole.
    new_colors, moved, feasible = plans.resolve_join_conflicts(
        problem.color_of, problem.color_mask, ids, valid0,
        problem.recolor_start,
    )
    ok = have_spare & feasible
    valid = valid0 & ok  # adopters actually repaired
    mv = moved & valid  # adopters actually recolored

    # 4. The newcomer's slot table: [self, adopted neighbor z-slots...],
    # free lanes restored from the pristine reserved ids (row recycling).
    pad_k = d_max - 1 - k_n
    sel_ids = jnp.concatenate(
        [slot[None], ids.astype(jnp.int32),
         jnp.zeros((pad_k,), jnp.int32)]
    )
    sel_valid = jnp.concatenate(
        [jnp.ones((1,), bool), valid0, jnp.zeros((pad_k,), bool)]
    )
    new_idx = jnp.where(sel_valid, sel_ids, lay.nbr_idx0[slot])
    pos2 = pos.at[slot].set(jnp.where(ok, x, pos[slot]))
    pos_pad = jnp.concatenate([pos2, jnp.zeros((1, pos2.shape[1]), dt)])
    gathered = pos_pad[jnp.where(sel_valid, sel_ids, n)]
    new_pos = jnp.where(sel_valid[:, None], gathered, x[None, :])  # (D, d)

    # 5. The joined sensor's local system + factor (shared by all fields —
    # the row starts arrival-free).
    kmat = problem.kernel(new_pos, new_pos)  # (D, D)
    outer = sel_valid[:, None] & sel_valid[None, :]
    gram_row = jnp.where(outer, kmat, 0.0).astype(problem.gram.dtype)
    diag = jnp.where(sel_valid, lam, 1.0)
    chol_row = jsl.cholesky(gram_row + jnp.diag(diag), lower=True)

    # 6. Reciprocal anchor lanes: each adopter's row grows a lane for the
    # newcomer at its stream boundary ``deg`` (so structural/anchor lanes
    # stay a contiguous prefix and absorb's left-to-right fill invariant
    # survives); absorbed arrivals shift up one lane, the LAST reserved id
    # falls out of the table (orphaned until a later lane deletion restores
    # it), and a field whose row was completely full drops its NEWEST
    # arrival.  O(degree) rows are gathered, repaired and refactored —
    # never all n.
    rows = jnp.where(valid, ids, n).astype(jnp.int32)  # (A,) pad: sentinel
    deg_r = topo.degrees[jnp.clip(rows, 0, n - 1)]  # (A,) pre-join degrees
    old_idx_r = problem.nbr_idx[rows]  # (A, D)
    ar = jnp.arange(d_max)
    at_new = ar[None, :] == deg_r[:, None]  # (A, D) the inserted lane
    src = jnp.where(
        ar[None, :] > deg_r[:, None], ar[None, :] - 1, ar[None, :]
    )
    shifted_idx = jnp.take_along_axis(old_idx_r, src, axis=1)
    new_idx_r = jnp.where(at_new, slot, shifted_idx).astype(
        problem.nbr_idx.dtype
    )
    orphan = old_idx_r[:, d_max - 1]  # (A,) reserved ids dropped

    old_pos_r = problem.nbr_pos[:, rows]  # (B, A, D, d)
    old_mask_r = problem.nbr_mask[:, rows]  # (B, A, D)
    old_gram_r = problem.gram[:, rows]  # (B, A, D, D)
    old_chol_r = problem.chol[:, rows]
    old_aw_r = problem.anchor_w[:, rows]  # (B, A, D)
    old_coef_r = state.coef[:, rows]
    # a field whose adopter row was completely FULL loses its newest
    # arrival to the inserted anchor lane — reported per (field, adopter)
    dropped = old_mask_r[:, :, d_max - 1] & valid[None, :]  # (B, A)
    pos_sh = jnp.take_along_axis(old_pos_r, src[None, :, :, None], axis=2)
    new_pos_r = jnp.where(
        at_new[None, :, :, None], x[None, None, None, :], pos_sh
    )
    mask_sh = jnp.take_along_axis(old_mask_r, src[None], axis=2)
    new_mask_r = jnp.where(at_new[None], True, mask_sh)
    coef_sh = jnp.take_along_axis(old_coef_r, src[None], axis=2)
    new_coef_r = jnp.where(at_new[None], 0.0, coef_sh)
    # anchor weights shift with their lanes; the inserted structural
    # anchor lane enters at the undecayed weight 1
    aw_sh = jnp.take_along_axis(old_aw_r, src[None], axis=2)
    new_aw_r = jnp.where(at_new[None], jnp.ones((), aw_sh.dtype), aw_sh)
    g1 = jnp.take_along_axis(old_gram_r, src[None, :, :, None], axis=2)
    g2 = jnp.take_along_axis(g1, src[None, :, None, :], axis=3)
    # the anchor's kernel row vs the row's occupied lanes (K(x,x) at deg);
    # decayed stream lanes carry their anchor weights into the new row
    # (gram invariant: entry (i, j) = omega_i * omega_j * K)
    kv = problem.kernel(x[None, :], new_pos_r.reshape(-1, x.shape[0]))[0]
    kv = kv.reshape(new_pos_r.shape[:-1])  # (B, A, D)
    krow = jnp.where(
        new_mask_r, kv * new_aw_r.astype(kv.dtype), 0.0
    ).astype(problem.gram.dtype)
    g3 = jnp.where(at_new[None, :, None, :], krow[..., None], g2)
    g3 = jnp.where(at_new[None, :, :, None], krow[..., None, :], g3)

    # Opt-in lambda repair (paper rule lambda_i = kappa / |N_i|^2): the
    # adopters' degrees grew by one, so their build-time regularizers are
    # stale relative to a from-scratch build.  Repairing rides the very
    # refactorization this event already pays — _refactor_rows reads
    # lam_pad, so patch it first.  repair=False writes the old floats
    # back (bitwise no-op).
    deg_new = (deg_r + 1).astype(problem.lam_pad.dtype)
    lam_fix = kappa / (deg_new * deg_new)
    do_fix = repair & valid
    lam_pad2 = problem.lam_pad.at[rows].set(
        jnp.where(do_fix, lam_fix, problem.lam_pad[rows])
    )
    problem = dataclasses.replace(problem, lam_pad=lam_pad2)

    # Affected-row refactorization (the adopters' factors gain a middle
    # row, so the rank-1 grow-one update does not apply): one batched
    # (B, A) masked Cholesky over the post-join effective lanes.
    alive2 = problem.alive.at[slot].set(
        jnp.where(ok, True, problem.alive[slot])
    )
    chol_r = _refactor_rows(problem, alive2, rows, new_idx_r, new_mask_r, g3)

    vB = valid[None, :, None]
    topo = dataclasses.replace(
        topo,
        positions=pos2.astype(topo.positions.dtype),
        degrees=topo.degrees.at[rows].add(
            jnp.where(valid, 1, 0).astype(topo.degrees.dtype)
        ).at[slot].set(
            jnp.where(
                ok,
                c.astype(topo.degrees.dtype),
                topo.degrees[slot],
            )
        ),
    )
    gate = lambda new, old: jnp.where(ok, new, old)
    nbr_idx2 = problem.nbr_idx.at[rows].set(
        jnp.where(valid[:, None], new_idx_r, old_idx_r)
    ).at[slot].set(gate(new_idx, problem.nbr_idx[slot]))
    nbr_mask2 = problem.nbr_mask.at[:, rows].set(
        jnp.where(vB, new_mask_r, old_mask_r)
    ).at[:, slot].set(
        gate(
            jnp.broadcast_to(sel_valid, (b, d_max)),
            problem.nbr_mask[:, slot],
        )
    )
    nbr_pos2 = problem.nbr_pos.at[:, rows].set(
        jnp.where(vB[..., None], new_pos_r, old_pos_r)
    ).at[:, slot].set(
        gate(
            jnp.broadcast_to(new_pos, (b,) + new_pos.shape),
            problem.nbr_pos[:, slot],
        )
    )
    gram2 = problem.gram.at[:, rows].set(
        jnp.where(vB[..., None], g3, old_gram_r)
    ).at[:, slot].set(
        gate(
            jnp.broadcast_to(gram_row, (b,) + gram_row.shape),
            problem.gram[:, slot],
        )
    )
    chol2 = problem.chol.at[:, rows].set(
        jnp.where(vB[..., None], chol_r, old_chol_r)
    ).at[:, slot].set(
        gate(
            jnp.broadcast_to(chol_row, (b,) + chol_row.shape),
            problem.chol[:, slot],
        )
    )
    anchor_w2 = problem.anchor_w.at[:, rows].set(
        jnp.where(vB, new_aw_r, old_aw_r)
    ).at[:, slot].set(
        gate(
            jnp.ones((b, d_max), problem.anchor_w.dtype),
            problem.anchor_w[:, slot],
        )
    )

    # 7. Color bookkeeping: recolored adopters change classes, the
    # newcomer (re)enters its reserved singleton class, and every repaired
    # row's scatter codes are rewritten for its post-join slot table.
    old_c = problem.color_of[rows]
    old_m = problem.member_pos[rows]
    cm, cmk = plans.members_clear(
        problem.color_members, problem.color_mask, old_c, old_m, mv, n
    )
    cm, cmk = plans.members_set(
        cm, cmk, new_colors, jnp.zeros_like(new_colors), rows, mv
    )
    cm, cmk = plans.members_set(
        cm, cmk, problem.color_of[slot][None],
        jnp.zeros((1,), jnp.int32), slot[None], jnp.asarray(ok)[None],
    )
    color_of2 = problem.color_of.at[rows].set(jnp.where(mv, new_colors, old_c))
    member_pos2 = problem.member_pos.at[rows].set(
        jnp.where(mv, 0, old_m).astype(problem.member_pos.dtype)
    )
    new_c_eff = jnp.where(mv, new_colors, old_c)
    new_m_eff = jnp.where(mv, 0, old_m).astype(old_m.dtype)
    plan_z, plan_coef = plans.plan_rows_remove(
        problem.plan_z, problem.plan_coef, old_c, rows, old_idx_r, valid
    )
    plan_z, plan_coef = plans.plan_rows_add(
        plan_z, plan_coef, new_c_eff, new_m_eff, rows, new_idx_r, valid
    )
    plan_z, plan_coef = plans.color_plans_add(
        plan_z, plan_coef, color_of2, member_pos2, slot, new_idx, ok
    )

    # 8. Orphaned reserved slots: their messages / arrival positions reset
    # (a full field's dropped newest arrival dies with its slot).
    s_cap = problem.n_stream
    z = state.z.at[:, orphan].set(
        jnp.where(valid[None, :], 0.0, state.z[:, orphan])
    )
    spv = jnp.pad(problem.stream_pos, ((0, 0), (0, 1), (0, 0)))
    sp_idx = jnp.where(valid, jnp.clip(orphan - n, 0, s_cap), s_cap)
    spv = spv.at[:, sp_idx].set(
        jnp.where(valid[None, :, None], 0.0, spv[:, sp_idx])
    )
    stream_pos2 = spv[:, :s_cap]

    problem = dataclasses.replace(
        problem,
        topology=topo,
        y=problem.y.at[:, slot].set(gate(ys, problem.y[:, slot])),
        nbr_idx=nbr_idx2,
        nbr_mask=nbr_mask2,
        nbr_pos=nbr_pos2,
        gram=gram2,
        chol=chol2,
        lam_pad=problem.lam_pad.at[slot].set(gate(lam, problem.lam_pad[slot])),
        stream_pos=stream_pos2,
        anchor_w=anchor_w2,
        plan_z=plan_z,
        plan_coef=plan_coef,
        color_members=cm,
        color_mask=cmk,
        color_of=color_of2,
        member_pos=member_pos2,
        alive=alive2,
    )

    # 9. State: the recycled row's owned slots reset, the new sensor seeds
    # its own message slot with its measurements (Table-1 init z_0 = y);
    # the adopters' shifted coefficient rows (0 at the new anchor lane)
    # were computed above.
    owned = (lay.slot_owner == slot) & ok  # (n_z,)
    z = jnp.where(owned[None, :], 0.0, z)
    z = z.at[:, slot].set(jnp.where(ok, ys, z[:, slot]))
    coef = state.coef.at[:, rows].set(
        jnp.where(vB, new_coef_r, old_coef_r)
    ).at[:, slot].set(jnp.where(ok, 0.0, state.coef[:, slot]))
    receipt = JoinReceipt(
        joined=ok,
        slot=slot,
        adopted=jnp.where(valid, ids, n).astype(jnp.int32),
        adopted_mask=valid,
        skipped=jnp.where(sk_valid & ok, sk_ids, n).astype(jnp.int32),
        skipped_mask=sk_valid & ok,
        dropped_newest=dropped,
    )
    return problem, SNTrainState(z=z, coef=coef), receipt


_add_sensor_copy = jax.jit(_add_sensor_core)
_add_sensor_donate = jax.jit(_add_sensor_core, donate_argnums=(0, 1))


def add_sensor(
    problem: SNTrainProblem,
    state: SNTrainState,
    x: jax.Array,
    ys: jax.Array,
    *,
    lam: float | jax.Array = -1.0,
    repair_lambda: bool = False,
    kappa: float = 0.01,
    donate: bool = False,
) -> tuple[SNTrainProblem, SNTrainState, JoinReceipt]:
    """A sensor JOINS the network at position ``x`` with measurements ``ys``.

    Occupies the first free spare row (``make_problem(..., n_max=...)``
    reserves them) and, entirely on device at fixed shapes:

      * adopts the nearest live in-radius sensors into its padded
        neighborhood (their message slots become its lanes; free lanes keep
        the row's reserved streaming ids, so the joined sensor absorbs
        arrivals like any other);
      * SYMMETRICALLY, every adopted neighbor grows a reciprocal anchor
        lane at ``x`` (inserted at its stream boundary; absorbed arrivals
        shift up one lane and its last reserved slot is orphaned until a
        later removal restores it) — exactly the bidirectional
        neighborhood coupling a from-scratch ``make_problem`` on the
        post-join topology would build, so post-join fits match a fresh
        build (tests/test_lifecycle.py pins the repaired scatter plans
        BITWISE against the host builder and the fit to <= 1e-5);
      * resolves the distance-2 conflicts the reciprocal lanes create
        (same-color adopters now share the newcomer's slot) by moving all
        but one adopter per color into reserved empty recolor classes
        (``plans.resolve_join_conflicts``; budget: ``build_topology(...,
        n_recolor=)``, default 2x the spare rows) — an exhausted pool
        DROPS the join rather than corrupting the coloring;
      * builds the newcomer's masked local Gram/Cholesky (one (D, D)
        factorization, shared across fields) and refactorizes the O(degree)
        ADOPTER rows only — one batched (B, degree) masked Cholesky, never
        all n rows;
      * patches the scatter plans of the newcomer AND every repaired
        adopter row so the colored engines sweep the post-join network
        with zero recompilation;
      * seeds its message slot with ``ys`` (the Table-1 init) and flips
        ``alive``.

    Every constraint set stays a subspace containing 0, so Fejér
    monotonicity of the weighted norm survives the event
    (tests/test_lifecycle.py).  Capacity caveats: candidates whose rows
    have no free lane (``degrees == d_max``) are not adopted in either
    direction (build with d_max headroom), and a field whose adopter row
    is completely full drops its NEWEST absorbed arrival to make room for
    the anchor lane.

    ``lam``: the newcomer's regularizer; negative (default) applies the
    paper's ``kappa``/|N|^2 rule to its adopted degree.  By default the
    ADOPTERS keep their build-time regularizers even though their degrees
    just grew — the paper rule says they are now stale.
    ``repair_lambda=True`` re-derives each adopter's lambda from its
    post-join degree (lambda_i = kappa / |N_i|^2, self included) inside
    the O(degree) refactorization this event already pays, so repaired
    joins match a from-scratch build's regularizers too (the accuracy
    drift of NOT repairing under sustained churn is recorded in
    tests/test_churn_soak.py).  Both settings share one compiled program
    (``repair_lambda``/``kappa`` are traced operands).

    Returns ``(problem, state, receipt)`` — a ``JoinReceipt`` whose
    ``joined`` is False (bitwise no-op) when no spare row is free or the
    recolor pool is exhausted (size capacity with ``n_max``/``n_recolor``),
    whose ``skipped`` lists the in-radius live sensors NOT adopted because
    their rows had no free lane, and whose ``dropped_newest`` flags the
    (field, adopter) pairs whose newest absorbed arrival was orphaned by
    the reciprocal anchor lane.  A serving process also patches its query
    plan: ``serving.plan_add_sensor(plan, x, receipt.slot)``.

    ``donate=True`` has the ``absorb`` contract (rebind, drop the old
    buffers).
    """
    if not problem.batched:
        raise ValueError("lifecycle ops require a batched problem (use B = 1)")
    if problem.topology.n_spare == 0:
        raise ValueError(
            "problem has no spare rows — build with "
            "make_problem(..., n_max=n + spares) (or build_topology n_max=)"
        )
    if float(problem.topology.radius) <= 0.0:
        raise ValueError(
            "add_sensor needs a geometric topology (radius > 0) to find "
            "the joining sensor's neighborhood"
        )
    fn = _add_sensor_donate if donate else _add_sensor_copy
    return fn(
        problem, state, x, ys, lam,
        jnp.asarray(repair_lambda, bool),
        jnp.asarray(kappa, problem.lam_pad.dtype),
    )


def _remove_sensor_core(problem, state, slot, repair, kappa):
    n = problem.n
    n_rows, d_max = problem.nbr_idx.shape
    dt = problem.nbr_pos.dtype
    lay = problem.layout
    topo = problem.topology
    slot = jnp.asarray(slot, jnp.int32)
    repair = jnp.asarray(repair, bool)
    kappa = jnp.asarray(kappa, problem.lam_pad.dtype)
    ok = (slot >= 0) & (slot < n) & problem.alive[slot]
    sl = jnp.clip(slot, 0, n - 1)  # safe READ index; writes are ok-gated

    alive = problem.alive.at[sl].set(
        jnp.where(ok, False, problem.alive[sl])
    )

    # Affected rows: joins are SYMMETRIC, so the rows referencing the
    # victim are exactly the live sensors its own slot table lists — a
    # static (D,)-padded gather, O(degree) rows repaired, never all n.
    victim_idx = problem.nbr_idx[sl]  # (D,)
    nb = (
        (victim_idx < n) & (victim_idx != sl)
        & problem.alive[jnp.clip(victim_idx, 0, n)] & ok
    )
    rows = jnp.where(nb, victim_idx, n).astype(jnp.int32)  # pad: sentinel

    # Each affected row DELETES its lane for the victim (the inverse of the
    # join's insertion): lanes above it shift down one — preserving the
    # [structural | arrivals | free] layout and absorb's fill invariant —
    # and the freed last lane restores the row's first orphaned reserved
    # id (none left => the lane is retired to the sentinel id and backs no
    # message slot; ``absorb`` skips such lanes).
    old_idx_r = problem.nbr_idx[rows]  # (R, D)
    lane = jnp.argmax(old_idx_r == sl, axis=1)  # (R,) the victim's lane
    ar = jnp.arange(d_max)
    src = jnp.where(
        ar[None, :] >= lane[:, None],
        jnp.minimum(ar[None, :] + 1, d_max - 1),
        ar[None, :],
    )
    shifted = jnp.take_along_axis(old_idx_r, src, axis=1)
    ids0 = lay.nbr_idx0[rows]  # (R, D) pristine table: the reserved pool
    owned0 = ids0 >= n
    present = (
        ids0[:, :, None] == shifted[:, None, : d_max - 1]
    ).any(-1)  # (R, D)
    cand_rest = owned0 & ~present
    pick = jnp.argmax(cand_rest, axis=1)
    restored = jnp.take_along_axis(ids0, pick[:, None], axis=1)[:, 0]
    sentinel_id = jnp.asarray(problem.sentinel, problem.nbr_idx.dtype)
    restored = jnp.where(cand_rest.any(axis=1), restored, sentinel_id)
    new_idx_r = shifted.at[:, d_max - 1].set(
        restored.astype(shifted.dtype)
    )
    freed = ar[None, :] == d_max - 1  # (1, D) uniform freed lane

    old_pos_r = problem.nbr_pos[:, rows]  # (B, R, D, d)
    old_mask_r = problem.nbr_mask[:, rows]
    old_gram_r = problem.gram[:, rows]
    old_chol_r = problem.chol[:, rows]
    old_aw_r = problem.anchor_w[:, rows]  # (B, R, D)
    old_coef_r = state.coef[:, rows]
    pos_sh = jnp.take_along_axis(old_pos_r, src[None, :, :, None], axis=2)
    own_pos = topo.positions[jnp.clip(rows, 0, n - 1)].astype(dt)  # (R, d)
    new_pos_r = jnp.where(
        freed[None, :, :, None], own_pos[None, :, None, :], pos_sh
    )
    mask_sh = jnp.take_along_axis(old_mask_r, src[None], axis=2)
    new_mask_r = jnp.where(freed[None], False, mask_sh)
    coef_sh = jnp.take_along_axis(old_coef_r, src[None], axis=2)
    new_coef_r = jnp.where(freed[None], 0.0, coef_sh)
    # anchor weights shift down with their lanes; freed lanes reset to 1
    aw_sh = jnp.take_along_axis(old_aw_r, src[None], axis=2)
    new_aw_r = jnp.where(freed[None], jnp.ones((), aw_sh.dtype), aw_sh)
    g1 = jnp.take_along_axis(old_gram_r, src[None, :, :, None], axis=2)
    g2 = jnp.take_along_axis(g1, src[None, :, None, :], axis=3)
    g3 = jnp.where(
        freed[None, :, :, None] | freed[None, :, None, :], 0.0, g2
    )

    # Opt-in lambda repair (the join-side mirror): the affected rows'
    # degrees shrank by one, so lambda_i = kappa / |N_i|^2 re-derives from
    # the post-removal degree before the refactorization reads lam_pad.
    # repair=False writes the old floats back (bitwise no-op).
    deg_post = jnp.maximum(
        topo.degrees[jnp.clip(rows, 0, n - 1)] - 1, 1
    ).astype(problem.lam_pad.dtype)
    lam_fix = kappa / (deg_post * deg_post)
    do_fix = repair & nb
    lam_pad2 = problem.lam_pad.at[rows].set(
        jnp.where(do_fix, lam_fix, problem.lam_pad[rows])
    )
    problem = dataclasses.replace(problem, lam_pad=lam_pad2)

    # O(degree) masked refactorization of the affected rows only (the
    # deleted lane sits mid-factor, so no rank-1 downdate applies); the
    # victim's own factor resets to the identity a masked rebuild of a
    # fully-dead row produces.
    chol_r = _refactor_rows(problem, alive, rows, new_idx_r, new_mask_r, g3)
    eye = jnp.eye(d_max, dtype=g3.dtype)

    nbB = nb[None, :, None]
    # The victim's own row resets to the pristine slot table with cleared
    # occupancy: a dead row references nothing (its mask gates every
    # consumer), and a recycled spare restores bitwise to its build state.
    nbr_idx2 = problem.nbr_idx.at[rows].set(
        jnp.where(nb[:, None], new_idx_r, old_idx_r)
    ).at[sl].set(jnp.where(ok, lay.nbr_idx0[sl], problem.nbr_idx[sl]))
    nbr_pos2 = problem.nbr_pos.at[:, rows].set(
        jnp.where(nbB[..., None], new_pos_r, old_pos_r)
    )
    nbr_mask2 = problem.nbr_mask.at[:, rows].set(
        jnp.where(nbB, new_mask_r, old_mask_r)
    ).at[:, sl].set(jnp.where(ok, False, problem.nbr_mask[:, sl]))
    gram2 = problem.gram.at[:, rows].set(
        jnp.where(nbB[..., None], g3, old_gram_r)
    ).at[:, sl].set(jnp.where(ok, 0.0, problem.gram[:, sl]))
    chol2 = problem.chol.at[:, rows].set(
        jnp.where(nbB[..., None], chol_r, old_chol_r)
    ).at[:, sl].set(jnp.where(ok, eye, problem.chol[:, sl]))
    # the victim's own anchor weights reset to the pristine build state
    # (bitwise spare-row recycling: make_problem inits anchor_w to ones)
    anchor_w2 = problem.anchor_w.at[:, rows].set(
        jnp.where(nbB, new_aw_r, old_aw_r)
    ).at[:, sl].set(
        jnp.where(
            ok,
            jnp.ones((), problem.anchor_w.dtype),
            problem.anchor_w[:, sl],
        )
    )
    coef2 = state.coef.at[:, rows].set(
        jnp.where(nbB, new_coef_r, old_coef_r)
    ).at[:, sl].set(jnp.where(ok, 0.0, state.coef[:, sl]))
    deg2 = topo.degrees.at[rows].add(
        jnp.where(nb, -1, 0).astype(topo.degrees.dtype)
    ).at[sl].set(
        jnp.where(ok, 0, topo.degrees[sl]).astype(topo.degrees.dtype)
    )

    # The departed sensor's messages (own slot + its absorbed arrivals) and
    # stream positions reset to the unoccupied convention.
    owned = (lay.slot_owner == sl) & ok  # (n_z,)
    z = jnp.where(owned[None, :], 0.0, state.z)
    sp_owned = owned[n:-1]  # (S,)
    stream_pos = jnp.where(
        sp_owned[None, :, None], 0.0, problem.stream_pos
    )

    # Scatter-plan + color bookkeeping: every affected row's codes are
    # rewritten for its post-removal slot table (distinct colors — two
    # same-color rows sharing the victim would violate the distance-2
    # coloring), the victim's own codes revert to "keep", and its class
    # membership clears (freeing its recolor class, if it sat in one, for
    # a later join's conflict repair).
    c_r = problem.color_of[rows]
    m_r = problem.member_pos[rows]
    plan_z, plan_coef = plans.plan_rows_remove(
        problem.plan_z, problem.plan_coef, c_r, rows, old_idx_r, nb
    )
    plan_z, plan_coef = plans.plan_rows_add(
        plan_z, plan_coef, c_r, m_r, rows, new_idx_r, nb
    )
    plan_z, plan_coef = plans.color_plans_remove(
        plan_z, plan_coef, problem.color_of, sl, victim_idx, ok
    )
    cm, cmk = plans.members_clear(
        problem.color_members, problem.color_mask,
        problem.color_of[sl][None], problem.member_pos[sl][None],
        jnp.asarray(ok)[None], n,
    )

    problem = dataclasses.replace(
        problem,
        topology=dataclasses.replace(topo, degrees=deg2),
        nbr_idx=nbr_idx2,
        nbr_pos=nbr_pos2,
        nbr_mask=nbr_mask2,
        gram=gram2,
        chol=chol2,
        stream_pos=stream_pos,
        anchor_w=anchor_w2,
        alive=alive,
        plan_z=plan_z,
        plan_coef=plan_coef,
        color_members=cm,
        color_mask=cmk,
    )
    return problem, SNTrainState(z=z, coef=coef2), ok


_remove_sensor_copy = jax.jit(_remove_sensor_core)
_remove_sensor_donate = jax.jit(_remove_sensor_core, donate_argnums=(0, 1))


def remove_sensor(
    problem: SNTrainProblem,
    state: SNTrainState,
    slot: jax.Array,
    *,
    repair_lambda: bool = False,
    kappa: float = 0.01,
    donate: bool = False,
) -> tuple[SNTrainProblem, SNTrainState, jax.Array]:
    """A sensor LEAVES the network (mote death, battery, redeployment).

    The exact inverse of the symmetric join, entirely on device at fixed
    shapes and O(degree) work: flips ``alive`` (which also kills the
    sensor's reserved streaming slots via the slot-owner map), then — for
    exactly the rows the victim's own slot table lists (symmetry makes
    that the complete set of referencing rows, a static (D,)-padded
    gather) — DELETES each row's lane for the victim: lanes above it shift
    down one (arrivals stay contiguous, so ``absorb``'s fill invariant
    survives), the freed last lane restores the row's first orphaned
    reserved id (or retires to the inert sentinel id when none is left),
    and the O(degree) affected factors are refactorized in one batched
    masked Cholesky — never all n rows.  Scatter-plan codes of every
    repaired row are rewritten, the victim's own codes revert to "keep",
    its class membership clears (freeing its recolor class for later
    joins) and its messages reset.

    Works on any live row.  Removed SPARE rows are recycled by the next
    ``add_sensor``; removed base rows stay reserved for their original
    sensor (their reserved slot ids are position-bound).  Returns
    ``(problem, state, removed)``; removing a dead/out-of-range slot is a
    BITWISE no-op with ``removed`` False (state, plans and serving
    candidates untouched — tests/test_lifecycle.py).  A serving process
    also patches its query plan: ``serving.plan_remove_sensor(plan, slot)``.

    ``repair_lambda=True`` re-derives each affected row's regularizer from
    its post-removal degree (the paper rule lambda_i = kappa / |N_i|^2;
    mirror of ``add_sensor``'s repair) inside the refactorization this
    event already pays; default keeps build-time regularizers.

    ``donate=True`` has the ``absorb`` contract (rebind, drop the old
    buffers).
    """
    if not problem.batched:
        raise ValueError("lifecycle ops require a batched problem (use B = 1)")
    fn = _remove_sensor_donate if donate else _remove_sensor_copy
    return fn(
        problem, state, slot,
        jnp.asarray(repair_lambda, bool),
        jnp.asarray(kappa, problem.lam_pad.dtype),
    )
