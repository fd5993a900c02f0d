"""SN-Train: distributed kernel regression by alternating projections.

Faithful implementation of the paper's Table 1 / Eq. 18.  Each sensor ``s``
keeps a local function ``f_s = sum_{j in N_s} c_{s,j} K(., x_j)`` (Lemma 3.3)
and a shared message vector ``z in R^n`` (the network's running estimate of
the field at sensor locations).  One projection step at sensor s:

    c_{s,t} = (K_s + lambda_s I)^{-1} (z_{N_s, t-1} + lambda_s c_{s,t-1})
    z_j <- f_{s,t}(x_j)   for j in N_s

Three execution engines, all with identical fixed points:

  * ``serial_sweep``   — the paper's Table-1 ordering, one sensor at a time
                         (lax.scan over sensors).
  * ``colored_sweep``  — the paper's Sec-3.3 "Parallelism": all sensors of one
                         distance-2 color class update simultaneously as a
                         single batched solve (MXU-shaped), colors sweep
                         serially.  This is the TPU-native engine.
  * ``sharded_sweep``  — ``colored_sweep`` distributed with shard_map over a
                         device axis.

Fixed shapes everywhere: neighborhoods are padded to D_max, color classes to
M_max, and the message vector carries one sentinel slot (its last index) so
padded scatters are harmless.

Message-slot layout and scatter plans
-------------------------------------
z has ``n + n_stream + 1`` slots:

  [0, n)                 one per sensor (the paper's z vector);
  [n, n + n_stream)      RESERVED slots: every free padded neighborhood slot
                         (s, k >= deg_s) owns the fixed global message id
                         ``n + offset(s) + (k - deg_s)``.  Streaming arrivals
                         (repro.core.streaming) occupy these in place;
  n + n_stream           the write sentinel.

Because the reserved ids are assigned at build time, ``nbr_idx`` NEVER
diverges across fields or over time, and the distance-2 coloring makes every
message slot touched by a color class have a UNIQUE ``(member, lane)`` owner
within that class.  The whole color-step message/coefficient update is
therefore a *static permutation* known at ``make_problem`` time, precomputed
host-side as two int32 **scatter plans** per color ``c``:

  ``plan_z[c]``    (n_z,)   for every message slot: its own index (keep), or
                            ``n_z + m*D + k`` — take the value sensor
                            ``members[c, m]`` just computed for its lane
                            ``k``.  One gather from
                            ``concat([z, z_new.reshape(B, -1)], -1)``
                            realizes the entire update in O(n_z);
  ``plan_coef[c]`` (n+1,)   the same for coefficient rows: keep, or
                            ``(n+1) + m`` from the color's fresh solves.

Engine selection (``colored_sweep(..., engine=...)``):

  ``"plan"``   (default)  the static-gather realization above — O(n·D) per
                          full sweep on bounded-degree networks;
  ``"onehot"`` (reference) materializes the one-hot matrix
                          ``(M·D, n_z)`` and applies the update as two dense
                          GEMMs — O(n²) per sweep, kept as the independently
                          simple oracle the plans are tested against;
  ``"pallas"``            the plan engine with its local solves in the
                          Pallas kernel ``repro.kernels.color_step``: the
                          B·M systems sit on the lane axis, forward/back
                          substitution and the local (D,D)@(D,) GEMM run
                          per 128-lane tile; the gather and scatter around
                          it are the plan's (interpret mode off-TPU).

All three produce identical fixed points (plan == onehot bit-for-bit; see
tests/test_scatter_plan.py).  ``sharded_sweep`` reuses the plans to shrink
its per-color transport to the (M·D,) touched slot values instead of full
(n_z,) + (n+1, D) deltas.

The serving half of the system applies the same static-plan idea to the
paper's *testing phase*: ``repro.core.serving.make_serving_plan``
precomputes per-cell kNN candidate lists so ``fusion.fuse(rule="knn",
engine="plan"/"pallas")`` answers queries in O(Q·k·D) instead of the dense
O(Q·n·D) oracle — see the query-plan taxonomy in ``repro.core.fusion``.

Multi-field batching
--------------------
``make_batch_problem`` runs B independent regression problems ("fields")
over the same network in one program: per-field arrays gain a leading
``(B, ...)`` axis (``y: (B, n)``, ``z: (B, n+S+1)``, ``coef: (B, n+1, D)``,
``gram``/``chol``: ``(B, n+1, D, D)``), while ``nbr_idx``, regularizers and
the coloring stay shared.  The colored engine's local solves run as
fixed-shape triangular substitution vectorized over all B*M lanes at once —
2D scan steps of batched row operations instead of B*M LAPACK calls (also
measurably MORE accurate than batched LAPACK cho_solve in f32 at the
paper's ill-conditioned lambdas) — and its message updates are one-hot
GEMMs, so throughput scales with B (see benchmarks/multifield_bench.py).
``sharded_sweep`` shards the *field* axis across devices (fields are
independent, so the transport is pure data parallelism).  With B = 1 the
batched path IS the single-field path (same core, vmapped), asserted in
tests/test_multifield.py.

Network lifecycle (paper Sec. 3.3 "Robustness")
-----------------------------------------------
``make_problem(..., n_max=...)`` builds at CAPACITY: spare sensor rows
(parked far away, each with a reserved singleton color — see
``repro.core.plans``) plus the reserved-slot streaming layout give every
membership operation a fixed-shape realization.  The problem carries a
device-side ``alive`` row mask and a ``layout`` (slot ownership, color
assignments, pristine slot tables); every sweep engine gates on it:

  * dead members never update (their scatters degrade to "keep" in all of
    plan/onehot/pallas — pallas shares the plan's gated scatter);
  * dead rows' message slots — and, via the slot-owner map, their absorbed
    arrivals' slots — drop out of every gather;
  * at all-True liveness the gates are identities BIT-FOR-BIT.

PERSISTENT membership changes go through ``streaming.add_sensor`` /
``remove_sensor``.  Joins are SYMMETRIC (the paper's Eq. 10-12 coupling):
the newcomer adopts its live in-radius neighbors AND each adopter grows a
reciprocal anchor lane at the new position, so the post-join problem
encodes exactly the constraint sets a from-scratch ``make_problem`` on
the post-join topology would (tests pin the repaired scatter plans
bitwise against the host builder, and the training iterates to <= 1e-5
against a fresh build).  Reciprocal lanes can put two same-color adopters
in conflict under the distance-2 rule; the event resolves that on device
(``plans.resolve_join_conflicts``) by moving all but one adopter per
color into reserved empty recolor classes — which is why the color
member tables / row->color maps are mutable problem state (seeded from
the topology, patched by events, scanned by every colored engine).  Both
events repair O(degree) rows only: lane insertions/deletions plus ONE
batched masked refactorization of the affected factors — never all n
(benchmarks/churn_bench.py ``--per-event`` tracks the flat-in-n curve).
Each event also patches the query-plan candidate lists
(``serving.plan_add_sensor`` / ``plan_remove_sensor``), and an arbitrary
join/leave/absorb/sweep/query trace compiles a constant number of
programs (jit-cache-counted in tests/test_lifecycle.py).  TRANSIENT
failures go through ``robust_sweep``, which refactorizes the masked
systems per sweep (no event, no patched factors) but dispatches the same
alive-masked colored engines — batched, engine-selectable, and
bitwise-equal to ``colored_sweep`` at full liveness on arrival-free
problems.  The single-field extensions (``weighted_sweep``,
``robust_sweep_links``) thread the same liveness masks: dead sensors
neither update nor are read anywhere.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat

from . import plans
from .kernels_math import Kernel
from .plans import LifecycleLayout
from .topology import SensorTopology, pad_topology


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SNTrainProblem:
    """Static per-network precomputation for SN-Train.

    All arrays are padded to fixed shapes. ``n`` below is the sensor count,
    ``D`` the padded neighborhood size, ``S`` the reserved streaming capacity
    (``n_stream``).  Single-field problems carry the shapes written below;
    batched problems (``make_batch_problem``) prepend a field axis ``B`` to
    ``y``, ``nbr_pos``, ``nbr_mask``, ``gram``, ``chol`` and ``stream_pos``
    (``nbr_idx`` and the scatter plans stay shared — reserved ids and the
    coloring are fixed).
    """

    topology: SensorTopology
    kernel: Kernel = dataclasses.field(metadata=dict(static=True))
    y: jnp.ndarray  # (n,) measurements
    lambdas: jnp.ndarray  # (n,) per-sensor regularizers
    nbr_pos: jnp.ndarray  # (n+1, D, d) neighbor positions (padded row n)
    nbr_idx: jnp.ndarray  # (n+1, D) message-slot ids (reserved ids on free slots)
    nbr_mask: jnp.ndarray  # (n+1, D)
    gram: jnp.ndarray  # (n+1, D, D) masked local Gram K_s (zeros off-mask)
    chol: jnp.ndarray  # (n+1, D, D) lower Cholesky of K_s + lambda_s I (padded dims get identity)
    lam_pad: jnp.ndarray  # (n+1,)
    stream_pos: jnp.ndarray  # (S, d) arrival positions (zeros until absorbed)
    plan_z: jnp.ndarray  # (n_colors, n_z) color-step gather plan for z
    plan_coef: jnp.ndarray  # (n_colors, n+1) color-step gather plan for coef
    # Mutable color assignment (shared across fields): symmetric joins can
    # recolor adopters into the reserved recolor classes, so the member
    # tables the colored engines scan — and the row -> (color, position)
    # maps the event repairs read — are problem state, seeded from the
    # topology's build-time tables.
    color_members: jnp.ndarray  # (n_colors, M) member rows per color class
    color_mask: jnp.ndarray  # (n_colors, M) validity of color_members
    color_of: jnp.ndarray  # (n+1,) color id per row (sentinel: n_colors)
    member_pos: jnp.ndarray  # (n+1,) position of each row in its color
    alive: jnp.ndarray  # (n+1,) bool row liveness, shared across fields; the
    # sentinel row n is PERMANENTLY dead — retired lanes point at its slot,
    # and its deadness keeps them retired when spare rows are recycled

    # Exponential forgetting (EW-RLS, Mateos & Giannakis arXiv:1109.4627)
    # for time-varying fields.  ``beta`` is the per-field forgetting factor
    # ((B,) batched, scalar single-field; 1.0 = the paper's static field).
    # ``anchor_w`` holds the per-lane representer anchor weight
    # omega = beta^(age/2): each absorb at (field, sensor) multiplies the
    # sensor's occupied STREAM lanes' omega by sqrt(beta) — structural
    # lanes never decay (they carry the network's live messages, not
    # time-stamped data).  The invariants the streaming tick maintains:
    #
    #   gram[b,s,i,j] = omega_i * omega_j * K(x_i, x_j)   (decay in place)
    #   chol[b,s]     = chol(gram + diag(occupied ? lambda_s : 1))
    #   z[b, slot_j]  = omega_j * (message value)          (stream slots)
    #
    # lambda is NEVER decayed, so every factor-rebuild path (evict's
    # downdate, rebuild_chol, robust_sweep's _masked_factors, the
    # lifecycle _refactor_rows) and every sweep engine (serial / colored
    # plan|onehot|pallas / sharded / robust) consumes the forgetting state
    # through these arrays UNCHANGED, and each local solve is exactly the
    # w-weighted regularized projection min_f sum_j w_j (z_j - f(x_j))^2
    # + lambda_s ||f||^2 with w_j = omega_j^2 (in omega-scaled coordinates
    # — the stored coef is v with TRUE representer coefficients
    # a = anchor_w * v; external evaluators multiply through, see
    # ``fusion``/``serving``).  With beta = 1.0 every tick multiplies by
    # exactly 1.0 and is gated bitwise (tests/test_streaming_beta.py).
    beta: jnp.ndarray  # () / (B,) per-field forgetting factor in (0, 1]
    anchor_w: jnp.ndarray  # (n+1, D) / (B, n+1, D) per-lane anchor weights

    layout: LifecycleLayout  # event-invariant lifecycle metadata (repro.core.plans)
    n_stream: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def n(self) -> int:
        return self.topology.n

    @property
    def batched(self) -> bool:
        """True when arrays carry a leading field axis (multi-field batch)."""
        return self.y.ndim == 2

    @property
    def batch_size(self) -> int | None:
        return int(self.y.shape[0]) if self.batched else None

    @property
    def sentinel(self) -> int:
        """Index of the write-sentinel slot of z (== n + n_stream)."""
        return self.n + self.n_stream

    @property
    def n_z(self) -> int:
        """Length of the message vector including the sentinel."""
        return self.n + self.n_stream + 1

    @property
    def n_base(self) -> int:
        """Build-time sensor count; rows [n_base, n) are join capacity."""
        return self.layout.n_base

    @property
    def alive_z(self) -> jnp.ndarray:
        """(n_z,) message-slot liveness (a slot lives with its owning row)."""
        return plans.alive_slots(self.alive, self.layout.slot_owner)

    @property
    def recolor_start(self) -> int:
        """First reserved recolor class (the pool symmetric joins use)."""
        return int(self.color_members.shape[0]) - self.topology.n_recolor


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SNTrainState:
    z: jnp.ndarray  # (n+S+1,) messages; the last slot is a write sentinel
    coef: jnp.ndarray  # (n+1, D) per-sensor representer coefficients


def default_lambdas(topology: SensorTopology, kappa: float = 0.01) -> jnp.ndarray:
    """Paper Sec. 4.1: lambda_i = kappa / |N_i|^2 with kappa = 0.01.

    Spare rows (degree 0) get a placeholder of 1.0; ``streaming.add_sensor``
    installs the joined sensor's regularizer.
    """
    deg = topology.degrees.astype(jnp.float32)
    return jnp.where(deg > 0, kappa / jnp.maximum(deg, 1) ** 2, 1.0)


def _pad_per_sensor(arr: jax.Array, n: int, fill) -> jax.Array:
    """Pad an (n_base,)-shaped per-sensor vector to capacity ``n``."""
    short = n - arr.shape[-1]
    if short == 0:
        return arr
    if short < 0:
        raise ValueError(f"per-sensor array longer ({arr.shape[-1]}) than n={n}")
    pad = jnp.full(arr.shape[:-1] + (short,), fill, arr.dtype)
    return jnp.concatenate([arr, pad], axis=-1)


def make_problem(
    topology: SensorTopology,
    kernel: Kernel,
    y: jax.Array,
    lambdas: jax.Array | None = None,
    *,
    dtype=jnp.float32,
    n_max: int | None = None,
    beta: float = 1.0,
) -> SNTrainProblem:
    """Precompute the padded SN-Train problem.

    dtype: float32 is the TPU-friendly default, but the paper's own
    regularizers (lambda_i = 0.01/|N_i|^2 ~ 1e-5) make the local systems
    condition at ~1e9 where f32 solves systematically violate the projection
    property (the weighted norm grows and the sweep diverges).  Pass
    jnp.float64 (with JAX_ENABLE_X64) to reproduce the paper's numerics;
    alternatively raise lambda (see tests/test_sn_train.py).

    Streaming capacity is implied by the topology's padding: every free
    neighborhood slot (build the topology with ``d_max`` headroom to get
    more) owns a reserved message slot that arrivals can occupy
    (repro.core.streaming).

    n_max: lifecycle capacity — pads the topology with ``n_max - n`` spare
    sensor rows (reserved singleton colors, see ``topology.pad_topology``)
    so ``streaming.add_sensor`` / ``remove_sensor`` can churn membership at
    fixed shapes, recompile-free.  ``y``/``lambdas`` may be given at the
    base length and are padded (0 / 1.0) over the spare rows.

    beta: forgetting factor in (0, 1] for time-varying fields (see the
    ``SNTrainProblem`` field docs); 1.0 (default) reproduces the paper's
    static estimator bitwise.
    """
    if not 0.0 < float(beta) <= 1.0:
        raise ValueError(f"beta must be in (0, 1], got {beta}")
    if n_max is not None:
        topology = pad_topology(topology, n_max)
    n, d_max = topology.nbr_idx.shape
    d = topology.positions.shape[1]
    n_base = topology.n_base if topology.n_base >= 0 else n
    if lambdas is None:
        lambdas = default_lambdas(topology)
    lambdas = _pad_per_sensor(jnp.asarray(lambdas, dtype), n, 1.0)
    y = _pad_per_sensor(jnp.asarray(y, dtype), n, 0.0)

    # Assign every free padded slot its fixed reserved message id, and give
    # the sentinel row n the sentinel id (duplicate writes there carry 0s).
    # Spare rows are dead at build: their color plans start at "keep" and
    # their rows are fully reserved capacity.
    idx_full, n_stream = plans.assign_stream_slots(
        np.asarray(topology.nbr_idx), np.asarray(topology.degrees)
    )
    nbr_idx = jnp.asarray(idx_full, jnp.int32)
    # Row liveness: base rows alive, spare rows dead until a join claims
    # them.  The sentinel row n is DEAD: lanes retired by remove_sensor
    # point at the sentinel slot, and its deadness is what keeps them
    # retired when a spare row is recycled.  (Padded color members and
    # sentinel lanes are already occupancy-masked, so this costs nothing.)
    alive0 = np.arange(n + 1) < n_base
    plan_z, plan_coef = plans.build_color_plans(
        np.asarray(topology.color_members),
        np.asarray(topology.color_mask),
        idx_full,
        n_stream,
        alive0,
    )
    layout = plans.build_layout(idx_full, n_stream, n_base)
    color_of, member_pos = plans.color_assignments(
        np.asarray(topology.colors),
        np.asarray(topology.color_members),
        np.asarray(topology.color_mask),
    )
    nbr_mask = jnp.concatenate(
        [topology.nbr_mask, jnp.zeros((1, d_max), bool)], axis=0
    )
    # Positions of free slots are placeholders (the sensor's own position,
    # the topology's padding convention) until streaming overwrites them.
    pos_pad = jnp.concatenate(
        [topology.positions.astype(dtype), jnp.zeros((1, d), dtype)], axis=0
    )
    nbr_pos = pos_pad[
        jnp.concatenate([topology.nbr_idx, jnp.full((1, d_max), n, jnp.int32)])
    ]  # (n+1, D, d)
    lam_pad = jnp.concatenate([lambdas, jnp.ones((1,), dtype)])

    def local_system(pos_s, mask_s, lam_s):
        k = kernel(pos_s, pos_s)  # (D, D)
        outer = mask_s[:, None] & mask_s[None, :]
        k = jnp.where(outer, k, 0.0)
        # Solve matrix: valid block gets +lambda on the diagonal; padded
        # diagonal entries are set to 1 so the factorization is SPD and the
        # padded coefficients stay exactly 0 (their rhs is 0).
        diag = jnp.where(mask_s, lam_s, 1.0)
        a = k + jnp.diag(diag)
        return k, jsl.cholesky(a, lower=True)

    gram, chol = jax.vmap(local_system)(nbr_pos, nbr_mask, lam_pad)
    return SNTrainProblem(
        topology=topology,
        kernel=kernel,
        y=y,
        lambdas=lambdas,
        nbr_pos=nbr_pos,
        nbr_idx=nbr_idx,
        nbr_mask=nbr_mask,
        gram=gram,
        chol=chol,
        lam_pad=lam_pad,
        stream_pos=jnp.zeros((n_stream, d), dtype),
        plan_z=jnp.asarray(plan_z),
        plan_coef=jnp.asarray(plan_coef),
        # distinct buffers from the topology's tables (the problem pytree
        # carries both; aliased buffers would break donate=True dispatch)
        color_members=jnp.asarray(
            np.asarray(topology.color_members), jnp.int32
        ),
        color_mask=jnp.asarray(np.asarray(topology.color_mask), bool),
        color_of=jnp.asarray(color_of),
        member_pos=jnp.asarray(member_pos),
        alive=jnp.asarray(alive0),
        beta=jnp.asarray(beta, dtype),
        anchor_w=jnp.ones((n + 1, d_max), dtype),
        layout=layout,
        n_stream=n_stream,
    )


def make_batch_problem(
    topology: SensorTopology,
    kernel: Kernel,
    ys: jax.Array,
    lambdas: jax.Array | None = None,
    *,
    dtype=jnp.float32,
    n_max: int | None = None,
    beta: float | jax.Array = 1.0,
) -> SNTrainProblem:
    """B independent fields over one network: ``ys`` is (B, n).

    Geometry (topology, regularizers, message-slot ids, liveness) is
    shared; the per-field ``nbr_pos``/``nbr_mask``/``gram``/``chol``/
    ``stream_pos``/``anchor_w`` arrays start as B identical copies and
    diverge only under streaming absorption.  ``n_max`` reserves lifecycle
    capacity as in ``make_problem``.

    beta: per-field forgetting factors — a scalar (shared) or a (B,)
    vector, so one batch can mix static (beta = 1.0) and time-varying
    (beta < 1) fields; each field's absorbs decay that field only.
    """
    ys = jnp.asarray(ys, dtype)
    if ys.ndim != 2:
        raise ValueError(f"ys must be (B, n), got shape {ys.shape}")
    base = make_problem(topology, kernel, ys[0], lambdas, dtype=dtype, n_max=n_max)
    ys = _pad_per_sensor(ys, base.n, 0.0)
    b = ys.shape[0]
    beta = jnp.broadcast_to(jnp.asarray(beta, dtype), (b,))
    if not bool(jnp.all((beta > 0.0) & (beta <= 1.0))):
        raise ValueError(f"beta must be in (0, 1] per field, got {beta}")

    def tile(a):
        return jnp.broadcast_to(a[None], (b,) + a.shape)

    return dataclasses.replace(
        base,
        y=ys,
        nbr_pos=tile(base.nbr_pos),
        nbr_mask=tile(base.nbr_mask),
        gram=tile(base.gram),
        chol=tile(base.chol),
        stream_pos=tile(base.stream_pos),
        beta=beta,
        anchor_w=tile(base.anchor_w),
    )


def field_view(
    problem: SNTrainProblem, state: SNTrainState, b: int
) -> tuple[SNTrainProblem, SNTrainState]:
    """Single-field view of field ``b`` of a batched problem/state."""
    if not problem.batched:
        raise ValueError("field_view expects a batched problem")
    prob = dataclasses.replace(
        problem,
        y=problem.y[b],
        nbr_pos=problem.nbr_pos[b],
        nbr_mask=problem.nbr_mask[b],
        gram=problem.gram[b],
        chol=problem.chol[b],
        stream_pos=problem.stream_pos[b],
        beta=problem.beta[b],
        anchor_w=problem.anchor_w[b],
    )
    return prob, SNTrainState(z=state.z[b], coef=state.coef[b])


def weighted_norm_sq(problem: SNTrainProblem, state: SNTrainState) -> jax.Array:
    """The SOP product-space norm  ||z||^2 + sum_i lambda_i ||f_i||^2_{H_K}.

    By Lemma 2.1 (0 is in the intersection C, all C_i are subspaces) this is
    non-increasing along ANY admissible SOP ordering — the invariant the
    property tests assert.  Note ||f_i||^2 = c_i^T K_i c_i.  Batched inputs
    return one norm per field, shape (B,).

    Forgetting (beta < 1): ``gram`` and the stream slots of ``z`` carry the
    anchor weights in place, so this expression IS the w-weighted product
    norm sum_j w_j z_j^2 + sum_i lambda_i ||f_i||^2 — the norm each
    weighted projection is orthogonal in.  It stays non-increasing across
    sweeps BETWEEN forgetting ticks; each absorb tick rescales the norm
    itself (the steady-state-error bound of tests/test_streaming_beta.py
    replaces cross-tick Fejér monotonicity).
    """
    z_part = jnp.sum(state.z[..., :-1] ** 2, axis=-1)  # excludes the sentinel
    quad = jnp.einsum(
        "...sd,...sde,...se->...s", state.coef, problem.gram, state.coef,
        precision="highest",
    )
    return z_part + jnp.sum(problem.lam_pad * quad, axis=-1)


def init_state(problem: SNTrainProblem) -> SNTrainState:
    """Paper Table 1 initialization: z_{s,0} = y_s, f_{s,0} = 0.

    Reserved stream slots and the sentinel start at 0 (they contribute
    nothing to the weighted norm until an arrival is absorbed).
    """
    n = problem.n
    d_max = problem.nbr_idx.shape[-1]
    dt = problem.y.dtype
    pad = problem.n_stream + 1
    if problem.batched:
        b = problem.batch_size
        z = jnp.concatenate([problem.y, jnp.zeros((b, pad), dt)], axis=-1)
        coef = jnp.zeros((b, n + 1, d_max), dt)
    else:
        z = jnp.concatenate([problem.y, jnp.zeros((pad,), dt)])
        coef = jnp.zeros((n + 1, d_max), dt)
    return SNTrainState(z=z, coef=coef)


def effective_coef(problem: SNTrainProblem, state: SNTrainState) -> jax.Array:
    """TRUE representer coefficients a = anchor_w * coef.

    The sweep engines store coefficients in omega-scaled coordinates (see
    the ``SNTrainProblem.anchor_w`` docs): the field estimate is
    f_s(x) = sum_j anchor_w[s, j] * coef[s, j] * K(x, x_j).  Everything
    INSIDE the training loop consumes gram/chol/z, which carry the weights
    in place; evaluators that expand f_s against raw kernel values
    (``fusion``, ``serving``, the Pallas knn_fuse / kernel_matvec serving
    kernels) must evaluate these effective coefficients instead.  With
    beta = 1.0 ``anchor_w`` is exactly 1.0 everywhere and this is a
    bitwise identity.
    """
    return state.coef * problem.anchor_w.astype(state.coef.dtype)


def _sensor_update(z, coef_s, nbr_idx_s, nbr_mask_s, gram_s, chol_s, lam_s):
    """One P_{C_s} projection (Eq. 18). Returns (coef_s', z-values at N_s)."""
    z_nbr = z[nbr_idx_s]  # (D,)
    rhs = jnp.where(nbr_mask_s, z_nbr + lam_s * coef_s, 0.0)
    coef_new = jsl.cho_solve((chol_s, True), rhs)
    z_new = jnp.matmul(gram_s, coef_new, precision="highest")  # f_s at N_s
    return coef_new, z_new


# ---------------------------------------------------------------------------
# Serial engine (the paper's Table-1 ordering; cho_solve per sensor).
# ---------------------------------------------------------------------------


def _serial_core(
    nbr_idx, nbr_mask, gram, chol, lam_pad, sentinel, z, coef, order, n_sweeps,
    alive_row, alive_slot, delivered=None,
):
    def make_body(deliv_t):
        def body(carry, s):
            z, coef = carry
            # Effective neighborhood: padded occupancy & slot/row liveness (a
            # dead sensor neither updates nor is heard from; identity when the
            # network is fully alive).
            mask_s = nbr_mask[s] & alive_slot[nbr_idx[s]] & alive_row[s]
            coef_new, z_new = _sensor_update(
                z, coef[s], nbr_idx[s], mask_s, gram[s], chol[s], lam_pad[s]
            )
            coef = coef.at[s].set(jnp.where(alive_row[s], coef_new, coef[s]))
            # Unreliable links (repro.core.faults): a dropped lane's WRITE
            # never lands — the stale message persists (hold-last-value,
            # the dead-target-slot semantics) while the local coefficient
            # update above still runs (compute is local).
            send = mask_s if deliv_t is None else mask_s & deliv_t[s]
            scatter_idx = jnp.where(send, nbr_idx[s], sentinel)
            z = z.at[scatter_idx].set(jnp.where(send, z_new, z[sentinel]))
            return (z, coef), None

        return body

    if delivered is None:
        body = make_body(None)

        def sweep(carry, _):
            carry, _ = jax.lax.scan(body, carry, order)
            return carry, None

        (z, coef), _ = jax.lax.scan(sweep, (z, coef), None, length=n_sweeps)
    else:

        def sweep(carry, deliv_t):
            carry, _ = jax.lax.scan(make_body(deliv_t), carry, order)
            return carry, None

        (z, coef), _ = jax.lax.scan(sweep, (z, coef), delivered)
    return z, coef


@partial(jax.jit, static_argnames=("n_sweeps",))
def serial_sweep(
    problem: SNTrainProblem,
    state: SNTrainState,
    n_sweeps: int = 1,
    *,
    delivered: jax.Array | None = None,
) -> SNTrainState:
    """The paper's Table-1 serial ordering: for t: for s: project.

    Batched problems run every field's serial sweep simultaneously (vmap over
    the field axis).

    delivered: optional (n_sweeps, n+1, D) bool per-sweep link-delivery
    mask (repro.core.faults), shared across fields; a dropped lane's
    message write never lands (hold-last-value).  All-True is bitwise
    the fault-free sweep."""
    order = jnp.arange(problem.n, dtype=jnp.int32)
    core = partial(
        _serial_core,
        nbr_idx=problem.nbr_idx,
        lam_pad=problem.lam_pad,
        sentinel=problem.sentinel,
        order=order,
        n_sweeps=n_sweeps,
        alive_row=problem.alive,
        alive_slot=problem.alive_z,
        delivered=delivered,
    )
    run = lambda nm, g, ch, z, c: core(
        nbr_mask=nm, gram=g, chol=ch, z=z, coef=c
    )
    if problem.batched:
        run = jax.vmap(run)
    z, coef = run(
        problem.nbr_mask, problem.gram, problem.chol, state.z, state.coef
    )
    return SNTrainState(z=z, coef=coef)


# ---------------------------------------------------------------------------
# Colored engine.  Field axis is explicit (B = 1 for single-field problems);
# local solves are fixed-shape triangular substitution vectorized over all
# B*M lanes (2D scan steps of batched row ops — no per-matrix LAPACK calls,
# and empirically tighter f32 error than batched cho_solve at the paper's
# ill-conditioned lambdas).  The message/coefficient updates are EXACT
# writes: within one color class every touched message slot has a unique
# owner (distance-2 coloring makes same-color neighborhoods disjoint;
# reserved slots are per-sensor), realized either as the precomputed static
# gather plans ("plan"/"pallas") or as the dense one-hot matmul reference
# ("onehot") — see the module docstring for the engine taxonomy.
# ---------------------------------------------------------------------------


def _tri_solve_spd(chol, rhs):
    """(L L^T)^{-1} rhs by forward+back substitution over the last axis.

    chol: (..., D, D) lower factors (padded rows identity), rhs: (..., D).
    Vectorized over every leading batch dim; each of the 2D scan steps is a
    batched row operation, so cost amortizes across B*M lanes.
    """
    d = chol.shape[-1]
    eye = jnp.eye(d, dtype=chol.dtype)
    rows = jnp.moveaxis(chol, -2, 0)  # (D, ..., D) rows of L
    cols = jnp.moveaxis(chol, -1, 0)  # (D, ..., D) rows of L^T
    rhs_r = jnp.moveaxis(rhs, -1, 0)  # (D, ...)

    def fwd(y, inp):
        li, bi, ei = inp
        yi = (bi - jnp.sum(li * y, axis=-1)) / jnp.sum(li * ei, axis=-1)
        return y + yi[..., None] * ei, None

    y, _ = jax.lax.scan(fwd, jnp.zeros_like(rhs), (rows, rhs_r, eye))

    def bwd(x, inp):
        ui, yi, ei = inp
        xi = (yi - jnp.sum(ui * x, axis=-1)) / jnp.sum(ui * ei, axis=-1)
        return x + xi[..., None] * ei, None

    x, _ = jax.lax.scan(
        bwd, jnp.zeros_like(rhs), (cols, jnp.moveaxis(y, -1, 0), eye),
        reverse=True,
    )
    return x


def _local_solve(chol_m, gram_m, rhs):
    """XLA local solves: (coef_new, z_new) = ((K_s + lambda_s I)^{-1} rhs,
    K_s @ coef_new) per lane; ``kernels.color_step.color_solve`` is the
    Pallas twin."""
    coef_new = _tri_solve_spd(chol_m, rhs)
    z_new = jnp.einsum("bmij,bmj->bmi", gram_m, coef_new, precision="highest")
    return coef_new, z_new


def _color_solve(
    nbr_idx, lam_pad, alive_row, alive_slot, nbr_mask, gram, chol, z, coef,
    members, member_mask, *, local_solve=_local_solve,
):
    """Simultaneous P_{C_s} local solves for one color, all B fields.

    Shapes: z (B, NZ); coef (B, n+1, D); nbr_idx (n+1, D) shared;
    nbr_mask/gram/chol per-field; members (M,), member_mask (M,);
    alive_row (n+1,) / alive_slot (n_z,) shared liveness.  Dead members
    solve to exact zeros (masked rhs) and dead neighbors/slots drop out of
    every rhs; at all-True liveness the masks are identities and the floats
    are bit-for-bit those of the lifecycle-free engine.
    Returns (idx_m (M, D), coef_new (B, M, D), z_new (B, M, D)); the engines
    differ only in how they solve (``local_solve``) and scatter these back.
    """
    with jax.named_scope("plan_gather"):
        idx_m = nbr_idx[members]  # (M, D) shared across fields
        live_m = member_mask & alive_row[members]  # (M,) updating members
        mask_m = (
            nbr_mask[:, members]
            & live_m[None, :, None]
            & alive_slot[idx_m][None]
        )  # (B, M, D)
        gram_m = gram[:, members]  # (B, M, D, D)
        chol_m = chol[:, members]  # (B, M, D, D)
        lam_m = lam_pad[members]  # (M,)
        coef_m = coef[:, members]  # (B, M, D)

        b = z.shape[0]
        z_nbr = z[:, idx_m.reshape(-1)].reshape(b, *idx_m.shape)  # (B, M, D)
        rhs = jnp.where(mask_m, z_nbr + lam_m[None, :, None] * coef_m, 0.0)
    with jax.named_scope("colour_solve"):
        coef_new, z_new = local_solve(chol_m, gram_m, rhs)
    return idx_m, coef_new, z_new


def _apply_plan(
    z, coef, z_new, coef_new, plan_z_c, plan_coef_c, live_m, alive_slot,
    deliv_flat=None,
):
    """Static-gather realization of the color-step scatter: O(n_z + n*D).

    Scatter codes whose source member OR target message slot is DEAD
    degrade to "keep" at runtime (transient liveness — robust_sweep —
    never patches the plans; lifecycle events patch them too, in which
    case the gates agree).  Target gating matches the paper's physics: a
    down mote's own message slot is unreachable, so its last value
    persists (exactly what the serial engine's masked scatter does).
    Coefficient rows need no target gate — a row's only writer is its own
    sensor, so source and target liveness coincide.

    deliv_flat: optional (M*D,) per-lane delivery gate in the color's
    flat member order (repro.core.faults) — an UNDELIVERED lane's
    message code degrades to "keep" exactly like a dead slot, while the
    coefficient scatter is untouched (the local solve still happened).
    """
    b, n_z = z.shape
    d = z_new.shape[-1]
    zc = jnp.concatenate([z, z_new.reshape(b, -1)], axis=-1)[:, plan_z_c]
    src_m = jnp.clip((plan_z_c - n_z) // d, 0, live_m.shape[0] - 1)
    fresh_ok = live_m[src_m] & alive_slot
    if deliv_flat is not None:
        lane = jnp.clip(plan_z_c - n_z, 0, deliv_flat.shape[0] - 1)
        fresh_ok = fresh_ok & deliv_flat[lane]
    use = (plan_z_c < n_z) | fresh_ok
    z = jnp.where(use[None, :], zc, z)
    n_rows = coef.shape[1]
    cc = jnp.concatenate([coef, coef_new], axis=1)[:, plan_coef_c]
    srcc = jnp.clip(plan_coef_c - n_rows, 0, live_m.shape[0] - 1)
    usec = (plan_coef_c < n_rows) | live_m[srcc]
    coef = jnp.where(usec[None, :, None], cc, coef)
    return z, coef


def _apply_onehot(
    z, coef, z_new, coef_new, idx_m, members, n_z, n_rows, live_m, alive_slot,
    deliv_flat=None,
):
    """Dense one-hot reference realization: O(M*D*n_z) GEMMs per color.

    Exact because slot ids are unique within a color; the sentinel id may
    repeat but only ever receives zeros, 0 * (1-hit) == 0.  Dead members'
    one-hot ROWS and dead slots' one-hot COLUMNS are zeroed, realizing the
    same source/target "keep" gates as the plan gather; an undelivered
    lane (``deliv_flat``, repro.core.faults) zeroes its one-hot ROW the
    same way — the message never lands, the slot keeps its value.
    """
    b = z.shape[0]
    d = idx_m.shape[-1]
    flat_idx = idx_m.reshape(-1)  # (M*D,)
    live_f = jnp.repeat(live_m, d).astype(z.dtype)  # (M*D,)
    if deliv_flat is not None:
        live_f = live_f * deliv_flat.astype(z.dtype)
    oh = (flat_idx[:, None] == jnp.arange(n_z)[None, :]).astype(z.dtype)
    oh = oh * live_f[:, None] * alive_slot.astype(z.dtype)[None, :]
    hit = oh.sum(axis=0)  # (NZ,)
    z = z * (1.0 - hit)[None, :] + jnp.einsum(
        "kz,bk->bz", oh, z_new.reshape(b, -1), precision="highest"
    )
    # One-hot coefficient scatter over member rows (padded members are the
    # sentinel sensor row n whose update is exactly 0).
    ohm = (members[:, None] == jnp.arange(n_rows)[None, :]).astype(coef.dtype)
    ohm = ohm * live_m.astype(coef.dtype)[:, None]
    hitm = ohm.sum(axis=0)  # (n+1,)
    coef = coef * (1.0 - hitm)[None, :, None] + jnp.einsum(
        "mn,bmd->bnd", ohm, coef_new, precision="highest"
    )
    return z, coef


ENGINES = ("plan", "onehot", "pallas")


def _colored_core(
    problem: SNTrainProblem, nbr_mask, gram, chol, z, coef, n_sweeps,
    engine: str = "plan",
    alive=None,
    delivered=None,
):
    """Batched colored sweep over explicitly-leading field axes.

    ``alive`` overrides the problem's persistent row liveness (used by
    ``robust_sweep`` for per-sweep transient liveness); all engines gate
    dead members' updates and dead slots' reads, reducing bit-for-bit to
    the lifecycle-free sweep at all-True liveness.

    ``delivered`` is the optional (n_sweeps, n+1, D) per-sweep
    link-delivery mask (repro.core.faults), shared across fields: an
    undelivered lane's message write degrades to "keep" in every engine
    (hold-last-value), the coefficient update is untouched, and
    all-True is bitwise the fault-free sweep.  ``None`` keeps the
    fault-free scan structure unchanged.
    """
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    alive_row = problem.alive if alive is None else alive
    alive_slot = plans.alive_slots(alive_row, problem.layout.slot_owner)
    if engine == "pallas":
        from repro.kernels.color_step import color_solve as local_solve
    else:
        local_solve = _local_solve
    solve = partial(
        _color_solve, problem.nbr_idx, problem.lam_pad, alive_row, alive_slot,
        local_solve=local_solve,
    )
    # The member tables are problem state (symmetric joins recolor), so a
    # churned problem sweeps its CURRENT classes with zero recompilation.
    xs = (
        problem.color_members, problem.color_mask,
        problem.plan_z, problem.plan_coef,
    )

    def make_color_body(deliv_t):
        def color_body(carry, cm):
            z, coef = carry
            members, member_mask, plan_z_c, plan_coef_c = cm
            live_m = member_mask & alive_row[members]
            deliv_flat = (
                None if deliv_t is None else deliv_t[members].reshape(-1)
            )
            idx_m, coef_new, z_new = solve(
                nbr_mask, gram, chol, z, coef, members, member_mask
            )
            if engine == "onehot":
                z, coef = _apply_onehot(
                    z, coef, z_new, coef_new, idx_m, members,
                    problem.n_z, problem.n + 1, live_m, alive_slot,
                    deliv_flat,
                )
            else:
                with jax.named_scope("plan_scatter"):
                    z, coef = _apply_plan(
                        z, coef, z_new, coef_new, plan_z_c, plan_coef_c,
                        live_m, alive_slot, deliv_flat,
                    )
            return (z, coef), None

        return color_body

    if delivered is None:
        color_body = make_color_body(None)

        def sweep(carry, _):
            carry, _ = jax.lax.scan(color_body, carry, xs)
            return carry, None

        (z, coef), _ = jax.lax.scan(sweep, (z, coef), None, length=n_sweeps)
    else:

        def sweep(carry, deliv_t):
            carry, _ = jax.lax.scan(make_color_body(deliv_t), carry, xs)
            return carry, None

        (z, coef), _ = jax.lax.scan(sweep, (z, coef), delivered)
    return z, coef


@partial(jax.jit, static_argnames=("n_sweeps", "engine"))
def colored_sweep(
    problem: SNTrainProblem,
    state: SNTrainState,
    n_sweeps: int = 1,
    *,
    engine: str = "plan",
    delivered: jax.Array | None = None,
) -> SNTrainState:
    """Distance-2-colored parallel SOP (paper Sec. 3.3 'Parallelism').

    Single-field problems run the same core with B = 1 (so batched B=1 and
    single-field results are identical by construction).

    engine: "plan" (static scatter plans, the O(n*D) default), "onehot"
    (dense one-hot GEMM reference, O(n^2)) or "pallas" (the plan engine
    with its local solves in the Pallas color-step kernel).  All three share the local solves and produce identical fixed
    points; see the module docstring.

    delivered: optional (n_sweeps, n+1, D) bool per-sweep link-delivery
    mask (repro.core.faults), shared across fields; dropped messages
    hold their last value.  All-True is bitwise the fault-free sweep,
    engine by engine.
    """
    if problem.batched:
        z, coef = _colored_core(
            problem, problem.nbr_mask, problem.gram, problem.chol,
            state.z, state.coef, n_sweeps, engine, delivered=delivered,
        )
        return SNTrainState(z=z, coef=coef)
    z, coef = _colored_core(
        problem,
        problem.nbr_mask[None], problem.gram[None], problem.chol[None],
        state.z[None], state.coef[None], n_sweeps, engine,
        delivered=delivered,
    )
    return SNTrainState(z=z[0], coef=coef[0])


def local_only(problem: SNTrainProblem) -> SNTrainState:
    """The paper's Sec-4.3 ablation: one local fit, no Update messages.

    Each sensor fits its neighborhood's raw measurements; information never
    propagates. Equivalent to SN-Train's first inner solve with the Update
    step removed.

    Pre-streaming ablation only: it rebuilds the measurement vector from
    ``problem.y``, which does not carry absorbed arrivals (their values live
    in the sweep state's z slots), so it refuses problems with occupied
    stream slots rather than silently fitting them as 0.
    """
    stream_used = problem.nbr_mask & (problem.nbr_idx >= problem.n)
    if bool(stream_used.any()):
        raise NotImplementedError(
            "local_only is the pre-streaming ablation; absorbed arrivals "
            "are not part of problem.y — run it before streaming.absorb"
        )
    pad = problem.n_stream + 1
    alive_row = problem.alive
    alive_slot = problem.alive_z

    def solve_field(y, nbr_mask, chol):
        y_pad = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])

        def solve_s(nbr_idx_s, nbr_mask_s, chol_s, alive_s):
            mask_s = nbr_mask_s & alive_slot[nbr_idx_s] & alive_s
            rhs = jnp.where(mask_s, y_pad[nbr_idx_s], 0.0)
            return jsl.cho_solve((chol_s, True), rhs)

        return y_pad, jax.vmap(solve_s)(
            problem.nbr_idx, nbr_mask, chol, alive_row
        )

    if problem.batched:
        z, coef = jax.vmap(solve_field)(
            problem.y, problem.nbr_mask, problem.chol
        )
    else:
        z, coef = solve_field(problem.y, problem.nbr_mask, problem.chol)
    return SNTrainState(z=z, coef=coef)


# ---------------------------------------------------------------------------
# Sharded engine: sensors (single-field) or fields (batched) distributed over
# a device axis via shard_map.
# ---------------------------------------------------------------------------


def sharded_sweep(
    problem: SNTrainProblem,
    state: SNTrainState,
    mesh: Mesh,
    *,
    axis: str = "sensors",
    n_sweeps: int = 1,
    engine: str = "plan",
    delivered: jax.Array | None = None,
) -> SNTrainState:
    """colored_sweep distributed with shard_map over `axis`.

    Single-field: color members are sharded across devices.  Every device
    solves its shard of the current color class; because a color's
    neighborhoods are disjoint, the per-device updates touch disjoint slots,
    and the transport reduces to one all-gather of the color's TOUCHED
    values — shape (M*D,) of fresh z messages plus (M, D) of fresh
    coefficients — after which every device applies the color's static
    scatter plan locally.  This replaces the former full (n_z,) + (n+1, D)
    delta psum: per-color traffic is proportional to the color's work, not
    the network size.  z and coef are replicated; the heavy per-sensor
    solves are fully parallel.

    Batched: the *field* axis is sharded instead — fields are independent
    problems, so each device runs the colored engine on its own B/n_dev
    fields with no cross-device traffic at all (the serving-throughput
    configuration).

    delivered: optional (n_sweeps, n+1, D) bool link-delivery mask
    (repro.core.faults).  Delivery is a property of the physical lane,
    so the mask is REPLICATED in both sharding regimes (every device
    applies the same gates to its shard of the work); dropped messages
    hold their last value, all-True is bitwise fault-free.
    """
    if problem.batched:
        return _sharded_sweep_fields(
            problem, state, mesh, axis=axis, n_sweeps=n_sweeps, engine=engine,
            delivered=delivered,
        )

    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    if engine != "plan":
        raise NotImplementedError(
            "single-field sharded_sweep implements the plan transport only "
            "(the psum payload IS the plan's touched-slot buffer); engine "
            "selection applies to batched, field-sharded problems"
        )
    n_dev = mesh.shape[axis]
    n_colors, m_max = problem.color_members.shape
    m_pad = -(-m_max // n_dev) * n_dev  # round up to device multiple
    pad = m_pad - m_max
    members = jnp.pad(
        problem.color_members, ((0, 0), (0, pad)), constant_values=problem.n
    )
    mask = jnp.pad(problem.color_mask, ((0, 0), (0, pad)))
    # Full flat member order per color — the coordinate system of the
    # scatter plans AND of the runtime liveness gate on their codes.
    members_full = members  # (n_colors, m_pad)
    live_full = mask & problem.alive[members_full]  # (n_colors, m_pad)
    # (n_colors, n_dev, m_pad // n_dev): device axis second for sharding.
    # Padding is APPENDED, so a member's global flat position (m*D + k, the
    # coordinate system of the scatter plans) is dev*m_local*D + local.
    members = members.reshape(n_colors, n_dev, -1)
    mask = mask.reshape(n_colors, n_dev, -1)
    solve = partial(
        _color_solve, problem.nbr_idx, problem.lam_pad,
        problem.alive, problem.alive_z,
    )

    def device_fn(z, coef, members_l, mask_l):
        # members_l: (n_colors, 1, m_local) local shard.
        members_l = members_l[:, 0]
        mask_l = mask_l[:, 0]
        xs = (
            members_l, mask_l, problem.plan_z, problem.plan_coef,
            live_full, members_full,
        )

        def make_color_body(deliv_t):
            def color_body(carry, cm):
                z, coef = carry
                mem, mmask, plan_z_c, plan_coef_c, live_c, mem_full = cm
                _, coef_new, z_new = solve(
                    problem.nbr_mask[None], problem.gram[None],
                    problem.chol[None], z[None], coef[None], mem, mmask,
                )
                # Assemble the color's touched values: device order equals
                # the plans' flat member order (padding is appended), so one
                # tiled all-gather of each device's fresh slice IS the
                # (m_pad, D) buffer — no zero-padded psum, payload exactly
                # M*D.
                z_full = jax.lax.all_gather(
                    z_new[0].reshape(-1), axis, tiled=True
                )  # (m_pad*D,)
                c_full = jax.lax.all_gather(
                    coef_new[0], axis, tiled=True
                )  # (m_pad, D)
                # Link delivery gates the full flat buffer (replicated —
                # every device sees the same drops).
                deliv_flat = (
                    None if deliv_t is None
                    else deliv_t[mem_full].reshape(-1)
                )
                z, coef = _apply_plan(
                    z[None], coef[None], z_full[None], c_full[None],
                    plan_z_c, plan_coef_c, live_c, problem.alive_z,
                    deliv_flat,
                )
                return (z[0], coef[0]), None

            return color_body

        if delivered is None:
            body = make_color_body(None)

            def sweep(carry, _):
                carry, _ = jax.lax.scan(body, carry, xs)
                return carry, None

            (z, coef), _ = jax.lax.scan(
                sweep, (z, coef), None, length=n_sweeps
            )
        else:

            def sweep(carry, deliv_t):
                carry, _ = jax.lax.scan(make_color_body(deliv_t), carry, xs)
                return carry, None

            (z, coef), _ = jax.lax.scan(sweep, (z, coef), delivered)
        return z, coef

    specs = (P(), P(), P(None, axis, None), P(None, axis, None))
    fn = compat.shard_map(
        device_fn, mesh=mesh, in_specs=specs, out_specs=(P(), P())
    )
    z, coef = jax.jit(fn)(
        *_place(mesh, specs, (state.z, state.coef, members, mask))
    )
    return SNTrainState(z=z, coef=coef)


def _place(mesh, specs, arrays):
    """Put each operand on the mesh as its spec says, before the jitted
    shard_map sees it: an operand left on device 0 would make the program
    run where it sits instead of across the mesh."""
    return tuple(
        jax.device_put(a, NamedSharding(mesh, spec))
        for a, spec in zip(arrays, specs)
    )


def _sharded_sweep_fields(
    problem, state, mesh, *, axis, n_sweeps, engine="plan", delivered=None
):
    """Field-data-parallel sharding of the batched colored engine.

    ``delivered`` rides in by closure: link delivery is shared across
    fields, so the mask is replicated on every device shard."""
    b = problem.batch_size
    n_dev = mesh.shape[axis]
    if b % n_dev != 0:
        raise ValueError(f"batch size {b} must divide over {n_dev} devices")

    def device_fn(nbr_mask, gram, chol, z, coef):
        return _colored_core(
            problem, nbr_mask, gram, chol, z, coef, n_sweeps, engine,
            delivered=delivered,
        )

    specs = (P(axis),) * 5
    fn = compat.shard_map(
        device_fn, mesh=mesh, in_specs=specs, out_specs=specs[:2]
    )
    z, coef = jax.jit(fn)(*_place(mesh, specs, (
        problem.nbr_mask, problem.gram, problem.chol, state.z, state.coef
    )))
    return SNTrainState(z=z, coef=coef)


# ---------------------------------------------------------------------------
# Paper Sec. 3.3 optional features: random orderings and robustness.
# (Single-field engines; batched problems use serial/colored/sharded above.)
# ---------------------------------------------------------------------------


def _require_single_field(problem: SNTrainProblem, fn_name: str) -> None:
    if problem.batched:
        raise NotImplementedError(
            f"{fn_name} supports single-field problems only; "
            "use serial_sweep/colored_sweep/sharded_sweep for batches"
        )


@partial(jax.jit, static_argnames=("n_sweeps",))
def random_sweep(
    problem: SNTrainProblem,
    state: SNTrainState,
    key: jax.Array,
    n_sweeps: int = 1,
) -> SNTrainState:
    """ALOHA-style randomized control ordering (paper Sec. 3.3 'Parallelism').

    Each outer iteration visits the sensors in a fresh uniformly-random
    permutation.  Admissible under the Bauschke-Borwein generalized control
    conditions (every sensor appears once per sweep), so Lemma 3.2 carries
    over: same fixed point as the serial Table-1 ordering.
    """
    _require_single_field(problem, "random_sweep")
    n = problem.n

    def sweep(carry, k):
        order = jax.random.permutation(k, n).astype(jnp.int32)
        z, coef = _serial_core(
            problem.nbr_idx, problem.nbr_mask, problem.gram, problem.chol,
            problem.lam_pad, problem.sentinel, carry[0], carry[1], order, 1,
            problem.alive, problem.alive_z,
        )
        return (z, coef), None

    keys = jax.random.split(key, n_sweeps)
    (z, coef), _ = jax.lax.scan(sweep, (state.z, state.coef), keys)
    return SNTrainState(z=z, coef=coef)


def _dynamic_sensor_update(problem, z, coef_s, s, alive_s, alive_row, alive_slot):
    """P_{C_s} with the CURRENT neighborhood N_{s,t} = N_s & alive_s.

    Solves the masked system directly (no cached Cholesky — the active set
    changes per step).  Padded/dead entries keep coefficient 0; the
    PERSISTENT liveness of the problem (``alive_row``/``alive_slot``,
    lifecycle removals) intersects the transient per-sweep link mask, so
    dead sensors neither update nor are read as neighbors here either.
    """
    mask = (
        problem.nbr_mask[s] & alive_s
        & alive_slot[problem.nbr_idx[s]] & alive_row[s]
    )
    gram = jnp.where(mask[:, None] & mask[None, :], problem.gram[s], 0.0)
    lam = problem.lam_pad[s]
    diag = jnp.where(mask, lam, 1.0)
    a = gram + jnp.diag(diag)
    coef_prev = jnp.where(mask, coef_s, 0.0)
    z_nbr = z[problem.nbr_idx[s]]
    rhs = jnp.where(mask, z_nbr + lam * coef_prev, 0.0)
    coef_new = jnp.linalg.solve(a, rhs)
    z_new = jnp.matmul(gram, coef_new, precision="highest")
    return coef_new, z_new, mask


@partial(jax.jit, static_argnames=("n_sweeps",))
def robust_sweep_links(
    problem: SNTrainProblem,
    state: SNTrainState,
    link_alive: jax.Array,  # (n_sweeps, n, D) bool: per-sweep link liveness
    n_sweeps: int = 1,
) -> SNTrainState:
    """Legacy LINK-level robustness: the paper's Sec. 3.3 model verbatim.

    Each sweep t uses neighborhoods N_{s,t} = N_s intersected with the alive
    links AND the problem's persistent ``alive`` row/slot liveness (a
    lifecycle-removed sensor neither updates nor is read, exactly as in the
    masked serial engine), solved densely per sensor in the serial Table-1
    ordering.  Kept as the single-field reference for asymmetric link
    failures; SENSOR-level churn (the common case) goes through the batched
    alive-masked colored path of ``robust_sweep``.
    """
    _require_single_field(problem, "robust_sweep_links")
    n = problem.n
    sentinel = problem.sentinel
    assert link_alive.shape[0] == n_sweeps
    alive_row = problem.alive
    alive_slot = problem.alive_z

    def body(carry, inp):
        s, alive_s = inp
        z, coef = carry
        coef_new, z_new, mask = _dynamic_sensor_update(
            problem, z, coef[s], s, alive_s, alive_row, alive_slot
        )
        coef = coef.at[s].set(jnp.where(alive_row[s], coef_new, coef[s]))
        scatter_idx = jnp.where(mask, problem.nbr_idx[s], sentinel)
        z = z.at[scatter_idx].set(jnp.where(mask, z_new, z[sentinel]))
        return (z, coef), None

    def sweep(carry, alive_t):
        idxs = jnp.arange(n, dtype=jnp.int32)
        carry, _ = jax.lax.scan(body, carry, (idxs, alive_t))
        return carry, None

    (z, coef), _ = jax.lax.scan(sweep, (state.z, state.coef), link_alive)
    return SNTrainState(z=z, coef=coef)


def _masked_factors(problem: SNTrainProblem, nbr_mask, gram, alive_row):
    """Refactor every local system under the CURRENT liveness mask.

    Mirrors ``make_problem``'s build: mask the Gram to the effective
    (occupancy & liveness) lanes, put lambda on live diagonal entries and 1
    on dead/padded ones, and Cholesky-factor row-wise.  At all-True
    liveness the masked Gram IS the stored Gram (same floats), so on an
    ARRIVAL-FREE problem the recomputed factors equal ``problem.chol``
    bit-for-bit — which is what makes ``robust_sweep`` at full liveness
    bitwise-equal to ``colored_sweep`` there.  Rows that absorbed
    streaming arrivals carry grow-one-updated cached factors whose float
    history a fresh factorization cannot reproduce; for those the
    recomputation matches to factorization noise (the same ~1e-7-level
    bound ``streaming.rebuild_chol`` is tested to).  Shapes:
    nbr_mask/gram carry an explicit leading field axis.
    """
    alive_slot = plans.alive_slots(alive_row, problem.layout.slot_owner)
    lane_alive = alive_slot[problem.nbr_idx] & alive_row[:, None]  # (n+1, D)
    mask_eff = nbr_mask & lane_alive[None]  # (B, n+1, D)
    outer = mask_eff[..., :, None] & mask_eff[..., None, :]
    gram_eff = jnp.where(outer, gram, 0.0)
    d = gram.shape[-1]
    diag = jnp.where(mask_eff, problem.lam_pad[None, :, None], 1.0)
    a = gram_eff + diag[..., None] * jnp.eye(d, dtype=gram.dtype)
    chol_eff = jax.vmap(jax.vmap(lambda m: jsl.cholesky(m, lower=True)))(a)
    return gram_eff, chol_eff


@partial(jax.jit, static_argnames=("n_sweeps", "engine"))
def _robust_colored(problem, state, alive_tn, n_sweeps, engine, delivered=None):
    batched = problem.batched
    nbr_mask = problem.nbr_mask if batched else problem.nbr_mask[None]
    gram = problem.gram if batched else problem.gram[None]
    z = state.z if batched else state.z[None]
    coef = state.coef if batched else state.coef[None]

    def sweep_body(carry, inp):
        alive_t, deliv_t = inp
        z, coef = carry
        alive_row = problem.alive & jnp.concatenate(
            [alive_t, jnp.ones((1,), bool)]
        )
        gram_eff, chol_eff = _masked_factors(problem, nbr_mask, gram, alive_row)
        z, coef = _colored_core(
            problem, nbr_mask, gram_eff, chol_eff, z, coef, 1, engine,
            alive=alive_row,
            delivered=None if deliv_t is None else deliv_t[None],
        )
        return (z, coef), None

    (z, coef), _ = jax.lax.scan(sweep_body, (z, coef), (alive_tn, delivered))
    if batched:
        return SNTrainState(z=z, coef=coef)
    return SNTrainState(z=z[0], coef=coef[0])


def robust_sweep(
    problem: SNTrainProblem,
    state: SNTrainState,
    alive: jax.Array,
    n_sweeps: int = 1,
    *,
    engine: str = "plan",
    delivered: jax.Array | None = None,
) -> SNTrainState:
    """SN-Train with a changing topology (paper Sec. 3.3 'Robustness').

    SENSOR-level liveness, batched: ``alive`` is (n,) or (n_sweeps, n)
    bool; sweep t runs the alive-masked colored engine under
    ``alive[t] & problem.alive`` — dead sensors neither update nor are
    heard from, and every engine's scatter is gated on BOTH the source
    member's and the target slot's liveness, so a down mote's messages and
    coefficients persist untouched and a healed sensor resumes from its
    last state (the paper's 'solution implied by the neighborhood
    occurring infinitely often').  Because liveness is TRANSIENT here (no
    lifecycle event patches the cached factors), every sweep refactorizes
    the masked local systems in one batched pass — O(n*D^3) per sweep, the
    robustness price — then dispatches the normal engines, so the call
    accepts a leading field axis and every
    ``engine={"plan","onehot","pallas"}`` like ``colored_sweep``:
    "plan" == "onehot" bit-for-bit at any liveness, and at all-True
    liveness on an ARRIVAL-FREE problem the recomputed factors equal the
    cached ones bit-for-bit, so ``robust_sweep == colored_sweep`` exactly,
    engine by engine (tests/test_lifecycle.py; after streaming absorption
    the cached factors carry grow-one float history, and the match is to
    ~1e-7 factorization noise instead — see ``_masked_factors``).
    ``alive`` is a traced operand: one compiled program serves every
    failure trace of a given length.

    PERSISTENT membership changes should use ``streaming.add_sensor`` /
    ``remove_sensor`` instead, which patch the factors once per event so
    ``colored_sweep`` keeps its cached-factor speed.

    ``delivered``: optional (n_sweeps, n+1, D) bool per-sweep
    link-delivery mask (repro.core.faults) composed ON TOP of the
    per-sweep liveness — a crashed-sensor schedule with lossy links is
    exactly this call (``faults.faulty_sweep`` dispatches here when the
    model crashes sensors).  All-True is the plain robust sweep bitwise.

    Legacy LINK-level traces — (n_sweeps, n, D) bool — route to the
    original serial dense path (``robust_sweep_links``), single-field
    only, unchanged (and without fault injection).
    """
    alive = jnp.asarray(alive)
    if alive.ndim == 3:
        if delivered is not None:
            raise NotImplementedError(
                "delivered masks compose with SENSOR-level alive traces; "
                "legacy link-level traces already encode per-lane loss"
            )
        return robust_sweep_links(problem, state, alive, n_sweeps)
    alive = alive.astype(bool)
    if alive.ndim == 1:
        alive = jnp.broadcast_to(alive[None], (n_sweeps,) + alive.shape)
    if alive.shape != (n_sweeps, problem.n):
        raise ValueError(
            f"alive must be (n,), (n_sweeps={n_sweeps}, n={problem.n}) "
            f"or legacy (n_sweeps, n, D); got {alive.shape}"
        )
    return _robust_colored(
        problem, state, alive, n_sweeps=n_sweeps, engine=engine,
        delivered=delivered,
    )


# ---------------------------------------------------------------------------
# Paper Sec. 5.2 extension: weighted (heteroscedastic) losses.
#
# The paper notes SOP generalizes to Bregman projections for other losses.
# The simplest non-trivial instance keeps orthogonality by reweighting the
# product-space norm:   sum_j w_j z_j^2 + sum_i lambda_i ||f_i||^2,
# i.e. per-sensor measurement confidences w_j (inverse noise variances).
# The local solve becomes  (W_s K_s + lambda_s I) c = W_s z + lambda_s c_prev
# (non-symmetric; solved directly, no cached Cholesky).
# ---------------------------------------------------------------------------


def _weighted_sensor_update(problem, z, coef_s, s, w_pad, alive_row, alive_slot):
    mask = (
        problem.nbr_mask[s] & alive_slot[problem.nbr_idx[s]] & alive_row[s]
    )
    gram = jnp.where(mask[:, None] & mask[None, :], problem.gram[s], 0.0)
    lam = problem.lam_pad[s]
    w_nbr = jnp.where(mask, w_pad[problem.nbr_idx[s]], 0.0)
    diag = jnp.where(mask, lam, 1.0)
    a = w_nbr[:, None] * gram + jnp.diag(diag)
    z_nbr = z[problem.nbr_idx[s]]
    rhs = jnp.where(mask, w_nbr * z_nbr + lam * coef_s, 0.0)
    coef_new = jnp.linalg.solve(a, rhs)
    z_new = jnp.matmul(gram, coef_new, precision="highest")
    return coef_new, z_new, mask


@partial(jax.jit, static_argnames=("n_sweeps",))
def weighted_sweep(
    problem: SNTrainProblem,
    state: SNTrainState,
    weights: jax.Array,  # (n,) per-sensor measurement confidences w_j > 0
    n_sweeps: int = 1,
) -> SNTrainState:
    """SN-Train under the reweighted norm (heteroscedastic measurements).

    weights == 1 reduces exactly to serial_sweep.  Fejér monotonicity holds
    in the reweighted norm (see weighted_norm_sq_hetero).  Liveness is
    threaded exactly as in the serial engine: dead (removed) sensors
    neither update nor are read as neighbors, and their messages persist
    (tests/test_sn_train.py pins this to the masked serial engine)."""
    _require_single_field(problem, "weighted_sweep")
    n = problem.n
    sentinel = problem.sentinel
    w_pad = jnp.concatenate(
        [
            jnp.asarray(weights, state.z.dtype),
            jnp.zeros((problem.n_stream + 1,), state.z.dtype),
        ]
    )
    idxs = jnp.arange(n, dtype=jnp.int32)
    alive_row = problem.alive
    alive_slot = problem.alive_z

    def body(carry, s):
        z, coef = carry
        coef_new, z_new, mask = _weighted_sensor_update(
            problem, z, coef[s], s, w_pad, alive_row, alive_slot
        )
        coef = coef.at[s].set(jnp.where(alive_row[s], coef_new, coef[s]))
        scatter_idx = jnp.where(mask, problem.nbr_idx[s], sentinel)
        z = z.at[scatter_idx].set(jnp.where(mask, z_new, z[sentinel]))
        return (z, coef), None

    def sweep(carry, _):
        carry, _ = jax.lax.scan(body, carry, idxs)
        return carry, None

    (z, coef), _ = jax.lax.scan(sweep, (state.z, state.coef), None, length=n_sweeps)
    return SNTrainState(z=z, coef=coef)


def weighted_norm_sq_hetero(
    problem: SNTrainProblem, state: SNTrainState, weights: jax.Array
) -> jax.Array:
    """sum_j w_j z_j^2 + sum_i lambda_i ||f_i||^2 — the Fejér invariant of
    weighted_sweep."""
    n = problem.n
    z_part = jnp.sum(jnp.asarray(weights) * state.z[..., :n] ** 2, axis=-1)
    quad = jnp.einsum(
        "...sd,...sde,...se->...s", state.coef, problem.gram, state.coef,
        precision="highest",
    )
    return z_part + jnp.sum(problem.lam_pad * quad, axis=-1)
