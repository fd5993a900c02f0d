"""Static query plans for kNN-fusion serving (paper Sec. 3.3, Eq. 19).

The paper's testing phase answers a query x by averaging the k sensors
nearest x (kNN fusion — the rule their Sec. 4 simulations show wins for
field estimation).  The dense realization (``fusion.evaluate_sensors`` +
``fusion.knn_fusion``) evaluates ALL n sensors at ALL Q queries and
materializes a (Q, n) distance matrix: O(Q*n*D) compute and O(Q*n) HBM for
an answer that only ever reads k ~ 1..5 sensors per query.

This module applies the same locality that makes SN-Train itself local: a
query's k nearest sensors live in a bounded spatial neighborhood, so
per-query work should be independent of n.  Mirroring the static scatter
plans of ``plans.build_color_plans``, everything data-dependent is
precomputed host-side at problem-build time:

  * the sensor positions are bucketed into a uniform spatial grid;
  * every cell gets a padded **candidate list** — the sensors PROVABLY
    sufficient for exact kNN of any query inside the cell.  With cell
    center m, half-diagonal h and d_k = distance from m to its k-th
    nearest sensor, any in-cell query's k-th neighbor lies within
    d_k + h, and every sensor that close to the query lies within
    d_k + 2h of m — so the candidate set {s : |s - m| <= d_k + 2h}
    is exact, and on bounded-density networks its size is O(k), not O(n).

Serving then touches one cell's candidate row per query:

  ``knn_select``  query -> cell -> masked top-k over K_max candidates;
  ``knn_fuse``    + gather the selected sensors' (D,) representers and
                  evaluate f_s(x) = K(x, N_s) @ c_s locally, O(Q*k*D) total.

Engines (``fusion.fuse(rule="knn", engine=...)`` dispatches here):

  ``"plan"``    the jnp realization of the plan path (any kernel, any
                dtype — the reference the Pallas kernel is tested against);
  ``"pallas"``  the Pallas kernels of ``repro.kernels.knn_fuse`` (RBF
                only): a selection kernel (distance tile + masked top-k
                network per query tile), an XLA gather of the selected
                representers, and an evaluate kernel for the k local
                (D,) contractions — the (n, Q) predictions and (Q, n)
                distances never exist in HBM;
  ``"dense"``   (in ``fusion``) the original all-sensors oracle.

Network lifecycle: the plan's candidate VALUES are device-side data, so
sensor joins/leaves repair them in place (``plan_add_sensor`` /
``plan_remove_sensor``, built on ``repro.core.plans``) with zero host work
and zero recompiles; build with ``spare=`` candidate columns and a
``slack=`` radius so exactness survives churn, and every select path also
gates candidates on the problem's ``alive`` mask.  Symmetric joins mean a
join changes MORE than the candidate lists: every adopting neighbor's
representer grows an anchor at the new position, so the repaired plan's
predictions track the dense oracle through the adopters' changed
functions too (tests/test_lifecycle.py).  When fewer than k candidates
are live, every engine averages the valid selections only — dense, plan
and pallas agree at all liveness fractions, all-dead included
(tests/test_serving.py).

Exactness contract: plans are exact for queries inside the plan's domain
[lo, hi] (default: the LIVE-sensor bounding box, which the paper's query
grids live in).  Queries outside are clipped to the boundary cell for candidate
lookup, so far-field queries degrade gracefully to approximate kNN rather
than erroring.  Distance ties are broken toward the lower sensor index by
every engine (top_k and the selection network both scan ascending), so
engines agree bit-for-bit on the selected set except on exact ties between
equidistant sensors at different indices.

Quantized + sparsified path: ``compute_dtype="bf16"`` stores the anchor
tables — serving's dominant operand, O(B*n*D*d) vs O(n*d) for the
sensor positions — in bf16, halving the bytes the Pallas path gathers
and reads so its query tile doubles, with kernel-value arithmetic upconverted to >= f32 in
registers and the representer contraction accumulating in the COEFFICIENT
dtype (f32/f64 — ``ecoef`` is never downcast).  Selection is EXACT under
quantization: queries, positions, distances, and top-k keep full
precision, so both engines select the same sensors as the f32 path
(quantizing selection was measured at ~2.3% field RMSE at n=1000 — over
the 1% budget — vs ~0.1% for anchors-only; ``knn_select_valid`` keeps an
opt-in ``compute_dtype`` for measuring that trade).  ``prune=`` ANDs a
``pruning.prune_mask`` keep mask into the liveness gate so near-zero-energy
representers drop out of selection exactly like dead sensors.
``prune_plan`` (re-exported from ``core.pruning``) compacts the candidate
lists to the kept sensors for a smaller ``K_max``.  Cell lookup
(``query_cells``) always stays full-precision: candidate-list exactness
depends on the query landing in the right cell.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from . import plans
from .sn_train import SNTrainProblem, SNTrainState, effective_coef


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ServingPlan:
    """Frozen-shape query-time plan: uniform grid + per-cell candidate lists.

    Built host-side by ``make_serving_plan``; all arrays are padded to fixed
    shapes so query answering is pure gathers (no data-dependent shapes).
    Under network lifecycle events the candidate VALUES are repaired on
    device (``plan_add_sensor`` / ``plan_remove_sensor`` — no host rebuild,
    no recompile); the shapes never change.

    Attributes:
      origin:    (d,) grid origin (domain lower corner).
      inv_cell:  (d,) reciprocal cell edge lengths.
      centers:   (C, d) cell centers (used by the lifecycle repairs).
      radii:     (C,) per-cell candidate radius (the exactness bound the
                 repairs re-apply when inserting a joined sensor).
      cells:     (C, K_max) int32 candidate sensor ids per flattened cell,
                 padded with n (the sentinel row of the padded problem
                 arrays — always masked).
      cell_mask: (C, K_max) bool validity of ``cells`` entries.
      grid_shape: static per-dim cell counts (prod == C).
      k:         static kNN order the plan guarantees exactness for
                 (queries inside the domain; any k' <= k is also exact).
    """

    origin: jnp.ndarray
    inv_cell: jnp.ndarray
    centers: jnp.ndarray
    radii: jnp.ndarray
    cells: jnp.ndarray
    cell_mask: jnp.ndarray
    grid_shape: tuple = dataclasses.field(metadata=dict(static=True))
    k: int = dataclasses.field(default=1, metadata=dict(static=True))

    @property
    def n_cells(self) -> int:
        return int(self.cells.shape[0])

    @property
    def k_max(self) -> int:
        """Padded candidate-list width (max candidates over cells)."""
        return int(self.cells.shape[1])


def make_serving_plan(
    problem: SNTrainProblem,
    *,
    k: int = 8,
    cells_per_dim: int | None = None,
    lo=None,
    hi=None,
    spare: int = 0,
    slack: int = 0,
) -> ServingPlan:
    """Host-side precomputation of the kNN query plan for ``problem``.

    k: largest kNN order the plan must answer exactly (candidate radii are
    computed for this k; serving with any smaller k reuses the same plan).
    cells_per_dim: grid resolution; the default targets ~4 sensors per
    cell so K_max stays O(k) on uniform-density networks.  lo/hi override
    the plan domain (defaults: the LIVE-sensor bounding box) — widen them
    when query grids extend beyond the sensors.

    Lifecycle capacity: ``spare`` reserves extra padded candidate columns
    for ``plan_add_sensor`` inserts, and ``slack`` widens the per-cell
    radius to the (k+slack)-th neighbor so exactness survives up to
    ``slack`` removals from any one cell's candidate list (see
    ``plans.build_cell_lists``).  Dead rows (spares, removed sensors) are
    excluded at build.
    """
    n = problem.n
    k = int(min(k, int(np.asarray(problem.alive[:n]).sum())))
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    grid = plans.build_cell_lists(
        np.asarray(problem.topology.positions),
        np.asarray(problem.alive[:n]),
        k,
        cells_per_dim,
        lo,
        hi,
        spare=spare,
        slack=slack,
    )
    dt = problem.topology.positions.dtype
    return ServingPlan(
        origin=jnp.asarray(grid["origin"], dt),
        inv_cell=jnp.asarray(1.0 / grid["cell"], dt),
        centers=jnp.asarray(grid["centers"], dt),
        radii=jnp.asarray(grid["radii"], dt),
        cells=jnp.asarray(grid["cells"]),
        cell_mask=jnp.asarray(grid["mask"]),
        grid_shape=grid["grid_shape"],
        k=k,
    )


@jax.jit
def plan_remove_sensor(plan: ServingPlan, slot: jax.Array) -> ServingPlan:
    """Lifecycle repair: drop a removed sensor from every candidate list.

    Device-side, fixed shapes, O(C*K_max) compare — pairs with
    ``streaming.remove_sensor``.  Removals never shrink the per-cell
    radius, so exactness holds while at most the plan's build ``slack``
    candidates of any one cell have been removed.
    """
    mask = plans.cells_remove(
        plan.cells, plan.cell_mask, jnp.asarray(slot, plan.cells.dtype), True
    )
    return dataclasses.replace(plan, cell_mask=mask)


@jax.jit
def plan_add_sensor(
    plan: ServingPlan, x: jax.Array, slot: jax.Array
) -> tuple[ServingPlan, jax.Array]:
    """Lifecycle repair: insert a joined sensor into every covering cell.

    Pairs with ``streaming.add_sensor``: the sensor enters the candidate
    list of every cell whose build-time exactness radius covers ``x`` (adds
    only shrink true kNN distances, so the bound stays valid).  Returns
    ``(plan, overflowed)`` where ``overflowed`` counts cells whose candidate
    rows were full — build the plan with more ``spare`` columns if nonzero.
    """
    x = jnp.asarray(x, plan.centers.dtype).reshape(-1)
    cells, mask, overflowed = plans.cells_add(
        plan.cells, plan.cell_mask, plan.centers, plan.radii, x,
        jnp.asarray(slot, plan.cells.dtype), True,
    )
    return dataclasses.replace(plan, cells=cells, cell_mask=mask), overflowed


def query_cells(plan: ServingPlan, xq: jax.Array) -> jax.Array:
    """Flattened cell id per query, (Q,) int32 (out-of-domain clipped)."""
    rel = (xq - plan.origin[None, :]) * plan.inv_cell[None, :]
    idx = jnp.floor(rel).astype(jnp.int32)
    dims = jnp.asarray(plan.grid_shape, jnp.int32)
    idx = jnp.clip(idx, 0, dims[None, :] - 1)
    strides = np.concatenate(
        [np.cumprod(plan.grid_shape[::-1])[-2::-1], [1]]
    ).astype(np.int32)
    return idx @ jnp.asarray(strides)


def _norm_compute_dtype(compute_dtype):
    """Canonical static name for the serving compute dtype (None = native).

    Accepts None, "f32"/"float32", "bf16"/"bfloat16", or any float dtype
    object; returns the numpy dtype-name string (hashable, stable as a jit
    static argument) or None.
    """
    if compute_dtype is None:
        return None
    aliases = {"bf16": "bfloat16", "f32": "float32", "f64": "float64",
               "f16": "float16"}
    if isinstance(compute_dtype, str):
        compute_dtype = aliases.get(compute_dtype, compute_dtype)
    try:
        dt = jnp.dtype(compute_dtype)
    except TypeError as e:
        raise ValueError(
            f"compute_dtype must be None or a float dtype "
            f"(e.g. 'bf16', 'f32'); got {compute_dtype!r}"
        ) from e
    if not jnp.issubdtype(dt, jnp.floating):
        raise ValueError(
            f"compute_dtype must be a float dtype, got {dt.name!r}"
        )
    return dt.name


@partial(jax.jit, static_argnames=("k", "compute_dtype"))
def knn_select_valid(
    plan: ServingPlan, positions: jax.Array, xq: jax.Array, k: int,
    alive: jax.Array | None = None,
    compute_dtype: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """((Q, k) selected ids, (Q, k) validity) via the cell plan.

    When fewer than k live candidates exist, ``top_k`` must still return k
    indices; the overflow picks +inf-distance (dead / padded) entries and
    ``valid`` marks them False so callers average the live selections only
    — matching the dense oracle ``fusion.knn_fusion`` at every liveness
    fraction (all-dead included: zero predictions).  ``compute_dtype``
    (normalized name, e.g. "bfloat16") is an OPT-IN measurement knob that
    rounds the query/candidate coordinates to a storage dtype before the
    (>= f32) distance/top-k arithmetic — the production quantized path
    does NOT use it (selection-exact; see the module docstring), but the
    quant bench and tests use it to quantify the selection-flip cost.
    Cell lookup stays full-precision.
    """
    with jax.named_scope("cell_lookup"):
        cid = query_cells(plan, xq)  # (Q,) — always full precision
    with jax.named_scope("candidates"):
        cand = plan.cells[cid]  # (Q, K_max)
        cmask = plan.cell_mask[cid]  # (Q, K_max)
        if alive is not None:
            cmask = cmask & (alive[cand] != 0)
        pos_pad = jnp.concatenate(
            [positions, jnp.zeros((1, positions.shape[1]), positions.dtype)]
        )
        cpos = pos_pad[cand]  # (Q, K_max, d)
        if compute_dtype is not None:
            cdt = jnp.dtype(compute_dtype)
            ar = cdt if cdt.itemsize >= 4 else jnp.dtype(jnp.float32)
            xq = xq.astype(cdt).astype(ar)  # round to storage, compute wide
            cpos = cpos.astype(cdt).astype(ar)
        d2 = jnp.sum((xq[:, None, :] - cpos) ** 2, axis=-1)
        d2 = jnp.where(cmask, d2, jnp.inf)
    with jax.named_scope("top_k"):
        neg, top = jax.lax.top_k(-d2, k)  # (Q, k) candidate positions
        return jnp.take_along_axis(cand, top, axis=1), jnp.isfinite(neg)


def knn_select(
    plan: ServingPlan, positions: jax.Array, xq: jax.Array, k: int,
    alive: jax.Array | None = None,
) -> jax.Array:
    """(Q, k) ids of each query's k nearest sensors via the cell plan.

    positions: the (n, d) sensor positions the plan was built from.  Ties
    break toward the lower sensor id, matching ``fusion.knn_fusion``.
    alive: optional (n+1,) row liveness — dead candidates are never
    selected, independent of the plan's repair state.  (When fewer than k
    live candidates exist the tail ids are dead/padded rows; use the
    validity mask of ``knn_select_valid`` to exclude them.)
    """
    return knn_select_valid(plan, positions, xq, k, alive)[0]


@partial(jax.jit, static_argnames=("kernel", "k", "compute_dtype"))
def _eval_selected(
    kernel, nbr_pos, nbr_mask, coef, sel, valid, xq, k: int,
    compute_dtype: str | None = None,
):
    """mean over VALID selections of f_{sel[q,j]}(xq[q]): O(Q*k*D).

    ``compute_dtype`` rounds the ANCHOR coordinates (the storage dtype of
    the quantized path's dominant table) before evaluating K(x, x_j)
    at >= f32 (the Pallas kernel's register-level upconversion contract);
    queries stay full-precision and the representer contraction and the
    average accumulate in the coefficient dtype regardless.
    """
    d = xq.shape[-1]
    d_max = nbr_pos.shape[-2]
    cdt = None if compute_dtype is None else jnp.dtype(compute_dtype)

    def per_query(x, sel_q, valid_q):
        with jax.named_scope("anchor_gather"):
            npos = nbr_pos[sel_q]  # (k, D, d)
            cf = jnp.where(nbr_mask[sel_q], coef[sel_q], 0.0)  # (k, D)
        with jax.named_scope("kernel_eval"):
            if cdt is not None:
                ar = x.dtype if x.dtype.itemsize >= 4 else jnp.dtype(
                    jnp.float32
                )
                npos = npos.astype(cdt).astype(ar)
            if cdt is not None and kernel.name == "rbf":
                # Direct (x - x_j)^2 form, not the matmul expansion the
                # generic kernel uses — matches the Pallas kernel
                # bit-for-bit on the same rounded inputs.
                dd = jnp.sum((x[None, None, :] - npos) ** 2, axis=-1)
                kv = jnp.exp(-kernel.gamma * dd)  # (k, D)
            else:
                kv = kernel(x[None, :], npos.reshape(k * d_max, d))
                kv = kv[0].reshape(k, d_max)
            f = jnp.sum(kv.astype(cf.dtype) * cf, axis=-1)  # (k,) coef dtype
            cnt = jnp.sum(valid_q)
            return jnp.sum(jnp.where(valid_q, f, 0.0)) / jnp.maximum(cnt, 1)

    return jax.vmap(per_query)(xq, sel, valid)


def knn_fuse(
    problem: SNTrainProblem,
    state: SNTrainState,
    xq: jax.Array,
    k: int = 1,
    *,
    plan: ServingPlan | None = None,
    engine: str = "plan",
    ecoef: jax.Array | None = None,
    compute_dtype=None,
    prune: jax.Array | None = None,
    block_q: int | None = None,
) -> jax.Array:
    """Plan-based kNN fusion (paper Eq. 19) — O(Q*k*D) per field.

    Returns (Q,) for single-field problems, (B, Q) for batched ones (the
    selected sensor set depends only on the shared positions, so selection
    runs once and the B evaluations share it).  ``plan`` defaults to a
    fresh ``make_serving_plan(problem, k=k)``; serving processes build the
    plan once and pass it in.  ``ecoef`` optionally supplies the TRUE
    representer coefficients (``sn_train.effective_coef``) precomputed —
    a snapshot-serving process (``launch.daemon``) publishes an immutable
    (problem, state) pair and pays the anchor-weight rescale ONCE per
    published snapshot instead of once per query dispatch.

    ``compute_dtype`` ("bf16"/"f32"/None=native) sets the storage dtype of
    the anchor tables on both engines (selection-exact quantization — see
    the module docstring); accumulation and the output stay in the
    coefficient dtype.  ``prune`` is an optional (n+1,) keep mask
    (``pruning.prune_mask``) ANDed into the liveness gate — pruned sensors
    drop out of selection exactly like dead ones, with zero recompiles
    across tau changes (mask values only).  ``block_q`` overrides the
    Pallas query tile (None = ``default_block_q(compute_dtype)``): the
    latency-oriented default stays small so bucketed small requests pad
    little; bulk offline sweeps tune it up (see benchmarks/quant_bench).
    """
    if engine not in ("plan", "pallas"):
        raise ValueError(f"engine must be 'plan' or 'pallas', got {engine!r}")
    if block_q is not None and engine != "pallas":
        raise ValueError("block_q applies to engine='pallas' only")
    if k < 1 or k > problem.n:
        raise ValueError(f"k must be in [1, n={problem.n}], got {k}")
    if plan is None:
        plan = make_serving_plan(problem, k=k)
    if k > plan.k:
        raise ValueError(
            f"plan guarantees exact kNN only up to k={plan.k}; got k={k} "
            "(rebuild with make_serving_plan(problem, k=...))"
        )
    cdt_name = _norm_compute_dtype(compute_dtype)
    if engine == "plan":
        # One compiled program per (bucket, kernel, k, dtype): a warm call
        # traces nothing and launches once through jit's cached path.
        if ecoef is None:
            coef, anchor_w = state.coef, problem.anchor_w
        else:
            coef, anchor_w = ecoef, None
        if not isinstance(xq, jax.Array):
            xq = np.asarray(xq)
        return _knn_plan(
            problem.kernel, plan, problem.topology.positions,
            problem.nbr_pos, problem.nbr_mask, coef, anchor_w,
            problem.alive, prune, xq, k=k, compute_dtype=cdt_name,
        )

    alive = problem.alive
    if prune is not None:
        alive = ((alive != 0) & (prune != 0)).astype(alive.dtype)
    dt = problem.nbr_pos.dtype
    xq = jnp.atleast_2d(jnp.asarray(xq, dt))
    positions = problem.topology.positions.astype(dt)

    # Serving reads the TRUE representer coefficients (the solved
    # coordinates rescaled by the forgetting anchor weights; all-ones for
    # static beta = 1 fields) — a value-level rescale, so both engines'
    # compiled programs and the Pallas kernel's operand shapes are
    # untouched by forgetting.
    if ecoef is None:
        ecoef = effective_coef(problem, state)

    from repro.kernels.knn_fuse import knn_fuse_fused

    if problem.kernel.name != "rbf":
        raise NotImplementedError(
            "engine='pallas' fuses the RBF kernel only; use "
            "engine='plan' for other kernels"
        )
    cid = query_cells(plan, xq)
    pos_pad = jnp.concatenate([positions, jnp.zeros((1, xq.shape[1]), dt)])
    if problem.batched:
        nbr_pos, nbr_mask, coef = (
            problem.nbr_pos, problem.nbr_mask, ecoef,
        )
    else:
        nbr_pos = problem.nbr_pos[None]
        nbr_mask = problem.nbr_mask[None]
        coef = ecoef[None]
    out = knn_fuse_fused(
        xq, cid, plan.cells, plan.cell_mask, pos_pad,
        nbr_pos, nbr_mask, coef,
        alive=alive, gamma=problem.kernel.gamma, k=k,
        block_q=block_q, compute_dtype=cdt_name,
    )
    return out if problem.batched else out[0]


@partial(jax.jit, static_argnames=("kernel", "k", "compute_dtype"))
def _knn_plan(
    kernel, plan, positions, nbr_pos, nbr_mask, coef, anchor_w, alive,
    prune, xq, k: int, compute_dtype: str | None = None,
):
    """The plan engine of ``knn_fuse`` as one program: (Q,) or (B, Q).

    ``anchor_w`` None means ``coef`` already holds the true representer
    coefficients (``effective_coef``); otherwise they are formed here.
    Batched problems (a leading field axis on ``nbr_mask``) share one
    selection across the B evaluations.
    """
    dt = nbr_pos.dtype
    xq = jnp.atleast_2d(jnp.asarray(xq, dt))
    positions = positions.astype(dt)
    if prune is not None:
        alive = ((alive != 0) & (prune != 0)).astype(alive.dtype)
    if anchor_w is not None:
        coef = coef * anchor_w.astype(coef.dtype)
    # (Q, k) shared across fields (liveness is network-level, not per-field)
    # Selection is ALWAYS full-precision — the quantized path is
    # selection-exact (see the module docstring); compute_dtype reaches
    # only the anchor-table evaluation below.
    sel, valid = knn_select_valid(plan, positions, xq, k, alive)

    def one_field(np_, nm, cf):
        return _eval_selected(
            kernel, np_, nm, cf, sel, valid, xq, k,
            compute_dtype=compute_dtype,
        )

    if nbr_mask.ndim == 3:
        return jax.vmap(one_field)(nbr_pos, nbr_mask, coef)
    return one_field(nbr_pos, nbr_mask, coef)


# Sparsified-serving surface (ISSUE: serving.prune_plan): implemented in
# core.pruning, re-exported here because they operate on ServingPlans.
from .pruning import (  # noqa: E402
    PruneReport, answer_bound, prune_mask, prune_plan, representer_energy,
)
