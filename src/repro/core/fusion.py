"""Fusion-center aggregation rules (paper Sec. 3.3 'Aggregation').

After SN-Train, every sensor holds a *global* field estimate
``f_s(x) = sum_{j in N_s} c_{s,j} K(x, x_j)``.  The fusion center combines
them with one of three strategies from the paper:

  * single-sensor:         f(x) = f_s(x) for one arbitrary sensor s
  * k-nearest-neighbor:    f(x) = mean_{s in kNN(x)} f_s(x)        (Eq. 19)
  * connectivity-averaged: f(x) = sum_s |N_s| f_s(x) / sum_s |N_s| (Eq. 20)

k = 1 is "nearest neighbor", k = n is the plain network average.

Every rule accepts single-field problems ((Q,) output) and batched
multi-field problems ((B, Q) output).  Dtypes follow the problem/state
arrays, so x64 problems (the paper-lambda configuration) serve f64
predictions end-to-end through every rule in this module and through the
plan engines of ``repro.core.serving``.  (The one f32 fast path is the
collapsed ``global_coefficients`` expansion when evaluated via
``repro.kernels.kernel_matvec``, whose Pallas matvec computes in f32 by
its documented TPU contract.)

Serving engines (the query-plan taxonomy; the training-side analogue is
``sn_train``'s color-step scatter plans):

  ``fuse(rule="knn"/"nn", engine=...)`` selects how kNN fusion executes —
  ``"dense"`` (default; this module) evaluates ALL n sensors at all Q
  queries and top-k's a dense (Q, n) distance matrix — O(Q*n*D), the
  independently simple oracle; ``"plan"`` and ``"pallas"`` route through
  the static cell-candidate query plans of ``repro.core.serving``
  (``make_serving_plan``), touching one bounded cell neighborhood per
  query — O(Q*k*D), with ``"pallas"`` running the selection and the
  evaluation as Pallas kernels (``repro.kernels.knn_fuse``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .sn_train import SNTrainProblem, SNTrainState, effective_coef


@partial(jax.jit, static_argnames=("kernel",))
def _eval_all(kernel, nbr_pos, nbr_mask, coef, xq):
    """f_s(xq) for every sensor s: returns (n+1, Q)."""

    def eval_s(pos_s, mask_s, coef_s):
        k = kernel(xq, pos_s)  # (Q, D)
        return jnp.matmul(
            k, jnp.where(mask_s, coef_s, 0.0), precision="highest"
        )

    return jax.vmap(eval_s)(nbr_pos, nbr_mask, coef)


def evaluate_sensors(
    problem: SNTrainProblem, state: SNTrainState, xq: jax.Array
) -> jax.Array:
    """Per-sensor global estimates at queries: (n, Q), batched (B, n, Q).

    Evaluates the TRUE representer coefficients ``effective_coef`` (the
    solved coordinates rescaled by the forgetting anchor weights); for
    static fields (``beta = 1``) the weights are all ones and this is the
    plain coefficient read.
    """
    xq = jnp.atleast_2d(jnp.asarray(xq, problem.nbr_pos.dtype))
    coef = effective_coef(problem, state)
    if problem.batched:
        preds = jax.vmap(
            lambda np_, nm, cf: _eval_all(problem.kernel, np_, nm, cf, xq)
        )(problem.nbr_pos, problem.nbr_mask, coef)
        return preds[:, : problem.n]
    preds = _eval_all(
        problem.kernel, problem.nbr_pos, problem.nbr_mask, coef, xq
    )
    return preds[: problem.n]


def single_sensor(preds: jax.Array, s: int = 0) -> jax.Array:
    return preds[..., s, :]


def knn_fusion(
    preds: jax.Array, positions: jax.Array, xq: jax.Array, k: int,
    alive: jax.Array | None = None,
) -> jax.Array:
    """Average the k LIVE sensors nearest each query (paper Eq. 19).

    preds: (..., n, Q) per-sensor estimates (any leading field axes); the
    selected sensors depend only on the shared positions, so the top-k runs
    once and broadcasts.  ``alive`` is the optional (n,) row liveness of a
    lifecycle problem — dead/spare rows are pushed to +inf distance so they
    are never selected.  This is the dense O(Q*n) oracle — serving goes
    through ``repro.core.serving.knn_fuse``, which answers the same rule
    from a static cell-candidate plan in O(Q*k).
    """
    xq = jnp.atleast_2d(jnp.asarray(xq, preds.dtype))
    positions = positions.astype(preds.dtype)
    d2 = jnp.sum((xq[:, None, :] - positions[None, :, :]) ** 2, axis=-1)  # (Q, n)
    if alive is not None:
        d2 = jnp.where(alive[None, :], d2, jnp.inf)
    neg, idx = jax.lax.top_k(-d2, k)  # (Q, k)
    pt = jnp.swapaxes(preds, -1, -2)  # (..., Q, n)
    gathered = jnp.take_along_axis(
        pt, jnp.broadcast_to(idx, pt.shape[:-2] + idx.shape), axis=-1
    )  # (..., Q, k)
    if alive is None:
        return jnp.mean(gathered, axis=-1)
    # Fewer than k live sensors: top_k must still return k indices, so the
    # overflow picks +inf-distance (dead) rows — average the live ones only.
    valid = jnp.isfinite(neg)  # (Q, k)
    return jnp.sum(jnp.where(valid, gathered, 0.0), axis=-1) / jnp.maximum(
        jnp.sum(valid, axis=-1), 1
    )


def nearest_neighbor(
    preds: jax.Array, positions: jax.Array, xq: jax.Array,
    alive: jax.Array | None = None,
) -> jax.Array:
    return knn_fusion(preds, positions, xq, k=1, alive=alive)


def network_average(
    preds: jax.Array, alive: jax.Array | None = None
) -> jax.Array:
    if alive is None:
        return jnp.mean(preds, axis=-2)
    w = alive.astype(preds.dtype)
    return (w[:, None] * preds).sum(-2) / w.sum()


def connectivity_averaged(
    preds: jax.Array, degrees: jax.Array, alive: jax.Array | None = None
) -> jax.Array:
    """Degree-weighted average (paper Eq. 20) over the LIVE sensors."""
    w = degrees.astype(preds.dtype)
    if alive is not None:
        w = jnp.where(alive, w, 0.0)
    return (w[:, None] * preds).sum(-2) / w.sum()


def global_coefficients(
    problem: SNTrainProblem, state: SNTrainState, rule: str = "conn"
) -> tuple[jax.Array, jax.Array]:
    """Collapse the per-sensor representers into ONE kernel expansion per
    field:  f(x) = sum_a cglob[a] K(x, anchor_a).

    Exactly equals the network-average ('avg') or connectivity-averaged
    ('conn', Eq. 20) fusion of the per-sensor estimates — every sensor's
    expansion is scattered onto the shared anchor set (the n sensor positions
    followed by the n_stream streaming-arrival positions), so the serving hot
    path is one batched kernel matvec (repro.kernels.kernel_matvec) instead
    of n per-sensor evaluations.

    Returns (anchors, coefs): single-field (A, d), (A,); batched
    (B, A, d), (B, A) with A = n + n_stream.  Dtypes follow the state.
    """
    n = problem.n
    s_cap = problem.n_stream
    cdt = state.coef.dtype
    # Dead/spare rows carry zero fusion weight (and their reserved anchors
    # zero coefficients), so churned problems serve from live sensors only.
    live = problem.alive[:n]
    deg = jnp.where(live, problem.topology.degrees, 0).astype(cdt)
    if rule == "conn":
        w = deg / deg.sum()
    elif rule == "avg":
        w = jnp.where(live, 1.0, 0.0).astype(cdt) / jnp.sum(live)
    else:
        raise ValueError(f"global_coefficients supports 'avg'/'conn', got {rule!r}")
    w_pad = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])  # sentinel sensor row

    positions = problem.topology.positions  # (n, d)
    ids = problem.nbr_idx  # (n+1, D) shared; sentinel row targets n + s_cap

    def one_field(nbr_mask, coef, stream_pos):
        contrib = jnp.where(nbr_mask, coef, 0.0) * w_pad[:, None]  # (n+1, D)
        cglob = (
            jnp.zeros((n + s_cap + 1,), coef.dtype)
            .at[ids.reshape(-1)]
            .add(contrib.reshape(-1))
        )
        anchors = jnp.concatenate([positions.astype(stream_pos.dtype), stream_pos])
        return anchors, cglob[: n + s_cap]

    ecoef = effective_coef(problem, state)  # true representer coefficients
    if problem.batched:
        return jax.vmap(one_field)(
            problem.nbr_mask, ecoef, problem.stream_pos
        )
    return one_field(problem.nbr_mask, ecoef, problem.stream_pos)


def fuse(
    problem: SNTrainProblem,
    state: SNTrainState,
    xq: jax.Array,
    rule: str = "nn",
    *,
    k: int = 1,
    sensor: int = 0,
    engine: str = "dense",
    plan=None,
    ecoef: jax.Array | None = None,
    compute_dtype=None,
    prune: jax.Array | None = None,
    block_q: int | None = None,
) -> jax.Array:
    """Convenience dispatcher over the paper's three rules.

    Returns (Q,) for single-field problems, (B, Q) for batched ones.

    engine: for the kNN rules ("nn"/"knn"), "dense" runs the all-sensors
    oracle in this module; "plan"/"pallas" route through the static query
    plans of ``repro.core.serving`` (pass a prebuilt ``plan`` from
    ``make_serving_plan`` to amortize the host-side precomputation across
    requests).  The other rules are already O(n)-per-query and accept only
    "dense".

    ecoef: optional precomputed ``effective_coef(problem, state)`` for the
    plan/pallas kNN engines — snapshot-serving processes (the daemon)
    compute it once per published snapshot and thread it through every
    query dispatch against that snapshot.

    compute_dtype/prune/block_q: the quantized + sparsified serving path
    (plan/pallas kNN engines only — the dense oracle stays full-precision
    by definition).  ``compute_dtype="bf16"`` stores the anchor tables in
    bf16 (selection-exact; accumulation stays in the coefficient dtype);
    ``prune`` is a (n+1,) ``pruning.prune_mask`` keep mask ANDed into
    liveness; ``block_q`` overrides the Pallas query tile for bulk sweeps.
    """
    if rule in ("nn", "knn") and engine != "dense":
        from . import serving

        return serving.knn_fuse(
            problem, state, xq,
            k=(1 if rule == "nn" else k), plan=plan, engine=engine,
            ecoef=ecoef, compute_dtype=compute_dtype, prune=prune,
            block_q=block_q,
        )
    if ecoef is not None:
        raise ValueError(
            "ecoef precomputation applies to the plan/pallas kNN engines "
            f"only; rule {rule!r} engine {engine!r} computes it internally"
        )
    if compute_dtype is not None or prune is not None or block_q is not None:
        raise ValueError(
            "compute_dtype/prune/block_q apply to the plan/pallas kNN "
            f"engines only; rule {rule!r} engine {engine!r} is the "
            "full-precision dense oracle"
        )
    if engine != "dense":
        raise ValueError(
            f"engine={engine!r} applies to the kNN rules only; "
            f"rule {rule!r} supports engine='dense'"
        )
    preds = evaluate_sensors(problem, state, xq)
    live = problem.alive[: problem.n]
    if rule == "single":
        return single_sensor(preds, sensor)
    if rule == "nn":
        return nearest_neighbor(preds, problem.topology.positions, xq, live)
    if rule == "knn":
        return knn_fusion(preds, problem.topology.positions, xq, k, live)
    if rule == "avg":
        return network_average(preds, live)
    if rule == "conn":
        return connectivity_averaged(preds, problem.topology.degrees, live)
    raise ValueError(f"unknown fusion rule {rule!r}")
