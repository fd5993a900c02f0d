"""Plan-based kNN-fusion serving engines (ISSUE-3 tentpole guarantees).

Covers:
  (a) plan/pallas kNN fusion == the dense oracle on random geometric
      topologies, k in {1, 3}, single-field and B > 1 (including
      streaming-diverged per-field anchors);
  (b) the plan's structural guarantees (every cell holds >= k valid
      candidates; ids in range);
  (c) ``streaming.absorb_many`` == repeated ``absorb`` EXACTLY (drop and
      evict policies, flags included);
  (d) the x64 dtype threading fix for the serving path (subprocess);
  (e) power-of-two query bucketing: a serving process with varied request
      sizes lowers O(log Q) Pallas programs, counted via the jit cache;
  (f) the plan engine as one program answers as its eager composition
      (selection, then a vmap of the evaluation over fields) did.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, strategies as st

from repro.core import (
    Kernel,
    build_topology,
    colored_sweep,
    field_view,
    fusion,
    init_state,
    make_batch_problem,
    make_problem,
    make_serving_plan,
    serving,
    streaming,
    uniform_sensors,
)

KERN = Kernel("rbf", gamma=1.0)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _single(n=35, radius=0.7, seed=0, d=1, sweeps=15):
    pos = uniform_sensors(n, d=d, seed=seed)
    topo = build_topology(pos, radius)
    rng = np.random.default_rng(seed + 1)
    y = np.sin(np.pi * pos[:, 0]) + 0.2 * rng.normal(size=n)
    prob = make_problem(topo, KERN, y, jnp.full((n,), 0.1))
    state = colored_sweep(prob, init_state(prob), n_sweeps=sweeps)
    return prob, state, pos, rng


def _batched(n=30, b=3, radius=0.7, seed=0, d=1, headroom=0, sweeps=10):
    pos = uniform_sensors(n, d=d, seed=seed)
    topo = build_topology(pos, radius)
    if headroom:
        d_max = int(np.asarray(topo.degrees).max()) + headroom
        topo = build_topology(pos, radius, d_max=d_max)
    rng = np.random.default_rng(seed + 1)
    freq = rng.uniform(0.5, 2.0, size=(b, 1))
    ys = np.sin(np.pi * freq * pos[None, :, 0]) + 0.3 * rng.normal(size=(b, n))
    prob = make_batch_problem(topo, KERN, ys, jnp.full((n,), 0.1))
    state = colored_sweep(prob, init_state(prob), n_sweeps=sweeps)
    return prob, state, pos, rng


# ---------------------------------------------------------------------------
# (a) engine agreement: dense == plan == pallas
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=5)
@given(seed=st.integers(0, 500), k=st.sampled_from([1, 3]))
def test_plan_and_pallas_match_dense_single_field(seed, k):
    """Acceptance: the three engines agree within 1e-5 on random geometric
    topologies (queries inside the plan domain)."""
    prob, state, pos, rng = _single(seed=seed)
    lo, hi = pos[:, 0].min(), pos[:, 0].max()
    xq = rng.uniform(lo, hi, size=(61, 1)).astype(np.float32)
    dense = np.asarray(fusion.fuse(prob, state, xq, "knn", k=k))
    plan = make_serving_plan(prob, k=k)
    for engine in ("plan", "pallas"):
        out = fusion.fuse(prob, state, xq, "knn", k=k, engine=engine, plan=plan)
        assert out.shape == dense.shape
        np.testing.assert_allclose(np.asarray(out), dense, atol=1e-5, err_msg=engine)


def test_plan_and_pallas_match_dense_2d():
    prob, state, pos, rng = _single(n=60, radius=0.5, seed=3, d=2)
    xq = rng.uniform(pos.min(), pos.max(), size=(47, 2)).astype(np.float32)
    plan = make_serving_plan(prob, k=3)
    dense = np.asarray(fusion.fuse(prob, state, xq, "knn", k=3))
    for engine in ("plan", "pallas"):
        out = fusion.fuse(prob, state, xq, "knn", k=3, engine=engine, plan=plan)
        np.testing.assert_allclose(np.asarray(out), dense, atol=1e-5, err_msg=engine)


def test_nn_rule_routes_through_plan_engines():
    prob, state, pos, rng = _single(seed=9)
    xq = rng.uniform(-0.8, 0.8, size=(33, 1)).astype(np.float32)
    dense = np.asarray(fusion.fuse(prob, state, xq, "nn"))
    for engine in ("plan", "pallas"):
        out = fusion.fuse(prob, state, xq, "nn", engine=engine)
        np.testing.assert_allclose(np.asarray(out), dense, atol=1e-5, err_msg=engine)


def test_batched_with_streaming_diverged_anchors():
    """B > 1 where streaming absorption made nbr_pos/coef diverge per field:
    the shared top-k selection + per-field evaluation still matches dense."""
    prob, state, pos, rng = _batched(b=3, headroom=5)
    for _ in range(12):
        f = int(rng.integers(0, 3))
        s = int(rng.integers(0, prob.n))
        x = (pos[s] + 0.1 * rng.normal(size=pos.shape[1])).astype(np.float32)
        prob, state, _ = streaming.absorb(prob, state, f, s, x, float(rng.normal()))
    state = colored_sweep(prob, state, n_sweeps=4)
    xq = rng.uniform(-0.9, 0.9, size=(41, 1)).astype(np.float32)
    dense_b = np.asarray(fusion.fuse(prob, state, xq, "knn", k=3))
    assert dense_b.shape == (3, 41)
    # the batched dense path itself equals the per-field single-field oracle
    for b in range(3):
        pv, sv = field_view(prob, state, b)
        np.testing.assert_allclose(
            dense_b[b], np.asarray(fusion.fuse(pv, sv, xq, "knn", k=3)),
            atol=1e-6,
        )
    plan = make_serving_plan(prob, k=3)
    for engine in ("plan", "pallas"):
        out = fusion.fuse(prob, state, xq, "knn", k=3, engine=engine, plan=plan)
        np.testing.assert_allclose(np.asarray(out), dense_b, atol=1e-5, err_msg=engine)


def test_other_rules_reject_plan_engines():
    prob, state, _, rng = _single()
    xq = np.zeros((4, 1), np.float32)
    with pytest.raises(ValueError, match="kNN rules"):
        fusion.fuse(prob, state, xq, "conn", engine="plan")
    with pytest.raises(ValueError, match="k="):
        plan = make_serving_plan(prob, k=1)
        fusion.fuse(prob, state, xq, "knn", k=3, engine="plan", plan=plan)


# ---------------------------------------------------------------------------
# (b) plan structure
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=4)
@given(seed=st.integers(0, 500), k=st.sampled_from([1, 3, 5]))
def test_plan_cells_hold_enough_valid_candidates(seed, k):
    prob, _, _, _ = _single(n=45, seed=seed, d=2, radius=0.6, sweeps=1)
    plan = make_serving_plan(prob, k=k)
    cells = np.asarray(plan.cells)
    mask = np.asarray(plan.cell_mask)
    assert (mask.sum(axis=1) >= k).all()  # exact top-k always has k sources
    assert (cells[mask] < prob.n).all() and (cells[mask] >= 0).all()
    assert (cells[~mask] == prob.n).all()  # padding points at the sentinel
    assert plan.n_cells == int(np.prod(plan.grid_shape))


def test_knn_select_matches_dense_argsort():
    prob, _, pos, rng = _single(n=50, seed=4, d=2, radius=0.6, sweeps=1)
    plan = make_serving_plan(prob, k=3)
    xq = rng.uniform(pos.min(), pos.max(), size=(29, 2)).astype(np.float32)
    sel = np.asarray(serving.knn_select(plan, prob.topology.positions, jnp.asarray(xq), 3))
    d2 = ((xq[:, None, :] - pos[None, :, :]) ** 2).sum(-1)
    ref = np.argsort(d2, axis=1, kind="stable")[:, :3]
    np.testing.assert_array_equal(sel, ref)


# ---------------------------------------------------------------------------
# (c) absorb_many == repeated absorb, exactly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("on_full", ["drop", "evict"])
def test_absorb_many_equals_repeated_absorb(on_full):
    prob0, state0, pos, _ = _batched(b=2, headroom=2, sweeps=3)
    rng = np.random.default_rng(17)
    a = 14
    fields = rng.integers(0, 2, size=a)
    sensors = rng.integers(0, prob0.n, size=a)
    # overflow the max-degree sensor (streaming capacity exactly 2) of
    # field 0 so the on_full policy actually fires mid-scan
    s_full = int(np.argmax(np.asarray(prob0.topology.degrees)))
    fields[:4] = 0
    sensors[:4] = s_full
    xs = (pos[sensors] + 0.05 * rng.normal(size=(a, pos.shape[1]))).astype(np.float32)
    ys = rng.normal(size=a).astype(np.float32)

    p1, s1 = prob0, state0
    flags_seq = []
    for i in range(a):
        p1, s1, ok = streaming.absorb(
            p1, s1, int(fields[i]), int(sensors[i]), xs[i], float(ys[i]),
            on_full=on_full,
        )
        flags_seq.append(bool(ok))
    p2, s2, receipt = streaming.absorb_many(
        prob0, state0, fields, sensors, xs, ys, on_full=on_full
    )
    assert receipt.absorbed.shape == (a,) and receipt.evicted.shape == (a,)
    assert [bool(f) for f in np.asarray(receipt.absorbed)] == flags_seq
    evicted = np.asarray(receipt.evicted)
    if on_full == "drop":
        assert not all(flags_seq)  # capacity 2/sensor: some drops occurred
        assert not evicted.any()  # the drop policy never evicts
    else:
        # the sliding window absorbed everything; over-capacity arrivals
        # are flagged as evictions (observable capacity pressure)
        assert all(flags_seq)
        assert evicted.any()
        assert (~evicted | np.asarray(receipt.absorbed)).all()
    for name in ("nbr_pos", "nbr_mask", "gram", "chol", "stream_pos"):
        np.testing.assert_array_equal(
            np.asarray(getattr(p1, name)), np.asarray(getattr(p2, name)),
            err_msg=name,
        )
    np.testing.assert_array_equal(np.asarray(s1.z), np.asarray(s2.z))
    np.testing.assert_array_equal(np.asarray(s1.coef), np.asarray(s2.coef))


def test_absorb_many_validates_like_absorb():
    prob, state, _, _ = _batched(b=2, headroom=2, sweeps=1)
    with pytest.raises(ValueError, match="xs must be"):
        streaming.absorb_many(
            prob, state, np.zeros(3, np.int32), np.zeros(3, np.int32),
            np.zeros((2, 1), np.float32), np.zeros(3, np.float32),
        )
    with pytest.raises(ValueError, match="on_full"):
        streaming.absorb_many(
            prob, state, np.zeros(1, np.int32), np.zeros(1, np.int32),
            np.zeros((1, 1), np.float32), np.zeros(1, np.float32),
            on_full="explode",
        )


# ---------------------------------------------------------------------------
# (d) dtype threading through the serving path (x64 subprocess)
# ---------------------------------------------------------------------------


def test_serving_path_preserves_f64_subprocess():
    """The fusion/serving path must not silently truncate x64 problems (the
    paper-lambda configuration) to f32."""
    code = r"""
import os
os.environ["JAX_ENABLE_X64"] = "1"
import numpy as np, jax.numpy as jnp
from repro.core import (Kernel, build_topology, colored_sweep, fusion,
                        init_state, make_problem, make_serving_plan,
                        uniform_sensors)
n = 25
pos = uniform_sensors(n, seed=0)
topo = build_topology(pos, 0.8)
y = np.sin(np.pi * pos[:, 0])
prob = make_problem(topo, Kernel("rbf", gamma=1.0), y, dtype=jnp.float64)
state = colored_sweep(prob, init_state(prob), n_sweeps=20)
xq = np.linspace(-0.9, 0.9, 17)[:, None]
preds = fusion.evaluate_sensors(prob, state, xq)
assert preds.dtype == jnp.float64, preds.dtype
for rule in ("nn", "conn", "avg", "single"):
    out = fusion.fuse(prob, state, xq, rule)
    assert out.dtype == jnp.float64, (rule, out.dtype)
plan = make_serving_plan(prob, k=3)
dense = fusion.fuse(prob, state, xq, "knn", k=3)
assert dense.dtype == jnp.float64
for engine in ("plan", "pallas"):
    out = fusion.fuse(prob, state, xq, "knn", k=3, engine=engine, plan=plan)
    assert out.dtype == jnp.float64, (engine, out.dtype)
    assert np.abs(np.asarray(out) - np.asarray(dense)).max() < 1e-10
anchors, coefs = fusion.global_coefficients(prob, state, rule="conn")
assert coefs.dtype == jnp.float64 and anchors.dtype == jnp.float64
print("OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# (e) recompile bucketing: O(log Q) lowered programs for varied request sizes
# ---------------------------------------------------------------------------


def test_kernel_matvec_buckets_query_sizes():
    from repro.analysis import compile_ledger
    from repro.kernels import bucket_rows, kernel_matvec
    from repro.kernels.ref import kernel_matvec_ref

    rng = np.random.default_rng(0)
    an = rng.normal(size=(40, 2)).astype(np.float32)
    cf = rng.normal(size=(40,)).astype(np.float32)
    sizes = list(range(1, 230, 11))
    buckets = {bucket_rows(q) for q in sizes}
    snap = compile_ledger.snapshot(("serving.matvec",))
    for q in sizes:
        xq = rng.normal(size=(q, 2)).astype(np.float32)
        out = kernel_matvec(xq, an, cf, gamma=1.0)
        assert out.shape == (q,)
        ref = kernel_matvec_ref(jnp.asarray(xq), jnp.asarray(an), jnp.asarray(cf), 1.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
    snap.assert_within(buckets=len(buckets), context="matvec query sizes")


def test_knn_fuse_buckets_query_sizes():
    from repro.analysis import compile_ledger
    from repro.kernels import bucket_rows

    prob, state, pos, rng = _single(n=30, seed=6)
    plan = make_serving_plan(prob, k=1)
    dense = lambda xq: np.asarray(fusion.fuse(prob, state, xq, "nn"))
    snap = compile_ledger.snapshot(("serving.knn_kernel",))
    sizes = [3, 9, 17, 33, 65, 100]
    for q in sizes:
        xq = rng.uniform(-0.9, 0.9, size=(q, 1)).astype(np.float32)
        out = fusion.fuse(prob, state, xq, "nn", engine="pallas", plan=plan)
        np.testing.assert_allclose(np.asarray(out), dense(xq), atol=1e-5)
    snap.assert_within(
        buckets=len({bucket_rows(q) for q in sizes}),
        context="knn_fuse query sizes",
    )


# ---------------------------------------------------------------------------
# ISSUE-5 satellite: dense / plan / pallas agree at EVERY liveness fraction
# (all-dead, one-alive, exactly-k-alive, fully-alive) — when fewer than k
# live sensors exist, every engine averages the live selections only.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("live_count", [0, 1, 3, None])
def test_knn_engines_agree_at_liveness_fractions(live_count):
    from repro.core import remove_sensor

    n, b, k = 8, 2, 3
    pos = np.linspace(-0.8, 0.8, n)[:, None].astype(np.float32)
    topo = build_topology(pos, 2.0, d_max=n + 2, n_max=n + 1)
    rng = np.random.default_rng(0)
    ys = np.sin(np.pi * pos[None, :, 0]) + 0.1 * rng.normal(size=(b, n))
    prob = make_batch_problem(topo, KERN, ys, jnp.full((n,), 0.2))
    state = colored_sweep(prob, init_state(prob), n_sweeps=15)
    # plan built at full liveness, then repaired through the removals
    plan = make_serving_plan(prob, k=k, spare=2, slack=n)
    if live_count is not None:
        for s in range(live_count, n):
            prob, state, ok = remove_sensor(prob, state, s)
            assert bool(ok)
            plan = serving.plan_remove_sensor(plan, s)
    xq = rng.uniform(-0.9, 0.9, size=(13, 1)).astype(np.float32)
    dense = np.asarray(fusion.fuse(prob, state, xq, "knn", k=k))
    out_plan = np.asarray(
        fusion.fuse(prob, state, xq, "knn", k=k, engine="plan", plan=plan)
    )
    out_pal = np.asarray(
        fusion.fuse(prob, state, xq, "knn", k=k, engine="pallas", plan=plan)
    )
    np.testing.assert_allclose(out_plan, dense, atol=1e-5, err_msg="plan")
    np.testing.assert_allclose(out_pal, dense, atol=1e-5, err_msg="pallas")
    if live_count == 0:
        # all dead: the kNN average is exactly zero in every engine
        assert np.abs(dense).max() == 0.0
        assert np.abs(out_plan).max() == 0.0
        assert np.abs(out_pal).max() == 0.0
    elif live_count is not None and live_count < k:
        # k exceeds the live count: predictions average the live sensors
        # only (no zero-dilution), so they are NOT scaled by live/k
        assert np.abs(dense).max() > 0.0


# ---------------------------------------------------------------------------
# (f) the plan engine is one program; it answers as the eager composition
# ---------------------------------------------------------------------------


def _eager_plan(prob, state, xq, k, plan, compute_dtype=None, prune=None):
    """The plan engine composed eagerly: the cast, the liveness gate,
    ``effective_coef``, the selection, then a vmap of the evaluation."""
    from repro.core import effective_coef

    cdt = serving._norm_compute_dtype(compute_dtype)
    alive = prob.alive
    if prune is not None:
        alive = ((alive != 0) & (prune != 0)).astype(alive.dtype)
    dt = prob.nbr_pos.dtype
    xq = jnp.atleast_2d(jnp.asarray(xq, dt))
    positions = prob.topology.positions.astype(dt)
    ecoef = effective_coef(prob, state)
    sel, valid = serving.knn_select_valid(plan, positions, xq, k, alive)

    def one_field(np_, nm, cf):
        return serving._eval_selected(
            prob.kernel, np_, nm, cf, sel, valid, xq, k, compute_dtype=cdt
        )

    if prob.batched:
        return jax.vmap(one_field)(prob.nbr_pos, prob.nbr_mask, ecoef)
    return one_field(prob.nbr_pos, prob.nbr_mask, ecoef)


@pytest.mark.parametrize("case", ["batched", "single", "bf16_prune", "churned"])
def test_plan_engine_program_matches_eager_composition(case):
    from repro.core import pruning, remove_sensor

    k, cdt, prune = 3, None, None
    if case == "single":
        prob, state, pos, rng = _single(n=30, seed=4)
    else:
        prob, state, pos, rng = _batched(n=30, seed=4)
    plan = make_serving_plan(prob, k=k, slack=4)
    if case == "bf16_prune":
        cdt = "bf16"
        energy = np.asarray(pruning.representer_energy(prob, state))
        tau = float(np.median(energy[: prob.n]))
        prune = pruning.prune_mask(prob, state, energy_tau=tau)
        assert 0 < int(np.asarray(prune[: prob.n]).sum()) < prob.n
    if case == "churned":
        for s in (3, 11, 20):
            prob, state, ok = remove_sensor(prob, state, s)
            assert bool(ok)
            plan = serving.plan_remove_sensor(plan, s)
        assert int(np.asarray(prob.alive[: prob.n]).sum()) == prob.n - 3
    xq = rng.uniform(-0.9, 0.9, size=(37, 1)).astype(np.float32)
    got = np.asarray(fusion.fuse(
        prob, state, xq, "knn", k=k, engine="plan", plan=plan,
        compute_dtype=cdt, prune=prune,
    ))
    want = np.asarray(_eager_plan(prob, state, xq, k, plan, cdt, prune))
    assert got.shape == want.shape == (
        (prob.y.shape[0], 37) if prob.batched else (37,)
    )
    np.testing.assert_array_equal(got, want)
    if prune is None:  # the dense oracle knows no prune mask
        dense = np.asarray(fusion.fuse(prob, state, xq, "knn", k=k))
        np.testing.assert_allclose(got, dense, atol=1e-5)
