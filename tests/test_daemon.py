"""Serving daemon (ISSUE 8 tentpole): bucketed coalescing queue,
double-buffered snapshot isolation, supervised degraded mode, and
crash-kill -> warm-restart.

The load-bearing pins:

  * ANY interleaving of request sizes drains through the daemon queue
    with <= O(log max_batch_rows) distinct compiled serving programs —
    the PR-3 power-of-two bucketing property, extended to the coalescing
    dispatcher and counted via the jit cache (hypothesis drives the
    interleavings);
  * answers are EXACT under coalescing + padding: each request's slice
    matches the dense oracle regardless of which batch it rode in;
  * a held snapshot keeps serving its own answers bitwise while training
    ticks publish new versions (double buffering — no torn reads);
  * admission control sheds with explicit receipts (queue_full /
    deadline), never silently;
  * a poisoned training tick rolls back, does NOT publish, flags the
    daemon degraded, and queries keep flowing from the last good
    snapshot; the next healthy tick recovers;
  * ``pad_arrivals`` sentinel padding is a bitwise no-op on the absorbed
    problem/state (the dead-row gates make padded windows exact);
  * a daemon rebuilt over the same templates warm-restarts from the
    latest intact checkpoint bitwise (digest + served answers), straight
    through a SIGKILLed serving process (subprocess).
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_shim import given, settings, strategies as st

from repro.core import (
    Kernel,
    build_topology,
    fusion,
    init_state,
    make_batch_problem,
    make_serving_plan,
    streaming,
    uniform_sensors,
)
from repro.analysis import compile_ledger
from repro.core import faults
from repro.kernels.ops import bucket_rows
from repro.launch.daemon import Daemon, DaemonConfig

KERN = Kernel("rbf", gamma=1.0)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(n=24, b=3, seed=0, headroom=4, n_max=None):
    pos = uniform_sensors(n, seed=seed)
    rng = np.random.default_rng(seed + 1)
    freq = rng.uniform(0.5, 2.0, size=(b, 1))
    ys = (
        np.sin(np.pi * freq * pos[None, :, 0])
        + 0.2 * rng.normal(size=(b, n))
    ).astype(np.float32)
    topo = build_topology(pos, 0.6)
    d_max = int(np.asarray(topo.degrees).max()) + headroom
    topo = build_topology(pos, 0.6, d_max=d_max, n_max=n_max)
    prob = make_batch_problem(topo, KERN, ys, jnp.full((n,), 0.1))
    return prob, init_state(prob), pos, rng


# One problem shared by every hypothesis example: the jit caches are
# process-global, so the bucket-count bound must hold ACROSS examples —
# exactly the sustained-traffic property the daemon claims.
_FIX = None
_CACHE_BASE: dict = {}
_BUCKETS_SEEN: set = set()


def _fix():
    global _FIX
    if _FIX is None:
        _FIX = _build()
    return _FIX


@settings(deadline=None, max_examples=15)
@given(sizes=st.lists(st.integers(1, 60), min_size=1, max_size=12))
def test_any_interleaving_drains_through_buckets(sizes):
    """The daemon queue inherits the O(log Q) program bound: over ALL
    interleavings of request sizes, the serving programs compiled grow at
    most one per distinct power-of-two bucket — and every request's
    answer slice is exact vs the dense oracle."""
    prob, state, pos, _ = _fix()
    if not _CACHE_BASE:
        _CACHE_BASE["snap"] = compile_ledger.snapshot("daemon")
    d = Daemon(prob, state, config=DaemonConfig(k=3, max_batch_rows=64))
    rng = np.random.default_rng(sum(sizes))
    grids = [
        rng.uniform(-0.9, 0.9, size=(q, 1)).astype(np.float32)
        for q in sizes
    ]
    tickets = [d.submit(g) for g in grids]
    assert all(t.admitted for t in tickets)
    answers = {a.id: a for a in d.pump()}
    assert len(answers) == len(sizes)
    _BUCKETS_SEEN.update(
        int(b) for b in d.health()["counters"]["dispatches"]
    )
    for t, g in zip(tickets, grids):
        got = answers[t.id].values
        want = np.asarray(fusion.fuse(prob, state, g, "knn", k=3))
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=1e-5)
    # every bucket is a power of two no larger than the batch cap's bucket
    assert all(
        b & (b - 1) == 0 and b <= bucket_rows(64) for b in _BUCKETS_SEEN
    )
    _CACHE_BASE["snap"].assert_within(
        buckets=len(_BUCKETS_SEEN), context="daemon interleavings"
    )


def test_warm_pump_traces_and_compiles_nothing():
    """One serving program per bucket: once every bucket has been pumped,
    pumping every bucket again traces no jaxpr (``serve_traces`` grows by
    0) and compiles nothing (the daemon group's ledger growth is 0)."""
    prob, state, pos, _ = _fix()
    d = Daemon(prob, state, config=DaemonConfig(k=3, max_batch_rows=64))
    rng = np.random.default_rng(7)
    buckets = sorted({bucket_rows(r) for r in range(1, 65)})

    def pump_each_bucket():
        for r in buckets:
            d.submit(rng.uniform(-0.9, 0.9, size=(r, 1)).astype(np.float32))
            (answer,) = d.pump()
            assert answer.values.shape == (prob.y.shape[0], r)

    pump_each_bucket()  # warm
    traces = d.health()["counters"]["serve_traces"]
    snap = compile_ledger.snapshot("daemon")
    pump_each_bucket()
    counters = d.health()["counters"]
    assert counters["serve_traces"] == traces
    assert snap.growth() == {"serving.knn_plan": 0}
    assert counters["dispatches"] == {str(b): 2 for b in buckets}


# Runs of 1-8-row points between full 256-row tiles: eight dispatches
# under the default 256-row cap (points, tile, points, tile, ...).
_MIX = [3, 8, 1, 5, 2, 7] * 4 + [256] + [4, 6, 1] * 9 + [256] + [2] * 40 \
    + [256] + [8, 8, 3] + [256]


def _serial_answers(snap, grids, ids, max_rows=256):
    """The one-at-a-time drain: coalesce front-to-back under the cap, pad
    to the bucket, answer each batch through ``fusion.fuse`` on ``snap``
    and wait for it before packing the next.  Returns the (id, values)
    answers and the number of dispatches."""
    out, i, dispatches = [], 0, 0
    while i < len(grids):
        j, rows = i + 1, grids[i].shape[0]
        while j < len(grids) and rows + grids[j].shape[0] <= max_rows:
            rows += grids[j].shape[0]
            j += 1
        xq = np.concatenate(grids[i:j])
        xq = np.concatenate([xq, np.repeat(xq[-1:], bucket_rows(rows) - rows, 0)])
        vals = np.asarray(fusion.fuse(
            snap.problem, snap.state, xq, "knn", k=3, engine="plan",
            plan=snap.plan, ecoef=snap.ecoef, prune=snap.keep,
        ))
        off = 0
        for g, qid in zip(grids[i:j], ids[i:j]):
            out.append((qid, vals[:, off:off + g.shape[0]]))
            off += g.shape[0]
        i, dispatches = j, dispatches + 1
    return out, dispatches


def test_pipelined_pump_answers_bitwise_like_the_serial_drain():
    """A pump launches every queued dispatch before collecting any; its
    answers equal the serial drain's bitwise (values, id order, version,
    degraded), before and after a publishing tick, and the counters say
    that the pipeline overlapped every dispatch of a pump but its first."""
    prob, state, pos, _ = _build(seed=11)
    cfg = DaemonConfig(k=3, arrival_rows=8, queue_rows=2048)
    d = Daemon(prob, state, config=cfg)
    rng = np.random.default_rng(11)

    def pump_and_check(version):
        grids = [rng.uniform(-0.9, 0.9, size=(q, 1)).astype(np.float32)
                 for q in _MIX]
        tickets = [d.submit(g) for g in grids]
        assert all(t.admitted for t in tickets)
        ids = [t.id for t in tickets]
        c0 = d.health()["counters"]
        answers = d.pump()
        want, n_disp = _serial_answers(d.snapshot, grids, ids)
        assert n_disp >= 6
        assert [a.id for a in answers] == ids
        for a, (qid, vals) in zip(answers, want):
            assert a.id == qid
            np.testing.assert_array_equal(a.values, vals)
            assert a.version == version and a.degraded is False
        c = d.health()["counters"]
        assert sum(c["dispatches"].values()) - sum(
            c0["dispatches"].values()) == n_disp
        assert c["pumps"] - c0["pumps"] == 1  # overlapped: n_disp - 1
        return c

    pump_and_check(0)
    ss = rng.integers(0, prob.n, size=5)
    d.offer_arrivals(rng.integers(0, 3, size=5), ss,
                     (pos[ss] + 0.02).astype(np.float32),
                     rng.normal(size=5).astype(np.float32))
    assert d.tick().published
    c = pump_and_check(1)  # the new snapshot
    d.submit(np.zeros((5, 1), np.float32))
    (one,) = d.pump()  # one dispatch: no pipeline
    assert one.version == 1
    c1 = d.health()["counters"]
    assert c1["pumps"] == c["pumps"] + 1
    assert c1["dispatches"]["8"] == c["dispatches"].get("8", 0) + 1
    assert d.pump() == []  # an empty pump is no pump
    assert d.health()["counters"]["pumps"] == c1["pumps"]


@pytest.mark.parametrize("launch_bound", [False, True],
                         ids=["device_bound", "launch_bound"])
def test_admission_estimate_stays_per_dispatch(launch_bound, monkeypatch):
    """The EMA dispatch time is the drain's mean service time of one
    dispatch, whatever its place in the pipeline: after a first pump of
    N >= 6 dispatches it lies between half and all of (pump span) / N.
    The launch-bound case finishes each dispatch inside its launch, as a
    chip does whose host launches slower than it computes; the later
    dispatches are then done before their collect, so a stamp per
    dispatch (completion - launch, or completion - previous completion)
    misreads them."""
    from repro import obs

    if launch_bound:
        fuse = fusion.fuse
        monkeypatch.setattr(
            fusion, "fuse", lambda *a, **kw: fuse(*a, **kw).block_until_ready())
    prob, state, _, _ = _fix()
    d = Daemon(prob, state, config=DaemonConfig(k=3, queue_rows=2048))
    rng = np.random.default_rng(12)
    for q in _MIX:
        assert d.submit(
            rng.uniform(-0.9, 0.9, size=(q, 1)).astype(np.float32)).admitted
    obs.enable()
    try:
        d.pump()
        spans, _ = obs.drain()
    finally:
        obs.disable()
        obs.drain()
    (pump,) = [s for s in spans if s[0] == "daemon.pump"]
    n = sum(s[0] == "serve.dispatch" for s in spans)
    assert n >= 6
    per_dispatch = (pump[2] - pump[1]) * 1e-9 / n
    assert 0.5 * per_dispatch <= d._ema_batch_s <= per_dispatch


def test_pad_arrivals_is_bitwise_noop():
    """Absorbing a window padded with sentinel-row arrivals must equal the
    unpadded absorb bitwise — problem, state, and real-row receipt flags."""
    prob, state, pos, rng = _build(seed=3)
    a = 5
    fs = rng.integers(0, 3, size=a).astype(np.int32)
    ss = rng.integers(0, prob.n, size=a).astype(np.int32)
    xs = (pos[ss] + 0.05 * rng.normal(size=(a, 1))).astype(np.float32)
    ys = rng.normal(size=a).astype(np.float32)
    p0, s0, r0 = streaming.absorb_many(prob, state, fs, ss, xs, ys)
    fp, sp, xp, yp, real = streaming.pad_arrivals(prob, fs, ss, xs, ys, 8)
    assert real.sum() == a and real.shape == (8,)
    p1, s1, r1 = streaming.absorb_many(prob, state, fp, sp, xp, yp)
    for l0, l1 in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        assert np.array_equal(np.asarray(l0), np.asarray(l1))
    for l0, l1 in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)):
        assert np.array_equal(np.asarray(l0), np.asarray(l1))
    assert np.array_equal(np.asarray(r0.absorbed), np.asarray(r1.absorbed)[real])
    # padding rows are no-op non-absorbs, never spurious writes
    assert not np.asarray(r1.absorbed)[~real].any()
    with pytest.raises(ValueError):
        streaming.pad_arrivals(prob, fs, ss, xs, ys, a - 1)


def test_snapshot_isolation_across_ticks():
    """A held snapshot serves its own answers bitwise while ticks publish
    new versions behind it (the double buffer never tears)."""
    prob, state, pos, rng = _build(seed=4)
    d = Daemon(prob, state, config=DaemonConfig(k=3))
    xq = rng.uniform(-0.9, 0.9, size=(16, 1)).astype(np.float32)
    snap0 = d.snapshot
    d.submit(xq)
    (a0,) = d.pump()
    assert a0.version == 0
    for _ in range(2):
        ss = rng.integers(0, prob.n, size=6)
        d.offer_arrivals(
            rng.integers(0, 3, size=6), ss,
            (pos[ss] + 0.02 * rng.normal(size=(6, 1))).astype(np.float32),
            rng.normal(size=6).astype(np.float32),
        )
        rcpt = d.tick()
        assert rcpt.published
    assert d.snapshot.version == 2
    d.submit(xq)
    (a2,) = d.pump()
    assert a2.version == 2
    assert not np.array_equal(a0.values, a2.values)  # training moved
    # the old snapshot's buffers are intact and reproduce a0 bitwise
    # (same padded grid -> same program -> deterministic replay)
    pad = bucket_rows(16) - 16
    xq_pad = np.concatenate([xq, np.repeat(xq[-1:], pad, axis=0)])
    again = fusion.fuse(
        snap0.problem, snap0.state, xq_pad,
        "knn", k=3, engine="plan", plan=snap0.plan, ecoef=snap0.ecoef,
    )
    assert np.array_equal(np.asarray(again)[:, :16], a0.values)


def test_admission_control_sheds_with_receipts():
    prob, state, _, rng = _build(seed=5)
    d = Daemon(prob, state, config=DaemonConfig(k=3, queue_rows=16))
    t1 = d.submit(np.zeros((12, 1), np.float32))
    t2 = d.submit(np.zeros((12, 1), np.float32))
    assert t1.admitted and not t2.admitted
    assert t2.shed_reason == "queue_full" and d.shed == 1
    assert len(d.pump()) == 1  # the admitted one still drains

    # deadline shedding: after one dispatch calibrates the EMA, a zero
    # budget rejects everything with the deadline receipt
    d2 = Daemon(prob, state, config=DaemonConfig(k=3, deadline_ms=0.0))
    assert d2.submit(np.zeros((4, 1), np.float32)).admitted  # EMA unset yet
    d2.pump()
    t = d2.submit(np.zeros((4, 1), np.float32))
    assert not t.admitted and t.shed_reason == "deadline"


def test_degraded_tick_serves_last_good_then_recovers():
    """A poisoned working state exhausts the watchdog ladder: the tick
    rolls back, nothing is published, the daemon flags degraded, queries
    keep serving the last good snapshot — and the next tick recovers
    because the working copy was restored from it."""
    import dataclasses

    prob, state, pos, rng = _build(seed=6)
    d = Daemon(
        prob, state,
        config=DaemonConfig(k=3, rounds_per_tick=14, arrival_rows=8),
    )
    assert d.tick().published  # version 1, known good
    xq = rng.uniform(-0.9, 0.9, size=(9, 1)).astype(np.float32)
    d.submit(xq)
    (good,) = d.pump()
    assert good.version == 1 and not good.degraded

    wp, ws = d._work
    d._work = (wp, dataclasses.replace(ws, z=ws.z.at[0, 0].set(jnp.nan)))
    ss = rng.integers(0, prob.n, size=3)
    d.offer_arrivals(
        rng.integers(0, 3, size=3), ss,
        (pos[ss]).astype(np.float32), rng.normal(size=3).astype(np.float32),
    )
    bad = d.tick()
    assert bad.watchdog.rolled_back and not bad.published
    assert bad.degraded and bad.version == 1
    assert bad.arrivals_rolled_back == 3 and bad.absorbed == 0
    assert d.health()["degraded"] is True

    d.submit(xq)
    (during,) = d.pump()
    assert during.degraded and during.version == 1
    assert np.array_equal(during.values, good.values)  # last good, bitwise

    rec = d.tick()  # working copy was restored from the published snapshot
    assert rec.published and not rec.degraded and rec.version == 2


def test_churn_events_apply_through_ticks():
    prob, state, pos, rng = _build(seed=7, n_max=28)
    plan = make_serving_plan(prob, k=3, spare=4, slack=2)
    d = Daemon(prob, state, config=DaemonConfig(k=3), plan=plan)
    d.offer_join(
        np.array([0.15], np.float32), np.zeros(3, np.float32), lam=0.1
    )
    r = d.tick()
    assert r.joins == 1 and r.published
    d.offer_leave(2)
    r = d.tick()
    assert r.leaves == 1 and r.published
    d.submit(rng.uniform(-0.9, 0.9, size=(7, 1)).astype(np.float32))
    (a,) = d.pump()
    assert np.isfinite(a.values).all()


def test_fault_drill_zero_recompiles():
    """Flipping drill rates on and off reuses the already-compiled
    training programs — rates are traced operands, structure is static."""
    prob, state, _, _ = _build(seed=8)
    d = Daemon(prob, state, config=DaemonConfig(k=3))
    d.tick()  # warm the training program set
    snap = compile_ledger.snapshot("faults")
    d.set_fault_model(faults.make_fault_model(0.25))
    d.tick()
    d.set_fault_model(faults.make_fault_model(0.0))
    d.tick()
    snap.assert_within(context="fault drill rate flips")
    # crash structure is static — swapping it in is a refused recompile
    with pytest.raises(ValueError):
        d.set_fault_model(faults.make_fault_model(0.1, crash=(0.1, 0.5)))


def test_warm_restart_is_bitwise():
    prob, state, _, rng = _build(seed=9)
    with tempfile.TemporaryDirectory() as snap:
        cfg = DaemonConfig(k=3, ckpt_every=1, snapshot_dir=snap)
        d = Daemon(prob, state, config=cfg)
        for _ in range(3):
            assert d.tick().published
        xq = rng.uniform(-0.9, 0.9, size=(11, 1)).astype(np.float32)
        d.submit(xq)
        (before,) = d.pump()
        digest = d.state_digest()

        d2 = Daemon(prob, state, config=cfg)  # same templates, fresh build
        assert d2.restored_step == 3
        assert d2.state_digest() == digest
        d2.submit(xq)
        (after,) = d2.pump()
        assert np.array_equal(before.values, after.values)


@pytest.mark.slow
def test_cli_sigkill_then_warm_restart_bitwise():
    """The CI smoke, in-process: run the daemon CLI with per-tick
    checkpoints, SIGKILL it mid-stream, restart over the same
    snapshot_dir, and assert the restored snapshot reproduces the
    pre-kill probe answers + state digest bitwise (--verify-restart)."""
    env = dict(os.environ, PYTHONPATH="src")
    with tempfile.TemporaryDirectory() as snap:
        argv = [
            sys.executable, "-m", "repro.launch.daemon",
            "--sensors", "16", "--fields", "2", "--ticks", "200",
            "--ckpt-every", "1", "--snapshot-dir", snap,
            "--queries-per-tick", "1", "--arrivals-per-tick", "4",
            "--tick-sleep", "0.2",
        ]
        proc = subprocess.Popen(
            argv, env=env, cwd=ROOT,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        try:
            deadline = time.time() + 240
            while time.time() < deadline:
                steps = [f for f in os.listdir(snap) if f.startswith("step_")]
                if len(steps) >= 2:
                    break
                if proc.poll() is not None:
                    _, err = proc.communicate()
                    pytest.fail(f"daemon exited early: {err[-2000:]}")
                time.sleep(0.5)
            else:
                pytest.fail("no checkpoints appeared before the deadline")
            proc.send_signal(signal.SIGKILL)  # crash, not a clean exit
        finally:
            proc.kill()
            proc.wait()
        out = subprocess.run(
            [
                sys.executable, "-m", "repro.launch.daemon",
                "--sensors", "16", "--fields", "2", "--ticks", "0",
                "--snapshot-dir", snap, "--verify-restart",
            ],
            env=env, cwd=ROOT, capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "warm restart verified" in out.stdout


def test_health_is_json_and_carries_the_watchdog_receipt():
    from repro.core import monitor

    prob, state, _, _ = _build(seed=10)
    d = Daemon(prob, state, config=DaemonConfig(k=3))
    h0 = json.loads(json.dumps(d.health()))
    assert h0["schema"] == "daemon_health/1" and h0["last_tick"] is None
    d.tick()
    h = json.loads(json.dumps(d.health()))
    assert h["version"] == 1 and h["ticks"] == 1
    wd = monitor.receipt_from_json(h["last_tick"]["watchdog"])
    assert wd.rounds >= 1 and not wd.rolled_back
