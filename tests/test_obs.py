"""Host spans (``repro.obs``) and the daemon's always-on counters.

The pins:

  * with tracing off a span is the shared no-op context: nothing is kept
    and no ``TraceAnnotation`` is made;
  * tracing and the device programs' named scopes change no answer: a
    daemon answers bitwise alike with ``obs`` on and off, and with the
    named scopes taken out;
  * a scripted pump and tick give the documented span tree (parents,
    nesting in time, attributes that add up to what was submitted);
  * the ``health()["counters"]`` equal hand-computed values;
  * ``serve_traces`` counts the jaxpr traces the pump caused;
  * the in-memory spans and the profiler's ``repro.*`` host events sit on
    one clock up to a single offset.
"""

import contextlib
import glob
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import (
    Kernel,
    build_topology,
    init_state,
    make_batch_problem,
    uniform_sensors,
)
from repro.kernels.ops import bucket_rows
from repro.launch.daemon import Daemon, DaemonConfig

KERN = Kernel("rbf", gamma=1.0)


@pytest.fixture(scope="module")
def trained():
    n, b = 24, 3
    pos = uniform_sensors(n, seed=0)
    rng = np.random.default_rng(1)
    ys = (np.sin(np.pi * pos[None, :, 0] * rng.uniform(0.5, 2.0, (b, 1)))
          + 0.2 * rng.normal(size=(b, n))).astype(np.float32)
    topo = build_topology(pos, 0.6)
    topo = build_topology(pos, 0.6, d_max=int(np.asarray(topo.degrees).max()) + 4)
    prob = make_batch_problem(topo, KERN, ys, jnp.full((n,), 0.1))
    return prob, init_state(prob), pos


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    obs.drain()
    yield
    obs.disable()
    obs.drain()


SIZES = (40, 20, 30)  # 64-row cap: one dispatch of 60 rows, one of 30


def _grids(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.uniform(-0.9, 0.9, size=(q, 1)).astype(np.float32) for q in SIZES]


def _daemon(trained, **kw):
    prob, state, _ = trained
    return Daemon(prob, state,
                  config=DaemonConfig(k=3, max_batch_rows=64, arrival_rows=8, **kw))


def _serve(d, grids, now=None):
    for i, g in enumerate(grids):
        d.submit(g, now=None if now is None else now[i])
    return d.pump()


def _arrivals(d, pos, count, seed=0):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, pos.shape[0], size=count)
    d.offer_arrivals(rng.integers(0, 3, size=count), s,
                     (pos[s] + 0.01).astype(np.float32),
                     rng.normal(size=count).astype(np.float32))


def _children(spans, parent):
    return [i for i, s in enumerate(spans) if s[3] == parent]


def test_off_keeps_nothing_and_makes_no_annotation(trained, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation made while tracing is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    d = _daemon(trained)
    assert len(_serve(d, _grids())) == len(SIZES)
    _arrivals(d, trained[2], 5)
    d.tick()
    assert obs.span("x") is obs.span("y")
    assert obs.drain() == ([], 0)


def test_answers_bitwise_alike_with_tracing_and_without_scopes(trained, monkeypatch):
    grids = _grids(1)

    def answers():
        d = _daemon(trained)
        _arrivals(d, trained[2], 11)
        d.tick()
        return [a.values for a in _serve(d, grids)], d.snapshot.state.z

    off, z_off = answers()
    obs.enable()
    on, z_on = answers()
    obs.disable()
    # the same programs traced again with every named scope taken out
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    bare, z_bare = answers()
    jax.clear_caches()
    for a, b, c in zip(off, on, bare):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    np.testing.assert_array_equal(np.asarray(z_off), np.asarray(z_on))
    np.testing.assert_array_equal(np.asarray(z_off), np.asarray(z_bare))


def test_pump_span_tree(trained):
    d = _daemon(trained)
    _serve(d, _grids())  # warm: the tree below is a steady pump's
    obs.enable()
    version = d.snapshot.version
    _serve(d, _grids())
    spans, dropped = obs.drain()
    assert dropped == 0
    (pump,) = [i for i, s in enumerate(spans) if s[0] == "daemon.pump"]
    assert spans[pump][3] == -1
    assert spans[pump][4] == {"requests": len(SIZES), "rows": sum(SIZES)}
    kids = _children(spans, pump)
    assert [spans[i][0] for i in kids] == (
        ["serve.dispatch"] * 2 + ["serve.collect"] * 2)
    dispatches, collects = kids[:2], kids[2:]
    attrs = [spans[i][4] for i in dispatches]
    assert [a["rows"] for a in attrs] == [60, 30]
    assert [a["bucket"] for a in attrs] == [64, 32]
    assert sum(a["requests"] for a in attrs) == len(SIZES)
    assert {a["version"] for a in attrs} == {version}
    for i, j in zip(dispatches, collects):
        launched = _children(spans, i)
        assert [spans[m][0] for m in launched] == ["serve.pack", "serve.launch"]
        assert "traces" in spans[launched[1]][4]
        collected = _children(spans, j)
        assert [spans[m][0] for m in collected] == [
            "serve.wait", "serve.fetch", "serve.answer"]
        assert spans[collected[1]][4]["bytes"] == 3 * spans[i][4]["bucket"] * 4
    for name, t0, t1, parent, _ in spans:
        assert t0 <= t1, name
        if parent >= 0:
            assert spans[parent][1] <= t0 and t1 <= spans[parent][2], name


def test_tick_span_tree(trained):
    d = _daemon(trained)
    _arrivals(d, trained[2], 11)
    obs.enable()
    rc = d.tick()
    spans, _ = obs.drain()
    (tick,) = [i for i, s in enumerate(spans) if s[0] == "daemon.tick"]
    assert spans[tick][4] == {"absorbed": rc.absorbed, "published": True}
    kids = _children(spans, tick)
    assert [spans[j][0] for j in kids] == [
        "tick.events", "tick.absorb", "tick.sweeps", "tick.publish"]
    events, absorb, sweeps, _ = kids
    assert spans[events][4] == {"joins": 0, "leaves": 0}
    small = min(bucket_rows(3), 8)
    assert spans[absorb][4] == {"rows": 11, "padded": 8 + small}
    windows = _children(spans, absorb)
    assert [spans[j][4] for j in windows] == [
        {"rows": 8, "padded": 8}, {"rows": 3, "padded": small}]
    assert spans[sweeps][4] == {"rounds": rc.watchdog.rounds,
                                "sweeps": rc.watchdog.sweeps}
    rounds = _children(spans, sweeps)
    assert len(rounds) == rc.watchdog.rounds
    for r in rounds:
        assert [spans[j][0] for j in _children(spans, r)] == [
            "watch.launch", "watch.sync"]


def test_counters_match_hand_computed(trained):
    d = _daemon(trained)
    c0 = d.health()["counters"]
    assert c0["dispatches"] == {} and c0["queue_wait_n"] == 0
    obs.enable()
    base = time.perf_counter()
    now = [base - 0.5, base - 0.25, base - 0.125]
    _serve(d, _grids(), now=now)
    spans, _ = obs.drain()
    starts = [s[1] * 1e-9 for s in spans if s[0] == "serve.dispatch"]
    waits = [starts[0] - now[0], starts[0] - now[1], starts[1] - now[2]]
    _arrivals(d, trained[2], 11)
    d.tick()
    c = d.health()["counters"]
    assert c["dispatches"] == {"64": 1, "32": 1}
    assert c["rows"] == 90 and c["padded_rows"] == 96
    assert c["queue_wait_n"] == 3
    assert c["queue_wait_s_sum"] == pytest.approx(sum(waits), abs=1e-6)
    assert c["queue_wait_s_max"] == pytest.approx(max(waits), abs=1e-6)
    assert c["arrival_rows"] == 11
    assert c["arrival_padded_rows"] == 8 + min(bucket_rows(3), 8)


def test_serve_traces_counts_the_pumps_jaxpr_traces(trained):
    seen = []

    def listen(event, duration_secs, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            seen.append(duration_secs)

    # Earlier tests compiled the pump's programs for these shapes: clear
    # them, so this daemon's first pump has to trace its programs again.
    jax.clear_caches()
    d = _daemon(trained)
    before = d.health()["counters"]["serve_traces"]
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        _serve(d, _grids(2))
        cold = len(seen)
        _serve(d, _grids(3))  # the same buckets, now warm
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    after = d.health()["counters"]["serve_traces"]
    assert cold > 0  # a cold pump traces its programs
    assert len(seen) == cold  # a warm one traces nothing
    assert after - before == len(seen)


def test_spans_share_the_profilers_clock(trained, tmp_path):
    from jax.profiler import ProfileData

    d = _daemon(trained)
    _serve(d, _grids())
    jax.profiler.start_trace(str(tmp_path))
    obs.enable()
    try:
        _serve(d, _grids())
    finally:
        obs.disable()
        jax.profiler.stop_trace()
    spans, _ = obs.drain()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    events = sorted(
        (ev.start_ns, ev.name[len("repro."):])
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("repro."))
    kept = sorted((s[1], s[0]) for s in spans)
    assert [n for _, n in events] == [n for _, n in kept]
    offsets = [e - k for (e, _), (k, _) in zip(events, kept)]
    assert max(offsets) - min(offsets) < 50_000


def test_buffer_is_bounded_and_counts_drops(monkeypatch):
    monkeypatch.setattr(obs, "CAPACITY", 2)
    obs.enable()
    with obs.span("a") as a:
        a["k"] = 1
        with obs.span("b"):
            with obs.span("c") as c:
                c["k"] = 2
    spans, dropped = obs.drain()
    assert [(s[0], s[3], s[4]) for s in spans] == [("a", -1, {"k": 1}),
                                                    ("b", 0, {})]
    assert dropped == 1
    assert obs.drain() == ([], 0)
