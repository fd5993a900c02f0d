"""The traffic generator and the loops that drive it (CPU, tiny sizes)."""

import time

import numpy as np

import deploy
import drivers
import traffic
from tinycfg import tiny

MIX = {"rate_per_s": 40.0,
       "requests": [{"share": 0.8, "kind": "points", "rows_min": 1, "rows_max": 8},
                    {"share": 0.2, "kind": "tile", "grid": 16, "tile_side": 0.125}]}
BOX = (np.array([-0.9, -0.9]), np.array([0.9, 0.9]))


def test_same_seed_same_schedule():
    a, b = (traffic.open_requests(MIX, 10.0, deploy.streams(2**40 + 7, 1)[0], BOX)
            for _ in range(2))
    assert np.array_equal(a.due, b.due) and a.kinds == b.kinds
    assert all(np.array_equal(x, y) for x, y in zip(a.queries, b.queries))
    c = traffic.open_requests(MIX, 10.0, deploy.streams(8, 1)[0], BOX)
    # every seed gets the same work at the same times, at other points
    assert np.array_equal(a.due, c.due) and a.kinds == c.kinds and len(c.due) == 400
    assert [len(q) for q in a.queries] == [len(q) for q in c.queries]
    assert sorted(len(q) for q in c.queries)[-80:] == [256] * 80
    assert not all(np.array_equal(x, y) for x, y in zip(a.queries, c.queries))


def test_seeds_share_the_arrival_times_not_the_sensors():
    spec, _ = tiny("city2k-daemon")
    cfg = spec.config("city-aq-2k")
    pos = deploy.positions(cfg)
    fields = deploy.Fields(cfg["fields"], np.random.default_rng(0), cfg["noise"])
    x, y = (traffic.reports(cfg, pos, fields, 30.0, np.random.default_rng(s))
            for s in (5, 6))
    assert np.array_equal(x.due, y.due) and not np.array_equal(x.sensors, y.sensors)


def test_city_arrivals_never_repeat_a_pair():
    spec, _ = tiny("city2k-daemon")
    cfg = spec.config("city-aq-2k")
    pos = deploy.positions(cfg)
    fields = deploy.Fields(cfg["fields"], np.random.default_rng(0), cfg["noise"])
    arr = traffic.reports(cfg, pos, fields, 30.0, np.random.default_rng(5))
    pairs = set(zip(arr.fields.tolist(), arr.sensors.tolist()))
    assert len(pairs) == len(arr.fields) == 4 * round(2000 * 30 / 120)
    assert np.all(np.diff(arr.due) >= 0)
    assert set(arr.fields.tolist()) == {15, 31, 47, 63}  # the newest interval


def _ctx(cell, seconds, **kw):
    spec, cfg = tiny(cell)
    w = spec.cell(cell)
    mix = dict(spec.traffic(w["traffic"]), **kw)
    return drivers.Context(cfg=cfg, mix=mix, seed=3, seconds=seconds,
                           t_start=time.perf_counter())


def test_open_loop_latency_counts_from_due_time(monkeypatch):
    from repro.launch.daemon import Daemon

    pump = Daemon.pump
    state = {"calls": 0}
    stall = 0.6

    def stalled(self):
        state["calls"] += 1
        if state["calls"] == 3:
            time.sleep(stall)
        return pump(self)

    ctx = _ctx("city2k-daemon", 2.0, rate_per_s=30.0)
    monkeypatch.setattr(drivers, "_warm_daemon", lambda *a: None)
    monkeypatch.setattr(Daemon, "pump", stalled)
    rec, _ = drivers.open_loop(ctx)
    lat = np.asarray(rec.latencies_ms)
    assert rec.failed == 0 and len(lat) == 60
    # requests due during the stall were submitted after it, yet their
    # latency counts from when they were due
    assert (lat > 0.5 * stall * 1e3).sum() >= 3


def test_closed_loop_keeps_clients_outstanding(monkeypatch):
    from repro.launch.daemon import Daemon

    pump = Daemon.pump
    queued = []

    def counting(self):
        queued.append((id(self), len(self._queries)))
        return pump(self)

    ctx = _ctx("lab54-history", 1.0)
    monkeypatch.setattr(Daemon, "pump", counting)
    rec, _ = drivers.closed_loop(ctx)
    clients = ctx.mix["clients"]
    served = max({d for d, _ in queued}, key=[d for d, _ in queued].count)
    queued = [q for d, q in queued if d == served]  # not the warm-up daemon's
    assert len(queued) > 5
    assert all(q == clients for q in queued[:-1])  # the last pump drains after close
    assert rec.notes["requests"] >= clients * (len(queued) - 2)


def test_traced_open_loop_offers_its_traced_segment_alone(monkeypatch, tmp_path):
    ctx = _ctx("city2k-daemon", 3.0, rate_per_s=40.0)
    ctx.trace_s, ctx.trace_dir = 1.0, str(tmp_path)
    monkeypatch.setattr(drivers, "_warm_daemon", lambda *a: None)
    rec, _ = drivers.open_loop(ctx)
    # the trace covers the settled loop, after the window's first third
    w = rec.window
    assert w.trace_t0 - w.t0 >= 1.0 and w.trace_t1 > w.trace_t0
    # the profiler's stop sheds nothing: traffic ends with the trace, so
    # no request falls due behind it
    assert rec.notes["requests"] == 80 and rec.failed == 0
