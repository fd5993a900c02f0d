"""The control comes out not correct at a size a test run holds.

Converge cells, and the daemon's sweeps: the reference computed with
three-pass bfloat16 matrix products in the program's place.  Serving
cells: the program with its own bfloat16 anchor tables switched on.  (On
the chip at the cells' own sizes: ``calibrate.py``; readings in PERF.md.)
"""

import time

import pytest

import calibrate
import deploy
import drivers
import harness
from tinycfg import tiny


@pytest.mark.parametrize("cell", ["city2k-converge", "lab54-converge"])
def test_converge_control_fails_a_limit(cell):
    spec, cfg = tiny(cell)
    # at the cells' sizes a solve takes 400-1000 sweeps; the tiny network
    # is run for as many
    cfg["watchdog"] = {"sweeps_per_round": 5, "tol": 1e-12, "max_rounds": 120}
    limits = spec.limits(cell)
    r = calibrate.solve_readings(deploy.network(cfg), cfg, seed=2**35 + 1,
                                 controls=("bf16x3",))
    assert r["sweeps"] == 600, r
    gaps = {k: lim for k, lim in limits.items() if k != "stop_residual"}
    assert all(r["program"][k] <= lim for k, lim in gaps.items()), r
    assert any(r["control_bf16x3"][k] > lim for k, lim in gaps.items()), r


@pytest.mark.parametrize("cell", ["lab54-history", "city2k-daemon"])
def test_serving_control_is_not_correct(cell):
    spec, cfg = tiny(cell)
    out = harness.run(cell, 2**35 + 2, 1.0, False, time.perf_counter(), spec=spec,
                      cfg=cfg, daemon={"serve_dtype": "bf16"})
    assert not out["correct"], out["checks"]


class _Steps(drivers.Window):
    """A window whose clock moves 10 ms per loop iteration: a 3 s window
    runs 300 ticks however busy the machine is."""

    n = 0

    def poll(self) -> float:
        self.n += 1
        return 0.01 * self.n


def test_daemon_sweep_control_fails_message_gap(monkeypatch):
    spec, cfg = tiny("city2k-daemon")
    mix = spec.traffic(spec.cell("city2k-daemon")["traffic"])
    # 300 ticks: enough sweeps on the tiny network for the three-pass
    # rounding to build up as over the cell's set-up solve
    monkeypatch.setattr(drivers, "Window", _Steps)
    ctx = drivers.Context(cfg=cfg, mix=mix, seed=2**35 + 4, seconds=3.0,
                          t_start=time.perf_counter())
    _, check = drivers.open_loop(ctx)
    monkeypatch.setattr(drivers, "Reference", calibrate.shadowed(("bf16x3",)))
    limit = spec.limits("city2k-daemon")["message_gap"]
    assert check()["message_gap"] <= limit
    assert drivers.Reference.gaps["bf16x3"] > limit
