#!/usr/bin/env python3
"""Bring-up smoke of the field-estimation service on a TPU.

Drives the system's main path once, through the library entry points a
deployment calls, at the size of a city network: 2000 sensors uniform on
[-1, 1]^2 with the serving benchmark's radius rule, 64 fields, a
256-arrival stream window, 8 spare rows for churn, 1024-point query grids.
Every phase checks its result against the repo's own reference (the plan
engine, the dense fusion oracle, ``kernels/ref.py``) and raises when it
disagrees; a failing phase ends the run with a non-zero exit.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: sharded_sweep vs one chip

Phases (one chip): device, build, train, stream, churn, faults, serve,
daemon.  Each prints ``phase <name>: pass`` with its wall time, the XLA
compiles it caused (and persistent-cache hits), and the device's
``peak_bytes_in_use`` so far.  These are bring-up timings, not benchmark
numbers.  The last line of stdout is one JSON object naming the device.

Without a TPU (``jax.devices()[0].platform != "tpu"``) the script exits
non-zero before any phase: it never runs on the CPU.  The compile cache
follows ``repro.launch.cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Deployment:
    n: int = 2000  # sensors
    fields: int = 64  # B concurrent fields
    window: int = 256  # streaming arrivals absorbed in one dispatch
    spares: int = 8  # join capacity (n_max = n + spares)
    queries: int = 1024  # query grid rows
    k: int = 3  # kNN fusion order
    sweeps: int = 30
    refresh_sweeps: int = 5
    churn_rounds: int = 4
    daemon_ticks: int = 3
    gamma: float = 1.0
    lam: float = 0.1
    seed: int = 0

    @property
    def radius(self) -> float:
        # benchmarks/serving_bench.py: constant expected degree as n grows
        return 0.3 * math.sqrt(100.0 / self.n)


# -- bookkeeping ------------------------------------------------------------

_COUNTS = {"compiles": 0, "cache_hits": 0}


def _on_duration(event, duration_secs, **kwargs):
    if event == "/jax/core/compile/backend_compile_duration":
        _COUNTS["compiles"] += 1


def _on_event(event, **kwargs):
    if event == "/jax/compilation_cache/cache_hits":
        _COUNTS["cache_hits"] += 1


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class Phase:
    """Times one phase and prints its result line; re-raises failures."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = dict(_COUNTS)
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.perf_counter() - self.t0
        compiles = _COUNTS["compiles"] - self.c0["compiles"]
        hits = _COUNTS["cache_hits"] - self.c0["cache_hits"]
        print(
            f"phase {self.name}: {'FAIL' if exc_type else 'pass'}  "
            f"wall {wall:.3f} s  compiles {compiles - hits} "
            f"(persistent-cache hits {hits})  "
            f"peak_bytes_in_use {peak_bytes()}",
            flush=True,
        )
        return False  # never swallow the exception


def note(msg: str) -> None:
    print(f"  {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not bool(cond):
        raise AssertionError(msg)


def assert_compiled_kernel(lowered_text: str, what: str) -> None:
    """A Pallas kernel compiled by Mosaic lowers to ``tpu_custom_call``;
    one run in interpret mode lowers to plain HLO and has none."""
    check("tpu_custom_call" in lowered_text,
          f"{what}: no tpu_custom_call in the lowered program "
          "(kernel ran interpreted)")


def max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


# -- phases -----------------------------------------------------------------


def build(dep: Deployment):
    from repro.core import (
        Kernel, build_topology, make_batch_problem, uniform_sensors,
    )
    from repro.core.topology import geometric_adjacency

    pos = uniform_sensors(dep.n, d=2, seed=dep.seed)
    deg_max = int(geometric_adjacency(pos, dep.radius).sum(1).max())
    # serve.py's headroom rule: free lanes for the stream window, the
    # lanes a joining sensor adopts, and the adopters' reciprocal lanes
    d_max = deg_max + -(-dep.window // dep.n) + 4 + 2
    topo = build_topology(pos, dep.radius, d_max=d_max, n_max=dep.n + dep.spares)
    rng = np.random.default_rng(dep.seed)
    freq = rng.uniform(0.5, 2.0, size=(dep.fields, 1))
    phase = rng.uniform(0, 2 * np.pi, size=(dep.fields, 1))
    ys = np.sin(np.pi * freq * pos[None, :, 0] + phase) + 0.3 * rng.normal(
        size=(dep.fields, dep.n)
    )
    prob = make_batch_problem(
        topo, Kernel("rbf", gamma=dep.gamma), ys, jnp.full((dep.n,), dep.lam)
    )
    jax.block_until_ready(prob)
    factor_bytes = prob.gram.nbytes + prob.chol.nbytes
    note(
        f"n={dep.n} capacity={prob.n} B={dep.fields} radius={dep.radius:.4f} "
        f"max_degree={deg_max} D={topo.d_max} colors={topo.n_colors} "
        f"M={prob.color_members.shape[1]} stream_capacity={prob.n_stream} "
        f"gram+chol bytes={factor_bytes}"
    )
    return pos, rng, prob


def train(dep: Deployment, prob):
    from repro.core import colored_sweep, init_state
    from repro.core.monitor import _round_metrics

    s0 = init_state(prob)
    lowered = colored_sweep.lower(prob, s0, n_sweeps=dep.sweeps, engine="pallas")
    assert_compiled_kernel(lowered.as_text(), "colored_sweep(engine='pallas')")
    ref1 = colored_sweep(prob, s0, n_sweeps=dep.sweeps, engine="plan")
    ref2 = colored_sweep(prob, ref1, n_sweeps=dep.sweeps, engine="plan")
    pal = colored_sweep(prob, s0, n_sweeps=dep.sweeps, engine="pallas")
    norm1, r1 = _round_metrics(prob, s0, ref1)
    norm2, r2 = _round_metrics(prob, ref1, ref2)
    err_z = max_err(pal.z, ref1.z)
    err_c = max_err(pal.coef, ref1.coef)
    note(
        f"relative z-residual per {dep.sweeps}-sweep round: first "
        f"{float(np.max(r1)):.3e}, second {float(np.max(r2)):.3e}; "
        f"pallas vs plan max|dz| {err_z:.3e} max|dcoef| {err_c:.3e}"
    )
    check(np.isfinite(np.asarray(ref2.z)).all(), "non-finite messages")
    check((np.asarray(r2) < np.asarray(r1)).all(), "residual did not fall")
    check((np.asarray(norm2) <= np.asarray(norm1) * (1 + 1e-5)).all(),
          "Fejer norm grew along a fault-free sweep")
    # tests/test_scatter_plan.py tolerances (2e-5 on messages after
    # streaming, 1e-3 on the non-unique coefficients): the two engines
    # round their substitutions differently, and at this conditioning
    # (lambda = 0.1, nearly flat local Grams) the gap grows ~1e-5 per 30
    # sweeps
    check(err_z <= 2e-5, f"pallas vs plan messages differ by {err_z}")
    check(err_c <= 1e-3, f"pallas vs plan coefficients differ by {err_c}")
    return ref2


def window(dep: Deployment, rng, pos, a: int):
    fs = rng.integers(0, dep.fields, size=a)
    ss = rng.integers(0, dep.n, size=a)
    xs = (pos[ss] + 0.05 * rng.normal(size=(a, pos.shape[1]))).astype(np.float32)
    return fs, ss, xs, rng.normal(size=a).astype(np.float32)


def stream(dep: Deployment, rng, pos, prob, state):
    from repro.core import colored_sweep, streaming
    from repro.core.monitor import _round_metrics

    prob2, st2, rec = streaming.absorb_many(
        prob, state, *window(dep, rng, pos, dep.window), on_full="drop"
    )
    absorbed = int(np.asarray(rec.absorbed).sum())
    grown = int(np.asarray(prob2.nbr_mask).sum() - np.asarray(prob.nbr_mask).sum())
    st3 = colored_sweep(prob2, st2, n_sweeps=dep.refresh_sweeps)
    _, resid = _round_metrics(prob2, st2, st3)
    note(f"absorbed {absorbed}/{dep.window}, occupied lanes +{grown}, "
         f"refresh residual {float(np.max(resid)):.3e}")
    check(absorbed == dep.window, "arrivals dropped with capacity left")
    check(grown == dep.window, "absorbed arrivals missing from the problem")
    check(np.isfinite(np.asarray(st3.z)).all(), "non-finite after refresh")
    return prob2, st3


def churn(dep: Deployment, rng, pos, prob, state):
    from repro.analysis import compile_ledger
    from repro.core import (
        add_sensor, colored_sweep, field_view, fusion, make_serving_plan,
        remove_sensor, streaming,
    )
    from repro.core.serving import plan_add_sensor, plan_remove_sensor

    plan = make_serving_plan(
        prob, k=dep.k, spare=dep.spares + 4, slack=dep.churn_rounds
    )
    xq = rng.uniform(-0.9, 0.9, size=(64, 2)).astype(np.float32)
    joined: list[int] = []
    counts = {"joins": 0, "leaves": 0}

    def one_round(prob, state, plan, i):
        x = rng.uniform(-0.9, 0.9, size=2).astype(np.float32)
        prob, state, rcpt = add_sensor(
            prob, state, x, rng.normal(size=dep.fields).astype(np.float32),
            lam=dep.lam,
        )
        check(bool(rcpt.joined), f"join {i} dropped with spare rows left")
        plan, _ = plan_add_sensor(plan, x, rcpt.slot)
        joined.append(int(rcpt.slot))
        counts["joins"] += 1
        prob, state, _ = streaming.absorb_many(
            prob, state, *window(dep, rng, pos, 8), on_full="drop"
        )
        state = colored_sweep(prob, state, n_sweeps=dep.refresh_sweeps)
        if i % 2 == 1:
            victim = joined.pop(0)
            prob, state, ok = remove_sensor(prob, state, victim)
            check(bool(ok), f"leave of row {victim} refused")
            plan = plan_remove_sensor(plan, victim)
            counts["leaves"] += 1
            state = colored_sweep(prob, state, n_sweeps=dep.refresh_sweeps)
        out = fusion.fuse(prob, state, xq, "knn", k=dep.k, engine="plan", plan=plan)
        check(np.isfinite(np.asarray(out)).all(), "non-finite answers after churn")
        return prob, state, plan

    # warm a join-only and a join+leave round, then count compiles
    prob, state, plan = one_round(prob, state, plan, 0)
    prob, state, plan = one_round(prob, state, plan, 1)
    snap = compile_ledger.snapshot(
        compile_ledger.churn_group(on_full="drop", donate=False)
    )
    for i in range(2, dep.churn_rounds):
        prob, state, plan = one_round(prob, state, plan, i)
    growth = snap.total_growth()
    dense = fusion.fuse(*field_view(prob, state, 0), xq, "knn", k=dep.k)
    served = fusion.fuse(prob, state, xq, "knn", k=dep.k, engine="plan", plan=plan)[0]
    err = max_err(served, dense)
    note(f"{counts['joins']} joins, {counts['leaves']} leaves; compile-ledger "
         f"growth after warm-up {growth}; repaired plan vs dense "
         f"(field 0) max err {err:.3e}")
    check(growth == 0, f"churn compiled {growth} programs after warm-up")
    check(err <= 1e-5, f"repaired plan disagrees with dense by {err}")


def faults(dep: Deployment, prob, state):
    from repro.core import faults as faults_mod, monitor

    model = faults_mod.parse_fault_spec("drop=0.1", dtype=state.z.dtype)
    cfg = monitor.WatchdogConfig(sweeps_per_round=dep.refresh_sweeps, max_rounds=1)
    _, st, rcpt = monitor.watch_sweeps(
        prob, state, model=model, key=jax.random.PRNGKey(dep.seed + 1),
        config=cfg,
    )
    note(monitor.format_receipt(rcpt))
    check(rcpt.rounds == 1 and not rcpt.rolled_back, "watchdog escalated")
    check(not np.asarray(rcpt.diverged).any(), "a field diverged at drop=0.1")
    check(np.isfinite(np.asarray(st.z)).all(), "non-finite under drop=0.1")


def serve(dep: Deployment, rng, prob, state):
    from repro.core import field_view, fusion, make_serving_plan
    from repro.kernels import kernel_matvec
    from repro.kernels.ref import kernel_matvec_batched_ref

    plan = make_serving_plan(prob, k=dep.k)
    xq = rng.uniform(-1, 1, size=(dep.queries, 2)).astype(np.float32)
    oracle_fields = sorted({0, dep.fields // 3, 2 * dep.fields // 3, dep.fields - 1})
    dense = np.stack([
        np.asarray(fusion.fuse(*field_view(prob, state, f), xq, "knn", k=dep.k))
        for f in oracle_fields
    ])
    rms = float(np.sqrt(np.mean(dense ** 2)))
    out = {}
    for engine in ("plan", "pallas"):
        for cdt in (None, "bf16"):
            def run(p, s, pl_, x, engine=engine, cdt=cdt):
                return fusion.fuse(
                    p, s, x, "knn", k=dep.k, engine=engine, plan=pl_,
                    compute_dtype=cdt,
                )

            if engine == "pallas":
                assert_compiled_kernel(
                    jax.jit(run).lower(prob, state, plan, xq).as_text(),
                    f"knn_fuse compute_dtype={cdt}",
                )
            out[engine, cdt] = np.asarray(run(prob, state, plan, xq))
    for (engine, cdt), got in out.items():
        check(got.shape == (dep.fields, dep.queries), f"{engine} shape {got.shape}")
        err = max_err(got[oracle_fields], dense)
        rel = float(np.sqrt(np.mean((got[oracle_fields] - dense) ** 2))) / rms
        note(f"knn engine={engine} dtype={cdt or 'f32'}: vs dense max err "
             f"{err:.3e}, relative RMSE {rel:.3e}")
        if cdt is None:  # tests/test_serving.py: engines agree within 1e-5
            check(err <= 1e-5, f"{engine} f32 disagrees with dense by {err}")
        else:  # tests/test_quant_serving.py: anchors-only rounding < 1%
            check(rel < 0.01, f"{engine} bf16 relative RMSE {rel}")
    for cdt in (None, "bf16"):
        err = max_err(out["pallas", cdt], out["plan", cdt])
        note(f"pallas vs plan dtype={cdt or 'f32'} all fields max err {err:.3e}")
        check(err <= (1e-5 if cdt is None else 2e-5), f"pallas vs plan {cdt}: {err}")

    anchors, coefs = fusion.global_coefficients(prob, state, rule="conn")
    conn = lambda x, a, c: kernel_matvec(x, a, c, gamma=dep.gamma)  # noqa: E731
    assert_compiled_kernel(
        jax.jit(conn).lower(xq, anchors, coefs).as_text(), "kernel_matvec"
    )
    got = np.asarray(conn(xq, anchors, coefs))
    ref = np.asarray(kernel_matvec_batched_ref(
        jnp.asarray(xq), anchors[jnp.asarray(oracle_fields)],
        coefs[jnp.asarray(oracle_fields)], dep.gamma,
    ))
    err = max_err(got[oracle_fields], ref)
    scale = max(1.0, float(np.max(np.abs(ref))))
    note(f"conn kernel_matvec ({anchors.shape[1]} anchors) vs ref max err {err:.3e}")
    check(np.isfinite(got).all(), "non-finite conn answers")
    check(err <= 1e-4 * scale, f"kernel_matvec disagrees with ref by {err}")


def daemon(dep: Deployment, rng, pos, prob, state, out_dir: Path):
    from repro.core import fusion
    from repro.launch.daemon import Daemon, DaemonConfig

    snap_dir = out_dir / "daemon"
    shutil.rmtree(snap_dir, ignore_errors=True)  # a cold start, not a restore
    cfg = DaemonConfig(
        k=dep.k, ckpt_every=dep.daemon_ticks, snapshot_dir=str(snap_dir)
    )
    probe = rng.uniform(-0.9, 0.9, size=(64, 2)).astype(np.float32)

    def probe_answers(d):
        snap = d.snapshot
        return np.asarray(fusion.fuse(
            snap.problem, snap.state, probe, "knn", k=dep.k,
            engine=cfg.engine, plan=snap.plan, ecoef=snap.ecoef,
        ))

    first = Daemon(prob, state, config=cfg)
    check(first.restored_step is None, "cold daemon restored a checkpoint")
    answers = 0
    for _ in range(dep.daemon_ticks):
        for rows in (int(rng.integers(1, 200)), int(rng.integers(1, 200))):
            check(first.submit(rng.uniform(-0.9, 0.9, size=(rows, 2))
                               .astype(np.float32)).admitted, "query shed")
        first.offer_arrivals(*window(dep, rng, pos, 32))
        for ans in first.pump():
            check(np.isfinite(ans.values).all(), "non-finite daemon answer")
            answers += 1
        rcpt = first.tick()
        check(rcpt.published, f"tick {rcpt.tick} not published")
    check(rcpt.ckpt_step == dep.daemon_ticks, "last tick wrote no checkpoint")
    expect = probe_answers(first)

    second = Daemon(prob, state, config=cfg)
    same_state = second.state_digest() == first.state_digest()
    same_answers = np.array_equal(probe_answers(second), expect)
    note(f"{answers} answers over {dep.daemon_ticks} ticks; warm restart from "
         f"step {second.restored_step}: digest equal {same_state}, "
         f"probe answers bitwise equal {same_answers}")
    check(second.restored_step == dep.daemon_ticks, "warm restart missed the checkpoint")
    check(same_state and same_answers, "warm restart is not bitwise")
    shutil.rmtree(snap_dir, ignore_errors=True)


def sharded(dep: Deployment, prob, n_dev: int):
    from repro import compat
    from repro.core import colored_sweep, field_view, init_state, sharded_sweep

    s0 = init_state(prob)
    ref = colored_sweep(prob, s0, n_sweeps=dep.sweeps)
    mesh = compat.make_mesh((n_dev,), ("fields",))
    got = sharded_sweep(prob, s0, mesh, axis="fields", n_sweeps=dep.sweeps)
    shards = got.z.addressable_shards
    devices = {s.device for s in shards}
    rows = sorted({s.data.shape[0] for s in shards})
    err = (max_err(got.z, ref.z), max_err(got.coef, ref.coef))
    note(f"fields mesh: shards on {len(devices)} devices, {rows} fields each; "
         f"vs one device max|dz| {err[0]:.3e} max|dcoef| {err[1]:.3e}")

    p1, s1 = field_view(prob, s0, 0)
    ref1 = colored_sweep(p1, s1, n_sweeps=dep.sweeps)
    mesh = compat.make_mesh((n_dev,), ("sensors",))
    got1 = sharded_sweep(p1, s1, mesh, axis="sensors", n_sweeps=dep.sweeps)
    err1 = (max_err(got1.z, ref1.z), max_err(got1.coef, ref1.coef))
    note(f"sensors mesh (field 0, all_gather transport): vs one device "
         f"max|dz| {err1[0]:.3e} max|dcoef| {err1[1]:.3e}")

    check(len(devices) == n_dev, f"shards sit on {len(devices)} devices")
    check(rows == [dep.fields // n_dev], f"fields per shard {rows}")
    # Same math per field, but each device runs B/n_dev fields, and the
    # chip's reductions round differently at another batch shape: the
    # engine-agreement tolerances of train() apply, not bitwise equality.
    check(err[0] <= 2e-5 and err[1] <= 1e-3, f"field-sharded sweep differs: {err}")
    # tolerances of tests/test_scatter_plan.py (8-device plan transport)
    check(err1[0] <= 2e-4 and err1[1] <= 2e-2, f"sensor-sharded sweep differs: {err1}")


# -- main -------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only sharded_sweep on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "smoke_out"),
                    help="directory for the daemon's snapshots")
    args = ap.parse_args(argv)

    from repro.kernels import auto_interpret
    from repro.launch.cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind}, {len(devices)} device(s)). Not running on it."
        )
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    with Phase("device"):
        note(f"device_kind {dev.device_kind!r}, {len(devices)} device(s), "
             f"jax {jax.__version__}, compile cache {cache_dir}")
        check(len(devices) >= args.chips, f"--chips {args.chips} needs "
              f"{args.chips} devices, found {len(devices)}")
        check(not auto_interpret(), "kernels would run in interpret mode")

    dep = Deployment(seed=args.seed)
    with Phase("build"):
        pos, rng, prob = build(dep)
    if args.chips == 4:
        with Phase("sharded"):
            sharded(dep, prob, args.chips)
    else:
        with Phase("train"):
            state = train(dep, prob)
        with Phase("stream"):
            prob_s, state_s = stream(dep, rng, pos, prob, state)
        with Phase("churn"):
            churn(dep, rng, pos, prob_s, state_s)
        with Phase("faults"):
            faults(dep, prob, state)
        with Phase("serve"):
            serve(dep, rng, prob, state)
        with Phase("daemon"):
            daemon(dep, rng, pos, prob, state, Path(args.out))
    print(f"compiles {_COUNTS['compiles'] - _COUNTS['cache_hits']} "
          f"persistent-cache hits {_COUNTS['cache_hits']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
