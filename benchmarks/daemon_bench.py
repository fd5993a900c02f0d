"""Serving daemon under sustained mixed traffic: latency SLO through a
fault episode, zero XLA compiles after warmup.

The ISSUE-8 acceptance run: one ``launch.daemon.Daemon`` serves a steady
mix of coalesced bucketed queries, streaming arrival waves, and churn
events while supervised training ticks run between pumps — then a fault
episode (10% link drops injected into every training tick) hits mid-run
and the daemon must keep its promises:

  * ZERO failed queries — every admitted query returns finite values
    from a published snapshot, episode included (queries read the double
    buffer; a struggling trainer can delay them, never corrupt them);
  * p99 latency within 3x the fault-free p99 — the watchdog's
    retry/rollback work during the episode bounds the serving stall;
  * ZERO XLA compiles after warmup — fault rates are traced operands and
    request/arrival sizes ride the power-of-two buckets, so the whole
    mixed trace (episode and recovery included) reuses the warm programs
    (counted via the jit caches, the PR-3/PR-7 witness).

Latency is measured submit -> answer with a training tick between: a
query that arrives mid-tick waits for the next pump, so episode-time
watchdog retries genuinely stretch the tail — the SLO is a real claim
about degraded-mode serving, not a no-op.

Run:  PYTHONPATH=src python -m benchmarks.daemon_bench
      PYTHONPATH=src python -m benchmarks.daemon_bench --n 200 --ticks 40
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax.numpy as jnp

from repro.core import (
    Kernel,
    build_topology,
    faults,
    init_state,
    make_batch_problem,
    make_serving_plan,
    monitor,
    serving,
    streaming,
    uniform_sensors,
)
from repro.launch import daemon as daemon_mod
from repro.launch.daemon import Daemon, DaemonConfig

EPISODE_DROP = 0.1
SLO_P99_RATIO = 3.0


def _build(n, b, radius, gamma, lam, spares, seed=0):
    pos = uniform_sensors(n, seed=seed)
    rng = np.random.default_rng(seed + 1)
    freq = rng.uniform(0.5, 2.0, size=(b, 1)).astype(np.float32)
    phase = rng.uniform(0, 2 * np.pi, size=(b, 1)).astype(np.float32)
    ys = (
        np.sin(np.pi * freq * pos[None, :, 0] + phase)
        + 0.1 * rng.normal(size=(b, n))
    ).astype(np.float32)
    topo = build_topology(pos, radius)
    d_max = int(np.asarray(topo.degrees).max()) + 6
    topo = build_topology(pos, radius, d_max=d_max, n_max=n + spares)
    prob = make_batch_problem(
        topo, Kernel("rbf", gamma=gamma), ys, jnp.full((n,), lam)
    )
    return pos, prob, init_state(prob), rng


def _cache_sizes():
    """Every program the daemon's steady state dispatches: the bucketed
    serving program, the supervised faulty trainer, absorbs, churn repairs,
    and the per-publish effective-coefficient read."""
    fns = (
        serving._knn_plan,
        serving.plan_add_sensor,
        serving.plan_remove_sensor,
        faults._faulty_colored,
        monitor._round_metrics,
        streaming._absorb_many_drop_copy,
        streaming._add_sensor_copy,
        streaming._remove_sensor_copy,
        daemon_mod._ecoef_jit,
    )
    return [f._cache_size() for f in fns]


def _run_phase(
    d, rng, pos, n, b, *, ticks, queries_per_tick, max_q, arrivals_per_tick,
    churn_every=0, label="",
):
    """Mixed traffic: submit -> train tick -> pump, per round.

    Returns (latencies_s, failed, degraded_ticks, rollbacks)."""
    lat, failed, degraded_ticks, rollbacks = [], 0, 0, 0
    for t in range(ticks):
        tickets = []
        for _ in range(queries_per_tick):
            q = int(rng.integers(1, max_q + 1))
            xq = rng.uniform(-0.9, 0.9, size=(q, 1)).astype(np.float32)
            tickets.append(d.submit(xq))
        a = int(rng.integers(1, arrivals_per_tick + 1))
        ss = rng.integers(0, n, size=a)
        d.offer_arrivals(
            rng.integers(0, b, size=a), ss,
            (pos[ss] + 0.05 * rng.normal(size=(a, 1))).astype(np.float32),
            rng.normal(size=a).astype(np.float32),
        )
        if churn_every and t % churn_every == 0:
            # alternate joins and (random-slot) leaves; a leave that picks
            # an already-dead slot is a counted no-op, like production
            if (t // churn_every) % 2 == 0:
                x = rng.uniform(-0.9, 0.9, size=(1,)).astype(np.float32)
                d.offer_join(
                    x, rng.normal(size=b).astype(np.float32), lam=0.1
                )
            else:
                d.offer_leave(int(rng.integers(0, n)))
        rcpt = d.tick()
        degraded_ticks += int(rcpt.degraded)
        rollbacks += int(rcpt.watchdog.rolled_back)
        answers = {a_.id: a_ for a_ in d.pump()}
        for tk in tickets:
            if not tk.admitted:
                continue  # shed at the door is admission, not failure
            ans = answers.get(tk.id)
            if ans is None or not np.isfinite(ans.values).all():
                failed += 1
            else:
                lat.append(ans.latency_s)
    return lat, failed, degraded_ticks, rollbacks


def run_daemon(
    n=60, b=4, *, radius=0.45, gamma=4.0, lam=0.05, ticks_clean=12,
    ticks_fault=8, queries_per_tick=4, max_q=60, arrivals_per_tick=12,
    churn_every=3, sweeps_per_tick=5, seed=0,
):
    spares = 2 + ticks_clean // max(churn_every, 1)
    pos, prob, state, rng = _build(n, b, radius, gamma, lam, spares, seed)
    plan = make_serving_plan(prob, k=3, spare=spares, slack=spares)
    cfg = DaemonConfig(
        k=3, max_batch_rows=64, arrival_rows=16,
        sweeps_per_tick=sweeps_per_tick,
    )
    d = Daemon(prob, state, config=cfg, plan=plan)

    # -- warmup: touch every program the measured trace can dispatch ------
    for q in (8, 16, 32, 64):  # every query bucket under max_batch_rows
        d.submit(rng.uniform(-0.9, 0.9, size=(q, 1)).astype(np.float32))
        d.pump()
    ss = rng.integers(0, n, size=17)  # full 16-window + partial bucket 8
    d.offer_arrivals(
        rng.integers(0, b, size=17), ss,
        (pos[ss] + 0.05 * rng.normal(size=(17, 1))).astype(np.float32),
        rng.normal(size=17).astype(np.float32),
    )
    d.tick()
    d.offer_arrivals(  # partial bucket 16 (9 rows pad up, not coalesce)
        np.zeros(9, np.int32), rng.integers(0, n, size=9),
        pos[rng.integers(0, n, size=9)].astype(np.float32),
        rng.normal(size=9).astype(np.float32),
    )
    d.tick()
    d.offer_join(  # join-only and join+leave tick program sets
        np.array([0.1], np.float32), np.zeros(b, np.float32), lam=0.1
    )
    d.tick()
    d.offer_leave(int(rng.integers(0, n)))
    d.tick()
    streaming.rebuild_chol(d.snapshot.problem)  # watchdog escalation path
    d.set_fault_model(faults.make_fault_model(EPISODE_DROP))
    d.tick()  # drill: same program, rates are traced
    d.set_fault_model(faults.make_fault_model(0.0))
    d.tick()
    base = _cache_sizes()

    # -- clean phase ------------------------------------------------------
    mix = dict(
        queries_per_tick=queries_per_tick, max_q=max_q,
        arrivals_per_tick=arrivals_per_tick, churn_every=churn_every,
    )
    lat_clean, failed_c, _, _ = _run_phase(
        d, rng, pos, n, b, ticks=ticks_clean, **mix
    )

    # -- fault episode: 10% drops injected into every training tick -------
    d.set_fault_model(faults.make_fault_model(EPISODE_DROP))
    lat_fault, failed_f, degraded_ticks, rollbacks = _run_phase(
        d, rng, pos, n, b, ticks=ticks_fault, **mix
    )
    d.set_fault_model(faults.make_fault_model(0.0))
    lat_rec, failed_r, _, _ = _run_phase(d, rng, pos, n, b, ticks=2, **mix)

    compiles = sum(a - b_ for a, b_ in zip(_cache_sizes(), base))
    failed = failed_c + failed_f + failed_r

    def pctl(xs, p):
        return float(np.percentile(np.asarray(xs) * 1e3, p)) if xs else 0.0

    p50_c, p99_c = pctl(lat_clean, 50), pctl(lat_clean, 99)
    p50_f, p99_f = pctl(lat_fault, 50), pctl(lat_fault, 99)
    slo_pass = (
        failed == 0
        and compiles == 0
        and p99_f <= SLO_P99_RATIO * max(p99_c, 1e-9)
    )
    return {
        "name": "daemon",
        "n": n, "batch": b, "ticks_clean": ticks_clean,
        "ticks_fault": ticks_fault, "episode_drop": EPISODE_DROP,
        "queries_served": int(d.served), "queries_shed": int(d.shed),
        "failed_queries": failed,
        "latency_ms": {
            "clean_p50": p50_c, "clean_p99": p99_c,
            "fault_p50": p50_f, "fault_p99": p99_f,
        },
        "p99_ratio_fault_vs_clean": p99_f / max(p99_c, 1e-9),
        "slo_p99_ratio_budget": SLO_P99_RATIO,
        "degraded_ticks": degraded_ticks,
        "rollbacks": rollbacks,
        "final_version": int(d.snapshot.version),
        "compiles_after_warmup": compiles,
        "slo_pass": bool(slo_pass),
    }


def daemon_fast(rows):
    """Trimmed run for ``benchmarks/run.py --fast`` (CI bench-json rows)."""
    r = run_daemon(n=40, b=2, ticks_clean=6, ticks_fault=4,
                   queries_per_tick=3, churn_every=3)
    lm = r["latency_ms"]
    rows.append((
        f"daemon.n{r['n']}.query",
        lm["clean_p50"] * 1e3,  # us, like every other us_per_call row
        f"p99_clean={lm['clean_p99']:.2f}ms;"
        f"p99_fault={lm['fault_p99']:.2f}ms;"
        f"ratio={r['p99_ratio_fault_vs_clean']:.2f}x;"
        f"failed={r['failed_queries']};"
        f"slo_pass={r['slo_pass']}",
    ))
    rows.append((
        f"daemon.n{r['n']}.compiles",
        float(r["compiles_after_warmup"]),
        "xla_compiles_after_warmup_across_mixed_traffic",
    ))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ticks", type=int, default=12,
                    help="clean-phase ticks (fault episode runs 2/3 of it)")
    ap.add_argument("--queries-per-tick", type=int, default=4)
    ap.add_argument("--max-q", type=int, default=60)
    ap.add_argument("--arrivals-per-tick", type=int, default=12)
    ap.add_argument("--churn-every", type=int, default=3)
    ap.add_argument("--out", default="BENCH_daemon.json")
    args = ap.parse_args()
    t0 = time.time()
    r = run_daemon(
        n=args.n, b=args.batch, ticks_clean=args.ticks,
        ticks_fault=max(2, 2 * args.ticks // 3),
        queries_per_tick=args.queries_per_tick, max_q=args.max_q,
        arrivals_per_tick=args.arrivals_per_tick,
        churn_every=args.churn_every,
    )
    r["wall_s"] = time.time() - t0
    with open(args.out, "w") as f:
        json.dump(r, f, indent=2)
        f.write("\n")
    lm = r["latency_ms"]
    print(f"served={r['queries_served']} shed={r['queries_shed']} "
          f"failed={r['failed_queries']}")
    print(f"latency ms: clean p50={lm['clean_p50']:.2f} "
          f"p99={lm['clean_p99']:.2f} | fault p50={lm['fault_p50']:.2f} "
          f"p99={lm['fault_p99']:.2f} "
          f"(ratio {r['p99_ratio_fault_vs_clean']:.2f}x, budget "
          f"{SLO_P99_RATIO:.0f}x)")
    print(f"degraded_ticks={r['degraded_ticks']} rollbacks={r['rollbacks']} "
          f"compiles_after_warmup={r['compiles_after_warmup']} (want 0)")
    print(f"SLO {'PASS' if r['slo_pass'] else 'FAIL'}; wrote {args.out}")


if __name__ == "__main__":
    main()
