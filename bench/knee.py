#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered request rate
at which the backlog does not grow over a window.

    python3 bench/knee.py --workload city2k-daemon \
        --rates 500,600,700,750,800,900 --seconds 30 --seeds 1,2

The daemon's loop releases what is due, then ``pump`` answers every
queued request, then ``tick`` trains; so each pump finds the requests
that came due during the previous loop iteration, and that count is the
backlog.  Below the knee it settles to a level (rate times one
iteration); above it every iteration is longer than the last and the
backlog grows without end.  A run's growth is the mean backlog of the
pumps in the window's last third over that of its middle third (the
first third holds the start from an empty queue).  A rate keeps up when
no request fails and its growth, averaged over the seeds, is at most
``GROWTH``: so its loop has settled within the first third of a window
as long as the cell's.  Near the knee each iteration feeds the next (a
longer iteration gathers a larger pump and more arrivals to absorb), the
loop settles slowly, and a rate whose backlog is still climbing through
the window reads a tail that swings from run to run.  One run's growth
swings by some 5% from seed to seed, as much as the margin, so the seeds
are averaged.  The knee is the highest rate below the lowest that does
not keep up; every rate listed runs, so the whole ladder is on record.

In one process (the network is built and trained once), for each rate
and seed the cell's open loop runs with that rate and a queue that never
sheds.  The rate ``SHARE`` x knee runs too if it is not on the ladder,
and the largest backlog in rows any of its pumps found is printed: the
cell's ``queue_rows`` is set from it.  Prints one JSON line per run and,
last, the knee.  It runs on the chip only.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
GROWTH = 1.1  # a backlog this much larger in the last third still grows
SHARE = 0.8  # the cell offers this share of the knee


def thirds(pumps: list, t0: float, seconds: float, value) -> list:
    """Mean of ``value(pump, next pump)`` over the pumps in each third of
    the window."""
    out = []
    for i in range(3):
        lo, hi = t0 + seconds * i / 3, t0 + seconds * (i + 1) / 3
        xs = [value(p, q) for p, q in zip(pumps, pumps[1:] + [None]) if lo <= p[0] < hi]
        xs = [x for x in xs if x is not None]
        out.append(sum(xs) / len(xs) if xs else float("nan"))
    return out


def backlog_thirds(pumps: list, t0: float, seconds: float) -> list:
    """Mean requests per pump in each third of the window (pumps that
    answered nothing count as 0)."""
    return thirds(pumps, t0, seconds, lambda p, q: p[2])


def keeps_up(growths: list, failed: int) -> bool:
    return bool(failed == 0 and sum(growths) / len(growths) <= GROWTH)


def run_rate(spec, w, cfg, net, rate, seconds, seed, reuse) -> dict:
    import numpy as np

    import drivers

    mix = dict(spec.traffic(w["traffic"]), rate_per_s=rate)
    ctx = drivers.Context(cfg=cfg, mix=mix, seed=seed, seconds=seconds,
                          t_start=time.perf_counter(), net=net,
                          daemon={"queue_rows": 1 << 30}, reuse=reuse)
    rec, _ = drivers.open_loop(ctx)
    lat = np.asarray(rec.latencies_ms)
    backlog = backlog_thirds(rec.pumps, rec.window.t0, seconds)
    iters = [b[0] - a[0] for a, b in zip(rec.pumps, rec.pumps[1:])]
    iter_thirds = thirds(rec.pumps, rec.window.t0, seconds,
                         lambda p, q: None if q is None else 1e3 * (q[0] - p[0]))
    return {"rate_per_s": rate, "seed": seed,
            "backlog_by_third": backlog, "growth": backlog[2] / backlog[1],
            "iteration_ms_by_third": iter_thirds,
            "max_backlog_rows": max(p[3] for p in rec.pumps),
            "pumps": len(rec.pumps), "iteration_ms": 1e3 * float(np.mean(iters)),
            "requests": len(lat), "failed": rec.failed,
            "p50_ms": float(np.median(lat)), "p95_ms": float(np.percentile(lat, 95)),
            "tick_ms": 1e3 * float(np.mean([b - a for n, a, b, _ in rec.spans
                                            if n == "tick"]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="city2k-daemon")
    ap.add_argument("--rates", default="500,600,700,750,800,900")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", default="1,2")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench_out" / "tpu_logs"))
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax

    import deploy
    import harness

    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    spec = harness.Spec(ROOT)
    w = spec.cell(args.workload)
    cfg = spec.config(w["config"])
    net = deploy.network(cfg)
    seeds = [int(x) for x in args.seeds.split(",")]
    reuse: dict = {}
    rows = {}
    for rate in [float(r) for r in args.rates.split(",")]:
        for seed in seeds:
            row = run_rate(spec, w, cfg, net, rate, args.seconds, seed, reuse)
            print(json.dumps(row), flush=True)
            rows.setdefault(rate, []).append(row)
    knee = None
    for rate, rs in sorted(rows.items()):
        if not keeps_up([r["growth"] for r in rs], sum(r["failed"] for r in rs)):
            break
        knee = rate
    print(json.dumps({"knee_rate_per_s": knee, "keeps_up": {
        r: keeps_up([x["growth"] for x in rs], sum(x["failed"] for x in rs))
        for r, rs in sorted(rows.items())}}), flush=True)
    if knee is not None:
        rate = float(round(SHARE * knee / 10) * 10)
        at_rate = rows.get(rate) or [
            run_rate(spec, w, cfg, net, rate, args.seconds, seed, reuse) for seed in seeds]
        print(json.dumps({"rate_per_s": rate, "offered_share_of_knee": SHARE,
                          "max_backlog_rows": max(r["max_backlog_rows"] for r in at_rate)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
