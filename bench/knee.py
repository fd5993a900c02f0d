#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered request rate
at which the backlog does not grow over a window.

    python3 bench/knee.py --workload city2k-daemon \
        --rates 200,300,400,500,600,700,800,900 --seconds 20 --seed 1

The daemon's loop releases what is due, then ``pump`` answers every
queued request, then ``tick`` trains; so each pump finds the requests
that came due during the previous loop iteration, and that count is the
backlog.  Below the knee it settles to a level (rate times one
iteration); above it every iteration is longer than the last and the
backlog grows without end.  A rate keeps up when no request fails and
the mean backlog of the pumps in the window's last third is at most
``GROWTH`` times that of its middle third (the first third holds the
start from an empty queue).

For each rate, in one process (the network is built and trained once),
the cell's open loop runs with that rate and a queue that never sheds.
Then the rate ``SHARE`` x knee runs once more, and the largest backlog
in rows any of its pumps found is printed: the cell's ``queue_rows`` is
set from it.  Prints one JSON line per rate and, last, the knee.  It runs
on the chip only.
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
GROWTH = 1.2  # a backlog this much larger in the last third grows
SHARE = 0.8  # the cell offers this share of the knee


def backlog_thirds(pumps: list, t0: float, seconds: float) -> list:
    """Mean requests per pump in each third of the window (pumps that
    answered nothing count as 0)."""
    out = []
    for i in range(3):
        lo, hi = t0 + seconds * i / 3, t0 + seconds * (i + 1) / 3
        counts = [p[2] for p in pumps if lo <= p[0] < hi]
        out.append(sum(counts) / len(counts) if counts else float("nan"))
    return out


def keeps_up(thirds: list, failed: int) -> bool:
    _, middle, last = thirds
    return bool(failed == 0 and last <= GROWTH * middle)


def run_rate(spec, w, cfg, net, rate, seconds, seed, reuse) -> dict:
    import numpy as np

    import drivers

    mix = dict(spec.traffic(w["traffic"]), rate_per_s=rate)
    ctx = drivers.Context(cfg=cfg, mix=mix, seed=seed, seconds=seconds,
                          t_start=time.perf_counter(), net=net,
                          daemon={"queue_rows": 1 << 30}, reuse=reuse)
    rec, _ = drivers.open_loop(ctx)
    lat = np.asarray(rec.latencies_ms)
    thirds = backlog_thirds(rec.pumps, rec.window.t0, seconds)
    iters = [b[0] - a[0] for a, b in zip(rec.pumps, rec.pumps[1:])]
    return {"rate_per_s": rate, "keeps_up": keeps_up(thirds, rec.failed),
            "backlog_by_third": thirds, "growth": thirds[2] / thirds[1],
            "max_backlog_rows": max(p[3] for p in rec.pumps),
            "pumps": len(rec.pumps), "iteration_ms": 1e3 * float(np.mean(iters)),
            "requests": len(lat), "failed": rec.failed,
            "p50_ms": float(np.median(lat)), "p95_ms": float(np.percentile(lat, 95)),
            "tick_ms": 1e3 * float(np.mean([b - a for n, a, b, _ in rec.spans
                                            if n == "tick"]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="city2k-daemon")
    ap.add_argument("--rates", default="200,300,400,500,600,700,800,900")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
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
    knee = None
    reuse: dict = {}
    for rate in [float(r) for r in args.rates.split(",")]:
        row = run_rate(spec, w, cfg, net, rate, args.seconds, args.seed, reuse)
        print(json.dumps(row), flush=True)
        if not row["keeps_up"]:
            break
        knee = rate
    print(json.dumps({"knee_rate_per_s": knee}), flush=True)
    if knee is not None:
        rate = round(SHARE * knee / 10) * 10
        row = run_rate(spec, w, cfg, net, rate, args.seconds, args.seed, reuse)
        row["offered_share_of_knee"] = SHARE
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
