"""Quantized + sparsified serving: dtype × energy_tau × n sweep.

Measures the two compounding serving optimizations of the quantized path
against the production configuration they upgrade:

  * ``compute_dtype="bf16"`` — bf16 STORAGE for the anchor tables (the
    dominant operand) with register-level upconversion, exact f32
    selection, and coefficient-dtype (f32) accumulation in the Pallas
    evaluate kernel; the halved bytes double the default query tile
    (``kernels.knn_fuse.default_block_q``).
  * ``energy_tau`` representer pruning — ``pruning.prune_plan`` compacts
    the per-cell candidate lists to sensors whose coefficient energy
    clears the threshold, shrinking the ``K_max`` gather width that
    lifecycle capacity (``spare``/``slack`` columns) and dead-weight
    representers inflate.
  * bulk tile retuning — pallas rows sweep ``block_q`` beyond the
    latency-oriented shipped default; on this repo's CPU interpret
    backend the per-grid-step table rematerialization dominates, so
    larger bulk tiles amortize it (on real TPU the same knob trades VMEM
    headroom for grid amortization).

The BASELINE is the serving configuration the repo shipped before this
path: the churn-ready capacity plan (spare/slack lifecycle rows), f32,
default tile.  Each (dtype, tau, block) grid cell reports warm
field-queries/s and the field RMSE against the f32 DENSE oracle
(relative, % of field RMS) — retuned f32 rows stay in the JSON so each
lever's contribution is auditable.  Tau values are fractions of the max
live-sensor energy; ``tau = 0`` compacts away only dead/spare candidate
entries (provably exact — nothing live is pruned).

Zero-recompile contract: after one warmup pass over the whole grid, the
timed pass compiles nothing (the jit caches of the pallas launcher and
the plan-engine helpers are counted and asserted; recorded in the JSON).

Results go to ``BENCH_quant.json``; ``quant_fast`` is the trimmed variant
``benchmarks/run.py --fast`` runs for the CI bench-json artifact.

Run:  PYTHONPATH=src python -m benchmarks.quant_bench
      PYTHONPATH=src python -m benchmarks.quant_bench --ns 100,1000 --taus 0,0.02
"""

from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np
import jax.numpy as jnp

from repro.core import (
    Kernel,
    build_topology,
    colored_sweep,
    fusion,
    init_state,
    make_batch_problem,
    make_serving_plan,
    pruning,
    uniform_sensors,
)
from repro.kernels.knn_fuse import default_block_q


def _problem(n, b, radius, lam, seed=0):
    rng = np.random.default_rng(seed)
    pos = uniform_sensors(n, d=2, seed=seed)
    topo = build_topology(pos, radius)
    freq = rng.uniform(0.5, 2.0, size=(b, 1))
    ys = np.sin(np.pi * freq * pos[None, :, 0]) + 0.3 * rng.normal(size=(b, n))
    prob = make_batch_problem(
        topo, Kernel("rbf", gamma=1.0), ys, jnp.full((n,), lam)
    )
    state = colored_sweep(prob, init_state(prob), n_sweeps=3)
    return prob, state


def _tracked_caches():
    from repro.core.serving import _knn_plan
    from repro.kernels.knn_fuse import knn_fuse_pallas

    return (knn_fuse_pallas, _knn_plan)


def _grid_cells(prob, state, plan_cap, taus):
    """(label, plan, report) per tau column: capacity plan + compactions."""
    n = prob.n
    e = np.asarray(pruning.representer_energy(prob, state))[:n]
    e_max = float(e.max()) if e.size else 1.0
    cells = [("cap", plan_cap, None)]  # the unpruned lifecycle plan
    for tau in taus:
        plan_t, rep = pruning.prune_plan(
            prob, state, plan_cap, energy_tau=float(tau) * e_max
        )
        cells.append((f"tau{tau:g}", plan_t, rep))
    return cells


def sweep(ns, queries, k, batch, taus, engines=("pallas", "plan"),
          radius=0.3, lam=0.1, spare=None, slack=4, reps=2,
          blocks=(None, 512)):
    rng = np.random.default_rng(1)
    xq = rng.uniform(-1, 1, size=(queries, 2)).astype(np.float32)
    entries = []
    print(f"{'n':>6s} {'eng':>7s} {'dtype':>6s} {'tau':>8s} {'K_max':>6s} "
          f"{'block':>7s} {'fq/s':>12s} {'rmse%':>8s}")
    for n in ns:
        r = radius * math.sqrt(100.0 / n)
        prob, state = _problem(n, batch, r, lam)
        # The production plan: lifecycle capacity inflates K_max — exactly
        # the dead weight compaction reclaims.  Spare provisions ~2% of
        # the network joining concurrently (min 8), the capacity the
        # daemon's churn tests exercise; compaction re-derives per publish
        # so the NEXT join still finds spare rows on the unpruned plan.
        n_spare = max(8, round(0.02 * n)) if spare is None else spare
        plan_cap = make_serving_plan(prob, k=k, spare=n_spare, slack=slack)
        dense = np.asarray(
            fusion.fuse(prob, state, xq, "knn", k=k, engine="dense")
        )
        dense_rms = float(np.sqrt(np.mean(dense**2)))
        cells = _grid_cells(prob, state, plan_cap, taus)

        def run(engine, cdt, plan, block):
            return fusion.fuse(
                prob, state, xq, "knn", k=k, engine=engine, plan=plan,
                compute_dtype=cdt, block_q=block,
            )

        # Pallas rows additionally sweep the bulk query tile: the shipped
        # default (None -> default_block_q) is latency-oriented (small
        # bucketed requests pad little); offline/bulk serving retunes it.
        grid = [
            (eng, dtype, cell, block)
            for eng in engines
            for dtype in (None, "bf16")
            for cell in cells
            for block in (blocks if eng == "pallas" else (None,))
        ]
        # Warmup pass over the WHOLE grid, then snapshot the jit caches:
        # the timed pass must compile nothing.
        for eng, dtype, (label, plan, _rep), block in grid:
            run(eng, dtype, plan, block).block_until_ready()
        warm = [f._cache_size() for f in _tracked_caches()]
        for eng, dtype, (label, plan, rep), block in grid:
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                run(eng, dtype, plan, block).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            out = np.asarray(run(eng, dtype, plan, block))
            rmse_pct = (
                float(np.sqrt(np.mean((out - dense) ** 2))) / dense_rms * 100
            )
            row = {
                "n": n, "engine": eng,
                "dtype": "f32" if dtype is None else dtype,
                "tau": label, "k": k, "batch": batch, "queries": queries,
                "k_max": plan.k_max, "s_per_call": best,
                "fqps": queries * batch / best, "rmse_pct": rmse_pct,
            }
            if eng == "pallas":
                row["block_q"] = (
                    default_block_q(None if dtype is None else jnp.bfloat16)
                    if block is None else block
                )
                row["block_default"] = block is None
            if rep is not None:
                row["tau_abs"] = rep.energy_tau
                row["pruned"] = rep.n_pruned
                row["n_live"] = rep.n_live
            entries.append(row)
            bq_s = f"bq{row.get('block_q', '-')}"
            print(f"{n:6d} {eng:>7s} {row['dtype']:>6s} {label:>8s} "
                  f"{plan.k_max:6d} {bq_s:>7s} {row['fqps']:12.0f} "
                  f"{rmse_pct:8.3f}")
        recompiles = sum(
            f._cache_size() - w for f, w in zip(_tracked_caches(), warm)
        )
        assert recompiles == 0, (
            f"timed grid pass compiled {recompiles} extra programs"
        )
    return entries


def _acceptance(entries, engines, at_n, rmse_budget_pct=1.0):
    """speedup = previous production config / best admissible quant cell.

    Baseline: f32, capacity plan, default tile — the serving configuration
    the repo shipped before the quantized path.  Admissible: bf16 + some
    (tau, tile) with RMSE within the budget of the dense oracle.  The full
    grid (including retuned f32 rows) stays in ``entries`` so the
    contribution of each lever is auditable.  Per engine, at n = at_n.
    """
    out = {}
    for eng in engines:
        rows = [e for e in entries if e["n"] == at_n and e["engine"] == eng]
        base = next(
            (
                e for e in rows
                if e["dtype"] == "f32" and e["tau"] == "cap"
                and e.get("block_default", True)
            ),
            None,
        )
        quant = [
            e for e in rows
            if e["dtype"] == "bf16" and e["rmse_pct"] <= rmse_budget_pct
        ]
        if base is None or not quant:
            continue
        best = min(quant, key=lambda e: e["s_per_call"])
        out[f"speedup_at_n{at_n}_{eng}"] = (
            base["s_per_call"] / best["s_per_call"]
        )
        out[f"best_cell_at_n{at_n}_{eng}"] = {
            "dtype": best["dtype"], "tau": best["tau"],
            "k_max": best["k_max"], "rmse_pct": best["rmse_pct"],
            "fqps": best["fqps"],
            "block_q": best.get("block_q"),
        }
    return out


def quant_fast(rows):
    """Trimmed grid for ``benchmarks/run.py --fast`` (CI bench-json rows)."""
    entries = sweep(
        ns=(100,), queries=512, k=3, batch=4, taus=(0.0, 0.02),
        engines=("pallas",), reps=1, blocks=(None,),
    )
    for e in entries:
        rows.append(
            (
                f"quant.n{e['n']}.{e['engine']}.{e['dtype']}.{e['tau']}",
                e["s_per_call"] * 1e6,
                f"fqps={e['fqps']:.0f};rmse_pct={e['rmse_pct']:.3f};"
                f"k_max={e['k_max']};recompiles=0",
            )
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ns", default="100,300,1000")
    ap.add_argument("--queries", type=int, default=4096)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--taus", default="0,0.02,0.05",
                    help="energy thresholds as fractions of the max live "
                         "sensor energy")
    ap.add_argument("--engines", default="pallas,plan")
    ap.add_argument("--radius", type=float, default=0.3)
    ap.add_argument("--lam", type=float, default=0.1)
    ap.add_argument("--spare", type=int, default=None,
                    help="join-capacity rows in the baseline plan "
                         "(default: max(8, 2%% of n))")
    ap.add_argument("--slack", type=int, default=4)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--blocks", default="default,512",
                    help="pallas query tiles to sweep ('default' = the "
                         "shipped default_block_q)")
    ap.add_argument("--out", default="BENCH_quant.json")
    args = ap.parse_args()
    ns = [int(s) for s in args.ns.split(",")]
    taus = [float(s) for s in args.taus.split(",")]
    engines = tuple(e.strip() for e in args.engines.split(",") if e.strip())
    blocks = tuple(
        None if s.strip() == "default" else int(s)
        for s in args.blocks.split(",") if s.strip()
    )
    entries = sweep(
        ns, args.queries, args.k, args.batch, taus, engines=engines,
        radius=args.radius, lam=args.lam, spare=args.spare,
        slack=args.slack, reps=args.reps, blocks=blocks,
    )
    out = {
        "name": "quant", "batch": args.batch, "queries": args.queries,
        "k": args.k, "taus": taus, "recompiles_after_warmup": 0,
        "entries": entries,
    }
    for at_n in {1000, ns[-1]}:
        out.update(_acceptance(entries, engines, at_n))
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    for key, v in out.items():
        if key.startswith("speedup"):
            print(f"{key}: {v:.2f}x")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
