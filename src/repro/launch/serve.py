"""Serving launcher.

Three workloads:

  * ``--mode lm``     — batched greedy decoding against a KV/SSM cache.
  * ``--mode daemon`` — the long-lived serving loop of
                       ``repro.launch.daemon``: coalesced bucketed
                       queries against a double-buffered snapshot while
                       supervised training ticks (watchdog + checkpoints
                       + fault drills) run behind it.  All other flags
                       are the daemon's own
                       (``python -m repro.launch.daemon --help``).
  * ``--mode field`` — multi-field sensor regression: B independent fields
                       over one network are trained with the batched SN-Train
                       engine, streaming arrivals are absorbed in ONE batched
                       dispatch (``streaming.absorb_many``, rank-1 Cholesky
                       updates under a lax.scan), and queries are answered
                       per request grid by the selected fusion rule:
                       ``--fusion conn`` collapses to global coefficients +
                       one fused batched Pallas kernel matvec;
                       ``--fusion knn`` (paper Eq. 19) routes through the
                       static cell-candidate query plan
                       (``core.serving.make_serving_plan``) with
                       ``--engine {plan,pallas,dense}``.
                       ``--churn N`` additionally replays a membership churn
                       trace (SYMMETRIC sensor joins/leaves via
                       ``streaming.add_sensor`` / ``remove_sensor``: adopters
                       grow reciprocal anchor lanes, conflicting adopters are
                       recolored on device, and every event repairs only the
                       O(degree) affected rows) interleaved with arrival
                       windows, refresh sweeps and query rounds — all at the
                       fixed ``n_max`` capacity, so the whole trace compiles
                       a constant number of programs (the report prints the
                       jit-cache growth after warmup; it should be 0).
                       ``--faults drop=P[,burst=..][,crash=..]`` replays
                       training over unreliable links: every message draw
                       comes from the seeded ``core.faults`` process
                       (i.i.d. drops, Gilbert–Elliott bursts, crash/restart
                       schedules) and the ``core.monitor`` watchdog
                       supervises each round — retrying poisoned rounds
                       with fresh draws, refactorizing once, rolling back
                       bitwise if divergence persists — and its receipt is
                       printed.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m \
    --variant smoke --batch 4 --prompt_len 32 --gen 64
  PYTHONPATH=src python -m repro.launch.serve --mode field \
    --fields 64 --sensors 50 --sweeps 30 --stream 128 --queries 512 \
    --fusion knn --k 3 --engine plan
  PYTHONPATH=src python -m repro.launch.serve --mode field \
    --fields 16 --sensors 100 --stream 64 --churn 12 --spares 8 \
    --fusion knn --k 3 --engine plan
  PYTHONPATH=src python -m repro.launch.serve --mode field \
    --fields 8 --sensors 60 --sweeps 150 \
    --faults drop=0.1,burst=0.05:0.4:0.5
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.launch.cache import enable_compile_cache
from repro.models import decode_step, init_cache, init_params, prefill

# Hardened launch environment (the HomebrewNLP run.sh pattern, see
# SNIPPETS.md): tcmalloc beats glibc malloc under the daemon's sustained
# small-allocation churn, the TCMALLOC threshold silences its large-alloc
# warnings at serving batch sizes, TF_CPP_MIN_LOG_LEVEL keeps XLA's C++
# logging off the serving stdout, and the XLA flag pins one host device so
# serving never shards a query dispatch across virtual CPU devices.  The
# shell twin is launch/env.sh (exec-style wrapper); both skip gracefully
# when tcmalloc is absent.
_TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/local/lib/libtcmalloc.so.4",
)
_HARDENED_GUARD = "_REPRO_HARDENED_ENV"


def hardened_env(base=None) -> tuple[dict, list[str]]:
    """Build the hardened serving environment; returns (env, notes).

    Never overrides values the caller already exported (setdefault
    semantics), and skips the tcmalloc preload with a note — not an error
    — when no known library path exists.
    """
    env = dict(os.environ if base is None else base)
    notes = []
    lib = next((p for p in _TCMALLOC_PATHS if os.path.exists(p)), None)
    if lib is not None:
        pre = env.get("LD_PRELOAD", "")
        if lib not in pre.split(":"):
            env["LD_PRELOAD"] = f"{lib}:{pre}" if pre else lib
        env.setdefault(
            "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", "60000000000"
        )
        notes.append(f"tcmalloc={lib}")
    else:
        notes.append("tcmalloc absent (preload skipped)")
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    notes.append(f"XLA_FLAGS={env['XLA_FLAGS']!r}")
    return env, notes


def _reexec_hardened() -> None:
    """Replace this process with one running under the hardened env.

    LD_PRELOAD only takes effect at process start, so the flag re-execs
    the identical command line once (the guard variable stops the loop).
    """
    env, notes = hardened_env()
    env[_HARDENED_GUARD] = "1"
    print("hardened-env: " + "; ".join(notes), flush=True)
    os.execve(
        sys.executable,
        [sys.executable, "-m", "repro.launch.serve"] + sys.argv[1:],
        env,
    )


def serve_lm(args):
    cfg = get_config(args.arch, variant=None if args.variant == "full" else "smoke")
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    print(f"arch={cfg.name} params={cfg.n_params()/1e6:.1f}M")

    b, s0 = args.batch, args.prompt_len
    max_seq = s0 + args.gen + 1
    prompt = jax.random.randint(key, (b, s0), 0, cfg.vocab_size)
    batch = {"tokens": prompt}
    if cfg.is_encoder_decoder:
        batch = {"frames": jax.random.normal(key, (b, cfg.encoder_seq, cfg.d_model))}
    if cfg.n_patches:
        batch["patch_embeds"] = jax.random.normal(key, (b, cfg.n_patches, cfg.d_model))

    cache = init_cache(cfg, b, max_seq)
    jpre = jax.jit(lambda p, bt, c: prefill(cfg, p, bt, c))
    jdec = jax.jit(lambda p, t, c, pos: decode_step(cfg, p, t, c, pos))

    t0 = time.time()
    logits, cache = jpre(params, batch, cache)
    if logits is None:
        tok = jnp.zeros((b, 1), jnp.int32)
        pos0 = 0
    else:
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        pos0 = s0
    print(f"prefill: {time.time()-t0:.2f}s ({b}x{s0} tokens)")

    out = []
    t0 = time.time()
    for i in range(args.gen):
        logits, cache = jdec(params, tok, cache, jnp.int32(pos0 + i))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out.append(tok)
    dt = time.time() - t0
    seq = jnp.concatenate(out, axis=1)
    print(f"decode: {args.gen} steps in {dt:.2f}s -> {b*args.gen/dt:.1f} tok/s")
    print("sample row 0:", jax.device_get(seq[0])[:24].tolist())


def serve_fields(args):
    import numpy as np

    from repro.core import (
        Kernel,
        build_topology,
        colored_sweep,
        fusion,
        init_state,
        make_batch_problem,
        make_serving_plan,
        streaming,
        uniform_sensors,
    )
    from repro.kernels import kernel_matvec

    b, n = args.fields, args.sensors
    rng = np.random.default_rng(args.seed)
    pos = uniform_sensors(n, seed=args.seed)
    # Per-field targets: random-frequency/phase sinusoids + noise.
    freq = rng.uniform(0.5, 2.0, size=(b, 1))
    phase = rng.uniform(0, 2 * np.pi, size=(b, 1))
    ys = np.sin(np.pi * freq * pos[None, :, 0] + phase) + 0.3 * rng.normal(size=(b, n))

    topo = build_topology(pos, args.radius)
    if args.stream or args.churn:
        # headroom: streaming arrivals occupy free neighborhood slots,
        # joining sensors adopt them, and (symmetric joins) every adopting
        # neighbor spends one lane on its reciprocal anchor
        per_sensor = -(-max(args.stream, 1) // n) + 4 + (2 if args.churn else 0)
        deg_max = int(np.asarray(topo.degrees).max()) + per_sensor
        topo = build_topology(pos, args.radius, d_max=deg_max)
    n_max = n + args.spares if args.churn else None
    prob = make_batch_problem(
        topo, Kernel("rbf", gamma=args.gamma), ys, jnp.full((n,), args.lam),
        n_max=n_max, beta=args.beta,
    )
    state = init_state(prob)
    print(
        f"fields={b} sensors={n} (capacity {prob.n}) D={prob.topology.d_max} "
        f"colors={prob.topology.n_colors} stream_capacity={prob.n_stream}"
    )

    # -- train: batched colored sweeps -------------------------------------
    if args.faults:
        # Unreliable-link replay: train under the seeded fault process with
        # the convergence watchdog supervising every round (retry with fresh
        # draws -> refactorize -> bitwise rollback).  The fault rates are
        # traced operands, so the whole replay reuses the fault-free
        # programs — zero extra compiles.
        from repro.core import faults as faults_mod, monitor

        model = faults_mod.parse_fault_spec(args.faults, dtype=state.z.dtype)
        engine = "pallas" if args.engine == "pallas" else "plan"
        cfg = monitor.WatchdogConfig(
            sweeps_per_round=args.refresh_sweeps,
            tol=args.watch_tol,
            max_rounds=max(1, -(-args.sweeps // args.refresh_sweeps)),
        )
        t0 = time.time()
        prob, state, receipt = monitor.watch_sweeps(
            prob, state, model=model,
            key=jax.random.PRNGKey(args.seed + 1), engine=engine, config=cfg,
        )
        state.z.block_until_ready()
        dt = time.time() - t0
        print(
            f"train[faults {args.faults}, engine={engine}]: "
            f"{receipt.sweeps} supervised sweeps x {b} fields in {dt:.3f}s"
        )
        print(monitor.format_receipt(receipt))
        # machine-readable twin of the line above (stable schema; the
        # exact inverse is monitor.receipt_from_json)
        import json

        print("watchdog.json: " + json.dumps(receipt.to_json()))
    else:
        # warm with the SAME n_sweeps: it is a static jit arg, so a
        # different value would compile a different program and the timing
        # would include it
        colored_sweep(prob, state, n_sweeps=args.sweeps).z.block_until_ready()
        t0 = time.time()
        state = colored_sweep(prob, state, n_sweeps=args.sweeps)
        state.z.block_until_ready()
        dt = time.time() - t0
        print(
            f"train: {args.sweeps} sweeps x {b} fields in {dt:.3f}s "
            f"-> {b/dt:.1f} fields/s"
        )

    # -- streaming: batched absorb, ONE dispatch per arrival window --------
    if args.stream:
        # Two equal arrival windows (plus a single-arrival remainder when
        # --stream is odd, so exactly args.stream arrivals are absorbed):
        # the first window compiles the scan-based absorb_many program (A is
        # a static shape), the second reuses it, so the reported ms/update
        # is one warm dispatch over A arrivals — not A host round-trips.
        half = args.stream // 2

        def window(a):
            fs = rng.integers(0, b, size=a)
            ss = rng.integers(0, n, size=a)
            xs = (
                pos[ss] + 0.05 * rng.normal(size=(a, pos.shape[1]))
            ).astype(np.float32)
            return fs, ss, xs, rng.normal(size=a).astype(np.float32)

        absorbed_flags, evicted_flags = [], []
        if args.stream % 2:
            # via absorb_many so the remainder's receipt (incl. a possible
            # eviction) lands in the printed counts like everyone else's
            prob, state, rec = streaming.absorb_many(
                prob, state, *window(1), donate=True, on_full=args.on_full
            )
            absorbed_flags.append(rec.absorbed)
            evicted_flags.append(rec.evicted)
        dt = None
        if half:
            prob, state, rec0 = streaming.absorb_many(
                prob, state, *window(half), donate=True, on_full=args.on_full
            )
            timed_window = window(half)  # generated before the clock starts
            jax.block_until_ready(prob.chol)
            t0 = time.time()
            prob, state, rec1 = streaming.absorb_many(
                prob, state, *timed_window, donate=True, on_full=args.on_full
            )
            jax.block_until_ready(prob.chol)
            dt = time.time() - t0
            absorbed_flags += [rec0.absorbed, rec1.absorbed]
            evicted_flags += [rec0.evicted, rec1.evicted]
        # the receipt flags keep the reported counts honest about capacity
        # pressure: every arrival is absorbed, absorbed-after-evict, or
        # dropped — nothing disappears silently
        absorbed = int(jnp.sum(jnp.concatenate(absorbed_flags)))
        evicted = (
            int(jnp.sum(jnp.concatenate(evicted_flags)))
            if evicted_flags else 0
        )
        dropped = args.stream - absorbed
        pressure = (
            f" (capacity pressure: {dropped} dropped, {evicted} evicted)"
            if dropped or evicted else ""
        )
        timing = (
            f", timed window of {half} in one dispatch: {dt:.3f}s -> "
            f"{dt/half*1e3:.3f} ms/update" if dt is not None else ""
        )
        print(f"stream: {absorbed} absorbed{timing}{pressure}")
        state = colored_sweep(prob, state, n_sweeps=args.refresh_sweeps)

    # -- churn: replay a join/leave lifecycle trace at fixed capacity ------
    churn_plan = None
    if args.churn:
        from repro.core import add_sensor, remove_sensor
        from repro.core.serving import plan_add_sensor, plan_remove_sensor

        # Slack >= the worst-case removals keeps the repaired query plan's
        # kNN exactness bound valid across the whole trace.
        churn_plan = make_serving_plan(
            prob, k=args.k, spare=args.spares + 4, slack=args.churn
        )
        xq_c = np.linspace(-0.9, 0.9, 64)[:, None].astype(np.float32)
        if pos.shape[1] > 1:
            xq_c = np.concatenate(
                [xq_c] + [np.zeros_like(xq_c)] * (pos.shape[1] - 1), axis=1
            )
        stats = dict(joins=0, join_drops=0, leaves=0, cell_overflows=0,
                     absorbed=0, dropped=0, skipped_couplings=0,
                     dropped_newest=0)
        joined: list[int] = []

        def churn_round(prob, state, plan, i):
            x = rng.uniform(-0.9, 0.9, size=pos.shape[1]).astype(np.float32)
            prob, state, rcpt = add_sensor(
                prob, state, x, rng.normal(size=b).astype(np.float32),
                lam=args.lam, repair_lambda=args.repair_lambda, donate=True,
            )
            slot, ok = rcpt.slot, rcpt.joined
            # JoinReceipt fidelity counters: couplings lost to
            # lane-exhausted neighbors and newest arrivals orphaned by
            # reciprocal anchor-lane growth — capacity pressure that used
            # to be silent
            stats["skipped_couplings"] += int(np.asarray(rcpt.skipped_mask).sum())
            stats["dropped_newest"] += int(np.asarray(rcpt.dropped_newest).sum())
            if bool(ok):  # a dropped join must not touch the query plan
                plan, over = plan_add_sensor(plan, x, slot)
                joined.append(int(slot))
                stats["joins"] += 1
                stats["cell_overflows"] += int(over)
            else:
                stats["join_drops"] += 1
            a = 8
            fs = rng.integers(0, b, size=a)
            ss = rng.integers(0, n, size=a)
            xs = (pos[ss] + 0.05 * rng.normal(size=(a, pos.shape[1]))).astype(np.float32)
            prob, state, rec = streaming.absorb_many(
                prob, state, fs, ss, xs, rng.normal(size=a).astype(np.float32),
                donate=True, on_full=args.on_full,
            )
            stats["absorbed"] += int(np.asarray(rec.absorbed).sum())
            stats["dropped"] += a - int(np.asarray(rec.absorbed).sum())
            state = colored_sweep(prob, state, n_sweeps=args.refresh_sweeps)
            if i % 2 == 1:  # every other round a sensor leaves
                victim = joined.pop(0) if joined else int(rng.integers(0, n))
                prob, state, rok = remove_sensor(
                    prob, state, victim,
                    repair_lambda=args.repair_lambda, donate=True,
                )
                plan = plan_remove_sensor(plan, victim)
                stats["leaves"] += int(bool(rok))
                state = colored_sweep(prob, state, n_sweeps=args.refresh_sweeps)
            # query with the engine under test (dense ignores the plan)
            fusion.fuse(
                prob, state, xq_c, "knn", k=args.k, engine=args.engine,
                plan=None if args.engine == "dense" else plan,
            ).block_until_ready()
            return prob, state, plan

        # Warm with one even + one odd round so both the join-only and the
        # join+leave program sets are compiled before counting.
        prob, state, churn_plan = churn_round(prob, state, churn_plan, 0)
        prob, state, churn_plan = churn_round(prob, state, churn_plan, 1)
        from repro.analysis import compile_ledger

        snap = compile_ledger.snapshot(
            compile_ledger.churn_group(on_full=args.on_full, donate=True)
        )
        t0 = time.time()
        for i in range(2, args.churn):
            prob, state, churn_plan = churn_round(prob, state, churn_plan, i)
        dt = time.time() - t0
        recompiles = snap.total_growth()
        per_round = dt / max(args.churn - 2, 1) * 1e3
        from repro.core import plans as _plans

        headroom = np.asarray(
            _plans.degree_headroom(
                prob.topology.degrees, prob.alive[: prob.n],
                prob.topology.d_max,
            )
        )
        live = np.asarray(prob.alive[: prob.n])
        hr = headroom[live]
        min_headroom = int(hr.min()) if hr.size else 0
        p50_headroom = int(np.median(hr)) if hr.size else 0
        rows_at_0 = int((hr == 0).sum())
        print(
            f"churn: {args.churn} rounds ({stats['joins']} joins, "
            f"{stats['leaves']} leaves, {stats['join_drops']} join-drops, "
            f"{stats['absorbed']} absorbed / {stats['dropped']} dropped "
            f"arrivals, {stats['cell_overflows']} cell overflows) "
            f"{per_round:.1f} ms/round warm; "
            f"recompiles after warmup: {recompiles} (want 0)"
        )
        print(
            f"churn receipts: {stats['skipped_couplings']} couplings "
            f"skipped (lane-exhausted neighbors), "
            f"{stats['dropped_newest']} newest arrivals dropped to anchor "
            f"lanes; live degree headroom min={min_headroom} "
            f"p50={p50_headroom} rows_at_0={rows_at_0}"
            + (" -- joins near 0-headroom rows lose couplings"
               if rows_at_0 else "")
        )

    # -- query: one dispatch per request grid ------------------------------
    xq = np.linspace(-1, 1, args.queries)[:, None].astype(np.float32)
    if pos.shape[1] > 1:
        xq = np.concatenate([xq] + [np.zeros_like(xq)] * (pos.shape[1] - 1), axis=1)
    if args.fusion == "knn":
        # kNN fusion (paper Eq. 19); plan/pallas route through the static
        # query plan — per-cell candidate lists, O(Q*k*D) per field instead
        # of O(Q*n*D) — while dense runs the all-sensors oracle.  A churn
        # trace's plan was repaired in place and keeps serving as-is.
        plan = (
            None if args.engine == "dense"
            else (churn_plan if churn_plan is not None
                  else make_serving_plan(prob, k=args.k))
        )
        cdt = (
            None if args.engine == "dense" or args.serve_dtype == "f32"
            else args.serve_dtype
        )
        note = f"knn k={args.k} engine={args.engine}"
        if plan is not None and args.energy_tau > 0:
            # Offline compaction: drop representers under the energy
            # threshold and shrink the candidate-list gather width.  Churn
            # repairs happened on the UNPRUNED plan above; pruning is
            # derived on top of the repaired lists.
            from repro.core import pruning

            plan, rep = pruning.prune_plan(
                prob, state, plan, energy_tau=args.energy_tau
            )
            note += (
                f" tau={args.energy_tau:g} pruned {rep.n_pruned}/"
                f"{rep.n_live}"
            )
        run = lambda: fusion.fuse(
            prob, state, xq, "knn", k=args.k, engine=args.engine, plan=plan,
            compute_dtype=cdt,
        )
        if cdt is not None:
            note += f" dtype={args.serve_dtype}"
        if plan is not None:
            note += f" (plan: {plan.n_cells} cells, K_max={plan.k_max})"
    else:
        # conn fusion (Eq. 20) collapses to one batched Pallas kernel matvec
        anchors, coefs = fusion.global_coefficients(prob, state, rule="conn")
        run = lambda: kernel_matvec(xq, anchors, coefs, gamma=args.gamma)
        note = "conn (global coefficients + fused matvec)"
    out = run()
    out.block_until_ready()
    t0 = time.time()
    out = run()
    out.block_until_ready()
    dt = time.time() - t0
    print(
        f"query[{note}]: {args.queries} points x {b} fields in {dt*1e3:.2f}ms "
        f"-> {args.queries*b/dt:.0f} field-queries/s"
    )
    print("sample field 0:", np.asarray(out[0, :6]).round(3).tolist())


def main():
    # daemon mode has its own flag set — peel --mode (and the env re-exec
    # flag) off and delegate the rest of argv to repro.launch.daemon
    pre = argparse.ArgumentParser(add_help=False)
    pre.add_argument("--mode", default="lm",
                     choices=["lm", "field", "daemon"])
    pre.add_argument("--hardened-env", action="store_true")
    ns, rest = pre.parse_known_args()
    if ns.hardened_env and os.environ.get(_HARDENED_GUARD) != "1":
        _reexec_hardened()  # never returns
    enable_compile_cache()
    if ns.mode == "daemon":
        from repro.launch import daemon

        return daemon.main(rest)

    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="lm", choices=["lm", "field", "daemon"])
    # lm mode
    ap.add_argument("--arch", default="smollm-135m", choices=ARCH_NAMES)
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt_len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    # field mode
    ap.add_argument("--fields", type=int, default=64, help="B concurrent fields")
    ap.add_argument("--sensors", type=int, default=50)
    ap.add_argument("--radius", type=float, default=0.8)
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--lam", type=float, default=0.1)
    ap.add_argument("--sweeps", type=int, default=30)
    ap.add_argument("--refresh_sweeps", type=int, default=5)
    ap.add_argument("--stream", type=int, default=0, help="streaming arrivals to absorb")
    ap.add_argument("--on_full", default="drop", choices=["drop", "evict"],
                    help="over-capacity arrival policy (evict = sliding window)")
    ap.add_argument("--beta", type=float, default=1.0,
                    help="per-field forgetting factor in (0, 1]; beta < 1 "
                         "decays old arrivals one step per absorb (EW-RLS) "
                         "so streams track time-varying fields; 1.0 is the "
                         "bitwise static path")
    ap.add_argument("--repair_lambda", action="store_true",
                    help="re-derive the paper rule lambda_i = 0.01/|N_i|^2 "
                         "for rows whose degree changes in churn events")
    ap.add_argument("--churn", type=int, default=0,
                    help="membership churn rounds to replay (symmetric "
                         "joins/leaves with O(degree) event repairs)")
    ap.add_argument("--spares", type=int, default=8,
                    help="spare sensor rows reserved for --churn joins "
                         "(n_max = sensors + spares; the recolor pool "
                         "defaults to 2x this)")
    ap.add_argument("--faults", default="",
                    help="unreliable-link replay spec for training: "
                         "drop=P[,burst=to_bad:to_good:drop_bad]"
                         "[,crash=p_crash:p_restart]; trains under the "
                         "seeded fault process with the convergence "
                         "watchdog supervising (retry / refactorize / "
                         "rollback) and prints the receipt")
    ap.add_argument("--watch_tol", type=float, default=1e-3,
                    help="--faults watchdog convergence tolerance "
                         "(max relative z-residual per round)")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--fusion", default="conn", choices=["conn", "knn"],
                    help="query fusion rule (knn routes through the query plan)")
    ap.add_argument("--k", type=int, default=3, help="kNN order for --fusion knn")
    ap.add_argument("--engine", default="plan", choices=["dense", "plan", "pallas"],
                    help="kNN serving engine for --fusion knn")
    ap.add_argument("--serve_dtype", default="f32", choices=["f32", "bf16"],
                    help="anchor-table storage dtype for the plan/pallas "
                         "kNN engines (bf16 rounds the stored anchors "
                         "only; selection and accumulation stay in full "
                         "precision — selection-exact)")
    ap.add_argument("--energy_tau", type=float, default=0.0,
                    help="representer-pruning energy threshold: compact "
                         "the query plan to sensors with coefficient "
                         "energy above tau before serving (plan/pallas "
                         "engines; 0 = off)")
    ap.add_argument("--hardened-env", action="store_true",
                    help="re-exec under the hardened launch env (tcmalloc "
                         "LD_PRELOAD + XLA/logging flags; see launch/"
                         "env.sh), skipped gracefully when libs are absent")
    args = ap.parse_args()
    if args.mode == "field":
        serve_fields(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
