"""The three loops that drive a cell, one per traffic ``loop`` kind, and
the comparison of what each window produced with the plain reference.

Each driver builds the deployment (set-up), warms every shape its window
uses, measures for the window, drains what is due, and returns the run's
``Record`` together with a ``check`` callable.  The harness reads the
device's memory peak, frees the program's state, and only then calls
``check``, which replays the window's work on ``reference.Reference``.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

import deploy
import traffic
import work
from record import Record, Spans, compile_count
from reference import Reference, adjacency


@dataclasses.dataclass
class Context:
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    t_start: float  # perf_counter at process start
    trace_s: float = 0.0  # 0: no trace
    trace_dir: str = ""
    net: object = None  # a prebuilt deploy.Network (calibration reuses one)
    daemon: dict = dataclasses.field(default_factory=dict)  # DaemonConfig overrides
    reuse: dict | None = None  # set-up results kept across calls with one seed (knee sweep)


class Window:
    """The measured window; traces ``trace_s`` seconds of it, from
    ``trace_from`` seconds after it opens."""

    def __init__(self, ctx: Context, spans: Spans, trace_from: float = 0.0):
        self.ctx, self.spans = ctx, spans
        self.trace_from = trace_from
        self.tracing = self.traced = False
        self.trace_t0 = self.trace_t1 = 0.0

    def open(self) -> float:
        if self.ctx.trace_s > 0 and self.trace_from <= 0:
            self.start()
        self.compiles_at_open = compile_count()
        print("bench: window open", file=sys.stderr, flush=True)
        self.t0 = time.perf_counter()
        self.trace_t0 = self.t0
        return self.t0

    def poll(self) -> float:
        """Seconds since the window opened; starts and ends the trace
        when due."""
        now = time.perf_counter()
        if self.tracing and now - self.trace_t0 >= self.ctx.trace_s:
            self.stop()
        elif self.ctx.trace_s > 0 and not self.traced and now - self.t0 >= self.trace_from:
            self.start()
            self.trace_t0 = time.perf_counter()
            print(f"bench: trace from {self.trace_t0 - self.t0:.3f} s "
                  f"(start took {self.trace_t0 - now:.3f} s)", file=sys.stderr, flush=True)
        return now - self.t0

    def close(self, rec: Record) -> None:
        """End of the measured window: its length, and the trace."""
        rec.window_s = time.perf_counter() - self.t0
        rec.window = self
        self.compiles_in_window = compile_count() - self.compiles_at_open
        print("bench: window closed", file=sys.stderr, flush=True)
        self.stop()

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.ctx.trace_dir)
        self.spans.annotate = True
        self.tracing = self.traced = True

    def stop(self) -> None:
        if self.tracing:
            import jax

            self.trace_t1 = time.perf_counter()
            self.spans.annotate = False
            jax.profiler.stop_trace()
            self.tracing = False


def _watch_config(cfg: dict):
    from repro.core import WatchdogConfig

    return WatchdogConfig(**cfg["watchdog"])


def _daemon_config(cfg: dict, overrides: dict):
    from repro.launch.daemon import DaemonConfig

    return DaemonConfig(k=cfg["k"], **{**cfg["daemon"], **overrides})


def _gap(a: np.ndarray, ref: np.ndarray) -> float:
    """max |a - ref| over max |ref|."""
    a = np.asarray(a, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(a - ref)) / max(np.max(np.abs(ref)), 1e-30))


def replay_solve(net, cfg: dict, ys, sweeps: int, precision: str = "highest") -> tuple:
    """The reference's solve of readings ``ys`` for ``sweeps`` sweeps from
    the initial state: (messages, coefficients, stop residual).

    The stop residual is the watchdog's own convergence test worked out on
    the reference: per field, max |dz| over max |z| across the last round
    of ``sweeps_per_round`` sweeps, its largest value over the configured
    ``tol``.  A solve that had converged when it stopped reads under 1 up
    to rounding; one that stopped early reads above.
    """
    wd = cfg["watchdog"]
    spr = int(wd["sweeps_per_round"])
    ref = Reference(net.pos, net.radius, cfg["gamma"], cfg["lambda"], ys,
                    precision=precision)
    ref.sweeps(max(sweeps - spr, 0))
    before = ref.slots()
    ref.sweeps(min(spr, sweeps))
    after = ref.slots()
    resid = np.max(np.abs(after - before), 1) / (np.max(np.abs(before), 1) + 1e-12)
    return ref.messages(), ref.coefficients(), float(resid.max() / wd["tol"])


def solve_gaps(net, cfg: dict, ys, sweeps: int, z, c) -> dict:
    """A solve's numbers: its messages and neighbor-lane coefficients
    against the reference's after as many sweeps, and the reference's
    stop residual at that count."""
    n = net.n
    msg, coef, stop = replay_solve(net, cfg, ys, sweeps)
    z, c = np.asarray(z), np.asarray(c)
    return {"message_gap": _gap(z[:, :n], msg),
            "coef_gap": _gap(c[:, :n, : coef.shape[2]], coef),
            "stop_residual": stop}


def _setup(ctx: Context, rng_fields):
    """Network, fields, readings and the problem they make."""
    cfg = ctx.cfg
    net = ctx.net if ctx.net is not None else deploy.network(cfg)
    fields = deploy.Fields(cfg["fields"], rng_fields, cfg["noise"])
    ys = fields.readings(net.pos, rng_fields)
    return net, fields, ys, deploy.problem(net, ys)


def _train(cfg: dict, prob):
    """Set-up training to convergence; returns (problem, state, sweeps)."""
    import jax

    from repro.core import init_state, monitor

    prob, state, rc = monitor.watch_sweeps(prob, init_state(prob), config=_watch_config(cfg))
    jax.block_until_ready(state)
    if not bool(np.all(rc.converged)) or rc.retries or rc.rolled_back:
        raise RuntimeError(f"set-up training did not converge cleanly: {rc}")
    return prob, state, int(rc.sweeps)


def _lanes(net) -> np.ndarray:
    """(B, n) occupied lanes per live sensor and field before any arrival."""
    deg = adjacency(net.pos, net.radius).sum(1)
    return np.broadcast_to(deg, (net.fields, net.n)).astype(np.int64)


# -- open loop: the served daemon ---------------------------------------------


def _warm_daemon(daemon_cls, prob, state, dcfg, plan, net, box):
    """Compile every serving bucket and absorb window on a scratch daemon
    that shares the (immutable) trained arrays, then drop it."""
    from repro.core import streaming
    from repro.kernels.ops import bucket_rows

    _warm_daemon_serving(daemon_cls, prob, state, dcfg, plan, net, box)
    rng = np.random.default_rng(0)
    d = daemon_cls(prob, state, config=dcfg, plan=plan)
    for a in range(1, dcfg.arrival_rows + 1):  # every window's padding
        s = np.zeros(a, np.int32)
        pad = a if a == dcfg.arrival_rows else min(bucket_rows(a), dcfg.arrival_rows)
        streaming.pad_arrivals(prob, s, s, net.pos[s], np.zeros(a, np.float32), pad)
    a_sizes = sorted({min(bucket_rows(a), dcfg.arrival_rows)
                      for a in range(1, dcfg.arrival_rows + 1)})
    for a in a_sizes:
        s = rng.integers(0, net.n, size=a)
        d.offer_arrivals(rng.integers(0, net.fields, size=a), s, net.pos[s],
                         rng.normal(size=a).astype(np.float32))
        d.tick()
    d.tick()
    del d
    gc.collect()


def open_loop(ctx: Context):
    from repro.core import make_serving_plan
    from repro.launch.daemon import Daemon

    cfg, mix = ctx.cfg, ctx.mix
    rng_fields, rng_req, rng_arr, rng_check = deploy.streams(ctx.seed, 4)
    spans = Spans()
    rec = Record(fields=cfg["fields"])
    if ctx.reuse:
        net, fields, ys, prob, state, setup_sweeps = ctx.reuse["trained"]
    else:
        net, fields, ys, prob = _setup(ctx, rng_fields)
        prob, state, setup_sweeps = _train(cfg, prob)
        if ctx.reuse is not None:
            ctx.reuse["trained"] = (net, fields, ys, prob, state, setup_sweeps)
    dcfg = _daemon_config(cfg, ctx.daemon)
    plan = make_serving_plan(prob, k=cfg["k"])
    box = deploy.query_box(net)
    _warm_daemon(Daemon, prob, state, dcfg, plan, net, box)
    d = Daemon(prob, state, config=dcfg, plan=plan)
    del prob, state
    # a traced run traces the settled loop, after the first third of the
    # window (the climb from an empty queue), and offers traffic until the
    # trace ends: the profiler's stop takes tens of seconds, and requests
    # falling due behind it would fill the queue and shed
    trace_from = ctx.seconds / 3 if ctx.trace_s else 0.0
    seconds = trace_from + ctx.trace_s if ctx.trace_s else ctx.seconds
    req = traffic.open_requests(mix, seconds, rng_req, box, net.pos)
    arr = traffic.reports(cfg, net.pos, fields, seconds, rng_arr)
    n_req, n_arr = len(req.due), len(arr.due)
    checked = set(rng_check.choice(n_req, size=min(mix["check_requests"], n_req),
                                   replace=False).tolist())

    latency = np.full(n_req, np.nan)
    admitted = np.zeros(n_req, bool)
    fresh = np.full(n_arr, np.nan)
    kept = {}  # request -> (version, values)
    ticks = []  # (first arrival, end arrival, sweeps, published, version, escalated)
    pending: list = []  # arrivals absorbed but not yet published
    id_of = {}  # daemon query id -> request index

    window = Window(ctx, spans, trace_from)
    rec.setup_s = time.perf_counter() - ctx.t_start
    t0 = window.open()
    i_req = i_arr = 0

    def release(limit: float):
        nonlocal i_req, i_arr
        with spans("submit"):
            while i_req < n_req and req.due[i_req] <= limit:
                tk = d.submit(req.queries[i_req], now=t0 + req.due[i_req])
                admitted[i_req] = tk.admitted
                id_of[tk.id] = i_req
                i_req += 1
        first = i_arr
        while i_arr < n_arr and arr.due[i_arr] <= limit:
            i_arr += 1
        if i_arr > first:
            sl = slice(first, i_arr)
            with spans("offer"):
                d.offer_arrivals(arr.fields[sl], arr.sensors[sl], arr.xs[sl], arr.ys[sl])
        return first

    def serve_and_train():
        with spans("pump") as info:
            t_a = time.perf_counter()
            answers = d.pump()
            # the answers are in the caller's hands: the benchmark's clock
            t_b = time.perf_counter()
            info["rows"] = int(sum(a.values.shape[1] for a in answers))
        rec.pumps.append((t_a, t_b, len(answers), info["rows"]))
        for a in answers:
            i = id_of.pop(a.id)
            latency[i] = t_b - (t0 + req.due[i])
            if i in checked:
                kept[i] = (a.version, np.asarray(a.values))
        return answers

    def tick(first: int):
        with spans("tick"):
            rc = d.tick()
        t_pub = time.perf_counter()
        wd = rc.watchdog
        escalated = bool(wd.retries or wd.refactorized or wd.rolled_back)
        ticks.append((first, i_arr, int(wd.sweeps), rc.published, rc.version, escalated))
        absorbed = list(range(first, i_arr))[: rc.absorbed]
        pending.extend(absorbed)
        if rc.published:
            for j in pending:
                fresh[j] = t_pub - (t0 + arr.due[j])
            pending.clear()

    while True:
        el = window.poll()
        if el >= seconds:
            break
        first = release(el)
        serve_and_train()
        tick(first)
    window.close(rec)
    # drain: everything due in the window is released, answered and
    # published, late but counted
    first = release(seconds)
    serve_and_train()
    tick(first)
    close = time.perf_counter()
    final_version = d.snapshot.version
    final_z = np.asarray(d.snapshot.state.z[:, : net.n])
    rec.notes["ticks"] = len(ticks)
    rec.notes["requests"] = n_req
    rec.notes["arrivals"] = n_arr

    failed_req = ~admitted | np.isnan(latency)
    lat = np.where(failed_req, close - (t0 + req.due), latency)
    failed_arr = np.isnan(fresh)
    fr = np.where(failed_arr, close - (t0 + arr.due), fresh)
    rec.latencies_ms = (lat * 1e3).tolist()
    rec.freshness_s = fr.tolist()
    rec.attempted = n_req + n_arr
    rec.failed = int(failed_req.sum() + failed_arr.sum())
    rec.spans = spans.items
    rec.notes["failed_requests"] = int(failed_req.sum())
    rec.notes["failed_arrivals"] = int(failed_arr.sum())
    d = None  # the daemon and its snapshots go before the replay

    def check() -> dict:
        per_pair = np.zeros((net.fields, net.n), np.int64)
        np.add.at(per_pair, (arr.fields, arr.sensors), 1)
        ref = Reference(net.pos, net.radius, cfg["gamma"], cfg["lambda"], ys,
                        lanes=int(per_pair.max()))
        ref.sweeps(setup_sweeps)
        by_version: dict = {}
        for i, (v, vals) in kept.items():
            by_version.setdefault(v, []).append((i, vals))
        gaps, scale, ties = [], [], 0

        def compare(version):
            nonlocal ties
            items = by_version.pop(version, [])
            if not items:
                return
            xq = np.concatenate([req.queries[i] for i, _ in items])
            want, tie = ref.answer(xq, cfg["k"])
            got = np.concatenate([vals for _, vals in items], axis=1)
            keep = ~tie
            ties += int(tie.sum())
            gaps.append(np.max(np.abs(got[:, keep] - want[:, keep]), initial=0.0))
            scale.append(np.max(np.abs(want[:, keep]), initial=0.0))

        compare(0)
        escalations = 0
        final_ref = ref.messages() if final_version == 0 else None
        for first, end, sweeps, published, version, escalated in ticks:
            escalations += escalated
            sl = slice(first, end)
            ref.absorb(arr.fields[sl], arr.sensors[sl], arr.xs[sl], arr.ys[sl])
            ref.sweeps(sweeps)
            if published:
                compare(version)
                if version == final_version:
                    final_ref = ref.messages()
        unmatched = sum(len(v) for v in by_version.values())
        ans_gap = float(max(gaps, default=0.0) / max(max(scale, default=0.0), 1e-30))
        out = {
            "answer_gap": ans_gap,
            "message_gap": 1.0 if final_ref is None else _gap(final_z, final_ref),
            "watchdog_escalations": float(escalations),
            "answers_unmatched": float(unmatched),
        }
        rec.notes["answers_compared"] = len(kept) - unmatched
        rec.notes["near_ties_skipped"] = ties
        rec.notes["reference_blocks"] = len(ref.blocks)
        return out

    lanes = _lanes(net)
    rec.serve_work = lambda rows: work.serve(rows, net.fields, cfg["k"], lanes,
                                             net.pos.shape[1], net.n)
    return rec, check


# -- closed loop: clients that wait for their answers --------------------------


def closed_loop(ctx: Context):
    from repro.core import make_serving_plan
    from repro.launch.daemon import Daemon

    cfg, mix = ctx.cfg, ctx.mix
    rng_fields, rng_req, rng_check = deploy.streams(ctx.seed, 3)
    spans = Spans()
    rec = Record(fields=cfg["fields"])
    net, fields, ys, prob = _setup(ctx, rng_fields)
    prob, state, setup_sweeps = _train(cfg, prob)
    dcfg = _daemon_config(cfg, ctx.daemon)
    plan = make_serving_plan(prob, k=cfg["k"])
    box = deploy.query_box(net)
    _warm_daemon_serving(Daemon, prob, state, dcfg, plan, net, box)
    d = Daemon(prob, state, config=dcfg, plan=plan)
    del prob, state
    clients = int(mix["clients"])
    quota = int(mix["check_requests"])
    kept: list = []  # reservoir of (xq, values)
    seen = 0
    lat: list = []
    rows_done = 0
    owner = {}

    window = Window(ctx, spans)
    rec.setup_s = time.perf_counter() - ctx.t_start
    t0 = window.open()

    def send(now):
        xq = traffic.closed_request(mix, rng_req, box, net.pos)
        tk = d.submit(xq, now=now)
        if not tk.admitted:
            raise RuntimeError("a closed-loop request was shed")
        owner[tk.id] = (xq, now)

    with spans("submit"):
        for _ in range(clients):
            send(time.perf_counter())
    while True:
        el = window.poll()
        if el >= ctx.seconds:
            break
        with spans("pump") as info:
            answers = d.pump()
            t_back = time.perf_counter()
            info["rows"] = int(sum(a.values.shape[1] for a in answers))
        with spans("submit"):
            for a in answers:
                xq, sent = owner.pop(a.id)
                lat.append((t_back - sent) * 1e3)
                rows_done += xq.shape[0]
                seen += 1
                if len(kept) < quota:
                    kept.append((xq, np.asarray(a.values)))
                else:
                    j = int(rng_check.integers(0, seen))
                    if j < quota:
                        kept[j] = (xq, np.asarray(a.values))
                send(time.perf_counter())
    window.close(rec)
    with spans("pump"):
        answers = d.pump()  # the requests outstanding at the close
        t_back = time.perf_counter()
        for a in answers:
            lat.append((t_back - owner.pop(a.id)[1]) * 1e3)
    rec.rows_answered = rows_done
    rec.latencies_ms = lat
    rec.attempted = seen + len(owner)
    rec.failed = 0
    rec.spans = spans.items
    rec.notes["requests"] = seen
    d = None

    def check() -> dict:
        ref = Reference(net.pos, net.radius, cfg["gamma"], cfg["lambda"], ys)
        ref.sweeps(setup_sweeps)
        xq = np.concatenate([x for x, _ in kept])
        got = np.concatenate([v for _, v in kept], axis=1)
        want, tie = ref.answer(xq, cfg["k"])
        keep = ~tie
        rec.notes["answers_compared"] = len(kept)
        rec.notes["near_ties_skipped"] = int(tie.sum())
        rec.notes["reference_blocks"] = len(ref.blocks)
        return {"answer_gap": _gap(got[:, keep], want[:, keep])}

    lanes = _lanes(net)
    rec.serve_work = lambda rows: work.serve(rows, net.fields, cfg["k"], lanes,
                                             net.pos.shape[1], net.n)
    return rec, check


def _warm_daemon_serving(daemon_cls, prob, state, dcfg, plan, net, box):
    """Compile every serving bucket on a scratch daemon (no ticks)."""
    from repro.kernels.ops import bucket_rows

    rng = np.random.default_rng(0)
    d = daemon_cls(prob, state, config=dcfg, plan=plan)
    for r in sorted({bucket_rows(r) for r in range(1, dcfg.max_batch_rows + 1)}):
        d.submit(rng.uniform(box[0], box[1], size=(r, net.pos.shape[1])).astype(np.float32))
        d.pump()
    del d
    gc.collect()


# -- solve: repeated solves from the initial state ------------------------------


def solve_loop(ctx: Context):
    import jax

    from repro.core import init_state, monitor

    cfg, mix = ctx.cfg, ctx.mix
    rng_fields, rng_check = deploy.streams(ctx.seed, 2)
    spans = Spans()
    rec = Record(fields=cfg["fields"])
    net = ctx.net if ctx.net is not None else deploy.network(cfg)
    fields = deploy.Fields(cfg["fields"], rng_fields, cfg["noise"])
    batches = [fields.readings(net.pos, rng_fields) for _ in range(int(mix["batches"]))]
    base = deploy.problem(net, batches[0])
    probs = [deploy.with_readings(base, ys) for ys in batches]
    del base
    wcfg = _watch_config(cfg)

    def solve(p, config=wcfg):
        _, st, rc = monitor.watch_sweeps(p, init_state(p), config=config)
        jax.block_until_ready(st)
        return st, rc

    # warm-up: one round compiles the round program and its checks
    solve(probs[0], dataclasses.replace(wcfg, max_rounds=1))
    kept: dict = {}
    solves = []
    window = Window(ctx, spans)
    rec.setup_s = time.perf_counter() - ctx.t_start
    t0 = window.open()
    if window.tracing:
        # the traced segment: the first trace_rounds rounds of a solve,
        # through the same call (a whole solve can outlast any trace)
        capped = dataclasses.replace(wcfg, max_rounds=int(mix["trace_rounds"]))
        with spans("solve") as info:
            _, rc = solve(probs[0], capped)
            info["sweeps"] = int(rc.sweeps)
        window.stop()
    i = 0
    while not solves or window.poll() < ctx.seconds:  # at least one whole solve
        b = i % len(probs)
        with spans("solve", batch=b) as info:
            t_a = time.perf_counter()
            st, rc = solve(probs[b])
            t_b = time.perf_counter()
            info["sweeps"] = int(rc.sweeps)
        ok = bool(np.all(rc.converged)) and not rc.rolled_back
        solves.append((t_a, t_b, int(rc.sweeps), ok))
        # the last solve and one drawn from the seed (reservoir of one)
        if i == 0 or int(rng_check.integers(0, i + 1)) == 0:
            kept["drawn"] = (i, b, int(rc.sweeps), st.z, st.coef)
        kept["last"] = (i, b, int(rc.sweeps), st.z, st.coef)
        i += 1
    window.close(rec)
    rec.solves = solves
    rec.attempted = len(solves)
    rec.failed = sum(not s[3] for s in solves)
    rec.spans = spans.items
    probs = None

    def check() -> dict:
        out = {}
        for _, b, sweeps, z, c in {v[0]: v for v in kept.values()}.values():
            gaps = solve_gaps(net, cfg, batches[b], sweeps, z, c)
            out = {k: max(v, out.get(k, v)) for k, v in gaps.items()}
        rec.notes["solves_compared"] = len({v[0] for v in kept.values()})
        return out

    rec.sweep_work = work.sweep(_lanes(net), net.n)
    return rec, check


DRIVERS = {"open": open_loop, "closed": closed_loop, "solve": solve_loop}
