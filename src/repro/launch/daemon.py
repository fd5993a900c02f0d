"""Always-on serving daemon: snapshot-isolated queries under a supervised
trainer.

The paper's SOP trainer is an ongoing message-passing process, not a
batch job — sensors keep measuring (cs/0507039 Sec. 4), links keep
dropping (the cs/0601089 operating regime), and queries arrive while
training is mid-sweep.  ``serve.py --mode field`` replays that pipeline
once and exits; this module is the long-lived process production needs,
built entirely from machinery earlier PRs already landed:

  queue      arriving queries coalesce into the power-of-two buckets of
             ``kernels.ops.bucket_rows`` (O(log Q) compiled programs for
             any request-size mix), behind a BOUNDED queue with
             admission-control backpressure: when the estimated wait
             exceeds the deadline budget the request is shed at submit
             time with an explicit receipt (the ``AbsorbReceipt``
             pattern — pressure is observable, never silent).

  snapshot   every query reads a DOUBLE-BUFFERED coefficient snapshot:
             an immutable (problem, state, plan, effective_coef) tuple.
             Queries serve from snapshot t while sweeps/absorbs/churn
             build t+1 on separate (functionally-updated) buffers; the
             publish is one Python reference flip, which the plan/alive
             split already makes safe — a wedged, retrying, or diverging
             trainer can never block or corrupt a query.

  supervise  every training tick runs through ``monitor.watch_sweeps``:
             its receipt IS the health endpoint
             (``WatchdogReceipt.to_json``), divergence climbs the
             existing retry -> refactorize -> rollback ladder, and a tick
             that ends rolled-back or diverged simply isn't published —
             the daemon keeps serving the last good snapshot (graceful
             degradation) and restores the trainer's working copy from
             it.  Fault drills come from ``core.faults``: the drop rates
             are TRACED operands of one compiled program, so drills and
             recovery never compile anything.

  restart    ``checkpoint.save_train`` snapshots the PUBLISHED state
             every ``ckpt_every`` ticks; on construction the daemon
             restores the latest INTACT step (``checkpoint.latest_step``
             verifies npz integrity, so a crash mid-save is skipped) —
             crash-kill -> warm restart resumes bitwise.

Concurrency model: the daemon is a cooperative state machine —
``pump()`` drains queries, ``tick()`` advances training — which is what
the bench and tests drive deterministically.  Because a published
snapshot is immutable and the flip is a single reference assignment
(atomic under the GIL), a threaded deployment may run ``pump`` and
``tick`` on separate threads without locks around the read path; the
cooperative loop is the same code with the interleaving made explicit.

CLI (used by the CI kill-and-warm-restart smoke):

  PYTHONPATH=src python -m repro.launch.daemon --sensors 40 --fields 3 \
      --ticks 20 --ckpt-every 1 --snapshot-dir /tmp/snap
  # SIGKILL it mid-run, then:
  PYTHONPATH=src python -m repro.launch.daemon --sensors 40 --fields 3 \
      --ticks 0 --snapshot-dir /tmp/snap --verify-restart
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import deque
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import (
    fusion,
    make_serving_plan,
    monitor,
    pruning,
    streaming,
)
from repro.core import faults as faults_mod
from repro.core.serving import plan_add_sensor, plan_remove_sensor
from repro.core.sn_train import effective_coef
from repro.kernels.ops import bucket_rows
from repro.launch.cache import enable_compile_cache


@dataclasses.dataclass(frozen=True)
class DaemonConfig:
    """Host-side knobs of the serving daemon (all static)."""

    k: int = 3  # kNN fusion order served
    engine: str = "plan"  # serving engine: "plan" | "pallas"
    train_engine: str = "plan"  # sweep engine for training ticks
    queue_rows: int = 1024  # hard cap on pending query rows
    max_batch_rows: int = 256  # rows coalesced into one dispatch
    deadline_ms: float = float("inf")  # admission budget (est. wait)
    sweeps_per_tick: int = 5  # sweeps per watchdog round
    rounds_per_tick: int = 2  # watchdog rounds per tick
    watch_tol: float = 1e-3  # per-round convergence tolerance
    arrival_rows: int = 32  # max arrivals absorbed per tick window
    on_full: str = "drop"  # over-capacity arrival policy
    ckpt_every: int = 0  # ticks between checkpoints (0 = off)
    snapshot_dir: str | None = None  # warm-restart / checkpoint home
    serve_dtype: str = "f32"  # anchor storage dtype: "f32" | "bf16"
    energy_tau: float = 0.0  # representer-pruning threshold (0 = off)


class Snapshot(NamedTuple):
    """One immutable published serving state (the double buffer's face).

    ``ecoef`` is ``effective_coef(problem, state)`` materialized at
    publish time, so every query dispatch against this snapshot skips
    the per-call anchor-weight rescale (``serving.knn_fuse(ecoef=...)``).
    ``ecoef`` stays in the COEFFICIENT dtype (f32/f64) regardless of the
    serving ``serve_dtype`` — bf16 rounds the stored anchor tables only
    (selection-exact; see ``core.serving``), never the coefficients or
    the accumulated contraction.  ``keep`` is the representer-prune
    mask re-derived from this snapshot's coefficients at publish time
    (``pruning.prune_mask``; None when pruning is off): values-only, so
    per-publish re-pruning compiles nothing.
    """

    version: int
    problem: object
    state: object
    plan: object
    ecoef: jax.Array
    serve_dtype: str = "f32"
    keep: object = None  # (n+1,) bool keep mask, or None
    pruned: int = 0  # live sensors pruned out of this snapshot


class QueryTicket(NamedTuple):
    """Admission receipt, returned by ``submit`` (AbsorbReceipt pattern).

    ``admitted`` False means the query was SHED at the door —
    ``shed_reason`` says why ("queue_full": the bounded queue is at
    capacity; "deadline": the estimated wait exceeds the deadline
    budget).  Shed requests are never silently dropped from the queue.
    """

    id: int
    admitted: bool
    shed_reason: str = ""


class QueryAnswer(NamedTuple):
    """One served query: values from the snapshot named by ``version``."""

    id: int
    values: np.ndarray  # (B, q) field estimates at the request's points
    version: int  # snapshot the answer was read from
    degraded: bool  # True: trainer unhealthy, snapshot is last-good
    latency_s: float  # submit -> answer wall time


class TickReceipt(NamedTuple):
    """What one training tick did (the health endpoint's raw material)."""

    tick: int
    published: bool  # a new snapshot went live
    degraded: bool  # trainer unhealthy; serving last good snapshot
    version: int  # currently PUBLISHED snapshot version
    absorbed: int  # arrivals absorbed this tick
    arrival_drops: int  # arrivals dropped by capacity pressure
    arrivals_rolled_back: int  # absorbed arrivals lost to a rollback
    joins: int
    leaves: int
    watchdog: monitor.WatchdogReceipt
    ckpt_step: int | None  # checkpoint written this tick (None: none)

    def to_json(self) -> dict:
        """Plain-JSON receipt (the /health payload's per-tick record)."""
        return {
            "schema": "tick_receipt/1",
            "tick": int(self.tick),
            "published": bool(self.published),
            "degraded": bool(self.degraded),
            "version": int(self.version),
            "absorbed": int(self.absorbed),
            "arrival_drops": int(self.arrival_drops),
            "arrivals_rolled_back": int(self.arrivals_rolled_back),
            "joins": int(self.joins),
            "leaves": int(self.leaves),
            "watchdog": self.watchdog.to_json(),
            "ckpt_step": None if self.ckpt_step is None else int(self.ckpt_step),
        }


class _Inflight(NamedTuple):
    """One dispatch launched by ``pump`` and not yet collected."""

    batch: list  # the coalesced requests: (id, xq, t_submit)
    rows: int  # real query rows (the output's padded columns are dropped)
    out: jax.Array  # (B, bucket) answers, copying to the host


_ecoef_jit = jax.jit(jax.named_scope("ecoef")(effective_coef))


def _state_digest(problem, state) -> str:
    """Order-stable sha256 over every problem/state leaf (bitwise id)."""
    h = hashlib.sha256()
    for leaf in jax.tree.leaves({"problem": problem, "state": state}):
        arr = np.asarray(leaf)
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


class Daemon:
    """Long-lived field-serving process; see the module docstring.

    problem/state: a BATCHED ``SNTrainProblem``/``SNTrainState`` pair —
    the live templates for warm restart (array leaves are replaced by
    the restored snapshot; statics carry over).  plan: a prebuilt
    ``ServingPlan`` (default: ``make_serving_plan(problem, k=config.k)``
    — pass one built with ``spare=``/``slack=`` when churn events will
    arrive).  fault_model: the link-fault process training ticks run
    under; defaults to ``make_fault_model(0.0)`` rather than None so the
    fault-free and drilled paths share ONE compiled program (rates are
    traced operands) — ``set_fault_model`` swaps rates without a single
    recompile.
    """

    def __init__(
        self,
        problem,
        state,
        *,
        config: DaemonConfig = DaemonConfig(),
        plan=None,
        fault_model: faults_mod.FaultModel | None = None,
        key: jax.Array | None = None,
    ):
        if not problem.batched:
            raise ValueError("the daemon serves batched problems (use B=1)")
        if config.on_full not in ("drop", "evict"):
            raise ValueError(f"bad on_full {config.on_full!r}")
        if config.serve_dtype not in ("f32", "bf16"):
            raise ValueError(f"bad serve_dtype {config.serve_dtype!r}")
        self.config = config
        # "f32" means the problem's native dtype (f64 problems serve f64);
        # bf16 rounds the stored anchor tables only (selection-exact).
        self._compute_dtype = (
            None if config.serve_dtype == "f32" else config.serve_dtype
        )
        self._energy_tau = float(config.energy_tau)
        self.restored_step: int | None = None
        if config.snapshot_dir is not None:
            from repro import checkpoint as ckpt

            step = ckpt.latest_step(config.snapshot_dir)  # verified intact
            if step is not None:
                problem, state = ckpt.restore_train(
                    config.snapshot_dir, step, problem, state
                )
                self.restored_step = step
        self._work = (problem, state)
        self._plan = (
            plan if plan is not None
            else make_serving_plan(problem, k=config.k)
        )
        self._model = (
            fault_model if fault_model is not None
            else faults_mod.make_fault_model(0.0)
        )
        self._key = jax.random.PRNGKey(0) if key is None else key
        self._watch_cfg = monitor.WatchdogConfig(
            sweeps_per_round=config.sweeps_per_tick,
            tol=config.watch_tol,
            max_rounds=config.rounds_per_tick,
        )
        # queues (host-side; bounded by admission control)
        self._queries: deque = deque()  # (id, xq np, t_submit)
        self._pending_rows = 0
        self._arrivals: deque = deque()  # (field, sensor, x, y)
        self._events: deque = deque()  # ("join", x, ys, lam) | ("leave", s)
        # stats / receipts
        self._next_id = 0
        self.tick_count = 0
        self.served = 0
        self.shed = 0
        self.degraded = False
        self.last_tick: TickReceipt | None = None
        # always-on counters of the query and arrival paths (``health``)
        self._counters = {
            "dispatches": {},  # str(bucket rows) -> dispatches
            "rows": 0,  # query rows dispatched
            "padded_rows": 0,  # rows after bucket padding
            "queue_wait_s_sum": 0.0,  # submit -> start of its dispatch
            "queue_wait_n": 0,
            "queue_wait_s_max": 0.0,
            "serve_traces": 0,  # jaxpr traces while launching dispatches
            "pumps": 0,  # pumps that launched at least one dispatch
            "arrival_rows": 0,  # arrivals taken into absorb windows
            "arrival_padded_rows": 0,  # window rows after padding
        }
        self._ema_batch_s: float | None = None
        # initial publish: version 0 serves the (possibly restored) state
        self._snap = self._make_snapshot(0, problem, state, self._plan)

    # -- snapshot plumbing -------------------------------------------------

    def _make_snapshot(self, version, problem, state, plan) -> Snapshot:
        ecoef = _ecoef_jit(problem, state)
        ecoef.block_until_ready()  # publish COMPLETE buffers only
        keep = None
        pruned = 0
        if self._energy_tau > 0.0:
            # Re-prune on EVERY publish: fresh coefficients (beta decay,
            # absorbs, churn) move sensor energies, and tau is a traced
            # operand of one compiled program — zero recompiles per
            # publish or per set_energy_tau change.
            keep = pruning.prune_mask(
                problem, ecoef=ecoef, energy_tau=self._energy_tau
            )
            keep.block_until_ready()
            n = problem.n
            pruned = int(
                np.asarray(problem.alive[:n]).astype(bool).sum()
                - np.asarray(keep[:n]).sum()
            )
        return Snapshot(
            version, problem, state, plan, ecoef,
            serve_dtype=self.config.serve_dtype, keep=keep, pruned=pruned,
        )

    def set_energy_tau(self, tau: float) -> None:
        """Change the pruning threshold; takes effect at the next publish.

        Values-only (the prune-mask program traces tau), so sweeping tau
        on a live daemon never compiles anything.
        """
        self._energy_tau = float(tau)

    @property
    def snapshot(self) -> Snapshot:
        """The currently published snapshot (immutable; safe to hold)."""
        return self._snap

    # -- query path --------------------------------------------------------

    def submit(self, xq, now: float | None = None) -> QueryTicket:
        """Enqueue a query grid (q, d); sheds instead of queueing unbounded.

        Admission control: a request is rejected when the queue is at
        ``queue_rows`` capacity, or when the estimated wait — pending
        dispatches times the EMA dispatch latency — exceeds
        ``deadline_ms``.  The ticket records the outcome; an admitted
        request is answered by a later ``pump`` with its latency stamped
        from this submit time.
        """
        now = time.perf_counter() if now is None else now
        xq = np.atleast_2d(np.asarray(xq))
        qid = self._next_id
        self._next_id += 1
        rows = xq.shape[0]
        cfg = self.config
        if self._pending_rows + rows > cfg.queue_rows:
            self.shed += 1
            return QueryTicket(qid, False, "queue_full")
        if self._ema_batch_s is not None and np.isfinite(cfg.deadline_ms):
            batches_ahead = 1 + self._pending_rows // cfg.max_batch_rows
            est_wait_ms = batches_ahead * self._ema_batch_s * 1e3
            if est_wait_ms > cfg.deadline_ms:
                self.shed += 1
                return QueryTicket(qid, False, "deadline")
        self._queries.append((qid, xq, now))
        self._pending_rows += rows
        return QueryTicket(qid, True)

    def pump(self) -> list[QueryAnswer]:
        """Drain the query queue against the published snapshot.

        Requests coalesce front-to-back into dispatches of at most
        ``max_batch_rows`` rows; each dispatch pads its row count to the
        power-of-two bucket (``bucket_rows``), so ANY interleaving of
        request sizes lowers O(log max_batch_rows) distinct programs
        (tests/test_daemon.py property-tests this with the jit cache).
        Every answer of one pump is read from one immutable snapshot — a
        concurrent ``tick`` can flip the pointer mid-drain and in-flight
        dispatches still see their snapshot's buffers.

        The drain runs in two phases, so the host never waits on one
        dispatch before packing the next.  Launch: pack and launch every
        queued dispatch, starting each output's copy to the host at once.
        Collect: in launch order, wait for each output, fetch it and
        answer its requests.  The answers are those of a one-at-a-time
        drain bitwise (same programs, same operands); only where the host
        waits changes.  The pipeline is as deep as the queue is when the
        pump starts (in the cooperative loop nothing joins the queue
        during a drain), so ``queue_rows`` bounds its device memory: the
        in-flight outputs hold B values per padded row of the at most
        ``queue_rows`` rows queued.
        """
        answers: list[QueryAnswer] = []
        with obs.span("daemon.pump") as sp:
            t_pump = sp.stamp()
            snap = self._snap
            inflight = []
            while self._queries:
                inflight.append(self._launch(snap))
            for disp in inflight:
                done_ns = self._collect(snap, disp, answers)
            if inflight:
                self._counters["pumps"] += 1
                # One dispatch's service time is the drain's mean: in a
                # launch-bound drain the later dispatches are done before
                # the host collects them, so no per-dispatch stamp
                # measures them.  It enters the EMA once per dispatch, as
                # the serial drain's per-dispatch updates did.
                n = len(inflight)
                dt = (done_ns - t_pump) * 1e-9 / n
                w = 0.8 ** n
                self._ema_batch_s = (
                    dt if self._ema_batch_s is None
                    else w * self._ema_batch_s + (1.0 - w) * dt
                )
            sp["requests"] = len(answers)
            sp["rows"] = sum(d.rows for d in inflight)
        return answers

    def _launch(self, snap: Snapshot) -> _Inflight:
        """Pack one coalesced dispatch off the queue's front and launch it
        without waiting for it."""
        c = self._counters
        with obs.span("serve.dispatch") as sp:
            t_disp = sp.stamp()
            with obs.span("serve.pack"):
                batch = [self._queries.popleft()]
                rows = batch[0][1].shape[0]
                while (
                    self._queries
                    and rows + self._queries[0][1].shape[0]
                    <= self.config.max_batch_rows
                ):
                    nxt = self._queries.popleft()
                    batch.append(nxt)
                    rows += nxt[1].shape[0]
                self._pending_rows -= rows
                xq = np.concatenate([b[1] for b in batch], axis=0)
                q_pad = bucket_rows(rows)
                if q_pad > rows:  # padded rows are sliced off below: exact
                    xq = np.concatenate(
                        [xq, np.repeat(xq[-1:], q_pad - rows, axis=0)],
                        axis=0,
                    )
            with obs.span("serve.launch") as launch:
                traced = obs.traces()[0]
                out = fusion.fuse(
                    snap.problem, snap.state, xq, "knn",
                    k=self.config.k, engine=self.config.engine,
                    plan=snap.plan, ecoef=snap.ecoef,
                    compute_dtype=self._compute_dtype, prune=snap.keep,
                )
                out.copy_to_host_async()
                traced = obs.traces()[0] - traced
                launch["traces"] = traced
            sp["rows"] = rows
            sp["bucket"] = q_pad
            sp["requests"] = len(batch)
            sp["version"] = snap.version
        key = str(q_pad)
        c["dispatches"][key] = c["dispatches"].get(key, 0) + 1
        c["rows"] += rows
        c["padded_rows"] += q_pad
        start = t_disp * 1e-9
        for _, _, t_submit in batch:
            wait = start - t_submit
            c["queue_wait_s_sum"] += wait
            c["queue_wait_s_max"] = max(c["queue_wait_s_max"], wait)
        c["queue_wait_n"] += len(batch)
        c["serve_traces"] += traced
        return _Inflight(batch, rows, out)

    def _collect(self, snap: Snapshot, disp: _Inflight,
                 answers: list) -> int:
        """Wait for one launched dispatch, append its answers, and return
        its completion stamp (ns)."""
        with obs.span("serve.collect"):
            with obs.span("serve.wait"):
                disp.out.block_until_ready()
            with obs.span("serve.fetch") as fetch:
                done_ns = fetch.stamp()
                vals = np.asarray(disp.out)
                fetch["bytes"] = vals.nbytes
            with obs.span("serve.answer"):
                done = done_ns * 1e-9
                off = 0
                for qid, grid, t_submit in disp.batch:
                    q = grid.shape[0]
                    answers.append(QueryAnswer(
                        id=qid,
                        values=vals[:, off:off + q],
                        version=snap.version,
                        degraded=self.degraded,
                        latency_s=done - t_submit,
                    ))
                    off += q
        self.served += len(disp.batch)
        return done_ns

    # -- trainer-side inputs -----------------------------------------------

    def offer_arrivals(self, fields, sensors, xs, ys) -> None:
        """Queue measurement arrivals for the next training ticks."""
        fields = np.asarray(fields).reshape(-1)
        sensors = np.asarray(sensors).reshape(-1)
        xs = np.atleast_2d(np.asarray(xs))
        ys = np.asarray(ys).reshape(-1)
        for f, s, x, y in zip(fields, sensors, xs, ys):
            self._arrivals.append((int(f), int(s), x, float(y)))

    def offer_join(self, x, ys, lam: float) -> None:
        """Queue a sensor join (position x, per-field targets ys)."""
        self._events.append(("join", np.asarray(x), np.asarray(ys), lam))

    def offer_leave(self, slot: int) -> None:
        """Queue a sensor leave by row slot."""
        self._events.append(("leave", int(slot)))

    def set_fault_model(self, model: faults_mod.FaultModel) -> None:
        """Swap the training fault process (degraded-mode drills).

        The model's rates are traced operands of the already-compiled
        training programs, so a drill changes VALUES only — zero
        recompiles (the bench counts the caches to prove it).
        """
        if model.has_crash != self._model.has_crash:
            raise ValueError(
                "crash-model structure is static (dispatches a different "
                "program); construct the daemon with the crash model"
            )
        self._model = model

    # -- training tick -----------------------------------------------------

    def _apply_events(self, problem, state, plan):
        joins = leaves = 0
        while self._events:
            ev = self._events.popleft()
            if ev[0] == "join":
                _, x, ys, lam = ev
                problem, state, rcpt = streaming.add_sensor(
                    problem, state, x, ys, lam=lam, donate=False,
                )
                if bool(rcpt.joined):
                    plan, _ = plan_add_sensor(plan, x, rcpt.slot)
                    joins += 1
            else:
                _, slot = ev
                problem, state, ok = streaming.remove_sensor(
                    problem, state, slot, donate=False,
                )
                plan = plan_remove_sensor(plan, slot)
                leaves += int(bool(ok))
        return problem, state, plan, joins, leaves

    def _absorb_pending(self, problem, state):
        """Drain queued arrivals in bucketed windows (O(log A) programs).

        Full windows run at exactly ``arrival_rows``; the final partial
        window pads to its power-of-two bucket with sentinel-row no-op
        arrivals (``streaming.pad_arrivals`` — bitwise-inert by the
        dead-sensor gates), so any arrival-traffic shape reuses a bounded
        program set.
        """
        absorbed = dropped = 0
        w = self.config.arrival_rows
        c = self._counters
        rows0, padded0 = c["arrival_rows"], c["arrival_padded_rows"]
        with obs.span("tick.absorb") as sp:
            while self._arrivals:
                take = min(len(self._arrivals), w)
                a_pad = take if take == w else min(bucket_rows(take), w)
                with obs.span("absorb.window") as win:
                    window = [self._arrivals.popleft() for _ in range(take)]
                    fs = np.array([a[0] for a in window], np.int32)
                    ss = np.array([a[1] for a in window], np.int32)
                    xs = np.stack([a[2] for a in window]).astype(
                        problem.nbr_pos.dtype, copy=False
                    )
                    ys = np.array([a[3] for a in window])
                    fs, ss, xs, ys, real = streaming.pad_arrivals(
                        problem, fs, ss, xs, ys, a_pad
                    )
                    # donate=False ALWAYS: right after a publish the working
                    # pair aliases the published snapshot's buffers —
                    # donating them would delete the arrays queries are
                    # still reading.
                    problem, state, rec = streaming.absorb_many(
                        problem, state, fs, ss, xs, ys,
                        donate=False, on_full=self.config.on_full,
                    )
                    ok = np.asarray(rec.absorbed)[real]
                    win["rows"] = take
                    win["padded"] = a_pad
                absorbed += int(ok.sum())
                dropped += int((~ok).sum())
                c["arrival_rows"] += take
                c["arrival_padded_rows"] += a_pad
            sp["rows"] = c["arrival_rows"] - rows0
            sp["padded"] = c["arrival_padded_rows"] - padded0
        return problem, state, absorbed, dropped

    def tick(self) -> TickReceipt:
        """One supervised training advance; publishes when healthy.

        Order: churn events -> arrival absorbs -> ``watch_sweeps`` under
        the current fault model.  A healthy tick publishes a fresh
        snapshot (pointer flip) and optionally checkpoints it.  A tick
        whose watchdog rolled back restores the working copy from the
        PUBLISHED snapshot — the trainer recovers from last-good while
        queries never left it; a diverged-but-not-rolled-back tick keeps
        its working state (it may recover next tick) but does not
        publish.  Either unhealthy outcome marks the daemon degraded.
        """
        with obs.span("daemon.tick") as sp:
            rc = self._tick()
            sp["absorbed"] = rc.absorbed
            sp["published"] = rc.published
        return rc

    def _tick(self) -> TickReceipt:
        cfg = self.config
        problem, state = self._work
        plan = self._plan
        with obs.span("tick.events") as sp:
            problem, state, plan, joins, leaves = self._apply_events(
                problem, state, plan
            )
            sp["joins"] = joins
            sp["leaves"] = leaves
        problem, state, absorbed, arrival_drops = self._absorb_pending(
            problem, state
        )
        self._key, sub = jax.random.split(self._key)
        with obs.span("tick.sweeps") as sp:
            problem, state, receipt = monitor.watch_sweeps(
                problem, state, model=self._model, key=sub,
                engine=cfg.train_engine, config=self._watch_cfg,
            )
            sp["rounds"] = receipt.rounds
            sp["sweeps"] = receipt.sweeps
        self.tick_count += 1
        arrivals_rolled_back = 0
        ckpt_step = None
        if receipt.rolled_back:
            # watch_sweeps restored its entry state (post-absorb) bitwise,
            # but that state is what diverged past recovery — fall back to
            # the last PUBLISHED snapshot, losing this tick's inputs
            # (counted, not silent).
            snap = self._snap
            problem, state, plan = snap.problem, snap.state, snap.plan
            arrivals_rolled_back = absorbed
            absorbed = 0
            joins = leaves = 0
            self.degraded = True
            published = False
        elif bool(np.asarray(receipt.diverged).any()):
            self.degraded = True  # keep training state; serve last good
            published = False
        else:
            self.degraded = False
            published = True
            with obs.span("tick.publish"):
                self._snap = self._make_snapshot(
                    self._snap.version + 1, problem, state, plan
                )
            if (
                cfg.ckpt_every
                and cfg.snapshot_dir is not None
                and self.tick_count % cfg.ckpt_every == 0
            ):
                from repro import checkpoint as ckpt

                ckpt.save_train(
                    cfg.snapshot_dir, self.tick_count, problem, state
                )
                ckpt_step = self.tick_count
        self._work = (problem, state)
        self._plan = plan
        self.last_tick = TickReceipt(
            tick=self.tick_count,
            published=published,
            degraded=self.degraded,
            version=self._snap.version,
            absorbed=absorbed,
            arrival_drops=arrival_drops,
            arrivals_rolled_back=arrivals_rolled_back,
            joins=joins,
            leaves=leaves,
            watchdog=receipt,
            ckpt_step=ckpt_step,
        )
        return self.last_tick

    # -- health ------------------------------------------------------------

    def health(self) -> dict:
        """Machine-readable health endpoint (plain-JSON types only).

        ``counters`` are running totals since construction: dispatches
        per padded bucket (``{str(rows): count}``), query ``rows`` and
        ``padded_rows`` (bucket fill = their ratio), the submit -> dispatch
        queue wait (``queue_wait_s_sum`` / ``_n`` / ``_max``), the jaxpr
        traces the dispatches caused (``serve_traces``; a warmed path that
        keeps tracing retraces per call), the non-empty ``pumps`` (every
        dispatch of a pump but its first is launched while an earlier one
        is in flight, so 1 - pumps / Σ ``dispatches`` is the share the
        pipeline overlapped), and ``arrival_rows`` /
        ``arrival_padded_rows`` over the absorb windows.
        """
        t = self.last_tick
        return {
            "schema": "daemon_health/1",
            "version": int(self._snap.version),
            "degraded": bool(self.degraded),
            "ticks": int(self.tick_count),
            "served": int(self.served),
            "shed": int(self.shed),
            "queue_rows": int(self._pending_rows),
            "queued_arrivals": len(self._arrivals),
            "restored_step": self.restored_step,
            "serve_dtype": self.config.serve_dtype,
            "energy_tau": float(self._energy_tau),
            "pruned": int(self._snap.pruned),
            "counters": {
                **self._counters,
                "dispatches": dict(self._counters["dispatches"]),
            },
            "last_tick": None if t is None else {
                "tick": t.tick,
                "published": t.published,
                "absorbed": t.absorbed,
                "arrival_drops": t.arrival_drops,
                "arrivals_rolled_back": t.arrivals_rolled_back,
                "joins": t.joins,
                "leaves": t.leaves,
                "ckpt_step": t.ckpt_step,
                "watchdog": t.watchdog.to_json(),
            },
        }

    def state_digest(self) -> str:
        """sha256 of the PUBLISHED snapshot's leaves (bitwise identity)."""
        return _state_digest(self._snap.problem, self._snap.state)


# ---------------------------------------------------------------------------
# CLI: the real long-lived process (and the CI kill/warm-restart smoke)
# ---------------------------------------------------------------------------


def _build_problem(args):
    """Deterministic problem build shared by cold start AND warm restart.

    Everything derives from ``--seed``; a restarted process rebuilds the
    same shapes/statics as templates and ``checkpoint.restore_train``
    replaces the array leaves bitwise.
    """
    from repro.core import Kernel, build_topology, init_state, \
        make_batch_problem, uniform_sensors

    rng = np.random.default_rng(args.seed)
    pos = uniform_sensors(args.sensors, seed=args.seed)
    freq = rng.uniform(0.5, 2.0, size=(args.fields, 1))
    phase = rng.uniform(0, 2 * np.pi, size=(args.fields, 1))
    ys = (
        np.sin(np.pi * freq * pos[None, :, 0] + phase)
        + 0.1 * rng.normal(size=(args.fields, args.sensors))
    ).astype(np.float32)
    topo = build_topology(pos, args.radius)
    per_sensor = -(-max(args.arrivals_per_tick, 1) // args.sensors) + 4
    deg_max = int(np.asarray(topo.degrees).max()) + per_sensor
    topo = build_topology(pos, args.radius, d_max=deg_max)
    prob = make_batch_problem(
        topo, Kernel("rbf", gamma=args.gamma), ys,
        jnp.full((args.sensors,), args.lam),
    )
    return pos, prob, init_state(prob), rng


def _probe_grid(args):
    xq = np.linspace(-0.9, 0.9, args.probe_points)[:, None].astype(np.float32)
    return xq


def main(argv=None):
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--fields", type=int, default=4)
    ap.add_argument("--sensors", type=int, default=40)
    ap.add_argument("--radius", type=float, default=0.5)
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--lam", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--engine", default="plan", choices=["plan", "pallas"])
    ap.add_argument("--serve-dtype", default="f32", choices=["f32", "bf16"],
                    help="anchor-table storage dtype (bf16 rounds stored "
                         "anchors only; selection and accumulation stay "
                         "full precision)")
    ap.add_argument("--energy-tau", type=float, default=0.0,
                    help="representer-pruning energy threshold, re-derived "
                         "per publish (0 = off)")
    ap.add_argument("--ticks", type=int, default=10,
                    help="training ticks to run (0: restart-verify only)")
    ap.add_argument("--queries-per-tick", type=int, default=2)
    ap.add_argument("--query-rows", type=int, default=48)
    ap.add_argument("--arrivals-per-tick", type=int, default=8)
    ap.add_argument("--sweeps-per-tick", type=int, default=5)
    ap.add_argument("--snapshot-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--faults", default="",
                    help="fault spec for training ticks (core.faults "
                         "syntax, e.g. drop=0.1)")
    ap.add_argument("--probe-points", type=int, default=32)
    ap.add_argument("--tick-sleep", type=float, default=0.0,
                    help="seconds to sleep between ticks (makes a "
                         "mid-run SIGKILL land mid-stream in CI)")
    ap.add_argument("--verify-restart", action="store_true",
                    help="after warm restart, assert the restored "
                         "snapshot matches the last checkpoint's probe "
                         "answers + state digest bitwise, then exit")
    args = ap.parse_args(argv)
    enable_compile_cache()

    pos, prob, state, rng = _build_problem(args)
    cfg = DaemonConfig(
        k=args.k, engine=args.engine,
        sweeps_per_tick=args.sweeps_per_tick,
        ckpt_every=args.ckpt_every, snapshot_dir=args.snapshot_dir,
        serve_dtype=args.serve_dtype, energy_tau=args.energy_tau,
    )
    model = (
        faults_mod.parse_fault_spec(args.faults, dtype=state.z.dtype)
        if args.faults else None
    )
    if model is not None and model.has_crash:
        d = Daemon(prob, state, config=cfg, fault_model=model)
    else:
        d = Daemon(prob, state, config=cfg)
        if model is not None:
            d.set_fault_model(model)
    if d.restored_step is not None:
        print(f"warm restart: restored step {d.restored_step} from "
              f"{args.snapshot_dir}")

    probe = _probe_grid(args)

    def probe_answers():
        snap = d.snapshot
        out = fusion.fuse(
            snap.problem, snap.state, probe, "knn", k=args.k,
            engine=args.engine, plan=snap.plan, ecoef=snap.ecoef,
            compute_dtype=(None if snap.serve_dtype == "f32"
                           else snap.serve_dtype),
            prune=snap.keep,
        )
        return np.asarray(out)

    if args.verify_restart:
        if d.restored_step is None:
            raise SystemExit("--verify-restart: no intact checkpoint found")
        path = os.path.join(
            args.snapshot_dir, f"probe_{d.restored_step:08d}.npz"
        )
        ref = np.load(path)
        assert ref["digest"] == d.state_digest(), (
            "restored state digest mismatch (not bitwise)"
        )
        got = probe_answers()
        assert np.array_equal(got, ref["answers"]), (
            "served probe answers differ from the pre-kill snapshot"
        )
        print(f"warm restart verified: step {d.restored_step} bitwise "
              f"(digest + {probe.shape[0]}-point probe answers)")
        return

    for i in range(args.ticks):
        for _ in range(args.queries_per_tick):
            q = int(rng.integers(1, args.query_rows + 1))
            d.submit(rng.uniform(-0.9, 0.9, size=(q, pos.shape[1]))
                     .astype(np.float32))
        a = args.arrivals_per_tick
        if a:
            ss = rng.integers(0, args.sensors, size=a)
            d.offer_arrivals(
                rng.integers(0, args.fields, size=a), ss,
                (pos[ss] + 0.05 * rng.normal(size=(a, pos.shape[1])))
                .astype(np.float32),
                rng.normal(size=a).astype(np.float32),
            )
        d.pump()
        rcpt = d.tick()
        if rcpt.ckpt_step is not None and args.snapshot_dir:
            # probe file rides NEXT TO the checkpoint: the restart smoke
            # compares restored serving output against it bitwise
            np.savez(
                os.path.join(
                    args.snapshot_dir, f"probe_{rcpt.ckpt_step:08d}.npz"
                ),
                answers=probe_answers(),
                digest=np.asarray(d.state_digest()),
            )
        print(json.dumps(d.health()), flush=True)
        if args.tick_sleep:
            time.sleep(args.tick_sleep)


if __name__ == "__main__":
    main()
