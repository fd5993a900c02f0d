"""Convergence watchdog for SN-Train under unreliable links.

The SOP recursion is Fejér monotone under perfect delivery (Lemma 2.1:
``weighted_norm_sq`` never increases along a sweep), but a partial
delivery is NOT a projection — the distributed-RLS stability line
(arXiv:1109.4627 in PAPERS.md) shows these recursions survive imperfect
exchanges yet can drift or diverge at high loss.  ``watch_sweeps`` is
the supervision loop that makes faulty training safe to leave running:

  per round (``sweeps_per_round`` sweeps in one jitted dispatch):
    track    per-field Fejér norm + relative z-residual;
    detect   divergence: a field's norm grew past ``divergence_ratio``
             (or went non-finite) for ``patience`` consecutive rounds;
    retry    the round with FRESH fault draws (bounded by
             ``max_retries``) — the burst that poisoned it is transient;
    escalate to a full factor refactorization
             (``streaming.rebuild_chol``) — heals drifted/corrupted
             cached factors once;
    rollback to the entry snapshot (in-memory, or an on-disk
             ``checkpoint.save_train`` directory) when even fresh
             factors keep diverging — the state is unrecoverable from
             here, restore the last good one bitwise and stop.

Everything device-side is fixed-shape and jitted once: the host loop
only decides WHICH warmed program to call next, so a whole watchdog run
compiles zero programs after warmup regardless of fault rates or how
many retries fire (``benchmarks/fault_bench.py`` counts the caches).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs

from . import faults as faults_mod
from . import sn_train
from .sn_train import SNTrainProblem, SNTrainState, weighted_norm_sq


@dataclasses.dataclass(frozen=True)
class WatchdogConfig:
    """Host-side knobs of ``watch_sweeps`` (all static)."""

    sweeps_per_round: int = 5
    tol: float = 1e-4  # converged: max |dz| / (max |z| + eps) < tol
    divergence_ratio: float = 1.05  # norm growth flagging a round
    patience: int = 2  # consecutive flagged rounds before acting
    max_retries: int = 3  # fresh-draw re-sweeps before escalating
    max_rounds: int = 60


RECEIPT_SCHEMA = "watchdog_receipt/1"


class WatchdogReceipt(NamedTuple):
    """What happened, per field and overall (printed by serve.py)."""

    converged: np.ndarray  # (B,) bool per-field residual < tol
    residual: np.ndarray  # (B,) final relative z-residual per round
    norm: np.ndarray  # (B,) final Fejér norm
    rounds: int  # rounds accepted or retried
    sweeps: int  # total sweeps executed (incl. retried rounds)
    retries: int  # fresh-draw re-sweeps taken
    refactorized: int  # 0/1: rebuild_chol escalations
    rolled_back: bool  # True: state restored from the snapshot
    diverged: np.ndarray  # (B,) bool fields flagged in the final round

    def to_json(self) -> dict:
        """Machine-readable receipt with a STABLE schema.

        Plain JSON types only (per-field arrays become lists), tagged with
        ``schema`` so consumers — the daemon health endpoint,
        ``serve.py --faults`` — can detect drift.  ``receipt_from_json``
        is the exact inverse (round-trip pinned in tests/test_faults.py).
        """
        return {
            "schema": RECEIPT_SCHEMA,
            "converged": [bool(v) for v in np.atleast_1d(self.converged)],
            "residual": [float(v) for v in np.atleast_1d(self.residual)],
            "norm": [float(v) for v in np.atleast_1d(self.norm)],
            "rounds": int(self.rounds),
            "sweeps": int(self.sweeps),
            "retries": int(self.retries),
            "refactorized": int(self.refactorized),
            "rolled_back": bool(self.rolled_back),
            "diverged": [bool(v) for v in np.atleast_1d(self.diverged)],
        }


def receipt_from_json(payload: dict) -> WatchdogReceipt:
    """Rebuild a ``WatchdogReceipt`` from ``WatchdogReceipt.to_json``."""
    schema = payload.get("schema")
    if schema != RECEIPT_SCHEMA:
        raise ValueError(
            f"unknown watchdog receipt schema {schema!r} "
            f"(expected {RECEIPT_SCHEMA!r})"
        )
    return WatchdogReceipt(
        converged=np.asarray(payload["converged"], bool),
        residual=np.asarray(payload["residual"], float),
        norm=np.asarray(payload["norm"], float),
        rounds=int(payload["rounds"]),
        sweeps=int(payload["sweeps"]),
        retries=int(payload["retries"]),
        refactorized=int(payload["refactorized"]),
        rolled_back=bool(payload["rolled_back"]),
        diverged=np.asarray(payload["diverged"], bool),
    )


@jax.jit
def _round_metrics(problem, state_old, state_new):
    """(Fejér norm of state_new, per-field relative z-residual)."""
    norm = weighted_norm_sq(problem, state_new)
    num = jnp.max(jnp.abs(state_new.z - state_old.z), axis=-1)
    den = jnp.max(jnp.abs(state_old.z), axis=-1) + 1e-12
    return norm, num / den


def _snapshot(problem, state, directory):
    if directory is None:
        return (problem, state)
    from repro.checkpoint import save_train

    save_train(directory, 0, problem, state)
    return None


def _rollback(problem, state, directory, mem):
    if directory is None:
        return mem
    from repro.checkpoint import restore_train

    return restore_train(directory, 0, problem, state)


def watch_sweeps(
    problem: SNTrainProblem,
    state: SNTrainState,
    *,
    model: "faults_mod.FaultModel | None" = None,
    key: jax.Array | None = None,
    engine: str = "plan",
    config: WatchdogConfig = WatchdogConfig(),
    snapshot_dir: str | None = None,
) -> tuple[SNTrainProblem, SNTrainState, WatchdogReceipt]:
    """Train to convergence under supervision; see the module docstring.

    model/key: fault process to inject (None trains fault-free but still
    watches — useful to detect numerically-poisoned states).  engine:
    any of ``faults.faulty_sweep``'s engines.  snapshot_dir: where the
    entry snapshot lives (None = in-memory); rollback restores it
    bitwise.  Returns the (possibly refactorized or rolled-back)
    problem, the final state, and the receipt.
    """
    if model is not None and key is None:
        raise ValueError("fault injection needs a PRNG key")
    key = jax.random.PRNGKey(0) if key is None else key
    mem = _snapshot(problem, state, snapshot_dir)
    spr = config.sweeps_per_round

    def run_round(problem, state, key):
        with obs.span("watch.round"):
            with obs.span("watch.launch"):
                key, sub = jax.random.split(key)
                if model is None:
                    cand = sn_train.colored_sweep(
                        problem, state, n_sweeps=spr, engine=engine
                    )
                else:
                    cand = faults_mod.faulty_sweep(
                        problem, state, model, sub, n_sweeps=spr,
                        engine=engine,
                    )
                norm, resid = _round_metrics(problem, state, cand)
            with obs.span("watch.sync"):
                norm = np.atleast_1d(np.asarray(norm))
                resid = np.atleast_1d(np.asarray(resid))
        return cand, norm, resid, key

    norm_prev = np.atleast_1d(np.asarray(weighted_norm_sq(problem, state)))
    resid = np.full_like(norm_prev, np.inf)
    diverged = np.zeros(norm_prev.shape, bool)
    flags = retries = refactorized = rounds = sweeps = 0
    rolled_back = False

    for _ in range(config.max_rounds):
        cand, norm_new, resid_new, key = run_round(problem, state, key)
        rounds += 1
        sweeps += spr
        diverged = ~np.isfinite(norm_new) | (
            norm_new > norm_prev * config.divergence_ratio + 1e-9
        )
        if diverged.any():
            flags += 1
            if flags >= config.patience:
                flags = 0
                if retries < config.max_retries:
                    # Discard the poisoned round; the next draw resamples
                    # the fault process (fresh key), so a transient burst
                    # doesn't kill the run.
                    retries += 1
                    continue
                if not refactorized:
                    # Factors may have drifted (streaming float history,
                    # repeated masked solves): rebuild them from the Gram
                    # — the bounded-escalation step.
                    from .streaming import rebuild_chol

                    # The retry budget stays spent: if fresh factors still
                    # diverge for `patience` rounds, roll back immediately.
                    problem = dataclasses.replace(
                        problem, chol=rebuild_chol(problem)
                    )
                    refactorized = 1
                    continue
                # Even fresh factors diverge: restore the entry snapshot
                # bitwise and stop — the caller gets the last good state.
                problem, state = _rollback(problem, state, snapshot_dir, mem)
                rolled_back = True
                break
        else:
            flags = 0
        state = cand
        norm_prev = norm_new
        resid = resid_new
        if (resid < config.tol).all():
            break

    receipt = WatchdogReceipt(
        converged=resid < config.tol,
        residual=resid,
        norm=norm_prev,
        rounds=rounds,
        sweeps=sweeps,
        retries=retries,
        refactorized=refactorized,
        rolled_back=rolled_back,
        diverged=diverged,
    )
    return problem, state, receipt


def format_receipt(receipt: WatchdogReceipt) -> str:
    """One watchdog receipt line for CLI surfaces (serve.py --faults)."""
    n_conv = int(np.sum(receipt.converged))
    n_tot = int(receipt.converged.size)
    status = (
        "ROLLED BACK" if receipt.rolled_back
        else ("converged" if n_conv == n_tot else "partial")
    )
    return (
        f"watchdog: {status} {n_conv}/{n_tot} fields | "
        f"rounds={receipt.rounds} sweeps={receipt.sweeps} "
        f"retries={receipt.retries} refactorized={receipt.refactorized} | "
        f"max residual {float(np.max(receipt.residual)):.3e}"
    )
