"""Central registry of jitted programs and their compile-cache budgets.

Every ``jax.jit``-compiled program the repo ships is declared here once,
with the budget its caching behavior is allowed to exhibit:

  * ``FROZEN`` — one program per (shape, static-arg) configuration;
    after a warmup call, re-running with new *values* (fault rates, tau,
    beta, churn events, drill toggles) must compile NOTHING.  This is
    the "rates are traced operands" contract the jaxpr auditor proves
    statically (:mod:`repro.analysis.jaxpr_audit`) and tests pin
    dynamically through :func:`snapshot` / :meth:`CacheSnapshot.assert_within`.
  * ``BUCKETS`` — the query axis is padded to power-of-two buckets
    (``kernels.ops.bucket_rows``), so a serving process with arbitrary
    request sizes compiles at most one program per distinct bucket:
    O(log Q) total, bounded by the caller-supplied bucket count.

Consumers (tests, ``launch/serve.py --churn``, benchmarks) take a
:func:`snapshot` of the entries they exercise, do their work, then call
:meth:`CacheSnapshot.assert_within` (or read :meth:`CacheSnapshot.growth`)
— replacing the hand-rolled ``warm = f._cache_size()`` arithmetic that
used to be copy-pasted per test file.  ``tools/audit.py`` verifies every
entry still resolves to a jit-compiled callable.

This ledger is the gate for the ROADMAP's hierarchical-topology
scale-up: cluster-tier consensus must land as new FROZEN entries here
(and pass the jaxpr audit) before it can claim the zero-recompile
property the flat engines already prove.
"""

from __future__ import annotations

import dataclasses
import importlib
from collections.abc import Iterable

from .report import Finding

FROZEN = "frozen"
BUCKETS = "buckets"


@dataclasses.dataclass(frozen=True)
class LedgerEntry:
    """One jitted program: ``target`` is ``"module.path:attribute"``."""

    name: str
    target: str
    budget: str
    note: str = ""

    def resolve(self):
        mod, _, attr = self.target.partition(":")
        return getattr(importlib.import_module(mod), attr)


def _entries() -> list[LedgerEntry]:
    e = LedgerEntry
    C = "repro.core."
    return [
        # --- training sweeps: one program per engine x shape x n_sweeps
        e("sweep.serial", C + "sn_train:serial_sweep", FROZEN),
        e("sweep.colored", C + "sn_train:colored_sweep", FROZEN),
        e("sweep.random", C + "sn_train:random_sweep", FROZEN),
        e("sweep.weighted", C + "sn_train:weighted_sweep", FROZEN),
        e("sweep.robust_links", C + "sn_train:robust_sweep_links", FROZEN),
        e("sweep.robust_colored", C + "sn_train:_robust_colored", FROZEN,
          "alive trace + delivered masks are traced operands"),
        # --- fault-injected sweeps: rates are traced, structure static
        e("faults.colored", C + "faults:_faulty_colored", FROZEN,
          "one program serves the whole drop/burst rate grid"),
        e("faults.serial", C + "faults:_faulty_serial", FROZEN),
        e("faults.robust", C + "faults:_faulty_robust", FROZEN),
        # --- serving: O(log Q) bucketed programs on the query axis
        e("serving.knn_plan", C + "serving:_knn_plan", BUCKETS,
          "the plan engine: select + evaluate, one program per bucket"),
        e("serving.select", C + "serving:knn_select_valid", BUCKETS),
        e("serving.eval", C + "serving:_eval_selected", BUCKETS),
        e("serving.knn_kernel",
          "repro.kernels.knn_fuse:knn_fuse_pallas", BUCKETS),
        e("serving.matvec",
          "repro.kernels.kernel_matvec:kernel_matvec_pallas", BUCKETS),
        e("serving.plan_add", C + "serving:plan_add_sensor", FROZEN),
        e("serving.plan_remove", C + "serving:plan_remove_sensor", FROZEN),
        # --- pruning: tau is a traced operand
        e("pruning.energy", C + "pruning:_lane_energy", FROZEN),
        e("pruning.keep", C + "pruning:_keep_mask", FROZEN,
          "sweeping tau compiles nothing after warmup"),
        # --- fusion / monitoring / kernels
        e("fusion.eval_all", C + "fusion:_eval_all", FROZEN),
        e("monitor.metrics", C + "monitor:_round_metrics", FROZEN),
        e("kernels.color_step",
          "repro.kernels.color_step:color_step_pallas", FROZEN),
        # --- streaming absorb / evict / churn (copy + donated variants)
        e("stream.absorb.copy", C + "streaming:_absorb_copy", FROZEN),
        e("stream.absorb.donate", C + "streaming:_absorb_donate", FROZEN),
        e("stream.absorb_evict.copy",
          C + "streaming:_absorb_evict_copy", FROZEN),
        e("stream.absorb_evict.donate",
          C + "streaming:_absorb_evict_donate", FROZEN),
        e("stream.absorb_many.drop.copy",
          C + "streaming:_absorb_many_drop_copy", FROZEN),
        e("stream.absorb_many.drop.donate",
          C + "streaming:_absorb_many_drop_donate", FROZEN),
        e("stream.absorb_many.evict.copy",
          C + "streaming:_absorb_many_evict_copy", FROZEN),
        e("stream.absorb_many.evict.donate",
          C + "streaming:_absorb_many_evict_donate", FROZEN),
        e("stream.wave.drop.copy",
          C + "streaming:_absorb_wave_drop_copy", FROZEN),
        e("stream.wave.drop.donate",
          C + "streaming:_absorb_wave_drop_donate", FROZEN),
        e("stream.wave.evict.copy",
          C + "streaming:_absorb_wave_evict_copy", FROZEN),
        e("stream.wave.evict.donate",
          C + "streaming:_absorb_wave_evict_donate", FROZEN),
        e("stream.evict.copy", C + "streaming:_evict_jit", FROZEN),
        e("stream.evict.donate", C + "streaming:_evict_donate", FROZEN),
        e("stream.add.copy", C + "streaming:_add_sensor_copy", FROZEN),
        e("stream.add.donate", C + "streaming:_add_sensor_donate", FROZEN),
        e("stream.remove.copy", C + "streaming:_remove_sensor_copy", FROZEN),
        e("stream.remove.donate",
          C + "streaming:_remove_sensor_donate", FROZEN),
        # --- daemon
        e("daemon.ecoef", "repro.launch.daemon:_ecoef_jit", FROZEN),
    ]


LEDGER: dict[str, LedgerEntry] = {x.name: x for x in _entries()}

# Named groups matching the repo's cache-pinning consumers.
GROUPS: dict[str, tuple[str, ...]] = {
    # the daemon's serving path: programs grow only with new buckets
    "daemon": ("serving.knn_plan",),
    # fault drills: toggling rates on/off reuses compiled programs
    "faults": ("faults.colored",),
    # quantized serving: tau sweep + bucket reuse compile nothing
    "quant": ("serving.knn_kernel", "serving.knn_plan", "pruning.keep"),
}


def churn_group(*, on_full: str = "drop", donate: bool = True) -> tuple[str, ...]:
    """The program set one churn round exercises (join + leave + absorb +
    refresh sweep + plan repairs + the plan engine's query)."""
    v = "donate" if donate else "copy"
    policy = "evict" if on_full == "evict" else "drop"
    return (
        f"stream.add.{v}",
        f"stream.remove.{v}",
        f"stream.absorb_many.{policy}.{v}",
        "sweep.colored",
        "serving.knn_plan",
        "serving.plan_add",
        "serving.plan_remove",
    )


def _resolve_names(names: str | Iterable[str]) -> tuple[str, ...]:
    if isinstance(names, str):
        names = GROUPS[names]
    names = tuple(names)
    unknown = [n for n in names if n not in LEDGER]
    if unknown:
        raise KeyError(f"not in the compile ledger: {unknown}")
    return names


def cache_size(name: str) -> int:
    return LEDGER[name].resolve()._cache_size()


class CacheSnapshot:
    """Warm-point cache sizes for a set of ledger entries."""

    def __init__(self, names: tuple[str, ...]):
        self.names = names
        self._base = {n: cache_size(n) for n in names}

    def growth(self) -> dict[str, int]:
        """Programs compiled per entry since the snapshot."""
        return {n: cache_size(n) - self._base[n] for n in self.names}

    def total_growth(self) -> int:
        return sum(self.growth().values())

    def assert_within(self, buckets: int | None = None, context: str = ""):
        """Enforce each entry's declared budget since the snapshot.

        FROZEN entries must not have compiled anything; BUCKETS entries
        may have compiled at most ``buckets`` programs (the number of
        distinct power-of-two query buckets exercised — pass 0 after a
        warmup that already covered them).  Returns the growth dict so
        callers can report it.
        """
        growth = self.growth()
        for name, grown in growth.items():
            budget = LEDGER[name].budget
            if budget == FROZEN:
                limit = 0
            else:
                if buckets is None:
                    raise ValueError(
                        f"{name} is bucket-budgeted: pass buckets= "
                        "(the distinct query buckets exercised)"
                    )
                limit = buckets
            assert grown <= limit, (
                f"compile budget exceeded{' (' + context + ')' if context else ''}: "
                f"{name} [{budget}] compiled {grown} new program(s), "
                f"budget {limit}"
            )
        return growth


def snapshot(names: str | Iterable[str]) -> CacheSnapshot:
    """Snapshot cache sizes for a group name or iterable of entry names."""
    return CacheSnapshot(_resolve_names(names))


def audit() -> list[Finding]:
    """Ledger self-check: every entry resolves to a jit-compiled callable
    with a countable cache, budgets are valid, groups reference entries."""
    findings = []
    for name, entry in LEDGER.items():
        if entry.budget not in (FROZEN, BUCKETS):
            findings.append(Finding(
                "ledger", name, "budget", f"unknown budget {entry.budget!r}"
            ))
        try:
            fn = entry.resolve()
        except (ImportError, AttributeError) as exc:
            findings.append(Finding(
                "ledger", name, "resolve", f"{entry.target}: {exc}"
            ))
            continue
        if not callable(getattr(fn, "_cache_size", None)):
            findings.append(Finding(
                "ledger", name, "interface",
                f"{entry.target} is not a jit-compiled callable "
                "(no _cache_size)",
            ))
    for group, names in GROUPS.items():
        for n in names:
            if n not in LEDGER:
                findings.append(Finding(
                    "ledger", f"group:{group}", n, "group names unknown entry"
                ))
    for kwargs in (dict(on_full="drop", donate=True),
                   dict(on_full="evict", donate=True),
                   dict(on_full="drop", donate=False),
                   dict(on_full="evict", donate=False)):
        for n in churn_group(**kwargs):
            if n not in LEDGER:
                findings.append(Finding(
                    "ledger", "group:churn", n, "group names unknown entry"
                ))
    return findings
