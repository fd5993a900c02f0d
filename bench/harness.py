"""Ties a cell's files together by name and runs it once.

``BENCHMARK.json`` names the cell; its configuration is
``configs/<config>.json`` (the file the entry names), its traffic mix
``traffic/<traffic>.json``, the limits its comparison is held to
``limits/<cell>.json``, and each metric it reports a reader
``metrics/<metric>.py`` with ``read(record) -> float | None`` (one reader
``metrics/<q>.py`` serves every ``<q>.<suffix>`` that has none of its
own).  A later cell, mix or metric is added with new files and entries;
none of this code changes.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import sys
from pathlib import Path

import drivers
import timeline
from record import count_compiles

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load(path) -> dict:
    return json.loads(Path(path).read_text())


class Spec:
    """BENCHMARK.json and the files it names."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.raw = load(self.root / "BENCHMARK.json")
        # cells not proven yet: ``unproven.json``, the same lists, appended
        # (a metric named in both serves the cells of both)
        extra = BENCH / "unproven.json"
        for key, entries in (load(extra) if extra.exists() else {}).items():
            if key not in ("configs", "workloads", "end_to_end", "per_layer"):
                continue
            have = {e["name"]: e for e in self.raw[key]}
            for e in entries:
                if e["name"] not in have:
                    self.raw[key].append(e)
                elif "workloads" in have[e["name"]]:
                    have[e["name"]]["workloads"] += e.get("workloads", [])

    def cell(self, name: str) -> dict:
        for w in self.raw["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.raw["workloads"])
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {known}")

    def config(self, name: str) -> dict:
        for c in self.raw["configs"]:
            if c["name"] == name:
                return load(self.root / c["file"])
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load(BENCH / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> dict:
        return load(BENCH / "limits" / f"{cell}.json")

    def metrics(self, cell: dict, trace: bool) -> list:
        """The cell's end-to-end metrics (trace off) or per-layer ones."""
        e2e = [m for m in self.raw["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
        if not trace:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.raw["per_layer"]
                if cell["name"] in m.get("workloads", [])
                or ("workloads" not in m and m["moves"] in moved)]


def reader(name: str):
    """``metrics/<name>.py``, else the quantity's reader ``metrics/<q>.py``
    for a name ``<q>.<cell kind>`` (``idle_share.daemon``)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _reduce_trace(rec, trace_dir: Path) -> None:
    """Load the window's trace and put it on the perf_counter clock."""
    tr = timeline.load_xplane(trace_dir)
    w = rec.window
    inside = [(n, a, b) for n, a, b, _ in rec.spans if a >= w.trace_t0 and b <= w.trace_t1]
    rec.offset = timeline.align(tr, inside)
    rec.trace = tr
    rec.trace_lo = w.trace_t0 + rec.offset
    rec.trace_hi = w.trace_t1 + rec.offset


def run(cell_name: str, seed: int, seconds: float, trace: bool, t_start: float,
        spec: Spec | None = None, cfg: dict | None = None, **ctx_extra) -> dict:
    """Run one cell once; returns the result line's object.

    ``cfg`` overrides the configuration (the CPU tests pass tiny ones);
    ``ctx_extra`` goes to ``drivers.Context`` (a prebuilt network, daemon
    overrides).
    """
    import jax

    spec = spec or Spec()
    cell = spec.cell(cell_name)
    cfg = cfg or spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    devices = jax.devices()[: cell["chips"]]
    kind = devices[0].device_kind
    trace_dir = spec.root / "bench_out" / "trace" / cell_name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    count_compiles()
    ctx = drivers.Context(
        cfg=cfg, mix=mix, seed=seed, seconds=seconds, t_start=t_start,
        trace_s=min(seconds, float(mix["trace_seconds"])) if trace else 0.0,
        trace_dir=str(trace_dir), **ctx_extra,
    )
    rec, check = drivers.DRIVERS[mix["loop"]](ctx)
    rec.device_kind = kind
    peak = memory_peak(devices)
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        _reduce_trace(rec, trace_dir)
        lo, hi = rec.trace_lo, rec.trace_hi
        device["busy_s"] = timeline.busy(rec.trace, lo, hi)
        device["window_s"] = hi - lo
        breakdown = {"device_ops": timeline.top_ops(rec.trace, lo, hi),
                     "idle_gaps": timeline.idle_gaps(rec.trace, lo, hi)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    numbers = check()
    print(f"window {rec.window_s:.3f} s, compiles inside it "
          f"{rec.window.compiles_in_window}, peak_bytes_in_use {peak}, "
          f"notes {json.dumps(rec.notes)}", flush=True)
    limits = spec.limits(cell_name)
    correct = True
    checks = {}
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = value is not None and value <= limit
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit}
    metrics = {}
    for m in spec.metrics(cell, trace):
        value = reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": rec.attempted, "failed": rec.failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return out
