"""Reduce a profiler trace to device busy time, idle share, top device ops
and idle gaps labelled by the benchmark's host spans.

A trace is reduced in two steps.  ``load_xplane`` reads the JAX
profiler's ``.xplane.pb`` into plain lists: the device operations (name,
start, end in seconds on the trace's clock) of every accelerator plane,
and the host spans the benchmark opened with
``jax.profiler.TraceAnnotation`` (names starting with ``bench.``).
``reduce`` then works on those lists alone, so the arithmetic is tested on
a small hand-made fixture (``fixtures/trace_small.json``) with no
profiler and no chip.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
from pathlib import Path

SPAN_PREFIX = "bench."
# Device-plane lines that hold the operations themselves; the module and
# step lines of the same plane enclose them and would hide the gaps.
OP_LINES = ("XLA Ops",)


@dataclasses.dataclass
class Trace:
    """A trace in plain form: seconds on the trace's own clock."""

    ops: list  # [(device, name, start, end)]
    spans: list  # [(name, start, end)] host spans named "bench.*"
    devices: int  # accelerator planes that held operations

    @classmethod
    def from_json(cls, path) -> "Trace":
        raw = json.loads(Path(path).read_text())
        return cls(
            ops=[tuple(o) for o in raw["ops"]],
            spans=[tuple(s) for s in raw["spans"]],
            devices=int(raw["devices"]),
        )


def load_xplane(trace_dir) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops, spans, devices = [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = [ln for ln in plane.lines if ln.name in OP_LINES]
            held = False
            for line in lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((plane.name, ev.name, s, s + ev.duration_ns * 1e-9))
                    held = True
            devices += held
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append(
                            (ev.name[len(SPAN_PREFIX):], s, s + ev.duration_ns * 1e-9)
                        )
    return Trace(ops=ops, spans=sorted(spans, key=lambda x: x[1]), devices=devices)


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def busy(trace: Trace, lo: float, hi: float, within=None) -> float:
    """Device-busy seconds in [lo, hi], averaged over the devices used.

    ``within``: optional host spans (start, end); only busy time inside
    them counts (a work count that covers those spans alone is then set
    against the device time those spans caused).
    """
    if not trace.devices:
        return 0.0
    per_dev: dict = {}
    for dev, _, s, e in trace.ops:
        per_dev.setdefault(dev, []).append((s, e))
    cover = None if within is None else union(within)
    sec = 0.0
    for ivs in per_dev.values():
        u = clip(union(ivs), lo, hi)
        if cover is not None:
            u = [piece for c0, c1 in cover for piece in clip(u, c0, c1)]
        sec += total(u)
    return sec / trace.devices


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[name, seconds]] of the device operations that took most time,
    summed by name over devices and averaged over the devices used."""
    acc: dict = {}
    for _, name, s, e in trace.ops:
        for a, b in clip([(s, e)], lo, hi):
            acc[name] = acc.get(name, 0.0) + (b - a)
    d = max(trace.devices, 1)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / d] for k, v in rows]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[label, seconds]]: idle device time in [lo, hi] summed by the
    innermost benchmark span the host was in ("driver" outside them).

    A gap is a stretch in which no operation ran on the first device
    that held any.  Each piece of a gap goes to the innermost (latest
    started) span covering it.
    """
    if not trace.devices:
        return []
    dev0 = sorted({o[0] for o in trace.ops})[0]
    u = clip(union([(s, e) for d, _, s, e in trace.ops if d == dev0]), lo, hi)
    gaps, t = [], lo
    for s, e in u:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    # elementary pieces between every span boundary inside the gaps
    spans = sorted(trace.spans, key=lambda sp: sp[1])
    span_starts = [sp[1] for sp in spans]
    marks = sorted({x for _, s, e in spans for x in (s, e)})
    acc: dict = {}
    for g0, g1 in gaps:
        lo_i = bisect.bisect_right(marks, g0)
        hi_i = bisect.bisect_left(marks, g1)
        cuts = [g0] + marks[lo_i:hi_i] + [g1]
        for a, b in zip(cuts[:-1], cuts[1:]):
            mid = 0.5 * (a + b)
            i = bisect.bisect_right(span_starts, mid)
            # innermost = latest-started span that still covers mid
            label = "driver"
            for sp in reversed(spans[max(0, i - 32):i]):
                if sp[2] > mid:
                    label = sp[0]
                    break
            acc[label] = acc.get(label, 0.0) + (b - a)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]


def align(trace: Trace, recorded) -> float:
    """Offset to add to host perf_counter seconds to get trace seconds.

    ``recorded``: the benchmark's own spans (name, t0, t1) on
    ``time.perf_counter``; the trace holds the spans of its window as
    ``TraceAnnotation`` events.  Each of the first few trace spans,
    paired with each recorded span of its name, proposes an offset; the
    offset under which most trace spans find a recorded span of the same
    name and duration (within ``tol`` seconds) wins.
    """
    tol = 1e-3
    rec_by: dict = {}
    for name, t0, t1 in sorted(recorded, key=lambda x: x[1]):
        rec_by.setdefault(name, []).append((t0, t1 - t0))
    starts = {k: [t0 for t0, _ in v] for k, v in rec_by.items()}

    def near(name, t) -> bool:
        xs = starts.get(name, [])
        i = bisect.bisect_left(xs, t - tol)
        return i < len(xs) and xs[i] < t + tol

    best, best_hits = None, -1
    for name, s, e in trace.spans[:4]:
        for t0, dur in rec_by.get(name, [])[:8]:
            if abs(dur - (e - s)) > tol:
                continue
            c = s - t0
            hits = sum(near(n2, s2 - c) for n2, s2, _ in trace.spans[:64])
            if hits > best_hits:
                best, best_hits = c, hits
    if best is None:
        raise ValueError("no benchmark span of the trace matches a recorded one")
    return best
