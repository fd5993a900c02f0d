"""What one run leaves for the metric readers, and the host spans."""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Record:
    """Filled by a driver; read by ``metrics/<name>.py``.

    Times are ``time.perf_counter`` seconds.  ``spans`` are the
    benchmark's own host spans (name, start, end, info) around its calls
    into the program.
    """

    device_kind: str = ""
    fields: int = 0
    setup_s: float = 0.0
    window_s: float = 0.0
    latencies_ms: list = dataclasses.field(default_factory=list)
    freshness_s: list = dataclasses.field(default_factory=list)
    solves: list = dataclasses.field(default_factory=list)  # (t0, t1, sweeps, ok)
    pumps: list = dataclasses.field(default_factory=list)  # (t0, t1, requests, rows)
    rows_answered: int = 0
    spans: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    # work counts (bench/work.py) for the roofline readers
    sweep_work: tuple | None = None  # (flops, bytes) per sweep
    serve_work: object = None  # rows -> (flops, bytes)
    # traced segment: trace (timeline.Trace), its bounds on the trace clock,
    # and the perf_counter -> trace clock offset
    trace: object = None
    trace_lo: float = 0.0
    trace_hi: float = 0.0
    offset: float = 0.0
    notes: dict = dataclasses.field(default_factory=dict)
    window: object = None  # drivers.Window of the run

    def traced(self, name: str) -> list:
        """Spans of ``name`` wholly inside the traced segment, on the
        trace clock."""
        if self.trace is None:
            return []
        out = []
        for n, t0, t1, info in self.spans:
            a, b = t0 + self.offset, t1 + self.offset
            if n == name and a >= self.trace_lo and b <= self.trace_hi:
                out.append((a, b, info))
        return out


class Spans:
    """Records host spans; inside a trace also emits them as
    ``TraceAnnotation`` events named ``bench.<name>``."""

    def __init__(self):
        self.items: list = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str, **info):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation("bench." + name):
                yield info
        else:
            yield info
        self.items.append((name, t0, time.perf_counter(), info))


# one process-wide counter, as the listener it is fed by is process-wide
_COMPILES = {"count": 0, "listening": False}


def _on_duration(event, duration_secs, **kwargs):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["count"] += 1


def count_compiles() -> None:
    """Count XLA backend compiles from here on (``compile_count``)."""
    import jax

    if not _COMPILES["listening"]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _COMPILES["listening"] = True


def compile_count() -> int:
    return _COMPILES["count"]


def span_mean_ms(rec: Record, name: str, served_only: bool = False) -> float | None:
    """Mean duration in ms of the spans named ``name`` inside the traced
    segment (the only part of a traced run whose host timing the trace's
    collection does not disturb); ``served_only`` keeps those whose
    ``rows`` info is positive."""
    xs = [b - a for a, b, info in rec.traced(name)
          if not served_only or info.get("rows", 0) > 0]
    return 1e3 * sum(xs) / len(xs) if xs else None


def idle_share(rec: Record) -> float | None:
    """Percent of the traced segment with no device operation running."""
    if rec.trace is None:
        return None
    import timeline

    span = rec.trace_hi - rec.trace_lo
    return 100.0 * (1.0 - timeline.busy(rec.trace, rec.trace_lo, rec.trace_hi) / span)


def p95(values) -> float | None:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), 95))
