"""Host spans and trace counters of the program's own paths.

``span(name)`` marks one phase of a host path (the daemon's pump and tick,
the watchdog's rounds).  Tracing is off by default: ``span`` then returns
one shared no-op context, at the cost of one module-global check, with no
allocation and no clock read.  Between ``enable()`` and ``disable()`` each
span is kept in memory as ``(name, t0_ns, t1_ns, parent, attrs)`` on
``time.perf_counter_ns`` (``parent``: the index of the enclosing open span
of the same thread, or -1) and is also emitted as a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so a profiler
shows the same names on its host lines.  ``drain()`` hands the kept spans
over and clears them; call it between spans, not inside one.

``traces()`` counts JAX's jaxpr traces (``/jax/core/compile/
jaxpr_trace_duration`` events) process-wide, always: a listener that costs
nothing until something is traced.  A count that rises on every call of a
warmed path is a retrace.

    from repro import obs
    obs.enable()
    with obs.span("serve.fetch") as sp:
        vals = np.asarray(out)
        sp["bytes"] = vals.nbytes
    spans, dropped = obs.drain()
"""

from __future__ import annotations

import threading
import time

import jax

CAPACITY = 1 << 16  # spans kept between drains; later ones are dropped

_on = False
_kept: list = []  # [name, t0_ns, t1_ns, parent, attrs]
_dropped = 0
_lock = threading.Lock()
_local = threading.local()
_traces = [0, 0.0]  # jaxpr traces: count, seconds


class _Off:
    """The context every span is while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __setitem__(self, key, value):
        pass

    def stamp(self) -> int:
        return time.perf_counter_ns()


_OFF = _Off()


class _Span:
    __slots__ = ("rec", "ann", "t0")

    def __init__(self, name: str):
        self.rec = [name, 0, 0, -1, {}]
        self.ann = jax.profiler.TraceAnnotation("repro." + name)

    def __enter__(self):
        global _dropped
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        with _lock:
            if len(_kept) < CAPACITY:
                self.rec[3] = stack[-1] if stack else -1
                stack.append(len(_kept))
                _kept.append(self.rec)
            else:
                _dropped += 1
                self.rec = None
        self.t0 = time.perf_counter_ns()
        self.ann.__enter__()
        if self.rec is not None:
            self.rec[1] = self.t0
        return self

    def __exit__(self, *exc):
        if self.rec is not None:
            self.rec[2] = time.perf_counter_ns()
            _local.stack.pop()
        return self.ann.__exit__(*exc)

    def __setitem__(self, key, value):
        if self.rec is not None:
            self.rec[4][key] = value

    def stamp(self) -> int:
        return self.t0


def span(name: str):
    """Context for one phase named ``name``.  Inside it, ``sp[key] =
    value`` sets an attribute, and ``sp.stamp()`` is the phase's start on
    ``perf_counter_ns`` (the clock read at that call while tracing is off),
    so a caller that times the phase itself reads no second clock."""
    if not _on:
        return _OFF
    return _Span(name)


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def drain() -> tuple[list, int]:
    """(spans kept since the last drain as tuples, spans dropped since)."""
    global _kept, _dropped
    with _lock:
        out, lost = _kept, _dropped
        _kept, _dropped = [], 0
    return [tuple(r) for r in out], lost


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == "/jax/core/compile/jaxpr_trace_duration":
        _traces[0] += 1
        _traces[1] += duration_secs


def traces() -> tuple[int, float]:
    """(jaxpr traces, seconds spent in them) since this module was
    imported."""
    return _traces[0], _traces[1]


jax.monitoring.register_event_duration_secs_listener(_on_duration)
