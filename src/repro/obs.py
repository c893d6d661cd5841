"""Spans and a compile counter on the profiler's clock.

``span(name, **meta)`` marks a piece of host work at a layer boundary (the
train loop's data, step and metrics sync; the serve dispatch's assembly,
generate and completion; the serve loop's idle poll).  Inside a profiler
session it opens a ``jax.profiler.TraceAnnotation``, so the span lands on
the host plane of the same trace as the device's operations, with ``meta``
as its event stats, and on exit it adds its duration to an in-memory
aggregate by name.  Outside a session it costs one ``is_enabled`` check and
returns a shared no-op: the switch is the profiler session itself.

While a session records, a ``jax.monitoring`` listener counts each backend
compile under the innermost span open on the compiling thread, or under
``"outside"``.  ``snapshot()`` returns both aggregates; ``reset()`` clears
them.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

from repro.compat import profiler_active, trace_annotation

OUTSIDE = "outside"
COMPILE_EVENT = "backend_compile_duration"

_lock = threading.Lock()
_local = threading.local()
_spans: dict[str, list] = {}  # name -> [count, total ns]
_compiles: dict[str, list] = {}  # innermost span -> [count, total s]


def _stack() -> list[str]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "ann", "t0")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.ann = trace_annotation(name, **meta)

    def __enter__(self):
        self.ann.__enter__()
        _stack().append(self.name)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self.t0
        _stack().pop()
        self.ann.__exit__(*exc)
        with _lock:
            acc = _spans.setdefault(self.name, [0, 0])
            acc[0] += 1
            acc[1] += dt
        return False


_NO_SPAN = contextlib.nullcontext()


def span(name: str, **meta):
    """A context manager over one piece of host work, recorded only while a
    profiler session is on."""
    if not profiler_active():
        return _NO_SPAN
    return _Span(name, meta)


def _on_duration(event: str, duration: float, **_) -> None:
    if not event.endswith(COMPILE_EVENT) or not profiler_active():
        return
    stack = _stack()
    where = stack[-1] if stack else OUTSIDE
    with _lock:
        acc = _compiles.setdefault(where, [0, 0.0])
        acc[0] += 1
        acc[1] += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def snapshot() -> dict:
    """``{"spans": {name: {"count", "total_s"}}, "compiles": {span: {"count",
    "total_s"}}}`` of everything recorded since the last ``reset``."""
    with _lock:
        return {
            "spans": {k: {"count": c, "total_s": ns * 1e-9} for k, (c, ns) in _spans.items()},
            "compiles": {k: {"count": c, "total_s": s} for k, (c, s) in _compiles.items()},
        }


def reset() -> None:
    with _lock:
        _spans.clear()
        _compiles.clear()
