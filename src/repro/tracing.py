"""Host spans and counters at the program's layer boundaries.

Off by default.  Off, :func:`span` returns one shared no-op context
manager after a single module-level bool check, and :func:`count`
returns at once: nothing is allocated, timed or annotated.

On (:func:`enable`), each span opens a ``jax.profiler.TraceAnnotation``,
so it sits on the profiler's ``/host:CPU`` plane on the same clock as the
device's ``XLA Modules`` events, and adds to an in-memory table keyed by
name: calls, total seconds and self seconds (the total less the part
covered by child spans of the same thread).  :func:`count` adds to an
in-memory counter.  :func:`snapshot` returns both tables; the profiler's
trace is the only trace this module writes.

    >>> from repro import tracing
    >>> tracing.enable(); tracing.reset()
    >>> with tracing.span("charz.estimate", op="and", n=2):
    ...     tracing.count("charz.trials", 64)
    >>> snap = tracing.snapshot(); tracing.disable()
    >>> snap["spans"]["charz.estimate"]["calls"], snap["counters"]
    (1, {'charz.trials': 64})
"""
from __future__ import annotations

import functools
import threading
import time

#: every span name the program writes (``SPAN_NAMES`` is what a trace
#: reader asks the profiler's host plane for)
SPAN_NAMES = (
    # MC harness (core/charz.py)
    "charz.estimate", "charz.chip", "charz.draw", "charz.op", "charz.count",
    # ISA (core/isa.py, at every bank count)
    "isa.stage", "isa.readout", "isa.inventory",
    # simulator and the resolve boundary (core/simulator.py, at every bank
    # count)
    "sim.apa", "sim.resolve_prep", "sim.resolve_call",
    # planner and resident executor (core/compiler.py, core/isa.py)
    "compiler.schedule", "resident.exec", "resident.rowclone",
    # engine (pud/engine.py)
    "engine.run_program", "engine.meter", "engine.stack", "engine.kernel",
)

_on = False
#: ``jax.profiler.TraceAnnotation``, looked up by :func:`enable`
_annotation = None
_lock = threading.Lock()
_local = threading.local()
#: name -> [calls, total seconds, self seconds]
_spans: dict[str, list] = {}
_counters: dict[str, int] = {}


class _Off:
    """The shared span of a disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "annotation", "t0", "child_s")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.annotation = _annotation(name, **meta)

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self.child_s = 0.0
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1].child_s += dt
        with _lock:
            row = _spans.setdefault(self.name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += dt
            row[2] += dt - self.child_s
        return False


def span(name: str, **meta):
    """A context manager timing one call of layer boundary ``name``
    (``meta`` goes into the profiler event's metadata)."""
    if not _on:
        return _OFF
    return _Span(name, meta)


def traced(name: str):
    """Decorator: run each call of the function inside ``span(name)``."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (tracing on only)."""
    if not _on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def enable() -> None:
    global _on, _annotation
    import jax
    _annotation = jax.profiler.TraceAnnotation
    _on = True


def disable() -> None:
    global _on
    _on = False


def reset() -> None:
    """Empty the span table and the counters."""
    with _lock:
        _spans.clear()
        _counters.clear()


def snapshot() -> dict:
    """``{"spans": {name: {"calls", "total_s", "self_s"}},
    "counters": {name: n}}``: a copy of what was recorded since the last
    :func:`reset`."""
    with _lock:
        return {"spans": {k: {"calls": c, "total_s": t, "self_s": s}
                          for k, (c, t, s) in _spans.items()},
                "counters": dict(_counters)}
