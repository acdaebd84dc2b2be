"""Named host spans: one helper for the profiler's trace and the program's
own per-phase seconds.

``with span("stream.fold"):`` does two things:

* it opens a ``jax.profiler.TraceAnnotation`` of that name, so a profile
  shows the block on the thread that ran it, on the clock the device's
  operations share;
* it times the block with ``time.perf_counter`` (``span.seconds`` after the
  block) and, when the calling thread is inside ``collect(phases)``, adds
  the seconds to ``phases[name]``.

A span on a thread with no collector (the edge reader's staging thread, the
channel's sender and receiver) only annotates the trace; its caller adds
``seconds`` to its own counters. Spans that one collector sums do not nest,
so the values of its dict are disjoint stretches of that thread's time.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

_local = threading.local()
# jax.profiler.TraceAnnotation, imported at the first span: streams/ sits on
# the worker's start-up import path, which must not load jax
_Trace = None


def _trace(name: str):
    global _Trace
    if _Trace is None:
        from jax.profiler import TraceAnnotation

        _Trace = TraceAnnotation
    return _Trace(name)


class span:
    """``with span(name) as s:`` — a named, timed block (see the module)."""

    __slots__ = ("name", "seconds", "_t0", "_trace")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._trace = _trace(self.name)
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._trace.__exit__(*exc)
        add(self.name, self.seconds)


def add(name: str, seconds: float) -> None:
    """Add ``seconds`` to ``name`` in this thread's collector, if any: for
    time measured on another thread (the staging thread's reads)."""
    phases = getattr(_local, "phases", None)
    if phases is not None:
        phases[name] = phases.get(name, 0.0) + seconds


@contextmanager
def collect(phases: dict[str, float]):
    """Sum the seconds of every span this thread closes inside the block
    into ``phases``, by name."""
    outer = getattr(_local, "phases", None)
    _local.phases = phases
    try:
        yield phases
    finally:
        _local.phases = outer
