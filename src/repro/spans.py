"""Named spans over the program's layers, on one clock for the profiler and
for ``sweep.stats``.

A span marks a piece of work at a layer boundary::

    with spans.span("repro.trace.generate"):
        tr = generate_trace(...)

Each span does two things:

* it opens ``jax.profiler.TraceAnnotation(name)``, so that while a profiler
  is active the span lands on the host plane of the same trace as the
  device's programs, on the same clock;
* it adds its duration to every :func:`recording` open on its thread: the
  count ``n``, the ``total`` and the ``self`` time (the total less the time
  of its child spans; a span's parent is the enclosing span on the same
  thread, whichever recording that one belongs to).

``run_sweep`` and ``run_mix_sweep`` each open a recording and report it as
``sweep.stats["spans"]``; a sweep started inside another recording nests
under it, and both see its spans. Outside any recording a span records
nothing, and its handle still carries its own duration (``elapsed_s``),
which the resilience layer's watchdog reads. Spans are kept in memory and
are coarse (one per generated trace or bucket, never per cell or request);
they are always on, and a profiler being active or not is the only
difference between a traced and an untraced run.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator

import jax

#: The spans' clock, integer nanoseconds (tests replace it).
clock = time.perf_counter_ns


class _ThreadState(threading.local):
    """A thread's open spans (innermost last) and open recordings."""

    def __init__(self) -> None:
        self.stack: list[Span] = []
        self.recordings: list[Recording] = []


_state = _ThreadState()


class Recording:
    """Per-name totals of the spans that closed while it was open."""

    def __init__(self) -> None:
        self._totals: dict[str, list[int]] = {}   # name -> [n, total, self]

    def add(self, name: str, total_ns: int, self_ns: int) -> None:
        t = self._totals.setdefault(name, [0, 0, 0])
        t[0] += 1
        t[1] += total_ns
        t[2] += self_ns

    def summary(self) -> dict[str, dict[str, float]]:
        """``{name: {"n", "total_s", "self_s"}}``, names in first-close
        order."""
        return {name: {"n": n, "total_s": tot / 1e9, "self_s": own / 1e9}
                for name, (n, tot, own) in self._totals.items()}


@contextlib.contextmanager
def recording() -> Iterator[Recording]:
    """Record the spans that close on this thread until the block ends."""
    rec = Recording()
    _state.recordings.append(rec)
    try:
        yield rec
    finally:
        _state.recordings.remove(rec)


class Span:
    """One open or closed span; use it through :func:`span`."""

    __slots__ = ("name", "elapsed_ns", "_annotation", "_start", "_child_ns")

    def __init__(self, name: str) -> None:
        self.name = name
        self.elapsed_ns = 0

    @property
    def elapsed_s(self) -> float:
        return self.elapsed_ns / 1e9

    def __enter__(self) -> "Span":
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        _state.stack.append(self)
        self._child_ns = 0
        self._start = clock()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed_ns = clock() - self._start
        stack = _state.stack
        stack.pop()
        if stack:
            stack[-1]._child_ns += self.elapsed_ns
        for rec in _state.recordings:
            rec.add(self.name, self.elapsed_ns,
                    self.elapsed_ns - self._child_ns)
        self._annotation.__exit__(*exc)


def span(name: str) -> Span:
    """A context manager that spans the block as ``name``."""
    return Span(name)
