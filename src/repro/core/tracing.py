"""Host spans of the program, on the profiler's clock.

``span(name, **meta)`` times a block of host work.  It is always on: each
span is a ``jax.profiler.TraceAnnotation`` named ``cpqx.<name>`` (so in a
profiled run every span sits on the device trace's clock, with ``meta``
as the event's stats), and it adds its seconds, its self seconds (its
duration less the spans opened inside it on the same thread) and one call
to in-memory totals.  ``snapshot()`` reads the totals and ``delta()``
subtracts two readings, so a caller measures a window of its own.

The spans, outermost first (``core.service``, ``core.engine``,
``core.backend``, ``core.relational``):

* ``service.flush``; ``service.prepare_round``, ``service.dispatch_round``
  and ``service.finalize_round``, each with the drain round's id
  (``round``);
* ``engine.plan``, ``engine.group_batch``, ``engine.dispatch``,
  ``engine.harvest``;
* inside ``engine.harvest``: ``harvest.wait`` (blocking on the overflow
  words), ``harvest.copy`` (device-to-host copy and lane slicing) and
  ``harvest.retry`` (the overflow ladder's synchronous re-dispatches,
  whose own waits and copies nest inside it).
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: Prefix of every span's name in a profiler trace.
PREFIX = "cpqx."


class SpanTotal(NamedTuple):
    """Totals of one span name: seconds, self seconds and calls."""

    seconds: float = 0.0
    self_seconds: float = 0.0
    calls: int = 0


class SpanTotals:
    """Running totals by span name, safe to add to from many threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self._totals: dict = collections.defaultdict(SpanTotal)
        self._local = threading.local()

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        # each open span on this thread holds the seconds of its children
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        stack.append(0.0)
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(PREFIX + name, **meta):
                yield
        finally:
            dur = time.perf_counter() - t0
            child = stack.pop()
            if stack:
                stack[-1] += dur
            with self._lock:
                s, self_s, calls = self._totals[name]
                self._totals[name] = SpanTotal(s + dur, self_s + dur - child,
                                               calls + 1)

    def snapshot(self) -> dict:
        """``{name: SpanTotal}`` as of now."""
        with self._lock:
            return dict(self._totals)


def delta(before: dict, after: dict) -> dict:
    """``after - before`` per span name, for the names called in between."""
    zero = SpanTotal()
    out = {}
    for name, b in after.items():
        a = before.get(name, zero)
        if b.calls != a.calls:
            out[name] = SpanTotal(b.seconds - a.seconds,
                                  b.self_seconds - a.self_seconds,
                                  b.calls - a.calls)
    return out


#: The process's span totals; ``span`` and ``snapshot`` use them.
TOTALS = SpanTotals()
span = TOTALS.span
snapshot = TOTALS.snapshot
