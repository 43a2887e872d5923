"""Host spans, the compile counter and the host's usage, from the
benchmark's side of the program's calls.

``CompileClock`` is a copy of the repository's ``chip_smoke.CompileClock``
(a ``jax.monitoring`` listener).  ``Spans`` wraps the methods of one
``Engine`` instance: each call becomes a ``jax.profiler.TraceAnnotation``
named ``chipbench.<name>`` (so the trace can say what the host was doing
while the device idled) and adds its seconds to a running total.
``HostUsage`` reads what the process did over an interval (CPU seconds,
page faults, context switches, seconds in the garbage collector), so a
wave that takes longer than its peers can be told apart: host work,
waiting, or a process that was not scheduled.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import gc
import resource
import threading
import time

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

#: Engine methods wrapped, by span name.  The service plans each distinct
#: query through ``Engine.plan``; ``dispatch_batch`` groups (through
#: ``group_batch``: lookup ranges, capacity estimates, buckets) and
#: dispatches; ``harvest_batch`` waits, copies answers to the host and
#: climbs the overflow ladder.
ENGINE_METHODS = {"plan": "plan", "group_batch": "group_batch",
                  "dispatch_batch": "dispatch", "harvest_batch": "harvest"}


class CompileClock:
    """Seconds JAX spent lowering and compiling (summed over threads), and
    the number of backend compiles, while registered (a context manager)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self._lock = threading.Lock()

    def _listen(self, event: str, duration_secs: float, **_):
        if event in _COMPILE_EVENTS:
            with self._lock:
                self.seconds += duration_secs
                self.compiles += event == _BACKEND_COMPILE

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


class Spans:
    """Seconds per span name, on the host clock."""

    def __init__(self):
        self.seconds: dict = collections.defaultdict(float)

    @contextlib.contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation("chipbench." + name):
            yield
        self.seconds[name] += time.perf_counter() - t0

    def instrument(self, engine) -> None:
        """Wrap ``ENGINE_METHODS`` on this engine instance only."""
        for method, name in ENGINE_METHODS.items():
            setattr(engine, method, self._wrap(getattr(engine, method), name))

    def _wrap(self, fn, name: str):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped


class HostUsage:
    """This process's usage, read with ``snapshot`` and differenced with
    ``delta``: user and system CPU seconds (all threads, the runtime's
    included), minor and major page faults, voluntary and involuntary
    context switches, and seconds in the garbage collector."""

    def __init__(self):
        self.gc_s = 0.0
        self._gc_t0 = None
        gc.callbacks.append(self._gc)

    def _gc(self, phase: str, _info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def snapshot(self) -> dict:
        r = resource.getrusage(resource.RUSAGE_SELF)
        return {"wall_s": time.perf_counter(), "user_s": r.ru_utime,
                "sys_s": r.ru_stime, "minflt": r.ru_minflt,
                "majflt": r.ru_majflt, "nvcsw": r.ru_nvcsw,
                "nivcsw": r.ru_nivcsw, "gc_s": self.gc_s}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}

    def close(self) -> None:
        gc.callbacks.remove(self._gc)
