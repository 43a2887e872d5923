"""Reduction of a profiler trace (``.xplane.pb``) to per-layer numbers.

Read with ``jax.profiler.ProfileData``.  Device planes are the planes
named ``/device:<kind>:<n>`` that hold XLA lines; their op events (the ``XLA Ops`` line) give
busy time, their module events (the ``XLA Modules`` line) give the time
of each jitted program.  The harness's own host spans (``chipbench.*``
``TraceAnnotation`` events on the host plane) give the traced window and
what the host was doing in each idle gap.  All times are in seconds.
"""

from __future__ import annotations

import collections
import glob
import os
import re

SPAN_PREFIX = "chipbench."
WINDOW = SPAN_PREFIX + "window"
#: Host spans an idle gap can be attributed to, innermost first.
GAP_SPANS = ("plan", "group_batch", "harvest", "dispatch", "wave")


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def events(plane, line_name: str) -> list:
    """(start_s, end_s, name) of every event on the named line."""
    out = []
    for line in plane.lines:
        if line.name == line_name:
            out.extend((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                       for e in line.events)
    return out


def op_label(name: str) -> str:
    """A device op's short name: the HLO instruction's name, with the
    fusion kind where it has one (``fusion.253 kCustom``).  Trace events
    carry the whole HLO instruction text."""
    head = name.split(" = ", 1)[0].lstrip("%")
    kind = re.search(r"kind=(k\w+)", name)
    return f"{head} {kind.group(1)}" if kind else head


def device_planes(profile) -> list:
    """The device planes that run XLA programs (a TPU trace also has
    device planes of other kinds, such as ``/device:CUSTOM:...``)."""
    planes = [p for p in profile.planes if p.name.startswith("/device:")
              and any(line.name in ("XLA Ops", "XLA Modules")
                      for line in p.lines)]
    if not planes:
        raise ValueError("the trace has no device plane")
    return planes


def host_spans(profile) -> list:
    """(start_s, end_s, name) of the harness's spans, ``chipbench.``
    stripped, from every host plane."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend((e.start_ns * 1e-9, e.end_ns * 1e-9,
                        e.name[len(SPAN_PREFIX):])
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIX))
    return out


def union(intervals, lo: float, hi: float) -> list:
    """The union of ``(start, end, ...)`` intervals clipped to
    ``[lo, hi]``, as sorted disjoint ``(start, end)`` pairs."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals
                     if e > lo and s < hi)
    merged: list = []
    for s, e in clipped:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(busy_union, lo: float, hi: float) -> list:
    """The idle ``(start, end)`` gaps of ``[lo, hi]`` around a union."""
    out, t = [], lo
    for s, e in busy_union:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute_gaps(idle, spans) -> dict:
    """Seconds of idle time by what the host was doing: each gap (or the
    part of it a span covers) goes to the innermost span of ``GAP_SPANS``
    open over it, and what no span covers to ``between_waves``."""
    out: dict = collections.defaultdict(float)
    by_name = collections.defaultdict(list)
    for s, e, name in spans:
        by_name[name].append((s, e))
    for gs, ge in idle:
        left = [(gs, ge)]
        for name in GAP_SPANS:
            rest = []
            for ps, pe in left:
                cut = union([(max(s, ps), min(e, pe))
                             for s, e in by_name.get(name, ())
                             if e > ps and s < pe], ps, pe)
                out[name] += sum(e - s for s, e in cut)
                rest.extend(gaps(cut, ps, pe))
            left = rest
        out["between_waves"] += sum(e - s for s, e in left)
    return dict(out)


def module_seconds(plane, lo: float, hi: float, names) -> float:
    """Device seconds of the modules whose name contains one of
    ``names``, clipped to the window."""
    return busy([(s, e) for s, e, n in events(plane, "XLA Modules")
                 if any(x in n for x in names)], lo, hi)


def reduce(profile, walker_modules=("run_plan_batch", "run_union_batch"),
           top: int = 10) -> dict:
    """Busy and window seconds (busy averaged over the device planes),
    walker seconds, the top device ops and the idle gaps by host span,
    over the window the ``chipbench.window`` span marks."""
    spans = host_spans(profile)
    windows = [(s, e) for s, e, n in spans if SPAN_PREFIX + n == WINDOW]
    if not windows:
        raise ValueError("the trace has no chipbench.window span")
    lo, hi = windows[0]
    planes = device_planes(profile)
    busy_s, walker_s, gap_s = 0.0, 0.0, collections.Counter()
    ops: collections.Counter = collections.Counter()
    for plane in planes:
        op_events = events(plane, "XLA Ops") or events(plane, "XLA Modules")
        u = union(op_events, lo, hi)
        busy_s += sum(e - s for s, e in u)
        walker_s += module_seconds(plane, lo, hi, walker_modules)
        for s, e, name in op_events:
            if e > lo and s < hi:
                ops[op_label(name)] += min(e, hi) - max(s, lo)
        gap_s.update(attribute_gaps(gaps(u, lo, hi), spans))
    n = len(planes)
    return {
        "window_s": hi - lo,
        "busy_s": busy_s / n,
        "walker_s": walker_s / n,
        "device_ops": [[k, v / n] for k, v in ops.most_common(top)],
        "idle_gaps": [[k, v / n] for k, v in gap_s.most_common(top)],
    }
