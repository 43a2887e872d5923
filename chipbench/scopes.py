"""What the program's own scopes and spans say about a traced window.

The plan walker (``core.relational``, ``core.backend``) wraps each
operator and each inner primitive in a ``jax.named_scope``; the device
ops of a TPU trace carry no ``op_name``, so each op's scope comes from
the ``op_name`` of its HLO instruction in the trace's metadata plane
(:mod:`chipbench.opnames`).  The program's host spans
(``repro.core.tracing``) are ``cpqx.*`` ``TraceAnnotation`` events on
the host plane.  The window is the harness's ``chipbench.window`` span,
as in :func:`chipbench.trace.reduce`.  All times are in seconds.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os

from . import trace

#: Prefix of the program's host spans (``repro.core.tracing``).
PROGRAM_PREFIX = "cpqx."
#: The plan walker's ``jax.named_scope`` names (``core.relational``'s
#: primitives, ``core.backend``'s plan operators).  A device op is
#: attributed to the innermost primitive scope of its ``op_name``, else
#: to its innermost plan-operator scope, else to ``unscoped``.
PRIMITIVE_SCOPES = ("sort", "search", "compact", "expand")
OPERATOR_SCOPES = ("lookup", "materialize", "conj_classes", "join",
                   "conj_pairs", "conj_id", "identity", "recap")
UNSCOPED = "unscoped"
#: Least share of the walker modules' device seconds that has to carry a
#: scope for the per-scope seconds to be reported.  An executable loaded
#: from a persistent compile cache keyed without metadata carries the
#: ``op_name``s of whichever build compiled it first: where that build
#: had no scopes, its ops read ``unscoped`` and the scopes' seconds would
#: read low, so they are left out instead.
MIN_SCOPE_COVERAGE = 0.95
#: Idle time under no program span.
OUTSIDE_SPANS = "outside_spans"


def scope_of(op_name: str) -> str:
    """The walker scope an ``op_name`` names.  Its last component is the
    op's own primitive, not a scope; under ``vmap`` a scope reads
    ``vmap(<scope>)``."""
    parts = [p[5:-1] if p.startswith("vmap(") and p.endswith(")") else p
             for p in op_name.split("/")[:-1]]
    for names in (PRIMITIVE_SCOPES, OPERATOR_SCOPES):
        for p in reversed(parts):
            if p in names:
                return p
    return UNSCOPED


def program_spans(profile) -> list:
    """(start_s, end_s, name) of the program's spans, ``cpqx.``
    stripped, from every host plane."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend((e.start_ns * 1e-9, e.end_ns * 1e-9,
                        e.name[len(PROGRAM_PREFIX):])
                       for e in line.events
                       if e.name.startswith(PROGRAM_PREFIX))
    return out


def innermost(spans, pieces) -> dict:
    """Seconds of the sorted, disjoint ``(start, end)`` pieces by the
    innermost of the ``(start, end, name)`` spans open over them (the one
    opened last: spans of one thread nest), and ``OUTSIDE_SPANS`` where
    none is open."""
    points = sorted({p for s, e, _ in spans for p in (s, e)})
    labelled = []  # (start, end, name) segments between span boundaries
    for a, b in zip(points, points[1:]):
        open_ = [(s, -e, n) for s, e, n in spans if s <= a and e >= b]
        if open_:
            labelled.append((a, b, max(open_)[2]))
    out: dict = collections.defaultdict(float)
    i = 0
    for ps, pe in pieces:
        covered = 0.0
        while i < len(labelled) and labelled[i][1] <= ps:
            i += 1
        j = i
        while j < len(labelled) and labelled[j][0] < pe:
            s, e, name = labelled[j]
            part = min(e, pe) - max(s, ps)
            if part > 0:
                out[name] += part
                covered += part
            j += 1
        out[OUTSIDE_SPANS] += (pe - ps) - covered
    return dict(out)


def walker_op_scopes(plane, op_names: dict, lo: float, hi: float,
                     walker_modules) -> list:
    """(start_s, end_s, op label, scope) of every op of the walker
    modules' events on a device plane, clipped to ``[lo, hi]``.  An op
    belongs to the module event it starts in; its scope comes from the
    ``op_name`` of its instruction in that program (``op_names``, by
    module event name, from :func:`chipbench.opnames.program_op_names`)."""
    mods = sorted((s, e, n)
                  for s, e, n in trace.events(plane, "XLA Modules")
                  if any(x in n for x in walker_modules))
    starts = [m[0] for m in mods]
    out = []
    for s, e, name in trace.events(plane, "XLA Ops"):
        k = bisect.bisect_right(starts, s) - 1
        if e <= lo or s >= hi or k < 0 or s >= mods[k][1]:
            continue
        head = name.split(" = ", 1)[0].lstrip("%")
        op_name = op_names.get(mods[k][2], {}).get(head, "")
        out.append((max(s, lo), min(e, hi), trace.op_label(name),
                    scope_of(op_name)))
    return out


def reduce_program(trace_dir: str,
                   walker_modules=("run_plan_batch", "run_union_batch"),
                   top: int = 10) -> dict:
    """What the program's scopes and spans say about the traced window
    (averaged over the device planes, in seconds):

    ``scopes``       device seconds of the walker by scope, each the union
                     of its ops' intervals (a ``while`` and the ops of its
                     body count once); empty where ``scope_coverage`` is
                     under :data:`MIN_SCOPE_COVERAGE`;
    ``walker_ops_s`` the union of all walker ops, ``scoped_s`` of those
                     with a scope (not ``unscoped``), ``unscoped_s`` of
                     those without;
    ``scope_coverage`` ``scoped_s`` over the walker modules' seconds
                     (``trace.reduce``'s ``walker_s``);
    ``op_scopes``    for the walker's top ops by label, seconds by scope;
    ``program_gaps`` idle seconds by the innermost program span open
                     over them (``cpqx.*``), most first.
    """
    from . import opnames

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    path = max(files, key=os.path.getmtime)
    profile = trace.load(trace_dir)
    with open(path, "rb") as f:
        op_names = opnames.program_op_names(f.read())
    windows = [(s, e) for s, e, n in trace.host_spans(profile)
               if trace.SPAN_PREFIX + n == trace.WINDOW]
    lo, hi = windows[0]
    spans = program_spans(profile)
    planes = trace.device_planes(profile)
    scopes: collections.Counter = collections.Counter()
    by_label: dict = collections.defaultdict(collections.Counter)
    walker_s = walker_ops_s = scoped_s = 0.0
    gap_s: collections.Counter = collections.Counter()
    for plane in planes:
        ops = walker_op_scopes(plane, op_names, lo, hi, walker_modules)
        walker_s += trace.module_seconds(plane, lo, hi, walker_modules)
        walker_ops_s += trace.busy(ops, lo, hi)
        scoped_s += trace.busy([o for o in ops if o[3] != UNSCOPED], lo, hi)
        for scope in {o[3] for o in ops}:
            scopes[scope] += trace.busy([o for o in ops if o[3] == scope],
                                        lo, hi)
        for s, e, label, scope in ops:
            by_label[label][scope] += e - s
        op_events = (trace.events(plane, "XLA Ops")
                     or trace.events(plane, "XLA Modules"))
        idle = trace.gaps(trace.union(op_events, lo, hi), lo, hi)
        gap_s.update(innermost(spans, idle))
    n = len(planes)
    heavy = sorted(by_label, key=lambda k: -sum(by_label[k].values()))[:top]
    coverage = scoped_s / walker_s if walker_s > 0 else 0.0
    return {
        "scopes": {k: v / n for k, v in scopes.most_common()}
        if coverage >= MIN_SCOPE_COVERAGE else {},
        "walker_ops_s": walker_ops_s / n,
        "scoped_s": scoped_s / n,
        "unscoped_s": scopes[UNSCOPED] / n,
        "scope_coverage": coverage,
        "op_scopes": {k: {s: v / n for s, v in by_label[k].most_common()}
                      for k in heavy},
        "program_gaps": [[k, v / n] for k, v in gap_s.most_common(top)],
    }
