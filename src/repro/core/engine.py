"""Device query processing with CPQx — Algorithms 3 & 4, backend-agnostic.

The host plans and a *backend* executes.  Planning is cost-based by
default: ``core.optimizer.optimize_query`` reorders join chains, splits
and conjunctions using the exact cardinalities of
:class:`~repro.core.stats.IndexStats` (pulled once per ``rebind``);
``core.query.plan_query`` remains the stats-free syntactic fallback
(``Engine(..., optimize=False)``), and is what the numpy oracle uses.
A plan is compiled once per (plan shape, capacity profile) — plans are
nested tuples, hence hashable jit keys; the per-query *data* (the
(start, len) ranges of each LOOKUP) streams in as traced scalars, so ten
queries of the same template hit one executable.

The physical algebra lives in ``core.backend`` (protocol + the
single-device :class:`~repro.core.backend.LocalBackend`) and
``core.distributed`` (:class:`~repro.core.distributed.ShardedBackend`,
the same plan walker inside one ``shard_map`` over a mesh axis).  The
:class:`Engine` here owns everything backend-independent: planning, the
host-side capacity estimator, the overflow retry schedule (the capacity
ladder itself is specified once, in the ``core.backend`` module
docstring), and plan-shape batching.  Constructing the engine with a
``mesh`` serves the identical API off a sharded index.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .backend import (  # noqa: F401  (QueryCaps/run_plan* are public API)
    OP_JOIN,
    OP_NOP,
    OVF_CLASS,
    OVF_JOIN,
    OVF_PAIR,
    ExecutionBackend,
    LocalBackend,
    QueryCaps,
    _join_pairs,
    default_caps,
    plan_program,
    program_ranges,
    run_plan,
    run_plan_batch,
)
from .index import CPQxIndex
from .optimizer import estimate_plan, optimize_query
from .query import CPQ, plan_query, plan_lookup_seqs, plan_shape
from .stats import IndexStats
from .tracing import span


# ---------------------------------------------------------------------- #
# host driver
# ---------------------------------------------------------------------- #


def _pow2(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


def _has_identity(shape) -> bool:
    if shape[0] == "identity":
        return True
    return any(_has_identity(s) for s in shape[1:]
               if isinstance(s, tuple))


def _joins_pairs(shape) -> bool:
    """Whether a plan shape joins in pair space: a JOIN node, or a LOOKUP
    of several segments (joined one after another)."""
    if shape[0] == "lookup":
        nseg = shape[1] if isinstance(shape[1], int) else len(shape[1])
        return nseg > 1
    return shape[0] == "join" or any(
        _joins_pairs(s) for s in shape[1:] if isinstance(s, tuple))


def _cap_rows(shape, opcodes, caps: QueryCaps, lanes: int) -> int:
    """Rows one dispatch of ``lanes`` lanes is sized for: ``join_cap`` for
    each lane whose plan joins in pair space (the expansion's output is
    its largest relation), ``pair_cap`` for each other lane.  A union
    group (``opcodes`` set) decides lane by lane."""
    if opcodes is not None:
        joins = int((opcodes == OP_JOIN).any(axis=1).sum())
    else:
        joins = lanes if _joins_pairs(shape) else 0
    return joins * caps.join_cap + (lanes - joins) * caps.pair_cap


@dataclasses.dataclass
class LadderTelemetry:
    """The engine's cumulative counters: the capacity ladder, the rows the
    walker is sized for against the rows it answers, and the bytes
    harvest copies to the host (reset on demand, *not* on rebind — they
    track the engine's lifetime traffic).

    Wall-clock hides retries: a query that ladders three rungs before
    fitting looks like one slow query.  These counters make estimator
    regressions (and estimator wins, e.g. from richer statistics or an
    adapted interest set) directly visible, and the ratios of the last
    three say how far capacities and copies exceed the answers.

    ``queries``      — queries evaluated (batch lanes count individually);
    ``dispatches``   — device dispatches, including every retry rung;
    ``retry_rungs``  — ladder rungs climbed past the first attempt,
                       summed per query/lane (0 when the estimate fit);
    ``default_jumps``— escalations that hit the jump-to-default rung
                       (attempt >= 3 — the expensive worst-case dispatch);
    ``union_lanes``  — lanes served through the union executable;
    ``cap_rows``     — rows the walker was sized for, over every dispatch
                       including retry rungs: per lane ``join_cap`` where
                       the plan joins in pair space, else ``pair_cap``;
    ``answer_pairs`` — answer rows returned to callers;
    ``d2h_bytes``    — bytes harvest copied from the device to the host,
                       as the backend that copied them reports.
    """

    queries: int = 0
    dispatches: int = 0
    retry_rungs: int = 0
    default_jumps: int = 0
    union_lanes: int = 0
    cap_rows: int = 0
    answer_pairs: int = 0
    d2h_bytes: int = 0

    def snapshot(self) -> "LadderTelemetry":
        return dataclasses.replace(self)

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)


@dataclasses.dataclass
class _Group:
    """One dispatch unit of a batch: a same-shape bucket, or a union
    group (``opcodes`` set, ``shape`` None) of mixed-shape stragglers."""

    shape: object
    caps: QueryCaps
    members: list
    ranges: np.ndarray
    opcodes: np.ndarray | None = None
    stack_size: int = 0
    handle: object = None


@dataclasses.dataclass
class BatchHandle:
    """In-flight batch: returned by :meth:`Engine.dispatch_batch`, settled
    by :meth:`Engine.harvest_batch`.  Between the two calls the device is
    executing every group while the host is free to plan the next batch —
    the service's pipelined drain lives on exactly this gap."""

    results: list
    groups: list


class Engine:
    """Query engine bound to a built index.

    ``mesh``/``axis``/``cluster`` select the execution backend: ``None``
    (default) binds the single-device :class:`LocalBackend`; a mesh binds
    a :class:`~repro.core.distributed.ShardedBackend` that shards the
    index over the mesh axis and evaluates every plan inside one
    ``shard_map``; ``cluster=n`` (an int, or a pre-built
    :class:`~repro.core.cluster.ClusterRuntime`) binds a
    :class:`~repro.core.cluster.ClusterBackend` serving off ``n``
    persistent worker *processes* driven over an instruction stream.
    Whichever way, the public API — ``execute``, ``execute_batch``,
    ``rebind`` — is identical, and answers are bit-identical.

    ``optimize`` selects the planner: True (default) runs the cost-based
    optimizer over the index statistics; False pins the syntactic
    ``plan_query`` (the stats-free fallback — what the oracle and the
    pre-PR-4 engine used), which benchmarks use as the baseline.

    ``cost_table`` (a :class:`~repro.core.costmodel.DeviceCostTable`)
    upgrades the row-count objective to calibrated device nanoseconds:
    the planner prices per-stage dispatch constants and
    :meth:`estimate_caps` picks the starting capacity rung with the
    minimal expected cost *including retry risk* instead of the pure row
    bound.  None (default) keeps today's behavior bit-for-bit.  The
    table survives :meth:`rebind` — like the telemetry, it describes the
    device, not the index.
    """

    def __init__(self, index: CPQxIndex, mesh=None, axis: str = "engine",
                 optimize: bool = True, cost_table=None, cluster=None):
        if mesh is not None and cluster is not None:
            raise ValueError("mesh and cluster are mutually exclusive "
                             "backend selectors")
        self.mesh = mesh
        self.axis = axis
        self.cluster = cluster
        self.optimize = optimize
        self.cost_table = cost_table
        self.telemetry = LadderTelemetry()
        self.rebind(index)

    def rebind(self, index: CPQxIndex,
               stats: IndexStats | None = None) -> None:
        """Swap in a new index (a maintenance flush or a rebuild) in
        place: re-pulls the host-side statistics view (optimizer +
        capacity estimator) and the default caps, and rebuilds the
        backend — for a mesh engine that reshards the flushed arrays.
        Compiled executables are keyed on (plan shape, caps, n_vertices)
        — not on the index identity — so traffic after a rebind keeps
        hitting the same jit cache as long as the flushed arrays keep
        their capacities.

        ``stats`` optionally supplies a pre-built statistics view for
        this exact index — a checkpoint restore passes one whose
        endpoint cache is pre-warmed from the donor, and the sharded
        path can pass its replicated-leaf view — skipping the default
        ``IndexStats.from_index`` pull."""
        self.index = index
        self._available = index.available_seqs() if index.interests is not None else None
        # the statistics view: per-class pair counts, the l2c class table
        # and per-seq prefix sums (a few KB — pulled once per rebind, so
        # a maintenance flush refreshes what the optimizer plans against)
        self.stats = stats if stats is not None else IndexStats.from_index(index)
        self._class_sizes = self.stats.class_sizes
        self._l2c_host = self.stats.l2c_cls
        self._default_caps = default_caps(index)  # one device sync, here
        if self.cluster is not None:
            from .cluster import ClusterBackend, ClusterRuntime

            prev = getattr(self, "backend", None)
            if isinstance(prev, ClusterBackend):
                prev.reshard(index)  # one FLUSH_REBIND/INTEREST broadcast
            elif isinstance(self.cluster, ClusterRuntime):
                if not self.cluster.started:
                    self.cluster.start(index)
                self.backend = ClusterBackend(self.cluster)
                if self.cluster.index is not index:
                    self.backend.reshard(index)
            else:
                self.backend = ClusterBackend.from_index(
                    index, int(self.cluster))
        elif self.mesh is None:
            self.backend: ExecutionBackend = LocalBackend(
                index.arrays, index.n_vertices)
        else:
            from .distributed import ShardedBackend  # engine <- distributed is one-way

            prev = getattr(self, "backend", None)
            if isinstance(prev, ShardedBackend) and prev.mesh is self.mesh:
                prev.reshard(index)  # keep the compiled plan cache warm
            else:
                self.backend = ShardedBackend.from_index(
                    index, self.mesh, axis=self.axis)

    def plan(self, q: CPQ):
        """Compile ``q`` to a physical plan: cost-optimized against the
        index statistics by default, syntactic (``plan_query``) when the
        engine was constructed with ``optimize=False``."""
        with span("engine.plan"):
            if self.optimize:
                return optimize_query(q, self.index.k, self.stats,
                                      available=self._available,
                                      cost_table=self.cost_table)
            return plan_query(q, self.index.k, available=self._available)

    def predict_cost_ns(self, plan) -> float:
        """Calibrated prediction of one dispatch of ``plan`` in device
        nanoseconds — what the service's SLO-aware shedding prices a
        request at *before* admitting it.  0.0 without a cost table (the
        row-count objective has no time unit), so SLO shedding is
        automatically inert on uncalibrated engines."""
        if self.cost_table is None:
            return 0.0
        est = estimate_plan(plan, self.stats, cost_table=self.cost_table)
        return float(est.cost_ns)

    def estimate_caps(self, ranges: np.ndarray, shape,
                      plan=None) -> QueryCaps:
        """Optimistic per-query capacities from the host index stats.

        With a ``plan``, the cost model walks it and sizes the pair cap
        to 2x the largest *estimated intermediate* (for a class-space
        conjunction that is a sound upper bound — the min operand — so a
        selective conjunction gets caps near its answer instead of near
        its largest lookup), and the join cap to the plan's largest
        pre-dedup witness bound (``PlanEstimate.max_join`` — with the
        endpoint/fanout statistics of PR 5 that bound is sound at leaf
        joins, so skewed hub fanout no longer ladders what the uniform
        estimate used to under-size).  Without one, the stats-free
        fallback keeps the PR-1 behavior: 2x the largest single-lookup
        materialization.  Either way the class cap covers the largest
        LOOKUP's class list exactly, and the sticky-overflow retry
        (doubling along the same power-of-two ladder, so executables are
        shared) keeps undersized estimates exact."""
        max_classes, max_pairs = 1, 1
        for start, length in np.asarray(ranges, np.int64).reshape(-1, 2):
            max_classes = max(max_classes, int(length))
            if plan is None:  # the cost model supersedes the per-leaf sum
                cls = self._l2c_host[start: start + length]
                max_pairs = max(max_pairs, int(self._class_sizes[cls].sum()))
        headroom = 2
        max_join = 0
        risky = False
        if plan is not None:
            est = estimate_plan(plan, self.stats, cost_table=self.cost_table)
            max_pairs = int(max(est.max_pairs, est.pairs))
            # conjunction bounds are exact (min operand) but join outputs
            # are *estimates* — give plans with pair-space joins double
            # the headroom so residual misestimates rarely ladder
            risky = est.max_join > 0
            headroom = 4 if risky else 2
            max_join = int(min(est.max_join, 4 * self._default_caps.join_cap))
        floor = self.index.n_vertices if _has_identity(shape) else 0
        # never *start* above the worst-case default (the retry ladder can
        # still climb past it if a join genuinely needs more)
        ceiling = max(self._default_caps.pair_cap, _pow2(floor))
        pair_cap = min(_pow2(max(64, headroom * max_pairs, floor)), ceiling)
        if self.cost_table is not None and plan is not None:
            # calibrated rung selection: among the tight rung and the
            # headroom rungs above it, start at the one whose *expected*
            # cost — this dispatch plus the overflow-risk-weighted retry
            # at the next rung — is minimal.  A cheap dispatch (small
            # fixed constants) makes optimistic starts worth the retry
            # risk; an expensive one buys headroom up front.
            base = min(_pow2(max(64, max_pairs, floor)), ceiling)
            cands = sorted({min(c, ceiling) for c in
                            (base, 2 * base, 4 * base, pair_cap)})
            pair_cap = min(cands, key=lambda c: self.cost_table.
                           expected_dispatch_ns(c, max_pairs, risky))
        join_cap = max(2 * pair_cap, _pow2(max_join))
        return QueryCaps(class_cap=_pow2(max(16, max_classes)),
                         pair_cap=pair_cap, join_cap=join_cap)

    def lookup_ranges(self, plan) -> np.ndarray:
        """(n_lookups, 2) int32 (start, len) rows, in plan order — the
        per-query data streamed into the compiled plan executable."""
        seqs = plan_lookup_seqs(plan)
        ranges = np.array(
            [self.index.lookup_range(s) for s in seqs], np.int32
        ).reshape(-1, 2)
        ranges[:, 1] = ranges[:, 1] - ranges[:, 0]  # (start, len)
        return ranges

    def execute(self, q: CPQ, caps: QueryCaps | None = None,
                max_retries: int = 10) -> np.ndarray:
        """Evaluate ⟦q⟧_G; returns (n, 2) numpy array of s-t pairs."""
        plan = self.plan(q)
        ranges = self.lookup_ranges(plan)
        shape = plan_shape(plan)
        caps = caps or self.estimate_caps(ranges, shape,
                                          plan if self.optimize else None)
        self.telemetry.queries += 1
        for attempt in range(max_retries):
            self.telemetry.dispatches += 1
            self.telemetry.cap_rows += _cap_rows(shape, None, caps, 1)
            copied = self.backend.d2h_bytes
            rows, overflow = self.backend.run(shape, caps, ranges)
            self.telemetry.d2h_bytes += self.backend.d2h_bytes - copied
            if not overflow:
                self.telemetry.answer_pairs += len(rows)
                return rows
            self.telemetry.retry_rungs += 1
            caps = self._escalate(caps, attempt, int(overflow))
            if attempt >= 3:
                self.telemetry.default_jumps += 1
        raise RuntimeError("query overflow not resolved after retries")

    def execute_rpq(self, q, srcs=None, dsts=None,
                    n_labels: int | None = None,
                    info=None) -> np.ndarray:
        """Evaluate a regular path query (:mod:`repro.core.rpq` AST) as
        an automaton fixpoint of per-sequence lookups; returns (n, 2)
        s-t pairs like :meth:`execute`.  Every device dispatch inside
        the fixpoint is an ordinary :meth:`execute_batch` round, so the
        capacity ladder, telemetry, the optimizer's query-time splits
        and (on a mesh engine) the sharded backend all apply unchanged.
        ``srcs``/``dsts`` pin the endpoints (the Cypher ``WHERE``
        lowering); ``info`` (an ``rpq.FixpointInfo``) captures iteration
        telemetry."""
        from .rpq import evaluate  # engine <- rpq is one-way at runtime

        return evaluate(self, q, srcs=srcs, dsts=dsts,
                        n_labels=n_labels, info=info)

    def _escalate(self, caps: QueryCaps, attempt: int,
                  kinds: int) -> QueryCaps:
        """Overflow-retry schedule (the host half of the ladder contract
        in the ``core.backend`` docstring): double the capacities named
        by ``kinds`` (``OVF_*`` bits; the others were large enough), and
        after a few failed attempts from a (possibly far-too-tight)
        estimate jump them to at least the worst-case default so the
        ladder can't exhaust below the caps the pre-estimator engine
        would have started from.  Growing only the overflowed kinds keeps
        a generously estimated join capacity from doubling along with an
        undersized pair capacity until it outgrows device memory.
        Early rungs are cheap (small executables), the default rung is
        not — so the jump waits for three doublings, which lets a mildly
        undersized estimate land on a right-sized rung instead of paying
        the worst-case dispatch.  (The default ``max_retries`` is 10 so
        the reachable ceiling past the jump — default x 2^6 — matches
        the pre-optimizer schedule's.)"""
        d = self._default_caps
        grown = []
        for bit, cap, dflt in ((OVF_CLASS, caps.class_cap, d.class_cap),
                               (OVF_PAIR, caps.pair_cap, d.pair_cap),
                               (OVF_JOIN, caps.join_cap, d.join_cap)):
            if kinds & bit:
                cap = max(2 * cap, dflt) if attempt >= 3 else 2 * cap
            grown.append(cap)
        return QueryCaps(*grown)

    def execute_batch(self, queries, caps: QueryCaps | None = None,
                      max_retries: int = 10, plans: list | None = None,
                      min_bucket: int = 4, union: bool = False) -> list:
        """Evaluate many queries; returns one (n, 2) array per query, in
        input order.  Equivalent to ``dispatch_batch`` + ``harvest_batch``
        back to back — callers that want to overlap host work with device
        execution use the two halves directly."""
        handle = self.dispatch_batch(queries, caps=caps, plans=plans,
                                     min_bucket=min_bucket, union=union)
        return self.harvest_batch(handle, max_retries=max_retries)

    def dispatch_batch(self, queries, caps: QueryCaps | None = None,
                       plans: list | None = None, min_bucket: int = 4,
                       union: bool = False) -> BatchHandle:
        """Plan, bucket and asynchronously dispatch a batch; returns a
        :class:`BatchHandle` the caller settles with ``harvest_batch``.

        Queries are grouped by (plan *shape*, estimated caps) — labels
        don't change the executable, and the power-of-two capacity
        estimates quantize size-similar queries into shared buckets, so
        a lane never pays for a much larger neighbor.  Buckets smaller
        than ``min_bucket`` merge upward into the next-larger caps rung
        (one dispatch beats a little lane padding).  Each group's lookup
        ranges stack into a (batch, n_lookups, 2) array evaluated by the
        backend (one vmapped dispatch on the local backend).

        With ``union=True`` (and a backend that supports it), the
        *mixed-shape* straggler buckets still smaller than ``min_bucket``
        after same-shape merging fuse into one union-executable group —
        their per-lane programs stream as data — instead of serializing
        into one dispatch per leftover shape.

        ``plans`` lets a caller with a plan cache (the service layer)
        skip re-planning; must align with ``queries``."""
        if not queries:
            return BatchHandle(results=[], groups=[])
        with span("engine.dispatch"):
            groups = self.group_batch(queries, caps=caps, plans=plans,
                                      min_bucket=min_bucket, union=union)
            self.telemetry.queries += len(queries)
            for g in groups:
                self.telemetry.dispatches += 1
                self.telemetry.cap_rows += _cap_rows(
                    g.shape, g.opcodes, g.caps, len(g.members))
                g.handle = self._dispatch_group(g)
        return BatchHandle(results=[None] * len(queries), groups=groups)

    def group_batch(self, queries, caps: QueryCaps | None = None,
                    plans: list | None = None, min_bucket: int = 4,
                    union: bool = False) -> list:
        """The dispatch units :meth:`dispatch_batch` would run for
        ``queries`` (same arguments), planned and bucketed but not
        dispatched — each is one executable, so a caller can compile
        them ahead of traffic."""
        with span("engine.group_batch"):
            return self._group_batch(queries, caps, plans, min_bucket,
                                     union)

    def _group_batch(self, queries, caps, plans, min_bucket, union) -> list:
        if plans is None:
            plans = [self.plan(q) for q in queries]
        all_ranges = [self.lookup_ranges(p) for p in plans]

        shape_groups: dict = {}
        for i, p in enumerate(plans):
            shape = plan_shape(p)
            e = caps or self.estimate_caps(all_ranges[i], shape,
                                           p if self.optimize else None)
            shape_groups.setdefault(shape, {}).setdefault(e, []).append(i)

        work: list = []  # (shape, caps, member indices)
        for shape, by_caps in shape_groups.items():
            if caps is not None:
                work.extend((shape, c, m) for c, m in by_caps.items())
                continue
            buckets = sorted(
                by_caps.items(),
                key=lambda kv: (kv[0].pair_cap, kv[0].join_cap,
                                kv[0].class_cap))
            cur_caps, cur_members = None, []
            for cb, mem in buckets:
                if cur_caps is None:
                    cur_caps, cur_members = cb, list(mem)
                else:
                    cur_caps = QueryCaps(
                        max(cur_caps.class_cap, cb.class_cap),
                        max(cur_caps.pair_cap, cb.pair_cap),
                        max(cur_caps.join_cap, cb.join_cap))
                    cur_members += mem
                if len(cur_members) >= min_bucket:
                    work.append((shape, cur_caps, cur_members))
                    cur_caps, cur_members = None, []
            if cur_caps is not None:
                # undersized largest-caps tail: keep it separate rather
                # than inflating an already-flushed smaller bucket
                work.append((shape, cur_caps, cur_members))

        groups = [_Group(shape, c, m, np.stack([all_ranges[i] for i in m]))
                  for shape, c, m in work]
        if union and self.backend.supports_union:
            groups = self._fuse_stragglers(groups, all_ranges, min_bucket)
        return groups

    def _fuse_stragglers(self, groups: list, all_ranges: list,
                         min_bucket: int) -> list:
        """Fuse the sub-``min_bucket`` shape buckets into one union group
        (caps = elementwise max, programs NOP-padded to the longest)."""
        stragglers = [g for g in groups if len(g.members) < min_bucket]
        if len(stragglers) < 2:
            return groups
        kept = [g for g in groups if len(g.members) >= min_bucket]
        programs = {}
        members, progs, ucaps = [], [], None
        for g in stragglers:
            if g.shape not in programs:
                programs[g.shape] = plan_program(g.shape)
            for i in g.members:
                members.append(i)
                progs.append(programs[g.shape])
            ucaps = g.caps if ucaps is None else QueryCaps(
                max(ucaps.class_cap, g.caps.class_cap),
                max(ucaps.pair_cap, g.caps.pair_cap),
                max(ucaps.join_cap, g.caps.join_cap))
        n_steps = max(len(p) for p, _ in progs)
        stack_size = max(2, max(d for _, d in progs))
        opcodes = np.full((len(members), n_steps), OP_NOP, np.int32)
        step_ranges = np.zeros((len(members), n_steps, 2), np.int32)
        for lane, (i, (prog, _)) in enumerate(zip(members, progs)):
            opcodes[lane, : len(prog)] = prog
            step_ranges[lane] = program_ranges(prog, all_ranges[i], n_steps)
        self.telemetry.union_lanes += len(members)
        kept.append(_Group(None, ucaps, members, step_ranges,
                           opcodes=opcodes, stack_size=stack_size))
        return kept

    def _dispatch_group(self, g: _Group):
        if g.opcodes is not None:
            return self.backend.run_union_batch_async(
                g.opcodes, g.caps, g.stack_size, g.ranges)
        return self.backend.run_batch_async(g.shape, g.caps, g.ranges)

    def harvest_batch(self, handle: BatchHandle,
                      max_retries: int = 10) -> list:
        """Block on a dispatched batch and drive the overflow ladder.

        Overflow is tracked per lane: only the queries whose own sticky
        flag tripped are retried (synchronously), at doubled capacities.
        ``retry_rungs`` and ``default_jumps`` both count per lane — a
        4-lane bucket that jumps to default caps records 4 jumps, the
        same as 4 single-query executes would."""
        with span("engine.harvest"):
            copied = self.backend.d2h_bytes
            try:
                return self._harvest_batch(handle, max_retries)
            finally:
                self.telemetry.d2h_bytes += self.backend.d2h_bytes - copied

    def _harvest_batch(self, handle: BatchHandle, max_retries: int) -> list:
        results = handle.results
        for g in handle.groups:
            if max_retries <= 0:
                raise RuntimeError("query overflow not resolved after retries")
            pending = np.asarray(g.members, np.int64)
            ranges = g.ranges
            opcodes = g.opcodes
            grp_caps = g.caps
            rows, overflow = self.backend.harvest_batch(g.handle)
            attempt = 0
            while True:
                for lane, r in enumerate(rows):
                    if r is not None:
                        results[pending[lane]] = r
                        self.telemetry.answer_pairs += len(r)
                overflow = np.asarray(overflow)
                tripped = overflow != 0
                if not tripped.any():
                    break
                # only the lanes whose own flag tripped climb a rung, by
                # the capacities any of them overflowed
                self.telemetry.retry_rungs += int(tripped.sum())
                if attempt >= 3:
                    self.telemetry.default_jumps += int(tripped.sum())
                grp_caps = self._escalate(
                    grp_caps, attempt,
                    int(np.bitwise_or.reduce(overflow[tripped])))
                attempt += 1
                if attempt >= max_retries:
                    raise RuntimeError(
                        "query overflow not resolved after retries")
                pending = pending[tripped]
                ranges = ranges[tripped]
                if opcodes is not None:
                    opcodes = opcodes[tripped]
                self.telemetry.dispatches += 1
                self.telemetry.cap_rows += _cap_rows(
                    g.shape, opcodes, grp_caps, len(pending))
                with span("harvest.retry"):
                    if opcodes is not None:
                        rows, overflow = self.backend.run_union_batch(
                            opcodes, grp_caps, g.stack_size, ranges)
                    else:
                        rows, overflow = self.backend.run_batch(
                            g.shape, grp_caps, ranges)
        return results
