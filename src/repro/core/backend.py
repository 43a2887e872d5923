"""Execution backends — the physical algebra behind the CPQx engine.

The planner (``core.query``) compiles a CPQ to a physical plan; *how*
that plan's operators execute is a backend concern.  This module defines
the backend protocol and writes the plan walker (:func:`run_plan_ops`)
ONCE against it:

  * :class:`PlanOps` — the operator protocol (lookup / materialize /
    conjoin / join / identity over capacity-padded relations) with the
    single-device math as shared default implementations;
  * :class:`LocalOps` — the protocol bound to one device's
    ``DeviceIndexArrays`` (the classic engine path);
  * :class:`ExecutionBackend` — the host-facing contract the
    :class:`repro.core.engine.Engine` drives (``run`` / ``run_batch``
    with numpy in, numpy-or-overflow out);
  * :class:`LocalBackend` — ``ExecutionBackend`` over :class:`LocalOps`
    (one jit per (plan shape, caps), vmapped for batches).

``repro.core.distributed.ShardedBackend`` implements the same two
protocols over a mesh: it subclasses :class:`PlanOps` with
repartitioning materialize/join and runs the *same* walker inside one
``shard_map``, so the local and distributed engines cannot drift — they
are one algorithm over two array layouts.

Evaluation is two-stage exactly as in the paper:
  * class space: LOOKUP returns sorted class-id lists; CONJUNCTION is a
    sorted intersection of class ids (Prop. 4.1); IDENTITY is a gather of
    the cycle-purity flag (classes are cycle-pure by construction).
  * pair space: after any JOIN the evaluator materializes s-t pairs
    (expansion join through I_c2p) and proceeds with sorted set algebra.

The overflow-ladder contract (canonical statement — ``core.engine``,
``core.distributed`` and the capacity estimators all defer here):
every relation is capacity-padded, and any operator that would drop
rows sets a *sticky* overflow flag that propagates to the plan's final
result instead of raising.  The flag is a word of bits, one per
capacity kind (class, pair or join: the ``OVF_*`` bits): the operator
that drops rows sets its capacity's bit, and ORing inputs carries the
bits to the plan's result.  The host is the only party that reacts: it
re-runs the whole plan with those capacities doubled, and after three
doublings from a (possibly far-too-tight) estimate it jumps them to at
least the worst-case ``default_caps`` so the ladder cannot exhaust
below where a stats-free engine would have started.  All
capacities live on the power-of-two ladder, so retried plans land on
already-compiled executables.  Variations are mechanical, not
semantic: the batched path keeps one sticky flag per lane and retries
only the lanes that tripped; the sharded backend ORs the per-shard
words so every shard and the host agree on the same retry decision.
This is the honest dynamic->static bridge — estimates and optimizer
cost models can be arbitrarily wrong about *sizes* without ever being
wrong about *answers*.

Two serving-oriented extensions (PR 7):

* **async dispatch** — ``run_batch_async`` returns immediately after the
  device dispatch (JAX dispatch is asynchronous); ``harvest_batch``
  blocks and converts.  The service's pipelined drain plans bucket N+1
  on the host while bucket N executes on device.
* **the union executable** — heterogeneous plan *shapes* normally
  serialize into one dispatch per shape.  :func:`plan_program` compiles
  any plan shape into a linear postorder program over a small value
  stack (opcodes below), and :func:`run_union_batch` interprets a whole
  *mixed-shape* batch in ONE vmapped executable: each lane streams its
  own opcode/range rows as data, shorter programs pad with ``OP_NOP``.
  Every step evaluates all candidate operators and selects by opcode
  (the price of shape-generic compilation under vmap), so the union
  path trades per-step redundancy for dispatch amortization — the
  engine reserves it for straggler buckets below ``min_bucket``.
  Union programs run entirely in pair space (lookups materialize
  eagerly); by cycle-purity of classes this is answer-identical to the
  two-stage walker, and the sticky overflow contract is unchanged.
"""

from __future__ import annotations

import abc
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from . import relational as R
from .index import DeviceIndexArrays
from .paths import _recap
from .tracing import span


@dataclasses.dataclass(frozen=True)
class QueryCaps:
    """Static capacities of the compiled plan (jit key)."""

    class_cap: int  # class-id sets
    pair_cap: int  # materialized pair sets
    join_cap: int  # expansion-join outputs (pre-dedup)

    def doubled(self) -> "QueryCaps":
        return QueryCaps(self.class_cap * 2, self.pair_cap * 2, self.join_cap * 2)


#: Capacity kinds an overflow is attributed to: the bits of the
#: relations' overflow word on the query path (0 = no overflow).
OVF_CLASS, OVF_PAIR, OVF_JOIN = 1, 2, 4


def default_caps(index) -> QueryCaps:
    n_pairs = max(16, int(index.arrays.pair_count))
    n_cls = max(16, int(index.arrays.n_classes))
    p2 = 1 << (n_pairs - 1).bit_length()
    c2 = 1 << (n_cls - 1).bit_length()
    return QueryCaps(class_cap=c2, pair_cap=p2, join_cap=2 * p2)


# ---------------------------------------------------------------------- #
# free-function device operators (shared math; backends compose these)
# ---------------------------------------------------------------------- #


def _join_pairs(a: R.Relation, b: R.Relation, join_cap: int, pair_cap: int) -> R.Relation:
    """(v,u) ⋈ (x,y) on u == x -> distinct (v, y).  b sorted by (x, y).
    The expansion overflows ``join_cap``, the deduped result ``pair_cap``."""
    with jax.named_scope("join"):
        out = R.expansion_join(a, b, a_on=[1], out_cols=[("a", 0), ("b", 1)],
                               out_capacity=join_cap, bit=OVF_JOIN)
        out = R.rel_unique(R.rel_sort(out, num_keys=2), 2)
    with jax.named_scope("recap"):
        return _recap(out, pair_cap, OVF_PAIR)


# ---------------------------------------------------------------------- #
# the operator protocol
# ---------------------------------------------------------------------- #


class PlanOps:
    """Device-side operator set a plan executes against.

    Subclasses bind the index arrays (one device's, or one shard's local
    view) as attributes before the walker runs:

    ``l2c_cls``       (l2c_cap,) class ids, ascending within a seq block
    ``class_starts``  (class_cap + 1,) CSR offsets into the c2p arrays
    ``c2p_v, c2p_u``  the I_c2p pair columns the offsets index
    ``class_cyclic``  (class_cap,) 0/1 cycle-purity flags
    ``n_vertices``    static vertex count (IDENTITY)

    The default method bodies are the exact single-device operators; a
    distributed backend overrides the pair-space producers (materialize,
    join, identity, finish) to add exchanges, and inherits the class-space
    ops verbatim — class relations are replicated by the paper's central
    size observation, so their math is layout-independent.
    """

    l2c_cls: jax.Array
    class_starts: jax.Array
    c2p_v: jax.Array
    c2p_u: jax.Array
    class_cyclic: jax.Array
    n_vertices: int

    # ---- class space ---- #

    def lookup_classes(self, start, length, cap: int) -> R.Relation:
        with jax.named_scope("lookup"):
            idx = jnp.arange(cap, dtype=R.I32)
            valid = idx < length
            src = jnp.clip(start + idx, 0, self.l2c_cls.shape[0] - 1)
            ids = jnp.where(valid, self.l2c_cls[src], R.SENTINEL)
            return R.Relation((ids,), jnp.minimum(length, cap).astype(R.I32),
                              R.overflow_bit(length > cap, OVF_CLASS))

    def conj_classes(self, a: R.Relation, b: R.Relation) -> R.Relation:
        """Prop. 4.1 on device: ``a``'s class ids found in the sorted list
        ``b`` (binary search), compacted.  An undersized ``b`` means
        missing matches, so its overflow is sticky on the result."""
        with jax.named_scope("conj_classes"):
            return R.rel_intersect(a, b, 1)

    def conj_id_classes(self, classes: R.Relation) -> R.Relation:
        with jax.named_scope("conj_id"):
            cyc = self.class_cyclic[
                jnp.clip(classes.cols[0], 0, self.class_cyclic.shape[0] - 1)]
            keep = (cyc == 1) & R.valid_mask(classes)
            return R.rel_compact(classes, keep)

    # ---- pair space ---- #

    def materialize(self, classes: R.Relation, pair_cap: int) -> R.Relation:
        """classes -> sorted distinct (v, u).  Classes are disjoint, so the
        expansion introduces no duplicate pairs: each class id expands to
        its CSR range of the I_c2p columns, then one sort by (v, u)."""
        with jax.named_scope("materialize"):
            cid = jnp.clip(classes.cols[0], 0, self.class_starts.shape[0] - 2)
            lo = self.class_starts[cid]
            cnt = self.class_starts[cid + 1] - lo
            cnt = jnp.where(R.valid_mask(classes), cnt, 0).astype(R.I32)
            _, bj, valid, total = R.expand_ranges(lo, cnt, pair_cap)
            with jax.named_scope("expand"):
                bj = jnp.clip(bj, 0, self.c2p_v.shape[0] - 1)
                v = jnp.where(valid, self.c2p_v[bj], R.SENTINEL)
                u = jnp.where(valid, self.c2p_u[bj], R.SENTINEL)
            rel = R.Relation(
                (v, u), jnp.minimum(total, pair_cap).astype(R.I32),
                classes.overflow | R.overflow_bit(total > pair_cap, OVF_PAIR))
            return R.rel_sort(rel, num_keys=2)

    def join_pairs(self, a: R.Relation, b: R.Relation, join_cap: int,
                   pair_cap: int) -> R.Relation:
        return _join_pairs(a, b, join_cap, pair_cap)

    def conj_pairs(self, a: R.Relation, b: R.Relation) -> R.Relation:
        with jax.named_scope("conj_pairs"):
            return R.rel_intersect(a, b, 2)

    def conj_id_pairs(self, pairs: R.Relation) -> R.Relation:
        with jax.named_scope("conj_id"):
            return R.rel_compact(pairs, pairs.cols[0] == pairs.cols[1])

    def identity_pairs(self, pair_cap: int) -> R.Relation:
        with jax.named_scope("identity"):
            v = jnp.arange(pair_cap, dtype=R.I32)
            m = v < self.n_vertices
            col = jnp.where(m, v, R.SENTINEL)
            return R.Relation(
                (col, col),
                jnp.asarray(min(self.n_vertices, pair_cap), R.I32),
                R.overflow_bit(self.n_vertices > pair_cap, OVF_PAIR))

    # ---- epilogue ---- #

    def finish(self, pairs: R.Relation):
        """Final (relation, overflow word) of a plan — a distributed
        backend reduces the per-shard words here."""
        return pairs, pairs.overflow


class LocalOps(PlanOps):
    """The operator protocol bound to one device's index arrays."""

    def __init__(self, a: DeviceIndexArrays, n_vertices: int):
        self.l2c_cls = a.l2c_cls
        self.class_starts = a.class_starts
        self.c2p_v = a.c2p_v
        self.c2p_u = a.c2p_u
        self.class_cyclic = a.class_cyclic
        self.n_vertices = n_vertices


#: Operators whose compiled form :func:`op_implementations` reports.
SCOPED_OPS = ("conj_classes", "materialize")


def op_implementations(hlo_text: str) -> dict:
    """Which implementation each of :data:`SCOPED_OPS` compiled to, read
    from a compiled program's text (``compiled.as_text()``): "pallas"
    where an instruction under the operator's ``named_scope`` is a
    ``tpu_custom_call``, "xla" where it has only XLA instructions, and
    "absent" where the program holds no instruction of it."""
    out = {}
    for op in SCOPED_OPS:
        # the scope shows as ".../op/..." or, under vmap, ".../vmap(op)/..."
        scope = re.compile(rf"[/(]{op}[)/]")
        lines = [ln for ln in hlo_text.splitlines() if scope.search(ln)]
        if any("tpu_custom_call" in ln for ln in lines):
            out[op] = "pallas"
        else:
            out[op] = "xla" if lines else "absent"
    return out


# ---------------------------------------------------------------------- #
# plan walker — written once against the protocol
# ---------------------------------------------------------------------- #


def run_plan_ops(ops: PlanOps, plan, caps: QueryCaps, lookup_ranges: jax.Array):
    """Execute a physical plan against a :class:`PlanOps` operator set.

    ``lookup_ranges``: (n_lookups, 2) int32 of (start, len) per LOOKUP
    segment, in plan order.  Returns what ``ops.finish`` yields: the pair
    Relation (sorted distinct (v, u)) and its overflow word — 0, or the
    ``OVF_*`` bits of the capacities that overflowed.

    ``plan`` may be a frozen plan or its :func:`repro.core.query.plan_shape`
    — the device computation only depends on the shape (LOOKUP nodes carry
    their segment count; the label values stream in via ``lookup_ranges``).
    """
    counter = [0]

    def next_range():
        i = counter[0]
        counter[0] += 1
        return lookup_ranges[i, 0], lookup_ranges[i, 1]

    def as_pairs(res):
        kind, rel = res
        if kind == "classes":
            return ops.materialize(rel, caps.pair_cap)
        return rel

    def ev(node):
        kind = node[0]
        if kind == "lookup":
            nseg = node[1] if isinstance(node[1], int) else len(node[1])
            start, length = next_range()
            cur = ("classes", ops.lookup_classes(start, length, caps.class_cap))
            for _ in range(nseg - 1):
                start, length = next_range()
                nxt = ops.lookup_classes(start, length, caps.class_cap)
                cur = ("pairs", ops.join_pairs(as_pairs(cur),
                                               ops.materialize(nxt, caps.pair_cap),
                                               caps.join_cap, caps.pair_cap))
            return cur
        if kind == "identity":
            return ("pairs", ops.identity_pairs(caps.pair_cap))
        if kind == "conj_id":
            res = ev(node[1])
            if res[0] == "classes":
                return ("classes", ops.conj_id_classes(res[1]))
            return ("pairs", ops.conj_id_pairs(res[1]))
        left = ev(node[1])
        right = ev(node[2])
        if kind == "conj":
            if left[0] == "classes" and right[0] == "classes":
                return ("classes", ops.conj_classes(left[1], right[1]))
            return ("pairs", ops.conj_pairs(as_pairs(left), as_pairs(right)))
        if kind == "join":
            return ("pairs", ops.join_pairs(as_pairs(left), as_pairs(right),
                                            caps.join_cap, caps.pair_cap))
        raise ValueError(kind)

    return ops.finish(as_pairs(ev(plan)))


# ---------------------------------------------------------------------- #
# jitted local entry points
# ---------------------------------------------------------------------- #


def _run_plan(a: DeviceIndexArrays, plan, caps: QueryCaps, n_vertices: int,
              lookup_ranges: jax.Array):
    return run_plan_ops(LocalOps(a, n_vertices), plan, caps, lookup_ranges)


run_plan = functools.partial(
    jax.jit, static_argnames=("plan", "caps", "n_vertices"))(_run_plan)


@functools.partial(jax.jit, static_argnames=("plan", "caps", "n_vertices"))
def run_plan_batch(a: DeviceIndexArrays, plan, caps: QueryCaps,
                   n_vertices: int, lookup_ranges: jax.Array):
    """Batched :func:`run_plan`: ``lookup_ranges`` is (batch, n_lookups, 2)
    and the whole batch evaluates through one vmapped dispatch of the same
    executable a single query would use.  Returns a batched Relation
    (cols (batch, cap)) and a per-query (batch,) overflow vector — each
    lane's overflow is its own sticky flag, so the host retries only the
    lanes that overflowed."""
    return jax.vmap(lambda r: _run_plan(a, plan, caps, n_vertices, r))(
        lookup_ranges)


# ---------------------------------------------------------------------- #
# the union executable — one dispatch for a mixed-shape batch
# ---------------------------------------------------------------------- #

OP_NOP = 0  # padding past the end of a lane's program
OP_LOOKUP = 1  # push materialize(lookup(start, len))
OP_JOIN = 2  # pop b, pop a, push a ⋈ b
OP_CONJ = 3  # pop b, pop a, push a ∩ b
OP_CONJ_ID = 4  # replace top with its v == u filter
OP_IDENTITY = 5  # push the identity relation

# per-opcode stack-pointer delta and write offset (relative to sp)
_OP_DELTA = (0, 1, -1, -1, 0, 1)
_OP_WRITE = (0, 0, -2, -2, -1, 0)


def plan_program(plan):
    """Compile a plan (or its shape) to the union executable's postorder
    program.  Returns ``(opcodes, stack_depth)`` — opcodes is a list of
    ints, LOOKUP steps consume ``lookup_ranges`` rows in exactly the
    order :func:`run_plan_ops` does (DFS, segments left to right)."""
    prog: list = []
    depth = 0
    max_depth = 0

    def push():
        nonlocal depth, max_depth
        depth += 1
        max_depth = max(max_depth, depth)

    def emit(node):
        nonlocal depth
        kind = node[0]
        if kind == "lookup":
            nseg = node[1] if isinstance(node[1], int) else len(node[1])
            prog.append(OP_LOOKUP)
            push()
            for _ in range(nseg - 1):
                prog.append(OP_LOOKUP)
                push()
                prog.append(OP_JOIN)
                depth -= 1
        elif kind == "identity":
            prog.append(OP_IDENTITY)
            push()
        elif kind == "conj_id":
            emit(node[1])
            prog.append(OP_CONJ_ID)
        elif kind in ("conj", "join"):
            emit(node[1])
            emit(node[2])
            prog.append(OP_CONJ if kind == "conj" else OP_JOIN)
            depth -= 1
        else:
            raise ValueError(kind)

    emit(plan)
    return prog, max_depth


def program_ranges(prog, ranges: np.ndarray, n_steps: int) -> np.ndarray:
    """Step-align one lane's (n_lookups, 2) ranges to its program: LOOKUP
    steps carry their (start, len) row, everything else (0, 0), padded to
    ``n_steps``."""
    out = np.zeros((n_steps, 2), dtype=np.int32)
    j = 0
    for i, op in enumerate(prog):
        if op == OP_LOOKUP:
            out[i] = ranges[j]
            j += 1
    return out


def _run_program_lane(ops: PlanOps, caps: QueryCaps, stack_size: int,
                      opcodes: jax.Array, step_ranges: jax.Array):
    """Interpret one lane of the union executable.

    The value stack holds ``stack_size`` capacity-padded pair relations;
    every step computes ALL candidate operator results and the opcode
    selects one (vmap executes every branch anyway, so a lax.switch
    would buy nothing).  Overflow is one sticky flag for the lane,
    exactly as in the shaped path.
    """
    cap = caps.pair_cap
    sentinel_col = jnp.full((cap,), R.SENTINEL, R.I32)

    def step(carry, inp):
        v, u, cnt, sp, ovf = carry
        op, rng = inp

        def slot(i):
            i = jnp.clip(i, 0, stack_size - 1)
            return R.Relation((v[i], u[i]), cnt[i], jnp.int32(0))

        top = slot(sp - 1)
        sec = slot(sp - 2)
        lk = ops.materialize(
            ops.lookup_classes(rng[0], rng[1], caps.class_cap), cap)
        cands = [
            R.Relation((sentinel_col, sentinel_col), jnp.asarray(0, R.I32),
                       jnp.int32(0)),  # NOP
            lk,  # LOOKUP
            ops.join_pairs(sec, top, caps.join_cap, cap),  # JOIN
            ops.conj_pairs(sec, top),  # CONJ
            ops.conj_id_pairs(top),  # CONJ_ID
            ops.identity_pairs(cap),  # IDENTITY
        ]
        sel_v = jnp.stack([r.cols[0] for r in cands])[op]
        sel_u = jnp.stack([r.cols[1] for r in cands])[op]
        sel_c = jnp.stack([jnp.asarray(r.count, R.I32) for r in cands])[op]
        sel_o = jnp.stack([jnp.asarray(r.overflow, R.I32)
                           for r in cands])[op]
        widx = jnp.where(op == OP_NOP, -1,
                         sp + jnp.asarray(_OP_WRITE, R.I32)[op])
        mask = jnp.arange(stack_size, dtype=R.I32) == widx
        v = jnp.where(mask[:, None], sel_v[None, :], v)
        u = jnp.where(mask[:, None], sel_u[None, :], u)
        cnt = jnp.where(mask, sel_c, cnt)
        ovf = ovf | jnp.where(op != OP_NOP, sel_o, 0)
        sp = sp + jnp.asarray(_OP_DELTA, R.I32)[op]
        return (v, u, cnt, sp, ovf), None

    zeros = jnp.full((stack_size, cap), R.SENTINEL, R.I32)
    carry = (zeros, zeros, jnp.zeros((stack_size,), R.I32),
             jnp.asarray(0, R.I32), jnp.int32(0))
    (v, u, cnt, _, ovf), _ = jax.lax.scan(step, carry, (opcodes, step_ranges))
    return ops.finish(R.Relation((v[0], u[0]), cnt[0], ovf))


@functools.partial(jax.jit, static_argnames=("caps", "stack_size",
                                             "n_vertices"))
def run_union_batch(a: DeviceIndexArrays, caps: QueryCaps, stack_size: int,
                    n_vertices: int, opcodes: jax.Array,
                    step_ranges: jax.Array):
    """Mixed-shape batch through ONE executable: ``opcodes`` (batch, T)
    and ``step_ranges`` (batch, T, 2) stream per-lane programs as traced
    data, so the jit key is only (T, stack_size, caps, n_vertices).
    Returns a batched Relation + per-lane sticky overflow, the same
    contract as :func:`run_plan_batch`."""
    ops = LocalOps(a, n_vertices)
    return jax.vmap(
        lambda oc, rg: _run_program_lane(ops, caps, stack_size, oc, rg)
    )(opcodes, step_ranges)


# ---------------------------------------------------------------------- #
# host-facing backend contract
# ---------------------------------------------------------------------- #


class ExecutionBackend(abc.ABC):
    """What the :class:`repro.core.engine.Engine` drives.

    A backend owns the physical index arrays (however they are laid out)
    and turns (plan shape, caps, lookup ranges) into numpy answers.  Both
    entry points report overflow instead of raising: the engine owns the
    double-and-retry capacity ladder, identically for every backend.
    """

    n_vertices: int

    #: whether :meth:`run_union_batch` is implemented (the engine falls
    #: back to per-shape dispatches when it is not).
    supports_union = False

    #: bytes this backend has copied from the device to the host for
    #: answers, cumulative (a backend that copies none reports 0).
    d2h_bytes = 0

    @abc.abstractmethod
    def run(self, shape, caps: QueryCaps, ranges: np.ndarray):
        """One query.  ``ranges`` (n_lookups, 2) -> (rows | None, overflow):
        sorted distinct (n, 2) int32 s-t pairs, or None when the sticky
        overflow tripped (the caller retries with larger caps).
        ``overflow`` is the int overflow word: 0 when clear, else the
        ``OVF_*`` bits of the capacities to grow."""

    @abc.abstractmethod
    def run_batch(self, shape, caps: QueryCaps, ranges: np.ndarray):
        """Batch of same-shape queries.  ``ranges`` (batch, n_lookups, 2)
        -> (list of rows-or-None per lane, (batch,) int overflow words,
        as :meth:`run`)."""

    def run_union_batch(self, opcodes: np.ndarray, caps: QueryCaps,
                        stack_size: int, step_ranges: np.ndarray):
        """Mixed-shape batch via the union executable.  ``opcodes``
        (batch, T), ``step_ranges`` (batch, T, 2); same result contract
        as :meth:`run_batch`.  Optional — guarded by ``supports_union``."""
        raise NotImplementedError

    def compile_batch(self, shape, caps: QueryCaps, ranges: np.ndarray):
        """Compile, without running, the executable :meth:`run_batch`
        dispatches for these arguments, and return it (a
        ``jax.stages.Compiled``).  It lands in the same compile cache the
        dispatch reads, so compiling ahead of traffic — several at once
        in threads — takes compile time off the first requests."""
        raise NotImplementedError

    # -- async dispatch (pipelined drain) -- #
    #
    # ``*_async`` returns an opaque handle immediately after the device
    # dispatch; ``harvest_batch`` blocks on it and converts to the
    # ``run_batch`` result contract.  The defaults degrade to synchronous
    # execution so every backend supports the pipelined drain.

    def run_batch_async(self, shape, caps: QueryCaps, ranges: np.ndarray):
        return ("sync", self.run_batch(shape, caps, ranges))

    def run_union_batch_async(self, opcodes: np.ndarray, caps: QueryCaps,
                              stack_size: int, step_ranges: np.ndarray):
        return ("sync", self.run_union_batch(opcodes, caps, stack_size,
                                             step_ranges))

    def harvest_batch(self, handle):
        tag, payload = handle[0], handle[1:]
        if tag == "sync":
            return payload[0]
        raise NotImplementedError(tag)


class LocalBackend(ExecutionBackend):
    """Single-device execution over :class:`DeviceIndexArrays`."""

    supports_union = True

    def __init__(self, arrays: DeviceIndexArrays, n_vertices: int):
        self.arrays = arrays
        self.n_vertices = n_vertices

    def run(self, shape, caps: QueryCaps, ranges: np.ndarray):
        pairs, overflow = run_plan(self.arrays, shape, caps, self.n_vertices,
                                   jnp.asarray(ranges))
        self.d2h_bytes += overflow.nbytes
        if int(overflow):
            return None, int(overflow)
        self.d2h_bytes += pairs.count.nbytes + sum(c.nbytes
                                                   for c in pairs.cols)
        return R.to_numpy(pairs), 0

    def run_batch(self, shape, caps: QueryCaps, ranges: np.ndarray):
        return self.harvest_batch(self.run_batch_async(shape, caps, ranges))

    def compile_batch(self, shape, caps: QueryCaps, ranges: np.ndarray):
        return run_plan_batch.lower(self.arrays, shape, caps, self.n_vertices,
                                    jnp.asarray(ranges)).compile()

    def run_union_batch(self, opcodes: np.ndarray, caps: QueryCaps,
                        stack_size: int, step_ranges: np.ndarray):
        return self.harvest_batch(self.run_union_batch_async(
            opcodes, caps, stack_size, step_ranges))

    def run_batch_async(self, shape, caps: QueryCaps, ranges: np.ndarray):
        rel, overflow = run_plan_batch(self.arrays, shape, caps,
                                       self.n_vertices, jnp.asarray(ranges))
        # JAX dispatch is asynchronous: the device is now computing while
        # the caller plans the next bucket; harvest_batch blocks.
        return ("lanes", rel, overflow)

    def run_union_batch_async(self, opcodes: np.ndarray, caps: QueryCaps,
                              stack_size: int, step_ranges: np.ndarray):
        rel, overflow = run_union_batch(
            self.arrays, caps, stack_size, self.n_vertices,
            jnp.asarray(opcodes, dtype=jnp.int32),
            jnp.asarray(step_ranges, dtype=jnp.int32))
        return ("lanes", rel, overflow)

    def harvest_batch(self, handle):
        if handle[0] != "lanes":
            return super().harvest_batch(handle)
        _, rel, overflow = handle
        with span("harvest.wait"):
            overflow = np.asarray(overflow)
        self.d2h_bytes += overflow.nbytes
        results: list = [None] * overflow.shape[0]
        ok = np.nonzero(overflow == 0)[0]
        if ok.size:
            with span("harvest.copy"):
                lanes = R.batch_to_numpy(rel, lanes=ok)
            self.d2h_bytes += rel.count.nbytes + sum(c.nbytes
                                                     for c in rel.cols)
            for lane, rows in zip(ok, lanes):
                results[lane] = rows
        return results, overflow
