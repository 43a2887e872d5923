"""Distributed CPQx — the engine's pair tables sharded over a mesh axis,
with all_to_all hash repartitioning for joins (shard_map manual
collectives; DESIGN.md §5).

Data layout
-----------
A *sharded relation* is a Relation whose column arrays carry a leading
``shards`` axis sharded over the mesh: cols (n_shards, cap, ...), count
(n_shards,).  Rows live on the shard that owns their partition key
(``mix32(key) % n_shards``), except "replicated" relations (class-id
lists — small by the paper's central observation) which are identical on
every shard.

Operators (all inside one shard_map):
  * ``exchange``            fixed-capacity all_to_all bucket shuffle
  * ``sharded_join``        repartition by join key -> local expansion join
  * ``sharded_conjunction`` replicated class intersect -> sharded
                            materialize -> local intersection
  * ``build_level``         one level of Algorithm 1's path join at scale

The fixed bucket capacity is the static-shape contract: each exchange
moves (n_shards, bucket_cap, arity) per shard; overflow is flagged and
resolved by the ONE overflow-ladder contract specified in the
``core.backend`` module docstring — this module adds nothing to it
beyond OR-reducing the per-shard sticky overflow words so every shard
and the host agree on a retry.

Whole-plan execution
--------------------
:class:`ShardedBackend` promotes these operators to a full execution
backend: the *same* plan walker the local engine runs
(``core.backend.run_plan_ops``) executes inside ONE ``shard_map`` over
the mesh axis, against :class:`ShardedOps` — class-space relations
replicated, pair-space relations hash-partitioned by source vertex (the
canonical distribution: conjunctions and identity filters are then
exchange-free; a join repartitions its probe side by the join key and
its output back to canonical).  Per-shard sticky overflow words are
OR-reduced so every shard — and the host — agrees on retry.

Planning (and the cost-based optimizer) stays a host concern: the
backend carries the replicated :class:`~repro.core.stats.IndexStats`
(``sharded_index.replicated_stats``) so any planner colocated with a
shard sees the exact statistics the local engine would — plans, and
therefore executables, are identical across backends.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from . import backend as B
from . import relational as R
from .paths import _recap
from .sharded_index import (
    ShardedIndexArrays,
    index_specs,
    partition_rows,
    place_index,
    replicated_stats,
    shard_index,
)

I32 = jnp.int32


# ---------------------------------------------------------------------- #
# local helpers (run per shard inside shard_map)
# ---------------------------------------------------------------------- #


def _bucket_of(key: jax.Array, n_shards: int) -> jax.Array:
    return (R.mix32(key, R.SHARD_SALT) % jnp.uint32(n_shards)).astype(I32)


def _pack_buckets(cols: tuple, valid: jax.Array, bucket: jax.Array,
                  n_shards: int, bucket_cap: int, bit: int = 1):
    """Arrange local rows into (n_shards, bucket_cap, arity) by bucket —
    sort by bucket then slot-gather (the MoE-dispatch pattern: no
    scatter).  Returns (packed cols tuple, per-bucket counts, overflow
    word: ``bit`` when a bucket exceeded ``bucket_cap``)."""
    cap = cols[0].shape[0]
    bkey = jnp.where(valid, bucket, n_shards)  # invalid -> trash bucket
    order = jax.lax.sort((bkey, jnp.arange(cap, dtype=I32)), num_keys=1,
                         is_stable=True)[1]
    sorted_cols = tuple(c[order] for c in cols)
    sorted_b = bkey[order]
    offs = jnp.searchsorted(sorted_b, jnp.arange(n_shards, dtype=I32),
                            side="left").astype(I32)
    ends = jnp.searchsorted(sorted_b, jnp.arange(n_shards, dtype=I32),
                            side="right").astype(I32)
    sizes = ends - offs
    overflow = R.overflow_bit(jnp.any(sizes > bucket_cap), bit)
    b = jnp.arange(n_shards * bucket_cap, dtype=I32) // bucket_cap
    slot = jnp.arange(n_shards * bucket_cap, dtype=I32) % bucket_cap
    src = jnp.clip(offs[b] + slot, 0, cap - 1)
    ok = slot < sizes[b]
    packed = tuple(
        jnp.where(ok, c[src], R.SENTINEL).reshape(n_shards, bucket_cap)
        for c in sorted_cols
    )
    return packed, jnp.minimum(sizes, bucket_cap).astype(I32), overflow


def _exchange(packed: tuple, counts: jax.Array, axis: str):
    """all_to_all: bucket b of shard s -> shard b.  packed cols are
    (n_shards, bucket_cap); returns (n_shards, bucket_cap) = one row-block
    from each peer, plus the per-peer counts."""
    out = tuple(
        jax.lax.all_to_all(c, axis, split_axis=0, concat_axis=0, tiled=True)
        for c in packed
    )
    cnt = jax.lax.all_to_all(counts, axis, split_axis=0, concat_axis=0,
                             tiled=True)
    return out, cnt


def _flatten_received(received: tuple, counts: jax.Array):
    """(n_shards, bucket_cap) blocks -> flat relation (sorted, compacted)."""
    flat = tuple(c.reshape(-1) for c in received)
    total = jnp.sum(counts)
    rel = R.Relation(flat, jnp.asarray(flat[0].shape[0], I32),
                     jnp.int32(0))
    # SENTINEL-padded rows inside each block sort to the end
    rel = R.rel_sort(rel)
    return R.Relation(rel.cols, total.astype(I32), rel.overflow)


# ---------------------------------------------------------------------- #
# sharded operators
# ---------------------------------------------------------------------- #


def repartition(cols: tuple, count: jax.Array, key_col: int, n_shards: int,
                bucket_cap: int, axis: str, bit: int = 1):
    """Move every row to the shard owning hash(key).  Local view in/out.
    Returns (cols, count, overflow) with capacity n_shards*bucket_cap; a
    block past ``bucket_cap`` sets ``bit`` in the overflow word."""
    valid = jnp.arange(cols[0].shape[0], dtype=I32) < count
    bucket = _bucket_of(cols[key_col], n_shards)
    packed, sizes, ovf = _pack_buckets(cols, valid, bucket, n_shards,
                                       bucket_cap, bit)
    received, cnt = _exchange(packed, sizes, axis)
    rel = _flatten_received(received, cnt)
    return rel.cols, rel.count, ovf | rel.overflow


def sharded_join_local(a_cols, a_count, b_cols, b_count, out_cap: int,
                       b_sorted: bool = False):
    """Local leg of the distributed join: both sides already partitioned
    by the join key (a's key col 1, b's key col 0).  ``b_sorted``: skip
    the build-side sort when the producer already emits sorted rows
    (repartition's _flatten_received does — §Perf iteration: the double
    sort was ~40% of the join's local traffic)."""
    a = R.Relation(a_cols, a_count, jnp.int32(0))
    b = R.Relation(b_cols, b_count, jnp.int32(0))
    if not b_sorted:
        b = R.rel_sort(b)
    out_cols = [("a", 0), ("b", 1)] + [("a", j) for j in range(2, len(a_cols))] \
        + [("b", j) for j in range(2, len(b_cols))]
    out = R.expansion_join(a, b, a_on=[1], out_cols=out_cols,
                           out_capacity=out_cap)
    out = R.rel_unique(R.rel_sort(out))
    return out.cols, out.count, out.overflow


# ---------------------------------------------------------------------- #
# jitted entry points (shard_map over one flat engine axis)
# ---------------------------------------------------------------------- #


def make_distributed_join(mesh, axis: str, n_shards: int, a_arity: int,
                          b_arity: int, bucket_cap: int, out_cap: int):
    """Factory: global (v,m,...) ⋈ (m,u,...) over one mesh axis.

    Inputs are sharded relations: cols (n_shards, cap), counts (n_shards,).
    Hash-repartitions both sides on the join key via all_to_all, joins
    locally, returns sharded output cols + counts + overflow.  This is
    Algorithm 1's level join at scale."""

    def body(ac, an, bc, bn):
        ac = tuple(c[0] for c in ac)
        bc = tuple(c[0] for c in bc)
        an, bn = an[0], bn[0]
        ac, an, ovf_a = repartition(ac, an, 1, n_shards, bucket_cap, axis)
        bc, bn, ovf_b = repartition(bc, bn, 0, n_shards, bucket_cap, axis)
        # b arrives fully sorted from the exchange (_flatten_received) —
        # skip the redundant build-side sort (§Perf engine iteration)
        oc, on, ovf_j = sharded_join_local(ac, an, bc, bn, out_cap,
                                           b_sorted=True)
        ovf = ovf_a | ovf_b | ovf_j
        return (tuple(c[None] for c in oc), on[None], ovf[None])

    spec = P(axis)
    out_arity = a_arity + b_arity - 2
    fn = compat.shard_map(
        body, mesh=mesh,
        in_specs=(
            tuple(spec for _ in range(a_arity)), spec,
            tuple(spec for _ in range(b_arity)), spec,
        ),
        out_specs=(tuple(spec for _ in range(out_arity)), spec, spec),
    )
    return jax.jit(fn)


def shard_relation(rows: np.ndarray, n_shards: int, cap: int,
                   key_col: int | tuple = 0, grow: bool = True):
    """Host-side: partition rows by hash(key) into (n_shards, cap, arity)
    numpy blocks (the initial distribution of the pair table), each
    shard's rows sorted lexicographically.

    Vectorized — one lexsort + searchsorted boundaries + one flat
    scatter, no per-shard Python loop.  A shard outgrowing ``cap``
    doubles the block capacity and retries (the host-side twin of the
    device operators' flagged grow-and-retry); the returned blocks'
    ``shape[1]`` is the possibly-grown capacity.  ``grow=False`` restores
    the old fail-fast ``ValueError``.  ``key_col`` may be a tuple to
    hash-combine several columns (e.g. ``(0, 1)`` for the (v, u) pair
    table)."""
    key_cols = key_col if isinstance(key_col, tuple) else (key_col,)
    blocks, counts, _ = partition_rows(rows, n_shards, cap,
                                       key_cols=key_cols, grow=grow)
    return blocks, counts


# ---------------------------------------------------------------------- #
# distributed conjunction query step (the paper's hot query path at scale)
# ---------------------------------------------------------------------- #


def make_distributed_query_step(mesh, axis: str):
    """Returns a jitted step: (classes_a, classes_b replicated;
    c2p shards) -> sharded result pairs of (⟦q_a⟧ ∩ ⟦q_b⟧).

    Class intersection runs replicated (tiny — the paper's point);
    materialization runs sharded: each shard scans only its own slice of
    I_c2p, so result rows are produced where they live (zero shuffle)."""
    spec = P(axis)

    def body(ca, cb, c2p_cls, c2p_v, c2p_u, c2p_count):
        # ca/cb replicated (full) SENTINEL-padded sorted class lists
        c2p_cls, c2p_v, c2p_u = c2p_cls[0], c2p_v[0], c2p_u[0]
        n = c2p_count[0]
        ra = R.Relation((ca,), jnp.sum(ca != R.SENTINEL).astype(I32),
                        jnp.int32(0))
        rb = R.Relation((cb,), jnp.sum(cb != R.SENTINEL).astype(I32),
                        jnp.int32(0))
        inter = R.rel_intersect(ra, rb, 1)
        # local materialize: my slice of c2p filtered to surviving classes
        local = R.Relation((c2p_cls, c2p_v, c2p_u), n, jnp.int32(0))
        keep = R.lex_count_matches((inter.cols[0],), (c2p_cls,),
                                   inter.count) > 0
        out = R.rel_compact(local, keep)
        return (out.cols[1][None], out.cols[2][None]), out.count[None]

    fn = compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), spec, spec, spec, spec),
        out_specs=((spec, spec), spec),
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------- #
# whole-plan sharded execution (the backend behind Engine(index, mesh=...))
# ---------------------------------------------------------------------- #


class ShardedOps(B.PlanOps):
    """The plan-operator protocol over one shard's local index view.

    Conventions (per relation kind):
      * class-space relations are **replicated** — every shard computes
        the identical sorted class list from the replicated l2c arrays,
        so LOOKUP / class-CONJUNCTION / IDENTITY-flag ops inherit the
        local math unchanged;
      * pair-space relations are **canonical sharded**: partitioned by
        ``mix32(v) % n_shards`` and locally sorted by (v, u).  Rows of a
        pair are globally unique, so concatenating shards reconstructs
        the exact local-engine relation.

    Producers restore the canonical distribution on exit: materialize
    expands the shard's own classes (I_c2p is class-hash sharded) and
    repartitions by v; a join repartitions its probe side by the join key
    (the build side is already keyed on v), joins locally, repartitions
    the output by v, and dedupes — the same (v, y) can be witnessed via
    intermediates on different shards.  Capacities are the *global*
    QueryCaps, so any answer the local engine can hold fits per shard
    too and the overflow ladder is shared."""

    def __init__(self, view: ShardedIndexArrays, n_vertices: int,
                 n_shards: int, axis: str):
        self.l2c_cls = view.l2c_cls
        self.class_starts = view.class_starts
        self.c2p_v = view.c2p_v
        self.c2p_u = view.c2p_u
        self.class_cyclic = view.class_cyclic
        self.n_vertices = n_vertices
        self.n_shards = n_shards
        self.axis = axis

    def _bucket_cap(self, pair_cap: int) -> int:
        """Exchange block capacity: ~2x the balanced per-peer share, so
        the received relation is capacity ~2*pair_cap per shard — flat in
        n_shards (memory *shards down* with the mesh instead of up).
        Hash skew past a block trips the sticky flag and rides the same
        double-and-retry ladder as every other capacity."""
        balanced = -(-2 * pair_cap // self.n_shards)  # ceil
        return min(pair_cap, 1 << (max(64, balanced) - 1).bit_length())

    def _canonical(self, rel: R.Relation, pair_cap: int,
                   unique: bool = False) -> R.Relation:
        """Repartition a pair relation by hash(v) and re-embed at
        ``pair_cap`` (exchange skew past a block or pair_cap trips the
        sticky flag)."""
        # exchange blocks scale with pair_cap: their overflow is its
        cols, cnt, ovf = repartition(rel.cols, rel.count, 0, self.n_shards,
                                     self._bucket_cap(pair_cap), self.axis,
                                     B.OVF_PAIR)
        out = R.Relation(cols, cnt, rel.overflow | ovf)
        if unique:
            out = R.rel_unique(out)
        return _recap(out, pair_cap, B.OVF_PAIR)

    def materialize(self, classes: R.Relation, pair_cap: int) -> R.Relation:
        local = super().materialize(classes, pair_cap)  # my classes only
        return self._canonical(local, pair_cap)

    def join_pairs(self, a: R.Relation, b: R.Relation, join_cap: int,
                   pair_cap: int) -> R.Relation:
        # probe side to the shard owning its join key u; the build side
        # is canonical — already partitioned by its key v
        ac, an, ovf = repartition(a.cols, a.count, 1, self.n_shards,
                                  self._bucket_cap(pair_cap), self.axis,
                                  B.OVF_PAIR)
        a2 = R.Relation(ac, an, a.overflow | ovf)
        out = B._join_pairs(a2, b, join_cap, pair_cap)
        return self._canonical(out, pair_cap, unique=True)

    def identity_pairs(self, pair_cap: int) -> R.Relation:
        base = super().identity_pairs(pair_cap)
        mine = _bucket_of(base.cols[0], self.n_shards) == jax.lax.axis_index(
            self.axis)
        return R.rel_compact(base, mine)

    def finish(self, pairs: R.Relation):
        # every shard's overflow bits count: OR them over the shards (a
        # max per bit) so the host and all shards agree on the retry
        bits = jnp.asarray((B.OVF_CLASS, B.OVF_PAIR, B.OVF_JOIN), I32)
        ovf = jnp.sum(jax.lax.pmax(pairs.overflow & bits, self.axis))
        return pairs, ovf


def sharded_run_plan(mesh, axis: str, n_vertices: int, shape,
                     caps: B.QueryCaps):
    """The plan walker of ``shape`` at ``caps`` inside one ``shard_map``
    over ``mesh``'s ``axis``, jitted: (ShardedIndexArrays, (n_lookups, 2)
    ranges) -> ((v, u) cols, counts, overflow words), one row per shard."""
    n_shards = int(dict(mesh.shape)[axis])
    specs = index_specs(axis)

    def body(arrs: ShardedIndexArrays, ranges):
        local = ShardedIndexArrays(*[
            leaf[0] if spec == P(axis) else leaf
            for leaf, spec in zip(arrs, specs)])
        ops = ShardedOps(local, n_vertices, n_shards, axis)
        pairs, ovf = B.run_plan_ops(ops, shape, caps, ranges)
        return (tuple(c[None] for c in pairs.cols), pairs.count[None],
                ovf[None])

    sh = P(axis)
    return jax.jit(compat.shard_map(
        body, mesh=mesh, in_specs=(specs, P()),
        out_specs=((sh, sh), sh, sh)))


class ShardedBackend(B.ExecutionBackend):
    """Whole-plan distributed execution: ``core.backend.run_plan_ops``
    — the exact walker the local engine compiles — inside one
    ``shard_map`` over ``axis``, against :class:`ShardedOps`.

    One executable per (plan shape, caps), cached; answers are gathered
    from the shards and lexsorted, which reproduces the local engine's
    output bit-for-bit (canonical pair rows are globally distinct)."""

    def __init__(self, sharded: ShardedIndexArrays, mesh, n_vertices: int,
                 axis: str = "engine", k: int | None = None):
        n_mesh = int(dict(mesh.shape)[axis])
        if sharded.n_shards != n_mesh:
            raise ValueError(
                f"index sharded {sharded.n_shards}-way but mesh axis "
                f"{axis!r} has {n_mesh} devices")
        self.sharded = place_index(sharded, mesh, axis)
        self.mesh = mesh
        self.axis = axis
        self.n_vertices = n_vertices
        self.n_shards = sharded.n_shards
        self.k = k
        self._stats = None  # lazy: see the `stats` property
        self._cache: dict = {}

    @property
    def stats(self):
        """The optimizer's statistics, reconstructed lazily from the
        replicated leaves alone — identical to the local engine's (see
        ``sharded_index.replicated_stats``; ``Engine`` plans from the
        index it was bound to, so this view exists for planners that
        only hold the sharded layout — a migration target, a remote
        planner — and for the parity tests).  None when ``k`` is
        unknown; invalidated by ``reshard``."""
        if self._stats is None and self.k is not None:
            self._stats = replicated_stats(self.sharded, self.n_vertices,
                                           self.k)
        return self._stats

    @classmethod
    def from_index(cls, index, mesh, axis: str = "engine") -> "ShardedBackend":
        n_shards = int(dict(mesh.shape)[axis])
        return cls(shard_index(index, n_shards), mesh, index.n_vertices,
                   axis=axis, k=index.k)

    # ---------------------- lifecycle (checkpoint) --------------------- #

    def save(self, ckpt_dir: str, step: int = 0) -> str:
        """Snapshot the per-shard leaves + layout metadata as one atomic
        committed step (see :mod:`repro.core.lifecycle`)."""
        from .lifecycle import save_sharded  # lazy: one-way dependency

        return save_sharded(self.sharded, self.n_vertices, self.k,
                            ckpt_dir, step)

    @classmethod
    def restore(cls, ckpt_dir: str, mesh, step: int | None = None,
                axis: str = "engine") -> "ShardedBackend":
        """Rebuild a live backend on ``mesh`` from a saved step.  If the
        mesh axis size differs from the saved shard count the leaves are
        resharded (``gather_index`` -> ``shard_index``) — elastic
        restore at any scale."""
        from .lifecycle import restore_sharded_backend

        return restore_sharded_backend(ckpt_dir, mesh, step, axis=axis)

    def reshard(self, index) -> None:
        """Re-shard a flushed/rebuilt index *into this backend* so the
        compiled executables survive a maintenance rebind: the cached
        shard_map functions take the arrays as arguments, so as long as
        the shard capacities are stable (they derive from the flush
        capacities) the new arrays hit the existing traces.  The cache
        must drop only when ``n_vertices`` moves — it is baked into the
        traced bodies (IDENTITY).  The replicated statistics view is
        invalidated with the arrays, mirroring ``Engine.rebind``."""
        self.sharded = place_index(shard_index(index, self.n_shards),
                                   self.mesh, self.axis)
        self.k = index.k
        self._stats = None
        if index.n_vertices != self.n_vertices:
            self.n_vertices = index.n_vertices
            self._cache.clear()

    def _compiled(self, shape, caps):
        key = (shape, caps)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._cache[key] = sharded_run_plan(
                self.mesh, self.axis, self.n_vertices, shape, caps)
        return fn

    def run(self, shape, caps: B.QueryCaps, ranges: np.ndarray):
        fn = self._compiled(shape, caps)
        with compat.set_mesh(self.mesh):
            cols, counts, ovf = fn(self.sharded, jnp.asarray(ranges))
        kinds = int(np.bitwise_or.reduce(np.asarray(ovf).ravel()))
        self.d2h_bytes += ovf.nbytes
        if kinds:
            return None, kinds
        self.d2h_bytes += counts.nbytes + cols[0].nbytes + cols[1].nbytes
        return self._gather_rows(cols, counts), 0

    def compile_batch(self, shape, caps: B.QueryCaps, ranges: np.ndarray):
        with compat.set_mesh(self.mesh):
            return self._compiled(shape, caps).lower(
                self.sharded, jnp.asarray(ranges[0])).compile()

    def run_batch(self, shape, caps: B.QueryCaps, ranges: np.ndarray):
        # lanes share one compiled executable; each dispatches its own
        # shard_map (collectives don't vmap portably across jax versions)
        results, overflow = [], []
        for lane in range(ranges.shape[0]):
            rows, ovf = self.run(shape, caps, ranges[lane])
            results.append(rows)
            overflow.append(ovf)
        return results, np.asarray(overflow, np.int32)

    def _gather_rows(self, cols, counts) -> np.ndarray:
        v, u = np.asarray(cols[0]), np.asarray(cols[1])
        cnt = np.asarray(counts)
        rows = np.concatenate([
            np.stack([v[s, :cnt[s]], u[s, :cnt[s]]], axis=1)
            for s in range(self.n_shards)]) if self.n_shards else \
            np.zeros((0, 2), np.int32)
        return rows[np.lexsort((rows[:, 1], rows[:, 0]))]
