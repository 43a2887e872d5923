"""Sharded CPQx index layout — first-class distribution of the index
arrays over one mesh axis.

The layout follows the paper's size asymmetry (Sec. VI: the class space
stays tiny even when the pair space grows with the graph):

* **I_c2p sharded by class hash** — the c2p pair columns are partitioned
  so every equivalence class lives whole on exactly one shard, with a
  *per-shard* CSR (``class_starts[s, c]``) over global class ids.  A
  shard materializes only its own classes; classes are disjoint in pair
  space, so sharded materialization never produces cross-shard
  duplicates.
* **pair table sharded by (v, u)** — the by-(v,u)-sorted pair table is
  hash-partitioned on both endpoints (the canonical pair-space
  distribution).
* **seq / l2c / cycle metadata replicated** — I_l2c class lists and the
  per-class cycle flags are small (the paper's central observation), so
  every shard carries a full copy and class-space query work needs no
  communication at all.

``shard_index`` / ``gather_index`` convert between this layout and the
single-device :class:`~repro.core.index.DeviceIndexArrays`; the shard
capacities derive from the device capacities (stable across maintenance
flushes, so ``Engine.rebind`` after a flush reshards into arrays of the
same shape and keeps the jit cache warm) and grow-and-retry on skew
(the host twin of the device overflow ladder specified in the
``core.backend`` module docstring).

Because the planning metadata is replicated, the cost-based optimizer's
statistics are too: :func:`replicated_stats` rebuilds the exact
:class:`~repro.core.stats.IndexStats` of the pre-shard index from a
sharded layout alone, so a planner next to any shard reorders plans
identically to a local engine — sharded planning can never drift from
local planning.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import relational as R
from .index import CPQxIndex, DeviceIndexArrays

from .relational import _MIX_A, _MIX_B, SHARD_SALT  # single hash source


class ShardedIndexArrays(NamedTuple):
    """A built index distributed over ``n_shards`` (a pytree).

    Sharded leaves carry a leading ``(n_shards, ...)`` axis (placed on
    the mesh axis by ``shard_map`` with spec ``P(axis)``); replicated
    leaves keep the single-device shape (spec ``P()``)."""

    # pair table sorted by (v, u), hash-partitioned on (v, u)
    pair_v: jax.Array  # (n_shards, pair_shard_cap)
    pair_u: jax.Array
    pair_cls: jax.Array
    pair_counts: jax.Array  # (n_shards,)
    # I_c2p sorted by (class, v, u), hash-partitioned on class
    c2p_cls: jax.Array  # (n_shards, c2p_shard_cap)
    c2p_v: jax.Array
    c2p_u: jax.Array
    c2p_counts: jax.Array  # (n_shards,)
    class_starts: jax.Array  # (n_shards, class_cap + 1) per-shard CSR
    # replicated: class-space + lookup metadata (small by Sec. VI)
    class_cyclic: jax.Array
    n_classes: jax.Array
    seq_table: jax.Array
    seq_count: jax.Array
    seq_starts: jax.Array
    seq_ends: jax.Array
    l2c_cls: jax.Array
    l2c_count: jax.Array

    @property
    def n_shards(self) -> int:
        return self.c2p_v.shape[0]


_SHARDED_FIELDS = frozenset({
    "pair_v", "pair_u", "pair_cls", "pair_counts",
    "c2p_cls", "c2p_v", "c2p_u", "c2p_counts", "class_starts",
})


def index_specs(axis: str) -> ShardedIndexArrays:
    """The ``shard_map`` in_specs pytree for :class:`ShardedIndexArrays`."""
    return ShardedIndexArrays(**{
        f: (P(axis) if f in _SHARDED_FIELDS else P())
        for f in ShardedIndexArrays._fields
    })


def place_index(sharded: ShardedIndexArrays, mesh,
                axis: str) -> ShardedIndexArrays:
    """Put the leaves on ``mesh``: each shard of a sharded leaf on its own
    device along ``axis``, replicated leaves on every device — so a
    ``shard_map`` call finds every shard where it runs and moves no
    index bytes."""
    return ShardedIndexArrays(*[
        jax.device_put(leaf, NamedSharding(mesh, spec))
        for leaf, spec in zip(sharded, index_specs(axis))])


# ---------------------------------------------------------------------- #
# host-side hash partitioning (vectorized; must agree with the device)
# ---------------------------------------------------------------------- #


def _mix32_np(x: np.ndarray, salt: int) -> np.ndarray:
    """Numpy twin of ``relational.mix32`` (wrapping uint32 avalanche)."""
    h = x.astype(np.uint32) ^ np.uint32(salt)
    h = (h ^ (h >> np.uint32(16))) * _MIX_A
    h = (h ^ (h >> np.uint32(15))) * _MIX_B
    return h ^ (h >> np.uint32(16))


def hash_buckets(rows: np.ndarray, key_cols: Sequence[int],
                 n_shards: int) -> np.ndarray:
    """Shard owning each row: single-column keys reproduce the device's
    ``_bucket_of`` exactly (so host placement == device repartitioning);
    multi-column keys fold left with the same mix."""
    h = _mix32_np(rows[:, key_cols[0]], SHARD_SALT)
    for j in key_cols[1:]:
        h = _mix32_np(rows[:, j].astype(np.uint32) ^ h, SHARD_SALT)
    return (h % np.uint32(n_shards)).astype(np.int64)


def partition_rows(rows: np.ndarray, n_shards: int, cap: int,
                   key_cols: Sequence[int] = (0,), grow: bool = True):
    """Hash-partition host rows into ``(n_shards, cap, arity)`` blocks,
    each shard's rows sorted lexicographically and SENTINEL-padded.

    Fully vectorized (one lexsort + searchsorted bucket boundaries + one
    flat scatter — no per-shard Python loop).  A shard overflowing ``cap``
    doubles the capacity and retries (the host twin of the device's
    flagged grow-and-retry) unless ``grow=False``, which raises instead.

    Returns ``(blocks, counts, cap)`` — ``cap`` is the possibly-grown
    per-shard capacity."""
    rows = np.asarray(rows, np.int32).reshape(-1, rows.shape[-1])
    n, arity = rows.shape
    bucket = hash_buckets(rows, tuple(key_cols), n_shards)
    # one lexsort: primary key bucket, then the row columns in order
    order = np.lexsort(
        tuple(rows[:, j] for j in range(arity - 1, -1, -1)) + (bucket,))
    srows, sb = rows[order], bucket[order]
    offs = np.searchsorted(sb, np.arange(n_shards), side="left")
    ends = np.searchsorted(sb, np.arange(n_shards), side="right")
    counts = (ends - offs).astype(np.int32)
    biggest = int(counts.max()) if n_shards else 0
    if biggest > cap:
        if not grow:
            raise ValueError(
                f"shard overflow: {biggest} rows > capacity {cap}")
        while biggest > cap:
            cap *= 2
    out = np.full((n_shards, cap, arity), R.SENTINEL, np.int32)
    slot = np.arange(n) - offs[sb]  # position within the shard block
    out.reshape(-1, arity)[sb * cap + slot] = srows
    return out, counts, cap


def _pow2(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


def shard_capacity(capacity: int, n_shards: int, min_cap: int = 64) -> int:
    """Starting per-shard capacity of a sharded leaf whose device
    capacity is ``capacity``: 2/n_shards of it, rounded up to a power of
    two (a balanced hash fits with 2x headroom)."""
    return _pow2(max(min_cap, min(capacity,
                                  -(-2 * capacity // max(1, n_shards)))))


# ---------------------------------------------------------------------- #
# shard / gather
# ---------------------------------------------------------------------- #


def shard_index(index: CPQxIndex, n_shards: int,
                min_cap: int = 64) -> ShardedIndexArrays:
    """Distribute a built index into :class:`ShardedIndexArrays`.

    Per-shard capacities start at ``2/n_shards`` of the device capacity
    (power-of-two, so a balanced hash fits with 2x headroom) and grow on
    skew.  Deriving from the *capacity* rather than the live count keeps
    shard shapes — and the compiled sharded executables keyed on them —
    stable across maintenance flushes."""
    a = index.arrays
    cap0 = shard_capacity(int(a.c2p_v.shape[0]), n_shards, min_cap)

    n_pairs = int(a.pair_count)
    pair_rows = np.stack([
        np.asarray(a.pair_v)[:n_pairs], np.asarray(a.pair_u)[:n_pairs],
        np.asarray(a.pair_cls)[:n_pairs]], axis=1)
    pair_blocks, pair_counts, _ = partition_rows(
        pair_rows.reshape(-1, 3), n_shards, cap0, key_cols=(0, 1))

    c2p_rows = np.stack([
        np.asarray(a.c2p_cls)[:n_pairs], np.asarray(a.c2p_v)[:n_pairs],
        np.asarray(a.c2p_u)[:n_pairs]], axis=1)
    c2p_blocks, c2p_counts, _ = partition_rows(
        c2p_rows.reshape(-1, 3), n_shards, cap0, key_cols=(0,))

    # per-shard CSR over global class ids: the padded class column is
    # ascending (SENTINEL pads sort last), so searchsorted per shard
    n_starts = int(a.class_starts.shape[0])
    ids = np.arange(n_starts, dtype=np.int64)
    class_starts = np.stack([
        np.searchsorted(c2p_blocks[s, :, 0].astype(np.int64), ids, side="left")
        for s in range(n_shards)]).astype(np.int32)

    return ShardedIndexArrays(
        pair_v=jnp.asarray(pair_blocks[:, :, 0]),
        pair_u=jnp.asarray(pair_blocks[:, :, 1]),
        pair_cls=jnp.asarray(pair_blocks[:, :, 2]),
        pair_counts=jnp.asarray(pair_counts),
        c2p_cls=jnp.asarray(c2p_blocks[:, :, 0]),
        c2p_v=jnp.asarray(c2p_blocks[:, :, 1]),
        c2p_u=jnp.asarray(c2p_blocks[:, :, 2]),
        c2p_counts=jnp.asarray(c2p_counts),
        class_starts=jnp.asarray(class_starts),
        class_cyclic=a.class_cyclic, n_classes=a.n_classes,
        seq_table=a.seq_table, seq_count=a.seq_count,
        seq_starts=a.seq_starts, seq_ends=a.seq_ends,
        l2c_cls=a.l2c_cls, l2c_count=a.l2c_count,
    )


def replicated_stats(sharded: ShardedIndexArrays, n_vertices: int,
                     k: int) -> "IndexStats":
    """The optimizer's :class:`~repro.core.stats.IndexStats`, derived
    entirely from a sharded layout: the seq/l2c/cyclic metadata is
    replicated, and per-class pair counts fall out of the per-shard CSRs
    — every class lives whole on exactly one shard, so summing the
    per-shard extents over the shard axis reconstructs the global class
    sizes exactly.  Bit-identical to ``IndexStats.from_index`` on the
    index that was sharded (tests pin this) — so a planner holding only
    the sharded layout (a migration target, a remote planner) reorders
    plans exactly as a local engine would."""
    from .index import _pull_seq_ranges  # sharded tuple has the seq fields
    from .stats import IndexStats

    starts = np.asarray(sharded.class_starts, np.int64)
    sizes = (starts[:, 1:] - starts[:, :-1]).sum(axis=0)
    # endpoint statistics need the actual pairs: every class lives whole
    # on one shard, so concatenating the valid per-shard prefixes and
    # re-sorting by class rebuilds the global (class, v, u) columns — the
    # distinct-endpoint/fanout numbers are order-insensitive within a
    # class, so this view is statistic-identical to the pre-shard one.
    # Deferred to the first seq_endpoints() call: the reassembly is
    # O(total pairs), far beyond the replicated few-KB metadata.
    def fetch():
        cc = np.asarray(sharded.c2p_counts)
        ccls, cv, cu = (np.asarray(x) for x in
                        (sharded.c2p_cls, sharded.c2p_v, sharded.c2p_u))
        rows = [np.stack([ccls[s, :cc[s]], cv[s, :cc[s]], cu[s, :cc[s]]], 1)
                for s in range(sharded.n_shards)]
        flat = (np.concatenate(rows) if rows
                else np.zeros((0, 3), np.int64))
        flat = flat[np.argsort(flat[:, 0].astype(np.int64), kind="stable")]
        return flat[:, 1], flat[:, 2]

    return IndexStats.from_host_arrays(
        n_vertices=n_vertices,
        n_classes=int(sharded.n_classes),
        total_pairs=int(np.asarray(sharded.c2p_counts).sum()),
        seq_ranges=_pull_seq_ranges(sharded, k),
        class_starts=np.concatenate([np.zeros(1, np.int64),
                                     np.cumsum(sizes)]),
        l2c_cls=np.asarray(sharded.l2c_cls),
        l2c_count=int(sharded.l2c_count),
        class_cyclic=np.asarray(sharded.class_cyclic),
        c2p_fetch=fetch,
    )


def gather_index(sharded: ShardedIndexArrays,
                 pair_cap: int | None = None) -> DeviceIndexArrays:
    """Collapse a sharded index back to single-device arrays (migration
    off a mesh, or the round-trip check in tests).  ``pair_cap`` pins the
    rebuilt pair/c2p capacity — pass the original device capacity to get
    arrays bit-identical to the pre-shard index."""
    pc = np.asarray(sharded.pair_counts)
    cc = np.asarray(sharded.c2p_counts)
    pv, pu, pcls = (np.asarray(x) for x in
                    (sharded.pair_v, sharded.pair_u, sharded.pair_cls))
    cv, cu, ccls = (np.asarray(x) for x in
                    (sharded.c2p_v, sharded.c2p_u, sharded.c2p_cls))
    n_shards = sharded.n_shards
    pair_rows = np.concatenate([
        np.stack([pv[s, :pc[s]], pu[s, :pc[s]], pcls[s, :pc[s]]], axis=1)
        for s in range(n_shards)]) if n_shards else np.zeros((0, 3), np.int32)
    c2p_rows = np.concatenate([
        np.stack([ccls[s, :cc[s]], cv[s, :cc[s]], cu[s, :cc[s]]], axis=1)
        for s in range(n_shards)]) if n_shards else np.zeros((0, 3), np.int32)
    pair_rows = pair_rows[np.lexsort(
        (pair_rows[:, 2], pair_rows[:, 1], pair_rows[:, 0]))]
    c2p_rows = c2p_rows[np.lexsort(
        (c2p_rows[:, 2], c2p_rows[:, 1], c2p_rows[:, 0]))]
    n = pair_rows.shape[0]
    cap = pair_cap if pair_cap is not None else _pow2(max(64, n))

    def pad(col):
        buf = np.full(cap, R.SENTINEL, np.int32)
        buf[:n] = col
        return jnp.asarray(buf)

    class_starts = np.searchsorted(
        np.concatenate([c2p_rows[:, 0],
                        np.full(cap - n, np.int64(R.SENTINEL))]).astype(np.int64),
        np.arange(cap + 1), side="left").astype(np.int32)
    return DeviceIndexArrays(
        pair_v=pad(pair_rows[:, 0]), pair_u=pad(pair_rows[:, 1]),
        pair_cls=pad(pair_rows[:, 2]),
        pair_count=jnp.asarray(n, R.I32),
        c2p_cls=pad(c2p_rows[:, 0]), c2p_v=pad(c2p_rows[:, 1]),
        c2p_u=pad(c2p_rows[:, 2]),
        class_starts=jnp.asarray(class_starts),
        class_cyclic=sharded.class_cyclic, n_classes=sharded.n_classes,
        seq_table=sharded.seq_table, seq_count=sharded.seq_count,
        seq_starts=sharded.seq_starts, seq_ends=sharded.seq_ends,
        l2c_cls=sharded.l2c_cls, l2c_count=sharded.l2c_count,
        overflow=jnp.int32(0),
    )
