"""CPQx index construction on device — Algorithm 2.

The index is two inverted maps materialized as sorted capacity-padded
arrays (Def. 4.3):

    I_l2c : label sequence  -> sorted list of class ids
    I_c2p : class id        -> sorted list of s-t pairs

Build pipeline (one jit):
    1. ``bisim.path_partition``        -> (v, u, class) over P^{<=k}
    2. ``paths.enumerate_path_levels`` -> distinct (v, u, seq) per level
    3. seq rows joined with the pair->class map (vectorized binary search)
    4. sort + dedup (seq, class)       -> I_l2c  (CSR: seq table + offsets)
    5. sort pairs by (class, v, u)     -> I_c2p  (CSR: class offsets)

The host wrapper (:class:`CPQxIndex`) owns the device arrays plus the tiny
host-side metadata needed at query time (the seq -> row-range dict — query
*planning* is host work; all set/join work stays on device).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import relational as R
from .bisim import path_partition
from .capacity import BuildCaps, FlushCaps, estimate_build_caps
from .graph import LabeledGraph
from .paths import DeviceGraph, device_graph, enumerate_path_levels, seq_rows_of_levels, _recap


class DeviceIndexArrays(NamedTuple):
    """All device-resident arrays of a built index (a pytree)."""

    # pair table sorted by (v, u):  P^{<=k} with class ids
    pair_v: jax.Array
    pair_u: jax.Array
    pair_cls: jax.Array
    pair_count: jax.Array
    # I_c2p: same pairs sorted by (class, v, u) + CSR offsets per class
    c2p_cls: jax.Array
    c2p_v: jax.Array
    c2p_u: jax.Array
    class_starts: jax.Array  # (class_cap + 1,)
    class_cyclic: jax.Array  # (class_cap,) int32 0/1
    n_classes: jax.Array
    # I_l2c: unique seq table (n_seq_cap, k) + per-seq class ranges
    seq_table: jax.Array  # (n_seq_cap, k) padded with -1
    seq_count: jax.Array
    seq_starts: jax.Array  # (n_seq_cap,) start into l2c_cls
    seq_ends: jax.Array  # (n_seq_cap,)
    l2c_cls: jax.Array  # (l2c_cap,) class ids, ascending within a seq block
    l2c_count: jax.Array
    overflow: jax.Array


@functools.partial(jax.jit, static_argnames=("k", "caps_key"))
def build_index_arrays(dg: DeviceGraph, k: int, caps_key: tuple) -> DeviceIndexArrays:
    caps = BuildCaps(*caps_key)
    part = path_partition(dg, k, caps.level_rows, caps.pair_cap, caps.union_pair_cap)
    levels = enumerate_path_levels(dg, k, caps.level_rows)
    seq_rows = seq_rows_of_levels(levels, k, caps.seq_rows)  # (s1..sk, v, u)
    overflow = part.overflow
    for lvl in levels:
        overflow = overflow | lvl.overflow
    return _assemble(part.pairs, part.n_classes, seq_rows, k, caps, overflow)


def _assemble(pairs: R.Relation, n_classes, seq_rows: R.Relation, k: int,
              caps: BuildCaps, overflow) -> DeviceIndexArrays:
    """Shared tail of CPQx / iaCPQx construction: given the classified pair
    table (sorted by (v,u)) and the (seq..., v, u) incidence rows, build
    both inverted maps."""
    # ---------------- I_c2p ---------------- #
    bypair = pairs  # (v, u, cls) sorted by (v, u)
    c2p = R.rel_sort(
        R.Relation((pairs.cols[2], pairs.cols[0], pairs.cols[1]),
                   pairs.count, pairs.overflow),
        num_keys=3,
    )
    class_cap = bypair.capacity
    cls_ids = jnp.arange(class_cap + 1, dtype=R.I32)
    class_starts = jnp.searchsorted(c2p.cols[0], cls_ids, side="left").astype(R.I32)
    first = jnp.clip(class_starts[:-1], 0, class_cap - 1)
    class_cyclic = jnp.where(
        cls_ids[:-1] < n_classes,
        (c2p.cols[1][first] == c2p.cols[2][first]).astype(R.I32),
        0,
    )

    # ---------------- I_l2c ---------------- #
    # class of each row's (v, u)
    pos = R.lex_searchsorted(bypair.cols[:2], (seq_rows.cols[k], seq_rows.cols[k + 1]),
                             "left")
    posc = jnp.clip(pos, 0, bypair.capacity - 1)
    hit = (
        (pos < bypair.count)
        & (bypair.cols[0][posc] == seq_rows.cols[k])
        & (bypair.cols[1][posc] == seq_rows.cols[k + 1])
    )
    cls_of_row = jnp.where(hit, bypair.cols[2][posc], R.SENTINEL)
    l2c = R.Relation(
        tuple(seq_rows.cols[:k]) + (cls_of_row,), seq_rows.count,
        seq_rows.overflow,
    )
    l2c = R.rel_unique(R.rel_sort(l2c))  # (seq..., cls) distinct, sorted
    l2c = _recap(l2c, caps.l2c_rows)

    # unique sequences + their row ranges
    seqs = R.rel_unique(l2c, num_keys=k)
    seqs = _recap(R.Relation(seqs.cols[:k], seqs.count, seqs.overflow),
                  caps.n_seqs)
    starts = R.lex_searchsorted(l2c.cols[:k], seqs.cols, "left").astype(R.I32)
    ends = R.lex_searchsorted(l2c.cols[:k], seqs.cols, "right").astype(R.I32)
    validm = R.valid_mask(seqs)
    starts = jnp.where(validm, starts, 0)
    ends = jnp.where(validm, ends, 0)

    overflow = (overflow | pairs.overflow | l2c.overflow | seqs.overflow
                | seq_rows.overflow)

    return DeviceIndexArrays(
        pair_v=bypair.cols[0], pair_u=bypair.cols[1], pair_cls=bypair.cols[2],
        pair_count=bypair.count,
        c2p_cls=c2p.cols[0], c2p_v=c2p.cols[1], c2p_u=c2p.cols[2],
        class_starts=class_starts, class_cyclic=class_cyclic,
        n_classes=n_classes,
        seq_table=jnp.stack(seqs.cols, axis=1), seq_count=seqs.count,
        seq_starts=starts, seq_ends=ends,
        l2c_cls=l2c.cols[k], l2c_count=l2c.count,
        overflow=overflow,
    )


# ---------------------------------------------------------------------- #
# host wrapper
# ---------------------------------------------------------------------- #


@dataclasses.dataclass
class CPQxIndex:
    """Host handle: device arrays + query-time metadata.

    ``seq_ranges`` maps a label-sequence tuple to its (start, end) row
    range in ``l2c_cls`` — the only host-side lookup structure (query
    planning is host work by design)."""

    k: int
    n_vertices: int
    arrays: DeviceIndexArrays
    seq_ranges: dict
    caps: BuildCaps | FlushCaps
    interests: frozenset | None = None  # None => full CPQx

    @property
    def n_classes(self) -> int:
        return int(self.arrays.n_classes)

    @property
    def n_pairs(self) -> int:
        return int(self.arrays.pair_count)

    def size_entries(self) -> tuple[int, int]:
        """(|I_l2c|, |I_c2p|) valid entries — paper's size measure."""
        return int(self.arrays.l2c_count), int(self.arrays.pair_count)

    def lookup_range(self, seq: tuple) -> tuple[int, int]:
        return self.seq_ranges.get(tuple(seq), (0, 0))

    def available_seqs(self) -> set:
        return set(self.seq_ranges)

    # ---------------------- lifecycle (checkpoint) --------------------- #

    def save(self, ckpt_dir: str, step: int = 0) -> str:
        """Snapshot this index as one atomic committed checkpoint step
        (``repro.checkpoint`` rename-commit layout); returns the step
        dir.  ``restore`` + ``Engine.rebind`` replaces a from-graph
        rebuild — see :mod:`repro.core.lifecycle`."""
        from . import lifecycle  # lazy: keep import cost off the build path

        return lifecycle.save_index(self, ckpt_dir, step)

    @staticmethod
    def restore(ckpt_dir: str, step: int | None = None) -> "CPQxIndex":
        """Load the latest committed step (or ``step``) back into a
        ready-to-bind index: arrays device-placed, ``seq_ranges``
        recomputed from the arrays, caps decoded."""
        from . import lifecycle

        return lifecycle.restore_index(ckpt_dir, step)


def _pull_seq_ranges(arrays: DeviceIndexArrays, k: int) -> dict:
    """Host dict of seq -> (start, end) — on the build path and every
    maintenance flush.  Vectorized: per-seq lengths come from one numpy
    column reduction and the int conversion from one bulk ``tolist``
    (python ints in C), instead of ~n*k numpy-scalar casts in a loop."""
    n = int(arrays.seq_count)
    table = np.asarray(arrays.seq_table)[:n]
    lengths = (table >= 0).sum(axis=1).tolist()
    rows = table.tolist()
    starts = np.asarray(arrays.seq_starts)[:n].tolist()
    ends = np.asarray(arrays.seq_ends)[:n].tolist()
    return {
        tuple(row[:ln]): (s, e)
        for row, ln, s, e in zip(rows, lengths, starts, ends)
    }


def from_host_mirror(
    k: int,
    n_vertices: int,
    l2c: Mapping,
    c2p: Mapping,
    cyclic: Mapping,
    caps: FlushCaps | None = None,
    interests: frozenset | None = None,
) -> CPQxIndex:
    """Serialize a host-form index (the ``oracle.Index`` dict triple) into
    :class:`DeviceIndexArrays` — the mirror→device half of lazy maintenance
    (Sec. IV-E).

    Class ids are *renumbered densely* (in ascending old-id order, so every
    sorted class list stays sorted under the order-preserving remap) but the
    partition itself is untouched: lazily-split classes are serialized
    exactly as the mirror holds them, never merged back.  ``caps`` lets a
    caller reuse (and geometrically grow) the capacities of a previous
    flush so array shapes — and the jit executables keyed on them — stay
    stable while the mirror fits.
    """
    old_ids = sorted(c for c, ps in c2p.items() if ps)
    remap = {c: i for i, c in enumerate(old_ids)}
    n_classes = len(old_ids)

    pair_rows = np.array(
        [(v, u, remap[c]) for c in old_ids for (v, u) in c2p[c]],
        np.int64,
    ).reshape(-1, 3)
    n_pairs = pair_rows.shape[0]
    seqs = sorted(tuple(s) for s in l2c)
    n_l2c = sum(len(l2c[s]) for s in seqs)
    caps = (caps or FlushCaps.for_sizes(n_pairs, n_l2c, len(seqs)))
    caps = caps.grown_for(n_pairs, n_l2c, len(seqs))

    def pad_col(values, cap, fill=int(R.SENTINEL)):
        buf = np.full(cap, fill, np.int32)
        buf[: len(values)] = values
        return buf

    # ---------------- pair table, sorted by (v, u) ---------------- #
    byp = pair_rows[np.lexsort((pair_rows[:, 1], pair_rows[:, 0]))]
    pair_v = pad_col(byp[:, 0], caps.pair_cap)
    pair_u = pad_col(byp[:, 1], caps.pair_cap)
    pair_cls = pad_col(byp[:, 2], caps.pair_cap)

    # ------------- I_c2p: sorted by (class, v, u) + CSR ------------- #
    byc = pair_rows[np.lexsort((pair_rows[:, 1], pair_rows[:, 0], pair_rows[:, 2]))]
    c2p_cls = pad_col(byc[:, 2], caps.pair_cap)
    c2p_v = pad_col(byc[:, 0], caps.pair_cap)
    c2p_u = pad_col(byc[:, 1], caps.pair_cap)
    class_starts = np.searchsorted(
        c2p_cls.astype(np.int64), np.arange(caps.pair_cap + 1), side="left"
    ).astype(np.int32)
    class_cyclic = np.zeros(caps.pair_cap, np.int32)
    for c in old_ids:
        class_cyclic[remap[c]] = 1 if cyclic[c] else 0

    # ------------- I_l2c: seq table + per-seq class ranges ------------- #
    seq_table = np.full((caps.seq_cap, k), -1, np.int32)
    seq_starts = np.zeros(caps.seq_cap, np.int32)
    seq_ends = np.zeros(caps.seq_cap, np.int32)
    l2c_flat: list[int] = []
    seq_ranges: dict = {}
    for i, s in enumerate(seqs):
        seq_table[i, : len(s)] = s
        start = len(l2c_flat)
        l2c_flat.extend(sorted(remap[c] for c in l2c[s]))
        seq_starts[i] = start
        seq_ends[i] = len(l2c_flat)
        seq_ranges[s] = (start, len(l2c_flat))
    l2c_cls = pad_col(l2c_flat, caps.l2c_cap)

    arrays = DeviceIndexArrays(
        pair_v=jnp.asarray(pair_v), pair_u=jnp.asarray(pair_u),
        pair_cls=jnp.asarray(pair_cls),
        pair_count=jnp.asarray(n_pairs, R.I32),
        c2p_cls=jnp.asarray(c2p_cls), c2p_v=jnp.asarray(c2p_v),
        c2p_u=jnp.asarray(c2p_u),
        class_starts=jnp.asarray(class_starts),
        class_cyclic=jnp.asarray(class_cyclic),
        n_classes=jnp.asarray(n_classes, R.I32),
        seq_table=jnp.asarray(seq_table),
        seq_count=jnp.asarray(len(seqs), R.I32),
        seq_starts=jnp.asarray(seq_starts), seq_ends=jnp.asarray(seq_ends),
        l2c_cls=jnp.asarray(l2c_cls),
        l2c_count=jnp.asarray(n_l2c, R.I32),
        overflow=jnp.int32(0),
    )
    return CPQxIndex(
        k=k, n_vertices=n_vertices, arrays=arrays, seq_ranges=seq_ranges,
        caps=caps, interests=interests,
    )


def build(g: LabeledGraph, k: int, caps: BuildCaps | None = None) -> CPQxIndex:
    """Build CPQx for graph ``g`` at diameter ``k`` (paper default k=2)."""
    if caps is None:
        caps = estimate_build_caps(g, k)
    dg = device_graph(g)
    arrays = build_index_arrays(dg, k, caps.key())
    if bool(arrays.overflow):
        raise RuntimeError(
            "index build overflow — estimator undersized a relation "
            "(should not happen with the exact estimator)"
        )
    return CPQxIndex(
        k=k, n_vertices=g.n_vertices, arrays=arrays,
        seq_ranges=_pull_seq_ranges(arrays, k), caps=caps,
    )
