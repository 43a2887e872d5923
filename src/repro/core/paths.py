"""Device-side enumeration of P^{<=k}: distinct labeled paths per level.

Level i holds the relation of distinct rows (v, u, s_1, ..., s_i) — one row
per *distinct label sequence* realized from v to u by some length-i path
(path multiplicity is deduped away; CPQ semantics are set-based).

Level 1 is the edge relation; level i is the capacity-padded expansion
join of level i-1 with the edges on the shared intermediate vertex,
followed by sort + exact dedup.  This same relation *is* the
language-unaware path index [14] (label sequence -> s-t pairs), so the
baseline and CPQx share one enumeration.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import relational as R
from .graph import LabeledGraph


class DeviceGraph(NamedTuple):
    """Edge relation on device, sorted by (src, dst, lbl)."""

    edges: R.Relation  # cols (src, dst, lbl)
    n_vertices: int  # static
    n_labels: int  # static (base labels; alphabet is 2x)


def device_graph(g: LabeledGraph, capacity: int | None = None) -> DeviceGraph:
    rows = np.stack([g.src, g.dst, g.lbl], axis=1)
    order = np.lexsort((rows[:, 2], rows[:, 1], rows[:, 0]))
    rows = rows[order]
    cap = capacity or max(1, rows.shape[0])
    return DeviceGraph(R.from_numpy(rows, cap), g.n_vertices, g.n_labels)


@functools.partial(jax.jit, static_argnames=("k", "caps"))
def enumerate_path_levels(dg: DeviceGraph, k: int, caps: tuple) -> tuple:
    """Compute levels 1..k.  ``caps[i-1]`` is the row capacity of level i.

    Returns a tuple of Relations; level i has cols (v, u, s_1..s_i),
    sorted by (v, u, s_1..s_i), exactly deduped.  Overflow flags are
    sticky through the pipeline.
    """
    assert len(caps) == k
    edges = dg.edges  # sorted by (src, dst, lbl)
    lvl1 = R.rel_sort(
        R.Relation(edges.cols, edges.count, edges.overflow), num_keys=3
    )
    # re-embed at requested capacity
    lvl1 = _recap(lvl1, caps[0])
    levels = [lvl1]
    for i in range(2, k + 1):
        prev = levels[-1]  # (v, m, s_1..s_{i-1}) sorted by (v, m, ...)
        # join key: prev's col 1 (m) against edges' src
        prev_by_m = R.rel_sort(prev, num_keys=prev.arity)  # ensure sorted
        # we need prev sorted by m for nothing — expansion join only needs
        # *edges* sorted on the key; prev rows are streamed.
        out_cols = (
            [("a", 0), ("b", 1)]
            + [("a", j) for j in range(2, prev.arity)]
            + [("b", 2)]
        )
        joined = R.expansion_join(
            prev_by_m, edges, a_on=[1], out_cols=out_cols, out_capacity=caps[i - 1]
        )
        joined = R.rel_sort(joined)
        joined = R.rel_unique(joined)
        levels.append(joined)
    return tuple(levels)


def _recap(rel: R.Relation, cap: int, bit: int = 1) -> R.Relation:
    """Re-embed a relation at a (>= count) capacity; more rows than
    ``cap`` set ``bit`` in the overflow."""
    if rel.capacity == cap:
        return rel
    idx = jnp.arange(cap, dtype=R.I32)
    m = idx < rel.count
    src = jnp.clip(idx, 0, rel.capacity - 1)
    cols = tuple(jnp.where(m, c[src], R.SENTINEL) for c in rel.cols)
    overflow = rel.overflow | R.overflow_bit(rel.count > cap, bit)
    return R.Relation(cols, jnp.minimum(rel.count, cap).astype(R.I32), overflow)


def pairs_of_levels(levels: tuple, cap: int, union_cap: int | None = None) -> R.Relation:
    """Distinct s-t pairs across all levels: P^{<=k} (cols v, u).
    ``union_cap`` must hold the pre-dedup union (defaults to sum of level
    capacities)."""
    if union_cap is None:
        union_cap = sum(lvl.capacity for lvl in levels)
    acc = None
    for lvl in levels:
        pairs = R.Relation(lvl.cols[:2], lvl.count, lvl.overflow)
        pairs = R.rel_unique(R.rel_sort(pairs), 2)
        acc = pairs if acc is None else R.rel_concat(acc, pairs, union_cap)
    acc = R.rel_unique(R.rel_sort(acc), 2)
    return _recap(acc, cap)


def seq_rows_of_levels(levels: tuple, k: int, cap: int) -> R.Relation:
    """All (s_1..s_k [padded -1], v, u) incidence rows across levels.

    The sequence columns come first so the result can be sorted/grouped by
    sequence; shorter sequences are padded with -1 (sorts before any real
    label)."""
    parts = []
    for i, lvl in enumerate(levels, start=1):
        v, u = lvl.cols[0], lvl.cols[1]
        seq = list(lvl.cols[2:])
        validm = R.valid_mask(lvl)
        pad = jnp.where(validm, jnp.int32(-1), R.SENTINEL)
        seq = seq + [pad] * (k - i)
        parts.append(R.Relation(tuple(seq) + (v, u), lvl.count, lvl.overflow))
    acc = parts[0]
    for p in parts[1:]:
        acc = R.rel_concat(acc, p, cap)
    return R.rel_unique(R.rel_sort(_recap(acc, cap)))
