"""Baselines: the state-of-the-art language-unaware path index [14]
(inverted index: label sequence -> s-t pairs) and index-free BFS.

The Path index shares the CPQx path enumeration — its payload is exactly
the (seq, v, u) incidence relation, CSR-organized by sequence.  Its
evaluator executes the *same* physical plans as CPQx but has no class
space: every operator works on materialized pair sets.  That contrast is
the paper's headline measurement (Fig. 6 / Table III): conjunctions cost
|pairs| here vs |classes| with CPQx.

``iaPath`` (interest-filtered variant) is the same structure built over
the L_q-filtered rows.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import relational as R
from .capacity import BuildCaps, estimate_build_caps
from .engine import QueryCaps, _join_pairs
from .graph import LabeledGraph
from .interest import normalize_interests
from .paths import DeviceGraph, _recap, device_graph, enumerate_path_levels, seq_rows_of_levels
from .query import CPQ, plan_query, plan_lookup_seqs


class PathArrays(NamedTuple):
    seq_table: jax.Array  # (n_seq_cap, k) padded -1, sorted
    seq_count: jax.Array
    seq_starts: jax.Array
    seq_ends: jax.Array
    l2p_v: jax.Array  # rows sorted by (seq, v, u)
    l2p_u: jax.Array
    l2p_count: jax.Array
    overflow: jax.Array


@functools.partial(jax.jit, static_argnames=("k", "caps_key", "interest_key"))
def build_path_arrays(dg: DeviceGraph, k: int, caps_key: tuple,
                      interest_key: tuple | None = None) -> PathArrays:
    caps = BuildCaps(*caps_key)
    levels = enumerate_path_levels(dg, k, caps.level_rows)
    rows = seq_rows_of_levels(levels, k, caps.seq_rows)  # (s1..sk, v, u) sorted
    overflow = rows.overflow
    for lvl in levels:
        overflow = overflow | lvl.overflow
    if interest_key is not None:
        itable = jnp.asarray(np.array(interest_key, np.int32))
        icols = tuple(itable[:, j] for j in range(k))
        cnt = R.lex_count_matches(icols, rows.cols[:k],
                                  jnp.asarray(itable.shape[0], R.I32))
        rows = R.rel_compact(rows, cnt > 0)

    seqs = R.rel_unique(rows, num_keys=k)
    seqs = _recap(R.Relation(seqs.cols[:k], seqs.count, seqs.overflow),
                  caps.n_seqs)
    starts = R.lex_searchsorted(rows.cols[:k], seqs.cols, "left").astype(R.I32)
    ends = R.lex_searchsorted(rows.cols[:k], seqs.cols, "right").astype(R.I32)
    validm = R.valid_mask(seqs)
    starts = jnp.where(validm, starts, 0)
    ends = jnp.where(validm, ends, 0)
    return PathArrays(
        seq_table=jnp.stack(seqs.cols, axis=1), seq_count=seqs.count,
        seq_starts=starts, seq_ends=ends,
        l2p_v=rows.cols[k], l2p_u=rows.cols[k + 1], l2p_count=rows.count,
        overflow=overflow | seqs.overflow,
    )


@dataclasses.dataclass
class PathIndex:
    k: int
    n_vertices: int
    arrays: PathArrays
    seq_ranges: dict
    interests: frozenset | None = None

    def size_entries(self) -> int:
        return int(self.arrays.l2p_count)

    def lookup_range(self, seq: tuple) -> tuple[int, int]:
        return self.seq_ranges.get(tuple(seq), (0, 0))


def build_path(g: LabeledGraph, k: int,
               interests: Iterable[tuple] | None = None,
               caps: BuildCaps | None = None) -> PathIndex:
    if caps is None:
        caps = estimate_build_caps(g, k)
    ikey = normalize_interests(g, k, interests) if interests is not None else None
    dg = device_graph(g)
    arrays = build_path_arrays(dg, k, caps.key(), ikey)
    if bool(arrays.overflow):
        raise RuntimeError("path index build overflow")
    n = int(arrays.seq_count)
    table = np.asarray(arrays.seq_table)[:n]
    st = np.asarray(arrays.seq_starts)[:n]
    en = np.asarray(arrays.seq_ends)[:n]
    ranges = {
        tuple(int(x) for x in row if x >= 0): (int(s), int(e))
        for row, s, e in zip(table, st, en)
    }
    return PathIndex(
        k=k, n_vertices=g.n_vertices, arrays=arrays, seq_ranges=ranges,
        interests=(frozenset(tuple(x for x in s if x >= 0) for s in ikey)
                   if ikey is not None else None),
    )


# ---------------------------------------------------------------------- #
# evaluator — same plans, pair space only
# ---------------------------------------------------------------------- #


def _lookup_pairs(a: PathArrays, start, length, cap: int) -> R.Relation:
    idx = jnp.arange(cap, dtype=R.I32)
    valid = idx < length
    src = jnp.clip(start + idx, 0, a.l2p_v.shape[0] - 1)
    v = jnp.where(valid, a.l2p_v[src], R.SENTINEL)
    u = jnp.where(valid, a.l2p_u[src], R.SENTINEL)
    # rows within a seq block are sorted by (v, u) and distinct
    return R.Relation((v, u), jnp.minimum(length, cap).astype(R.I32),
                      R.overflow_bit(length > cap))


@functools.partial(jax.jit, static_argnames=("plan", "caps", "n_vertices"))
def run_plan_path(a: PathArrays, plan, caps: QueryCaps, n_vertices: int,
                  lookup_ranges: jax.Array):
    counter = [0]

    def next_range():
        i = counter[0]
        counter[0] += 1
        return lookup_ranges[i, 0], lookup_ranges[i, 1]

    def ev(node):
        kind = node[0]
        if kind == "lookup":
            nseg = node[1] if isinstance(node[1], int) else len(node[1])
            start, length = next_range()
            cur = _lookup_pairs(a, start, length, caps.pair_cap)
            for _ in range(nseg - 1):
                start, length = next_range()
                nxt = _lookup_pairs(a, start, length, caps.pair_cap)
                cur = _join_pairs(cur, nxt, caps.join_cap, caps.pair_cap)
            return cur
        if kind == "identity":
            v = jnp.arange(caps.pair_cap, dtype=R.I32)
            m = v < n_vertices
            col = jnp.where(m, v, R.SENTINEL)
            return R.Relation((col, col),
                              jnp.asarray(min(n_vertices, caps.pair_cap), R.I32),
                              R.overflow_bit(n_vertices > caps.pair_cap))
        if kind == "conj_id":
            rel = ev(node[1])
            return R.rel_compact(rel, rel.cols[0] == rel.cols[1])
        left = ev(node[1])
        right = ev(node[2])
        if kind == "conj":
            return R.rel_intersect(left, right, 2)
        if kind == "join":
            return _join_pairs(left, right, caps.join_cap, caps.pair_cap)
        raise ValueError(kind)

    pairs = ev(plan)
    return pairs, pairs.overflow


class PathEngine:
    def __init__(self, index: PathIndex):
        self.index = index
        self._available = (set(index.seq_ranges)
                           if index.interests is not None else None)

    def execute(self, q: CPQ, caps: QueryCaps | None = None,
                max_retries: int = 8) -> np.ndarray:
        from .query import plan_shape

        plan = plan_query(q, self.index.k, available=self._available)
        seqs = plan_lookup_seqs(plan)
        ranges = np.array([self.index.lookup_range(s) for s in seqs],
                          np.int32).reshape(-1, 2)
        ranges[:, 1] = ranges[:, 1] - ranges[:, 0]
        if caps is None:
            n = max(16, int(self.index.arrays.l2p_count))
            p2 = 1 << (n - 1).bit_length()
            caps = QueryCaps(class_cap=16, pair_cap=p2, join_cap=2 * p2)
        for _ in range(max_retries):
            pairs, overflow = run_plan_path(
                self.index.arrays, plan_shape(plan), caps, self.index.n_vertices,
                jnp.asarray(ranges),
            )
            if not bool(overflow):
                return R.to_numpy(pairs)
            caps = caps.doubled()
        raise RuntimeError("query overflow not resolved after retries")
