"""Capacity-padded sorted relational algebra on device — the TPU-native
substrate of the CPQx engine.

The paper's C++ artifact manipulates dynamically-sized ``std::vector``s of
s-t pairs with pointer-walking sort-merge joins.  XLA needs static shapes,
so every relation here is a fixed-capacity set of int32 columns where the
valid rows occupy ``[0, count)`` and the padding rows are filled with
``SENTINEL`` (``2^31 - 1``), which sorts to the end.  Every operator
returns ``(relation, overflow)``-style results; the host driver sizes
capacities with the numpy estimator and retries on overflow.

Design notes (hardware adaptation, see DESIGN.md §2):

* multi-column lexicographic sort  -> stable two-operand sorts, one per
  key, least significant first (``lex_argsort``), then gathers
* pointer-walk merge join          -> branch-free *vectorized binary
  search* (fixed trip count = bit-length of capacity) + capacity-padded
  expansion join (prefix sums + one scatter for row recovery)
* compaction (filter)              -> prefix sums + the same row recovery
  + gather
* hash maps                        -> dense ranks (exact, collision-free)
* per-pair signature sets          -> order-invariant two-lane uint32
  fingerprints (sum of avalanche-mixed rows after exact dedup)

Everything is int32 on the hot path (TPU x64 off); values must be
``< SENTINEL``.

Each primitive runs under a ``jax.named_scope``, so the ``op_name`` of
every device op says which primitive it belongs to: ``sort``
(``lex_argsort``, ``rel_sort``), ``search`` (``lex_searchsorted``,
``lex_count_matches``), ``compact`` (``rel_compact``, ``rel_unique``,
``prefix_sum``, and the row recovery of compaction) and ``expand``
(``expand_ranges``' row recovery and the gathers of ``expand_ranges``
and ``expansion_join``).  Scopes only add metadata; the compiled
programs are the same.

Row recovery (which source row feeds output row ``t``) needs no search:
the start offsets of the source rows (the exclusive prefix sum of their
output counts) are sorted, so the source of output row ``t`` is the
number of rows starting at or before ``t``, less one — a scatter-add of
ones at the starts and a prefix sum (``_row_owners``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

SENTINEL = np.int32(2**31 - 1)
I32 = jnp.int32
U32 = jnp.uint32

def _scoped(name: str):
    """Run the decorated function under ``jax.named_scope(name)``, a new
    scope object per call: a scope object keeps the name stack it
    replaced, so one shared object (``@jax.named_scope(...)`` on a
    function) cannot be re-entered, by recursion or from another
    thread, without leaking its name onto the ops that follow."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


class Relation(NamedTuple):
    """A capacity-padded relation: parallel int32 columns + valid count.

    ``cols``     tuple of (cap,) int32 arrays; rows >= count are SENTINEL.
    ``count``    scalar int32 — number of valid rows.
    ``overflow`` scalar int32 — sticky overflow word: 0, or the bits of
                 the capacities some producer exceeded (it dropped rows).
                 Operators OR their inputs' words; an operator with an
                 output capacity sets the bit its caller names (1 unless
                 the caller attributes capacities, as the query engine's
                 ``OVF_*`` bits do).
    """

    cols: tuple
    count: jax.Array
    overflow: jax.Array

    @property
    def capacity(self) -> int:
        return self.cols[0].shape[0]

    @property
    def arity(self) -> int:
        return len(self.cols)


def make_relation(cols: Sequence[jax.Array], count=None, overflow=None) -> Relation:
    cols = tuple(jnp.asarray(c, I32) for c in cols)
    if count is None:
        count = jnp.asarray(cols[0].shape[0], I32)
    if overflow is None:
        overflow = 0
    return Relation(cols, jnp.asarray(count, I32), jnp.asarray(overflow, I32))


def overflow_bit(exceeded, bit: int = 1) -> jax.Array:
    """The overflow word of one capacity check: ``bit`` where
    ``exceeded`` holds, else 0."""
    return jnp.where(exceeded, jnp.int32(bit), jnp.int32(0))


def from_numpy(rows: np.ndarray, capacity: int) -> Relation:
    """Host rows (n, arity) -> padded device relation."""
    rows = np.asarray(rows, np.int32).reshape(rows.shape[0], -1)
    n, a = rows.shape
    if n > capacity:
        raise ValueError(f"{n} rows exceed capacity {capacity}")
    buf = np.full((capacity, a), SENTINEL, np.int32)
    buf[:n] = rows
    return make_relation(tuple(buf[:, j] for j in range(a)), count=n)


def to_numpy(rel: Relation) -> np.ndarray:
    """Valid rows as a host (count, arity) array."""
    n = int(rel.count)
    return np.stack([np.asarray(c)[:n] for c in rel.cols], axis=1)


def valid_mask(rel: Relation) -> jax.Array:
    return jnp.arange(rel.capacity, dtype=I32) < rel.count


# ---------------------------------------------------------------------- #
# batched (vmapped) relations: cols (batch, cap), count (batch,)
# ---------------------------------------------------------------------- #


def batch_to_numpy(rel: Relation, lanes=None) -> list[np.ndarray]:
    """Lanes of a vmapped relation as host (count_j, arity) arrays —
    all of them, or just the ``lanes`` indices.

    One device->host transfer per column (not per lane)."""
    cols = [np.asarray(c) for c in rel.cols]
    counts = np.asarray(rel.count)
    if lanes is None:
        lanes = range(counts.shape[0])
    return [
        np.stack([c[j, : counts[j]] for c in cols], axis=1)
        for j in lanes
    ]


# ---------------------------------------------------------------------- #
# sorting / compaction / dedup / ranks
# ---------------------------------------------------------------------- #


@_scoped("sort")
def lex_argsort(keys: Sequence[jax.Array]) -> jax.Array:
    """Stable lexicographic argsort of parallel key columns.

    One stable two-operand ``(key, permutation)`` sort per key, least
    significant key first, inside a ``fori_loop`` so the program holds a
    single sort.  The TPU compiler's time grows steeply with a sort's
    operands and keys (a five-key sort of a million rows takes it over
    ten minutes, one two-operand sort well under one), and a plan holds
    several sorts, so this keeps compile time bounded."""
    n = keys[0].shape[0]
    perm = jnp.arange(n, dtype=I32)
    if len(keys) == 1:
        return jax.lax.sort((keys[0], perm), num_keys=1, is_stable=True)[1]
    stacked = jnp.stack(keys)

    def one_key(i, perm):
        key = stacked[len(keys) - 1 - i][perm]
        return jax.lax.sort((key, perm), num_keys=1, is_stable=True)[1]

    return jax.lax.fori_loop(0, len(keys), one_key, perm)


@_scoped("sort")
def rel_sort(rel: Relation, num_keys: int | None = None) -> Relation:
    """Sort rows lexicographically by the first ``num_keys`` columns
    (stable).  SENTINEL padding rows sort to the end (values < SENTINEL
    invariant)."""
    nk = num_keys if num_keys is not None else rel.arity
    if nk == 1 and rel.arity <= 2:
        sorted_cols = jax.lax.sort(rel.cols, num_keys=1, is_stable=True)
        return Relation(tuple(sorted_cols), rel.count, rel.overflow)
    perm = lex_argsort(rel.cols[:nk])
    return Relation(tuple(c[perm] for c in rel.cols), rel.count,
                    rel.overflow)


#: Row width of :func:`prefix_sum`'s tiles.
_SCAN_BLOCK = 1024


def _prefix_sum(x: jax.Array) -> jax.Array:
    """:func:`prefix_sum` in the caller's scope."""
    n = x.shape[0]
    x = x.astype(I32)
    if n <= _SCAN_BLOCK:
        return jnp.cumsum(x, dtype=I32)
    rows = -(-n // _SCAN_BLOCK)
    pad = rows * _SCAN_BLOCK - n
    tiles = jnp.pad(x, (0, pad)).reshape(rows, _SCAN_BLOCK)
    inner = jnp.cumsum(tiles, axis=1, dtype=I32)
    totals = inner[:, -1]
    offsets = _prefix_sum(totals) - totals
    return (inner + offsets[:, None]).reshape(-1)[:n]


@_scoped("compact")
def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive int32 prefix sum of a 1-D array — the values of
    ``jnp.cumsum`` (wrapping int32 addition is associative), computed as
    a row-wise cumsum over ``(n / 1024, 1024)`` tiles plus the running
    row offsets.  The TPU compiler builds this in seconds, where one flat
    ``cumsum`` of a million elements takes it tens of seconds."""
    return _prefix_sum(x)


def _row_owners(cnt: jax.Array, out_capacity: int):
    """Row recovery: source row ``i`` owns ``cnt[i]`` consecutive output
    rows from ``starts[i]``, the exclusive prefix sum of ``cnt``.
    Returns ``(ai, starts, total)``: for each output row
    ``t < out_capacity`` the last row ``i`` with ``starts[i] <= t`` —
    its owner where ``t < total``, else the last row — and
    ``total = sum(cnt)``.  These are the values of
    ``clip(searchsorted(ends, t, side="right"), 0, n - 1)``.

    O(n), no search: ``starts`` is sorted, so the last row starting at or
    before ``t`` is one less than the number of rows that do — one
    scatter-add of ones at ``starts`` (sorted indices, so the TPU
    compiler sorts nothing) and a prefix sum over the output rows.  The
    second prefix sum runs in the caller's scope."""
    ends = prefix_sum(cnt)  # inclusive
    total = ends[-1] if cnt.shape[0] > 0 else jnp.int32(0)
    starts = ends - cnt
    hits = jnp.zeros(out_capacity, I32).at[starts].add(
        1, mode="drop", indices_are_sorted=True)
    return _prefix_sum(hits) - 1, starts, total


@_scoped("compact")
def rel_compact(rel: Relation, keep: jax.Array) -> Relation:
    """Stable-move rows with keep=True to the front; drop the rest.

    No sort and no search: kept row ``i`` is output row ``csum[i] - 1``
    (``csum`` the running keep-count); :func:`_row_owners` finds each
    output row's source with one scatter and prefix sums, then one gather
    per column."""
    keep = keep & valid_mask(rel)
    src, _, new_count = _row_owners(keep.astype(I32), rel.capacity)
    m = jnp.arange(rel.capacity, dtype=I32) < new_count
    cols = tuple(jnp.where(m, c[src], SENTINEL) for c in rel.cols)
    return Relation(cols, new_count, rel.overflow)


@_scoped("compact")
def rel_unique(rel: Relation, num_keys: int | None = None) -> Relation:
    """Dedup a *sorted* relation on its first ``num_keys`` columns
    (keeps the first row of each group)."""
    nk = num_keys if num_keys is not None else rel.arity
    first = _new_group_mask(rel.cols[:nk])
    return rel_compact(rel, first)


def _new_group_mask(cols: Sequence[jax.Array]) -> jax.Array:
    """True where a row differs from its predecessor (row 0 always True)."""
    neq = jnp.zeros(cols[0].shape, dtype=bool)
    for c in cols:
        neq = neq | (c != jnp.concatenate([c[:1] - 1, c[:-1]]))
    return neq


def dense_rank(rel: Relation, num_keys: int | None = None):
    """Dense rank of each row of a *sorted* relation over its first
    ``num_keys`` cols.  Returns (ranks (cap,) int32 with SENTINEL on padding,
    n_unique int32).  Exact — no hashing."""
    nk = num_keys if num_keys is not None else rel.arity
    first = _new_group_mask(rel.cols[:nk]) & valid_mask(rel)
    ranks = prefix_sum(first) - 1
    n_unique = jnp.sum(first, dtype=I32)
    ranks = jnp.where(valid_mask(rel), ranks, SENTINEL)
    return ranks, n_unique


# ---------------------------------------------------------------------- #
# vectorized lexicographic binary search
# ---------------------------------------------------------------------- #


def _lex_lt(a: Sequence[jax.Array], b: Sequence[jax.Array]) -> jax.Array:
    """Lexicographic a < b over parallel column tuples (broadcasting)."""
    lt = jnp.zeros(jnp.broadcast_shapes(a[0].shape, b[0].shape), bool)
    eq = jnp.ones_like(lt)
    for x, y in zip(a, b):
        lt = lt | (eq & (x < y))
        eq = eq & (x == y)
    return lt


def _lex_le(a, b) -> jax.Array:
    lt = jnp.zeros(jnp.broadcast_shapes(a[0].shape, b[0].shape), bool)
    eq = jnp.ones_like(lt)
    for x, y in zip(a, b):
        lt = lt | (eq & (x < y))
        eq = eq & (x == y)
    return lt | eq


@_scoped("search")
def lex_searchsorted(
    hay: Sequence[jax.Array], needles: Sequence[jax.Array], side: str = "left"
) -> jax.Array:
    """Vectorized binary search over rows sorted lexicographically.

    ``hay``: tuple of (n,) sorted columns; ``needles``: tuple of (m,)
    columns.  Returns (m,) int32 insertion positions.  Branch-free with a
    fixed trip count (bit length of n) — VPU-lane parallel on TPU."""
    n = hay[0].shape[0]
    steps = max(1, int(n).bit_length())
    lo = jnp.zeros(needles[0].shape, I32)
    hi = jnp.full(needles[0].shape, n, I32)

    cmp = _lex_lt if side == "left" else _lex_le

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) >> 1
        row = tuple(h[jnp.clip(mid, 0, n - 1)] for h in hay)
        go_right = cmp(row, needles)  # hay[mid] < needle (or <= for right)
        active = lo < hi  # converged lanes must not move (mid would read OOB)
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & (~go_right), mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


@_scoped("search")
def lex_count_matches(hay, needles, hay_count) -> jax.Array:
    """Number of hay rows equal to each needle row (0 for SENTINEL
    needles / rows beyond hay_count)."""
    left = lex_searchsorted(hay, needles, "left")
    right = lex_searchsorted(hay, needles, "right")
    cnt = right - left
    # guard the sentinel zone: positions >= hay_count are padding
    cnt = jnp.where(left < hay_count, cnt, 0)
    needle_ok = needles[0] != SENTINEL
    return jnp.where(needle_ok, cnt, 0).astype(I32)


# ---------------------------------------------------------------------- #
# set operations on sorted relations
# ---------------------------------------------------------------------- #


def rel_intersect(a: Relation, b: Relation, num_keys: int | None = None) -> Relation:
    """a ∩ b on the first num_keys columns; both must be sorted+unique on
    those columns.  Keeps a's rows (incl. extra payload columns).
    b's overflow is sticky on the result (an undersized b means missing
    matches — the caller must retry, not silently under-answer)."""
    nk = num_keys if num_keys is not None else min(a.arity, b.arity)
    cnt = lex_count_matches(b.cols[:nk], a.cols[:nk], b.count)
    out = rel_compact(a, cnt > 0)
    return Relation(out.cols, out.count, out.overflow | b.overflow)


def rel_difference(a: Relation, b: Relation, num_keys: int | None = None) -> Relation:
    nk = num_keys if num_keys is not None else min(a.arity, b.arity)
    cnt = lex_count_matches(b.cols[:nk], a.cols[:nk], b.count)
    out = rel_compact(a, cnt == 0)
    return Relation(out.cols, out.count, out.overflow | b.overflow)


def rel_concat(a: Relation, b: Relation, capacity: int,
               bit: int = 1) -> Relation:
    """Union-all into a fresh capacity (rows beyond capacity overflow,
    setting ``bit``)."""
    assert a.arity == b.arity
    total = a.count + b.count
    overflow = a.overflow | b.overflow | overflow_bit(total > capacity, bit)
    cols = []
    idx = jnp.arange(capacity, dtype=I32)
    for ca, cb in zip(a.cols, b.cols):
        from_a = idx < a.count
        ai = jnp.clip(idx, 0, a.capacity - 1)
        bi = jnp.clip(idx - a.count, 0, b.capacity - 1)
        col = jnp.where(from_a, ca[ai], cb[bi])
        col = jnp.where(idx < total, col, SENTINEL)
        cols.append(col)
    return Relation(tuple(cols), jnp.minimum(total, capacity).astype(I32), overflow)


# ---------------------------------------------------------------------- #
# capacity-padded expansion join
# ---------------------------------------------------------------------- #


def expansion_join(
    a: Relation,
    b: Relation,
    a_on: Sequence[int],
    out_cols: Sequence[tuple],
    out_capacity: int,
    bit: int = 1,
) -> Relation:
    """Join a with b where ``a.cols[a_on] == b.cols[:len(a_on)]``.

    ``b`` must be sorted on its first len(a_on) columns.  ``out_cols`` is a
    list of ("a"|"b", col_index) selectors for the output projection.
    More than ``out_capacity`` output rows set ``bit`` in the overflow.

    The classic TPU-native expansion join: per-a-row match counts from two
    binary searches, then output-row recovery by :func:`expand_ranges`
    (prefix sums and one scatter) and gathers — no dynamic shapes."""
    nk = len(a_on)
    a_keys = tuple(a.cols[i] for i in a_on)
    lo = lex_searchsorted(b.cols[:nk], a_keys, "left")
    hi = lex_searchsorted(b.cols[:nk], a_keys, "right")
    cnt = jnp.where(valid_mask(a) & (lo < b.count), hi - lo, 0).astype(I32)
    ai, bj, out_valid, total = expand_ranges(lo, cnt, out_capacity)
    bj = jnp.clip(bj, 0, b.capacity - 1)

    cols = []
    with jax.named_scope("expand"):
        for which, ci in out_cols:
            src = a.cols[ci][ai] if which == "a" else b.cols[ci][bj]
            cols.append(jnp.where(out_valid, src, SENTINEL))
    overflow = (a.overflow | b.overflow
                | overflow_bit(total > out_capacity, bit))
    return Relation(tuple(cols), jnp.minimum(total, out_capacity).astype(I32), overflow)


@_scoped("expand")
def expand_ranges(lo: jax.Array, cnt: jax.Array, out_capacity: int):
    """Output-row recovery of an expansion: probe row ``i`` owns
    ``cnt[i]`` consecutive output rows, reading build rows ``lo[i]``,
    ``lo[i] + 1``, ...  Returns ``(ai, bj, valid, total)``: for each
    output row ``t < out_capacity`` its probe row and build row
    (unclipped), ``t < total``, and the true output count ``total``.
    Where ``valid`` is False, ``ai`` is the last probe row and ``bj`` is
    arbitrary.  Row recovery is :func:`_row_owners`, with no search."""
    t = jnp.arange(out_capacity, dtype=I32)
    if cnt.shape[0] == 0:  # no probe rows: every output row is padding
        z = jnp.zeros_like(t)
        return z, z, z > 0, jnp.int32(0)
    ai, starts, total = _row_owners(cnt, out_capacity)
    return ai, lo[ai] + (t - starts[ai]), t < total, total


# ---------------------------------------------------------------------- #
# order-invariant fingerprints (for signature *sets*)
# ---------------------------------------------------------------------- #

_MIX_A = np.uint32(0x7FEB352D)
_MIX_B = np.uint32(0x846CA68B)

# the one shard-placement salt: device repartitioning (distributed) and
# host partitioning (sharded_index) must hash identically
SHARD_SALT = 0xB0C4


def mix32(x: jax.Array, salt: int) -> jax.Array:
    """splitmix-style avalanche mix on uint32 lanes (wrapping arithmetic)."""
    h = x.astype(U32) ^ jnp.uint32(salt)
    h = (h ^ (h >> 16)) * _MIX_A
    h = (h ^ (h >> 15)) * _MIX_B
    h = h ^ (h >> 16)
    return h


def fingerprint_rows(cols: Sequence[jax.Array], salt: int = 0) -> tuple:
    """Two independent uint32 fingerprints per row (64 effective bits)."""
    h1 = jnp.full(cols[0].shape, np.uint32(0x9E3779B9), U32)
    h2 = jnp.full(cols[0].shape, np.uint32(0x85EBCA6B), U32)
    for j, c in enumerate(cols):
        h1 = mix32(c.astype(U32) ^ (h1 * np.uint32(31)), salt * 2 + 101 + j)
        h2 = mix32(c.astype(U32) ^ (h2 * np.uint32(37)), salt * 2 + 202 + j)
    return h1, h2


def segment_fingerprint(
    h1: jax.Array, h2: jax.Array, segment_ids: jax.Array, num_segments: int,
    valid: jax.Array,
) -> tuple:
    """Order-invariant per-segment fingerprint: wrapping uint32 sums of the
    row mixes.  Rows must be exactly deduped beforehand (set == multiset).
    Invalid rows contribute 0.  SENTINEL segment ids are routed to a trash
    segment (caller sizes num_segments accordingly or clips)."""
    sid = jnp.clip(segment_ids, 0, num_segments - 1).astype(I32)
    z = jnp.uint32(0)
    f1 = jax.ops.segment_sum(jnp.where(valid, h1, z), sid, num_segments)
    f2 = jax.ops.segment_sum(jnp.where(valid, h2, z), sid, num_segments)
    return f1.astype(U32), f2.astype(U32)
