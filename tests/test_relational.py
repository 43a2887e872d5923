"""Unit + property tests for the capacity-padded relational algebra —
the substrate every engine op builds on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need hypothesis
from hypothesis import given, settings, strategies as st

from repro.core import relational as R


def _np_rel(rows, cap):
    return R.from_numpy(np.asarray(rows, np.int32).reshape(-1, 2), cap)


class TestSortUniqueRank:
    def test_sort_and_sentinel_padding(self):
        rel = _np_rel([[3, 1], [1, 2], [2, 0]], 8)
        s = R.rel_sort(rel)
        out = R.to_numpy(s)
        assert out.tolist() == [[1, 2], [2, 0], [3, 1]]
        # padding sorts to the end
        assert int(np.asarray(s.cols[0])[-1]) == R.SENTINEL

    def test_unique(self):
        rel = R.rel_sort(_np_rel([[1, 1], [1, 1], [2, 2], [2, 3], [2, 3]], 8))
        u = R.rel_unique(rel)
        assert R.to_numpy(u).tolist() == [[1, 1], [2, 2], [2, 3]]

    def test_dense_rank(self):
        rel = R.rel_sort(_np_rel([[1, 1], [1, 1], [2, 2], [3, 3]], 8))
        ranks, n = R.dense_rank(rel)
        assert int(n) == 3
        assert np.asarray(ranks)[:4].tolist() == [0, 0, 1, 2]

    def test_compact_stable(self):
        rel = _np_rel([[5, 0], [1, 0], [7, 0], [2, 0]], 8)
        keep = jnp.array([True, False, True, False] + [False] * 4)
        c = R.rel_compact(rel, keep)
        assert R.to_numpy(c)[:, 0].tolist() == [5, 7]


class TestBinarySearch:
    @given(
        hay=st.lists(st.integers(0, 50), min_size=1, max_size=80),
        needles=st.lists(st.integers(-5, 60), min_size=1, max_size=40),
        side=st.sampled_from(["left", "right"]),
    )
    @settings(max_examples=60, deadline=None)
    def test_matches_numpy_1col(self, hay, needles, side):
        h = np.sort(np.asarray(hay, np.int32))
        n = np.asarray(needles, np.int32)
        got = np.asarray(R.lex_searchsorted((jnp.array(h),), (jnp.array(n),), side))
        exp = np.searchsorted(h, n, side)
        assert (got == exp).all()

    @given(
        seed=st.integers(0, 2**31 - 1),
        side=st.sampled_from(["left", "right"]),
    )
    @settings(max_examples=40, deadline=None)
    def test_matches_numpy_2col(self, seed, side):
        rng = np.random.default_rng(seed)
        hay = rng.integers(0, 8, (rng.integers(1, 60), 2)).astype(np.int32)
        hay = hay[np.lexsort((hay[:, 1], hay[:, 0]))]
        nee = rng.integers(-1, 10, (20, 2)).astype(np.int32)
        enc_h = hay[:, 0] * 100 + hay[:, 1]
        enc_n = nee[:, 0] * 100 + nee[:, 1]
        got = np.asarray(
            R.lex_searchsorted(
                (jnp.array(hay[:, 0]), jnp.array(hay[:, 1])),
                (jnp.array(nee[:, 0]), jnp.array(nee[:, 1])),
                side,
            )
        )
        assert (got == np.searchsorted(enc_h, enc_n, side)).all()


class TestSetOps:
    def test_intersect(self):
        a = R.rel_sort(_np_rel([[1, 1], [2, 2], [3, 3], [5, 5]], 8))
        b = R.rel_sort(_np_rel([[2, 2], [3, 3], [9, 9]], 8))
        assert R.to_numpy(R.rel_intersect(a, b)).tolist() == [[2, 2], [3, 3]]

    def test_difference(self):
        a = R.rel_sort(_np_rel([[1, 1], [2, 2], [3, 3]], 8))
        b = R.rel_sort(_np_rel([[2, 2]], 4))
        assert R.to_numpy(R.rel_difference(a, b)).tolist() == [[1, 1], [3, 3]]

    @given(seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=30, deadline=None)
    def test_intersect_matches_python_sets(self, seed):
        rng = np.random.default_rng(seed)
        a = np.unique(rng.integers(0, 20, (30, 2)).astype(np.int32), axis=0)
        b = np.unique(rng.integers(0, 20, (30, 2)).astype(np.int32), axis=0)
        ra = R.rel_sort(R.from_numpy(a, 64))
        rb = R.rel_sort(R.from_numpy(b, 64))
        got = {tuple(r) for r in R.to_numpy(R.rel_intersect(ra, rb)).tolist()}
        exp = {tuple(r) for r in a.tolist()} & {tuple(r) for r in b.tolist()}
        assert got == exp

    def test_concat_overflow_flag(self):
        a = _np_rel([[1, 1], [2, 2]], 4)
        b = _np_rel([[3, 3], [4, 4], [5, 5]], 4)
        c = R.rel_concat(a, b, 4)
        assert bool(c.overflow)
        c2 = R.rel_concat(a, b, 8)
        assert not bool(c2.overflow) and int(c2.count) == 5


class TestExpansionJoin:
    @given(seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=30, deadline=None)
    def test_matches_python_join(self, seed):
        rng = np.random.default_rng(seed)
        a = rng.integers(0, 8, (rng.integers(1, 25), 2)).astype(np.int32)
        b = rng.integers(0, 8, (rng.integers(1, 25), 2)).astype(np.int32)
        b = b[np.lexsort((b[:, 1], b[:, 0]))]
        ra = R.from_numpy(a, 32)
        rb = R.from_numpy(b, 32)
        out = R.expansion_join(ra, rb, a_on=[1], out_cols=[("a", 0), ("b", 1)],
                               out_capacity=1024)
        got = sorted(map(tuple, R.to_numpy(out).tolist()))
        exp = sorted(
            (int(x), int(w)) for x, y in a for v, w in b if y == v
        )
        assert got == exp

    def test_overflow_flag(self):
        a = _np_rel([[0, 1]], 4)
        b = R.rel_sort(_np_rel([[1, 5], [1, 6], [1, 7]], 4))
        out = R.expansion_join(a, b, [1], [("a", 0), ("b", 1)], 2)
        assert bool(out.overflow) and int(out.count) == 2


class TestFingerprints:
    def test_order_invariance(self):
        c1 = (jnp.array([5, 3, 9], jnp.int32), jnp.array([1, 2, 0], jnp.int32))
        c2 = (jnp.array([9, 5, 3], jnp.int32), jnp.array([0, 1, 2], jnp.int32))
        seg = jnp.zeros(3, jnp.int32)
        ok = jnp.array([True] * 3)
        f1 = R.segment_fingerprint(*R.fingerprint_rows(c1), seg, 1, ok)
        f2 = R.segment_fingerprint(*R.fingerprint_rows(c2), seg, 1, ok)
        assert int(f1[0][0]) == int(f2[0][0]) and int(f1[1][0]) == int(f2[1][0])

    def test_different_sets_differ(self):
        c1 = (jnp.array([5, 3], jnp.int32),)
        c2 = (jnp.array([5, 4], jnp.int32),)
        seg = jnp.zeros(2, jnp.int32)
        ok = jnp.array([True] * 2)
        f1 = R.segment_fingerprint(*R.fingerprint_rows(c1), seg, 1, ok)
        f2 = R.segment_fingerprint(*R.fingerprint_rows(c2), seg, 1, ok)
        assert (int(f1[0][0]), int(f1[1][0])) != (int(f2[0][0]), int(f2[1][0]))


# ---------------------------------------------------------------------- #
# row recovery of compaction and expansion against a numpy reference
# (np.searchsorted over the prefix sums), padding included
# ---------------------------------------------------------------------- #

SCAN = R._SCAN_BLOCK
#: Row counts around the scan's tile width.
SIZES = [0, 1, 7, SCAN, SCAN + 1, 2 * SCAN + 5]
#: Per-row output counts: all zero, all one, random, and random with runs
#: of zero at the start, in the middle and at the end.
PATTERNS = ["zeros", "ones", "random", "zero_runs"]


def _counts(pattern: str, n: int, rng) -> np.ndarray:
    if pattern == "zeros":
        return np.zeros(n, np.int32)
    if pattern == "ones":
        return np.ones(n, np.int32)
    cnt = rng.integers(0, 4, n).astype(np.int32)
    if pattern == "zero_runs":
        cnt[: n // 4] = 0
        cnt[n // 2: n // 2 + n // 8] = 0
        cnt[n - n // 4:] = 0
    return cnt


def _np_compact(cols, count, keep):
    """(cols, count) of compaction, rows found by np.searchsorted."""
    cap = cols[0].shape[0]
    keep = keep & (np.arange(cap) < count)
    csum = np.cumsum(keep)
    new_count = int(csum[-1]) if cap else 0
    j = np.arange(cap)
    src = np.searchsorted(csum, j, side="right")[j < new_count]
    out = []
    for c in cols:
        o = np.full(cap, R.SENTINEL, np.int32)
        o[:new_count] = c[src]
        out.append(o)
    return out, new_count


def _np_expand(lo, cnt, out_capacity):
    """(ai, bj) of the valid output rows, valid mask, total, and ai of
    every output row (the last row where none is valid)."""
    ends = np.cumsum(cnt, dtype=np.int64)
    total = int(ends[-1]) if cnt.shape[0] else 0
    t = np.arange(out_capacity)
    valid = t < total
    every = np.clip(np.searchsorted(ends, t, side="right"), 0,
                    max(cnt.shape[0] - 1, 0))
    ai = every[valid]
    bj = lo[ai] + t[valid] - (ends - cnt)[ai]
    return ai, bj, valid, total, every


def _out_capacity(kind: str, n: int, total: int) -> int:
    return {"smaller": max(n // 2, 1), "larger": 2 * n + 3,
            "total": total, "below_total": max(total - 1, 0)}[kind]


class TestRowRecovery:
    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("keep_kind", ["all_false", "all_true", "random",
                                           "zero_runs"])
    def test_compact_matches_reference(self, n, keep_kind):
        rng = np.random.default_rng(n * 31 + len(keep_kind))
        cols = [rng.integers(0, 1000, n).astype(np.int32) for _ in range(2)]
        count = int(rng.integers(n // 2, n + 1)) if n else 0
        keep = {"all_false": np.zeros(n, bool), "all_true": np.ones(n, bool)
                }.get(keep_kind)
        if keep is None:
            keep = _counts(keep_kind, n, rng) > 0
        rel = R.make_relation(cols, count=count, overflow=4)
        got = R.rel_compact(rel, jnp.asarray(keep))
        exp, exp_count = _np_compact(cols, count, keep)
        assert int(got.count) == exp_count and int(got.overflow) == 4
        for g, e in zip(got.cols, exp):
            np.testing.assert_array_equal(np.asarray(g), e)

    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("pattern", PATTERNS)
    @pytest.mark.parametrize("cap_kind", ["smaller", "larger", "below_total"])
    def test_expand_ranges_matches_reference(self, n, pattern, cap_kind):
        rng = np.random.default_rng(n * 7 + len(pattern) + len(cap_kind))
        cnt = _counts(pattern, n, rng)
        lo = rng.integers(0, 500, n).astype(np.int32)
        out_capacity = _out_capacity(cap_kind, n, int(cnt.sum()))
        ai, bj, valid, total = R.expand_ranges(jnp.asarray(lo),
                                               jnp.asarray(cnt), out_capacity)
        e_ai, e_bj, e_valid, e_total, every = _np_expand(lo, cnt,
                                                         out_capacity)
        assert int(total) == e_total
        valid = np.asarray(valid)
        np.testing.assert_array_equal(valid, e_valid)
        np.testing.assert_array_equal(np.asarray(bj)[valid], e_bj)
        if n:  # padding rows read the last probe row, in range
            np.testing.assert_array_equal(np.asarray(ai), every)

    @pytest.mark.parametrize("n", SIZES[1:])
    @pytest.mark.parametrize("pattern", ["random", "zero_runs"])
    @pytest.mark.parametrize("cap_kind", ["smaller", "larger", "total",
                                          "below_total"])
    def test_expansion_join_matches_reference(self, n, pattern, cap_kind):
        """Rows of ``a`` whose key is 99 match nothing; more output rows
        than ``out_capacity`` set the overflow bit and keep the first
        ``out_capacity`` rows."""
        rng = np.random.default_rng(n * 13 + len(pattern) + len(cap_kind))
        b = rng.integers(0, 6, (max(n, 4), 2)).astype(np.int32)
        b = b[np.lexsort((b[:, 1], b[:, 0]))]
        a = rng.integers(0, 8, (n, 2)).astype(np.int32)
        a[_counts(pattern, n, rng) == 0, 1] = 99
        bk = b[:, 0]
        lo = np.searchsorted(bk, a[:, 1], "left").astype(np.int32)
        cnt = (np.searchsorted(bk, a[:, 1], "right") - lo).astype(np.int32)
        out_capacity = max(_out_capacity(cap_kind, n, int(cnt.sum())), 1)
        got = R.expansion_join(R.from_numpy(a, n), R.from_numpy(b, b.shape[0]),
                               [1], [("a", 0), ("b", 1)], out_capacity,
                               bit=8)
        e_ai, e_bj, valid, total, _ = _np_expand(lo, cnt, out_capacity)
        exp = np.full((2, out_capacity), R.SENTINEL, np.int32)
        exp[0, valid], exp[1, valid] = a[e_ai, 0], b[e_bj, 1]
        assert int(got.count) == min(total, out_capacity)
        assert int(got.overflow) == (8 if total > out_capacity else 0)
        np.testing.assert_array_equal(np.stack([np.asarray(c) for c in got.cols]),
                                      exp)

    @pytest.mark.parametrize("n", [5, SCAN + 1])
    def test_vmapped_lanes_match_reference(self, n):
        """One lane per pattern, each with its own counts."""
        rng = np.random.default_rng(n)
        cnt = np.stack([_counts(p, n, rng) for p in PATTERNS])
        lo = rng.integers(0, 500, cnt.shape).astype(np.int32)
        cols = rng.integers(0, 1000, (2,) + cnt.shape).astype(np.int32)
        counts = np.array([n, n - 1, n // 2, 0], np.int32)
        out_capacity = 2 * n
        ai, bj, valid, total = jax.vmap(
            lambda l, c: R.expand_ranges(l, c, out_capacity))(
                jnp.asarray(lo), jnp.asarray(cnt))
        comp = jax.vmap(lambda c0, c1, k, m: R.rel_compact(
            R.make_relation((c0, c1), count=k), m))(
                jnp.asarray(cols[0]), jnp.asarray(cols[1]),
                jnp.asarray(counts), jnp.asarray(cnt > 0))
        for lane in range(len(PATTERNS)):
            e_ai, e_bj, e_valid, e_total, every = _np_expand(
                lo[lane], cnt[lane], out_capacity)
            v = np.asarray(valid[lane])
            assert int(total[lane]) == e_total
            np.testing.assert_array_equal(v, e_valid)
            np.testing.assert_array_equal(np.asarray(ai[lane]), every)
            np.testing.assert_array_equal(np.asarray(bj[lane])[v], e_bj)
            exp, exp_count = _np_compact([cols[0, lane], cols[1, lane]],
                                         counts[lane], cnt[lane] > 0)
            assert int(comp.count[lane]) == exp_count
            for g, e in zip(comp.cols, exp):
                np.testing.assert_array_equal(np.asarray(g[lane]), e)


def _compact_2col(c0, c1, keep):
    return R.rel_compact(R.make_relation((c0, c1)), keep)


def _expand(lo, cnt):
    return R.expand_ranges(lo, cnt, 3 * SCAN)


@pytest.mark.parametrize("fn", [_compact_2col, _expand],
                         ids=["rel_compact", "expand_ranges"])
@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmapped"])
def test_row_recovery_lowers_without_loops(fn, vmapped):
    """Row recovery is O(n): the lowered program holds a scatter with
    sorted indices and no ``while`` (the binary search it replaces was a
    loop of log2(cap) dependent gathers)."""
    n = 2 * SCAN + 5
    shape = (3, n) if vmapped else (n,)
    args = [jax.ShapeDtypeStruct(shape, jnp.int32) for _ in range(2)]
    if fn is _compact_2col:
        args.append(jax.ShapeDtypeStruct(shape, jnp.bool_))
    text = jax.jit(jax.vmap(fn) if vmapped else fn).lower(*args).as_text()
    assert "while" not in text
    assert "stablehlo.scatter" in text
    assert "indices_are_sorted = true" in text
