"""Per-kernel validation: Pallas (interpret mode on CPU) vs the pure-jnp
oracle in ref.py, swept across shapes and dtypes — and the XLA programs
that run the query path's conjunction and expansion (``core.relational``)
against numpy ground truth."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need hypothesis
from hypothesis import given, settings, strategies as st

from repro.core import relational as R
from repro.kernels import ops, ref

SENTINEL = np.int32(2**31 - 1)


class TestSortedIntersect:
    """Class-id membership (Prop. 4.1) as ``conj_classes`` runs it: one
    binary search per probe into the sorted list's valid prefix."""

    @staticmethod
    def _member(hay, count, queries):
        return (R.lex_count_matches((hay,), (queries,), count) > 0
                ).astype(np.int32)

    @pytest.mark.parametrize("n_hay", [1, 7, 128, 1000])
    @pytest.mark.parametrize("n_q", [1, 64, 1024, 1500])
    def test_shape_sweep(self, n_hay, n_q):
        rng = np.random.default_rng(n_hay * 10_007 + n_q)
        hay = np.sort(rng.choice(5 * n_hay, n_hay, replace=False)).astype(np.int32)
        count = rng.integers(0, n_hay + 1)
        queries = rng.integers(0, 5 * n_hay, n_q).astype(np.int32)
        got = np.asarray(self._member(jnp.array(hay), count,
                                      jnp.array(queries)))
        gt = np.isin(queries, hay[:count]).astype(np.int32)
        np.testing.assert_array_equal(got, gt)

    def test_sentinel_queries_never_match(self):
        hay = jnp.array([1, 5, 9, SENTINEL], jnp.int32)
        q = jnp.array([5, SENTINEL, 9, SENTINEL], jnp.int32)
        got = np.asarray(self._member(hay, 3, q))
        np.testing.assert_array_equal(got, [1, 0, 1, 0])


class TestExpandJoin:
    """The expansion the join and ``materialize`` run
    (``relational.expansion_join`` over ``expand_ranges``)."""

    @given(seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_matches_ref_and_python(self, seed):
        rng = np.random.default_rng(seed)
        n_a, n_b = int(rng.integers(1, 40)), int(rng.integers(1, 40))
        a = rng.integers(0, 6, (n_a, 2)).astype(np.int32)
        b = rng.integers(0, 6, (n_b, 2)).astype(np.int32)
        b = b[np.lexsort((b[:, 1], b[:, 0]))]
        rows = sorted(
            (int(bv), int(bu), int(av))
            for (av, ak) in a
            for (bv, bu) in b
            if bv == ak
        )
        cap = max(8, 1 << max(0, (len(rows) - 1)).bit_length())
        out = R.expansion_join(R.from_numpy(a, n_a), R.from_numpy(b, n_b),
                               a_on=[1],
                               out_cols=[("b", 0), ("b", 1), ("a", 0)],
                               out_capacity=cap)
        assert not bool(out.overflow)
        assert int(out.count) == len(rows)
        got = [np.asarray(c) for c in out.cols]
        got_rows = sorted(zip(*(g[:len(rows)].tolist() for g in got)))
        assert got_rows == rows
        for g in got:  # padding past the output count
            assert np.all(g[len(rows):] == SENTINEL)


class TestFingerprint:
    @pytest.mark.parametrize("n_cols", [1, 2, 4])
    @pytest.mark.parametrize("n", [16, 100, 2048, 4096])
    def test_bit_identical_to_relational(self, n_cols, n):
        from repro.core.relational import fingerprint_rows as core_fp

        rng = np.random.default_rng(n * 31 + n_cols)
        cols = tuple(
            jnp.array(rng.integers(-5, 1000, n), jnp.int32) for _ in range(n_cols)
        )
        g1, g2 = ops.fingerprint_rows(cols, salt=3)
        e1, e2 = core_fp(cols, salt=3)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(e1))
        np.testing.assert_array_equal(np.asarray(g2), np.asarray(e2))


class TestSegmentSoftmax:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("e,d,n", [(512, 1, 16), (1024, 8, 64), (2048, 4, 100)])
    def test_matches_ref(self, dtype, e, d, n):
        rng = np.random.default_rng(e + d + n)
        scores = jnp.array(rng.normal(0, 3, (e, d)), dtype)
        seg = jnp.array(np.sort(rng.integers(0, n, e)), jnp.int32)
        got = np.asarray(ops.segment_softmax(scores, seg, n), np.float32)
        exp = np.asarray(ref.segment_softmax(scores, seg, n), np.float32)
        tol = 1e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(got, exp, rtol=tol, atol=tol)

    def test_normalization_sums_to_one(self):
        rng = np.random.default_rng(0)
        scores = jnp.array(rng.normal(0, 1, (512, 1)), jnp.float32)
        seg = jnp.array(np.sort(rng.integers(0, 10, 512)), jnp.int32)
        out = np.asarray(ops.segment_softmax(scores, seg, 10))
        sums = np.zeros(10)
        np.add.at(sums, np.asarray(seg), out[:, 0])
        present = np.unique(np.asarray(seg))
        np.testing.assert_allclose(sums[present], 1.0, rtol=1e-5)


class TestEngineUsesKernels:
    def test_engine_results_invariant_to_pallas_flag(self, monkeypatch, ex_graph):
        """The engine must produce identical answers with kernels on/off."""
        from repro.core import index as cindex
        from repro.core.engine import Engine
        from repro.core.query import parse

        q = parse("(f . f) & f-", {"f": 0, "v": 1}, 2)
        eng = Engine(cindex.build(ex_graph, 2))
        a = {tuple(r) for r in eng.execute(q).tolist()}
        monkeypatch.setenv("REPRO_DISABLE_PALLAS", "1")
        b = {tuple(r) for r in eng.execute(q).tolist()}
        assert a == b == {(0, 2), (1, 0), (2, 1)}
