"""Differential tests for the batched execution path (PR 1 tentpole):
``Engine.execute_batch`` must be bit-identical to the semantics oracle
(and to sequential ``execute``) across templates, mixed batches,
duplicates, singleton batches, and the per-lane overflow-retry path."""

import numpy as np
import pytest

from conftest import random_graph
from repro.core import index as cindex
from repro.core import oracle
from repro.core.engine import Engine, QueryCaps
from repro.core.graph import LabeledGraph
from repro.core.query import TEMPLATES, TEMPLATE_ARITY, instantiate_template


def _rows(arr) -> set:
    return {tuple(r) for r in arr.tolist()}


@pytest.fixture(scope="module")
def built(ex_graph):
    return ex_graph, Engine(cindex.build(ex_graph, 2))


def _template_queries(g, rng, names, n_per=1):
    out = []
    for name in names:
        for _ in range(n_per):
            labels = rng.integers(0, g.alphabet_size,
                                  TEMPLATE_ARITY[name]).tolist()
            out.append(instantiate_template(name, labels))
    return out


class TestBatchedDifferential:
    def test_all_templates_in_one_mixed_batch(self, built):
        """One mixed batch covering all 12 Fig. 5 templates, two label
        draws each — results must equal the oracle query by query."""
        g, eng = built
        rng = np.random.default_rng(7)
        qs = _template_queries(g, rng, sorted(TEMPLATES), n_per=2)
        res = eng.execute_batch(qs)
        assert len(res) == len(qs)
        for q, r in zip(qs, res):
            assert _rows(r) == oracle.cpq_eval(g, q), q

    def test_batch_matches_sequential_execute(self, built):
        g, eng = built
        rng = np.random.default_rng(3)
        qs = _template_queries(g, rng, ["C2", "T", "S", "St", "C2i"], n_per=2)
        batched = eng.execute_batch(qs)
        for q, r in zip(qs, batched):
            assert _rows(r) == _rows(eng.execute(q)), q

    @pytest.mark.parametrize("seed", [1, 5])
    def test_random_graphs(self, seed):
        g = random_graph(seed, n_max=16, m_max=40)
        eng = Engine(cindex.build(g, 2))
        rng = np.random.default_rng(seed)
        qs = [oracle.random_cpq(rng, g, 2) for _ in range(6)]
        for q, r in zip(qs, eng.execute_batch(qs)):
            assert _rows(r) == oracle.cpq_eval(g, q), q

    def test_duplicate_queries_in_batch(self, built):
        g, eng = built
        q = instantiate_template("T", [0, 0, 1])
        qs = [q, q, q, instantiate_template("C2", [0, 1]), q]
        res = eng.execute_batch(qs)
        gt = oracle.cpq_eval(g, q)
        for i in (0, 1, 2, 4):
            assert _rows(res[i]) == gt
        assert _rows(res[3]) == oracle.cpq_eval(
            g, instantiate_template("C2", [0, 1]))

    def test_batch_of_one(self, built):
        g, eng = built
        q = instantiate_template("S", [0, 1, 1, 0])
        (r,) = eng.execute_batch([q])
        assert _rows(r) == oracle.cpq_eval(g, q)

    def test_empty_batch(self, built):
        _, eng = built
        assert eng.execute_batch([]) == []


class TestBatchOverflowRetry:
    def test_tiny_caps_per_lane_retry(self, built):
        """Every lane starts overflowing at caps (2,2,2); the sticky
        per-lane flags must drive retries until all answers are exact."""
        g, eng = built
        rng = np.random.default_rng(11)
        qs = _template_queries(g, rng, ["C2", "C4", "T", "TT"], n_per=2)
        res = eng.execute_batch(qs, caps=QueryCaps(2, 2, 2))
        for q, r in zip(qs, res):
            assert _rows(r) == oracle.cpq_eval(g, q), q

    def test_mixed_sizes_only_overflowing_lanes_grow(self, built):
        """A batch mixing an empty-answer query with heavy ones: caps
        sized so some lanes succeed on the first dispatch while others
        must retry — both kinds end exact."""
        g, eng = built
        heavy = instantiate_template("C4", [0, 2, 0, 2])
        light = instantiate_template("C2", [1, 1])
        qs = [heavy, light, heavy, light]
        res = eng.execute_batch(qs, caps=QueryCaps(4, 4, 4))
        for q, r in zip(qs, res):
            assert _rows(r) == oracle.cpq_eval(g, q), q

    @pytest.mark.parametrize("batched", [False, True])
    def test_only_the_overflowed_capacity_grows(self, batched):
        """A join-free lookup whose pair capacity is too small: the
        ladder doubles the pair capacity alone, so a generous join
        capacity never grows with it (it would outgrow HBM first)."""
        g = _heavy_graph()
        eng = Engine(cindex.build(g, 2))
        seen = []
        run, run_batch = eng.backend.run, eng.backend.run_batch
        eng.backend.run = lambda s, c, r: seen.append(c) or run(s, c, r)
        eng.backend.run_batch = (
            lambda s, c, r: seen.append(c) or run_batch(s, c, r))
        q = instantiate_template("C2", [0, 0])  # 72 answer pairs
        start = QueryCaps(64, 4, 1 << 10)
        rows = (eng.execute_batch([q], caps=start)[0] if batched
                else eng.execute(q, caps=start))
        assert _rows(rows) == oracle.cpq_eval(g, q)
        assert len(seen) > 1  # the ladder climbed
        assert {c.join_cap for c in seen} == {1 << 10}
        assert {c.class_cap for c in seen} == {64}
        assert seen[-1].pair_cap >= 72

    def test_min_bucket_variants_agree(self, built):
        """Bucket merging is a perf knob, never a semantics knob."""
        g, eng = built
        rng = np.random.default_rng(13)
        qs = _template_queries(g, rng, ["T", "S", "C2"], n_per=3)
        base = [_rows(r) for r in eng.execute_batch(qs, min_bucket=1)]
        merged = [_rows(r) for r in eng.execute_batch(qs, min_bucket=16)]
        assert base == merged
        assert base == [oracle.cpq_eval(g, q) for q in qs]


def _heavy_graph() -> LabeledGraph:
    """Complete bipartite label-0 waves in both directions: (0.0) has
    72 answer pairs, so tiny caps overflow through every doubling rung
    and land on the default-caps jump (attempt >= 3)."""
    A, B = range(0, 6), range(6, 12)
    edges = [(a, b, 0) for a in A for b in B]
    edges += [(b, a, 0) for a in A for b in B]
    return LabeledGraph.from_edges(12, 2, edges)


class TestTelemetryParity:
    def test_one_lane_batch_matches_execute(self):
        """Bug 3 regression, half one: a 1-lane ``execute_batch`` must
        report the SAME ladder telemetry as ``execute`` — queries,
        rungs, and default jumps."""
        g = _heavy_graph()
        idx = cindex.build(g, 2)
        e1, e2 = Engine(idx), Engine(idx)
        q = instantiate_template("C2", [0, 0])
        r1 = e1.execute(q, caps=QueryCaps(2, 2, 2))
        (r2,) = e2.execute_batch([q], caps=QueryCaps(2, 2, 2))
        assert _rows(r1) == _rows(r2) == oracle.cpq_eval(g, q)
        t1, t2 = e1.telemetry, e2.telemetry
        assert t1.default_jumps > 0  # the ladder actually jumped
        assert (t1.queries, t1.retry_rungs, t1.default_jumps) == \
            (t2.queries, t2.retry_rungs, t2.default_jumps)

    def test_default_jumps_count_per_lane(self):
        """Bug 3 regression, half two: N lanes that each exhaust the
        doubling rungs are N default-caps jumps, not one per dispatch —
        the pre-fix per-dispatch counter under-reported by the batch
        width, hiding estimator misses exactly when batching amortized
        them."""
        g = _heavy_graph()
        idx = cindex.build(g, 2)
        q = instantiate_template("C2", [0, 0])
        single = Engine(idx)
        single.execute(q, caps=QueryCaps(2, 2, 2))
        per_lane = single.telemetry.default_jumps
        assert per_lane > 0
        batch = Engine(idx)
        batch.execute_batch([q] * 4, caps=QueryCaps(2, 2, 2))
        assert batch.telemetry.default_jumps == 4 * per_lane
        assert batch.telemetry.retry_rungs == \
            4 * single.telemetry.retry_rungs


class TestUnionExecutable:
    def test_union_matches_shaped_and_oracle(self, built):
        """Straggler fusion is a perf knob, never a semantics knob: a
        mixed-template batch forced through ONE union dispatch is
        bit-identical to the per-shape path and the oracle."""
        g, _ = built
        idx = cindex.build(g, 2)
        shaped, fused = Engine(idx), Engine(idx)
        rng = np.random.default_rng(19)
        qs = _template_queries(g, rng, ["C2", "T", "S", "C2i", "St", "C4"])
        base = shaped.execute_batch(qs, min_bucket=1)
        got = fused.execute_batch(qs, union=True, min_bucket=64)
        for q, r, u in zip(qs, base, got):
            assert _rows(u) == _rows(r) == oracle.cpq_eval(g, q), q
        assert fused.telemetry.union_lanes == len(qs)
        assert fused.telemetry.dispatches <= shaped.telemetry.dispatches

    def test_union_drives_the_retry_ladder(self, built):
        """Per-lane sticky overflow keeps working through the union VM:
        tiny caps force the ladder and every answer ends exact."""
        g, _ = built
        eng = Engine(cindex.build(g, 2))
        rng = np.random.default_rng(23)
        qs = _template_queries(g, rng, ["C2", "C4", "T", "TT"])
        res = eng.execute_batch(qs, caps=QueryCaps(2, 2, 2), union=True,
                                min_bucket=64)
        for q, r in zip(qs, res):
            assert _rows(r) == oracle.cpq_eval(g, q), q
        assert eng.telemetry.union_lanes == len(qs)
        assert eng.telemetry.retry_rungs > 0

    def test_full_buckets_are_not_fused(self, built):
        """Only sub-``min_bucket`` stragglers fuse; a bucket already
        wide enough keeps its specialized executable."""
        g, _ = built
        eng = Engine(cindex.build(g, 2))
        rng = np.random.default_rng(29)
        qs = _template_queries(g, rng, ["T"], n_per=5)  # one shape, 5 wide
        res = eng.execute_batch(qs, union=True, min_bucket=4)
        for q, r in zip(qs, res):
            assert _rows(r) == oracle.cpq_eval(g, q), q
        assert eng.telemetry.union_lanes == 0


class TestAdaptiveCaps:
    def test_estimates_are_safe_or_retried(self, built):
        """estimate_caps may undersize (that's the design) but execute
        must still deliver exact answers via the retry ladder."""
        g, eng = built
        rng = np.random.default_rng(17)
        for q in _template_queries(g, rng, sorted(TEMPLATES)):
            assert _rows(eng.execute(q)) == oracle.cpq_eval(g, q), q

    def test_identity_floor(self, built):
        """A bare `id` query needs pair_cap >= n_vertices up front."""
        g, eng = built
        from repro.core.query import Identity, plan_query, plan_shape

        plan = plan_query(Identity(), eng.index.k)
        caps = eng.estimate_caps(eng.lookup_ranges(plan), plan_shape(plan))
        assert caps.pair_cap >= g.n_vertices
        assert _rows(eng.execute(Identity())) == {
            (v, v) for v in range(g.n_vertices)}
