"""Cluster runtime (core.cluster + launch.workers): the in-process
thread twin of the exchange fabric, multi-process parity at 1/2/4
workers against the local engine and the oracle, the service stack
(pipelined drain, maintenance flush, interest rounds, checkpoints) over
worker processes, fault injection (mid-round, pre-rebind-ack,
mid-checkpoint, hard kill) with oracle-identical recovery and no lost
accepted requests, and elastic RESHARD."""

import threading
import time

import numpy as np
import pytest

from conftest import random_graph
from repro.core import cluster as cl
from repro.core import oracle
from repro.core.engine import Engine
from repro.core.maintenance import MaintainableIndex
from repro.core.query import (TEMPLATE_ARITY, TEMPLATES,
                              instantiate_template, plan_shape)
from repro.core.rpq import RAlt, RConcat, RStar, RSym
from repro.core.service import QueryService


def _rows(arr) -> set:
    return {tuple(r) for r in arr.tolist()}


def _queries(g, names, seed=11):
    rng = np.random.default_rng(seed)
    return [instantiate_template(
        n, rng.integers(0, g.alphabet_size, TEMPLATE_ARITY[n]).tolist())
        for n in names]


@pytest.fixture(scope="module")
def fleet_graph():
    return random_graph(5, n_max=20, m_max=55)


@pytest.fixture(scope="module")
def fleet(fleet_graph):
    """One shared 2-worker fleet (max_workers=4 for the resize test at
    the end).  Spawning + per-worker jax init is seconds — tests share
    the fleet and derive ground truth from the maintainer's live graph,
    so earlier mutations never invalidate later assertions."""
    maint = MaintainableIndex.build(fleet_graph, 2)
    engine = Engine(maint.flush(), cluster=2)
    yield {"maint": maint, "engine": engine}
    engine.backend.shutdown()


# ---------------------------------------------------------------------- #
# the exchange fabric + ClusterOps, in-process (threads, no spawn cost)
# ---------------------------------------------------------------------- #


def _thread_cluster_run(idx, n, shape, caps, ranges):
    """Drive the real ClusterOps/WorkerState over thread fabrics — the
    exact worker code path minus the processes."""
    slices = cl.make_slices(idx, n)
    fabrics, _abort = cl.make_thread_fabrics(n)
    parts = [None] * n
    errs = []

    def run(r):
        try:
            st = cl.WorkerState(r, fabrics[r].inboxes, fabrics[r].outboxes,
                                fabrics[r].abort)
            st._apply_slice(slices[r])
            parts[r] = st._execute(
                1, {"shape": shape, "caps": caps, "ranges": ranges})
        except Exception as e:  # pragma: no cover - surfaced via errs
            errs.append(e)

    threads = [threading.Thread(target=run, args=(r,)) for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    assert all(p is not None for p in parts)
    return cl.merge_partitions(parts, np.asarray(ranges).shape[0])


class TestThreadFabric:
    def test_plan_walk_matches_local(self, fleet_graph):
        from repro.core import index as cindex

        idx = cindex.build(fleet_graph, 2)
        eng = Engine(idx)
        for q in _queries(fleet_graph, ["C2", "TT", "S", "Ti"], seed=3):
            plan = eng.plan(q)
            ranges = eng.lookup_ranges(plan)
            shape = plan_shape(plan)
            caps = eng.estimate_caps(ranges, shape, plan)
            expect = eng.execute(q)  # local reference (ladder included)
            results, ovf = _thread_cluster_run(
                idx, 3, shape, caps, ranges[None])
            if not ovf[0]:
                assert np.array_equal(results[0], expect), q
            else:
                # advisory flag fired: legal, the ladder would retry —
                # a doubled rung must then land exactly on local
                results, ovf = _thread_cluster_run(
                    idx, 3, shape, caps.doubled().doubled(), ranges[None])
                assert not ovf[0] and np.array_equal(results[0], expect), q

    def test_overflow_word_names_the_short_capacity(self):
        """The merged per-worker overflow word carries the bit of the
        capacity that was short (pair), not every capacity's."""
        from repro.core import index as cindex
        from repro.core.backend import OVF_PAIR, QueryCaps
        from test_batch_exec import _heavy_graph

        g = _heavy_graph()
        idx = cindex.build(g, 2)
        eng = Engine(idx)
        plan = eng.plan(instantiate_template("C2", [0, 0]))
        ranges = eng.lookup_ranges(plan)
        results, ovf = _thread_cluster_run(
            idx, 2, plan_shape(plan), QueryCaps(64, 4, 1 << 10),
            ranges[None])
        assert results[0] is None
        assert int(ovf[0]) == OVF_PAIR

    def test_exchange_tags_drop_stale_rounds(self):
        fabrics, _abort = cl.make_thread_fabrics(2)
        a, b = fabrics
        stale = np.zeros((1, 2), np.int32)
        fresh = np.ones((2, 2), np.int32)
        # a message from an aborted round (older seq) sits in the queue;
        # the receiver must skip it and deliver the current tag
        b.outboxes[0].put((1, 0, 1, stale))
        b.outboxes[0].put((2, 0, 1, fresh))
        a.begin(2)
        got = a._recv(1, 0)
        assert np.array_equal(got, fresh)

    def test_abort_unblocks_a_waiting_receive(self):
        fabrics, abort = cl.make_thread_fabrics(2)
        f = fabrics[0]
        f.begin(7)
        abort.set()
        with pytest.raises(cl.RoundAborted):
            f._recv(1, 0)
        abort.clear()


# ---------------------------------------------------------------------- #
# multi-process parity
# ---------------------------------------------------------------------- #


class TestClusterParity:
    def test_two_workers_full_template_suite(self, fleet):
        maint, eng = fleet["maint"], fleet["engine"]
        local = Engine(maint.flush())
        for q in _queries(maint.g, sorted(TEMPLATES)):
            a, b = local.execute(q), eng.execute(q)
            assert np.array_equal(a, b), q
            assert _rows(b) == oracle.cpq_eval(maint.g, q), q

    def test_one_and_four_workers(self, fleet):
        maint = fleet["maint"]
        idx = maint.flush()
        local = Engine(idx)
        qs = _queries(maint.g, ["C2", "TT", "S", "Ti"], seed=5)
        for n in (1, 4):
            eng = Engine(idx, cluster=n)
            try:
                for q in qs:
                    assert np.array_equal(local.execute(q),
                                          eng.execute(q)), (n, q)
            finally:
                eng.backend.shutdown()

    def test_rpq_fixpoint_through_the_cluster(self, fleet):
        maint, eng = fleet["maint"], fleet["engine"]
        local = Engine(maint.flush())
        q = RConcat(RStar(RAlt(RSym(0), RSym(1))), RSym(2))
        assert np.array_equal(local.execute_rpq(q), eng.execute_rpq(q))


# ---------------------------------------------------------------------- #
# the service stack over worker processes
# ---------------------------------------------------------------------- #


class TestClusterService:
    def test_pipelined_drain_uses_dispatch_harvest(self, fleet):
        maint, eng = fleet["maint"], fleet["engine"]
        runtime = eng.backend.runtime
        before = runtime.instructions[cl.DISPATCH]
        svc = QueryService(eng, max_batch=3, auto_flush=False)
        qs = _queries(maint.g, sorted(TEMPLATES), seed=13)
        reqs = [svc.submit(q) for q in qs]
        svc.flush()
        for q, r in zip(qs, reqs):
            assert r.done and not r.shed
            assert _rows(r.result) == oracle.cpq_eval(maint.g, q), q
        assert runtime.instructions[cl.DISPATCH] > before
        assert runtime.instructions[cl.HARVEST] >= \
            runtime.instructions[cl.DISPATCH] - before

    def test_maintenance_flush_broadcasts_one_rebind(self, fleet):
        maint, eng = fleet["maint"], fleet["engine"]
        runtime = eng.backend.runtime
        before = runtime.instructions[cl.FLUSH_REBIND]
        svc = QueryService(eng, maintainer=maint)
        svc.apply_updates([("insert_edge", 0, 1, 0),
                           ("insert_edge", 1, 2, 1)])
        for q in _queries(maint.g, ["C2", "TT", "T"], seed=17):
            got = svc.query(q)  # first query drains the coalesced batch
            assert _rows(got) == oracle.cpq_eval(maint.g, q), q
        assert runtime.instructions[cl.FLUSH_REBIND] == before + 1

    def test_interest_round_broadcasts_as_instruction(self, fleet_graph):
        mi = MaintainableIndex.build(fleet_graph, 2,
                                     interests=[(0,), (1,), (0, 1)])
        eng = Engine(mi.flush(), cluster=2)
        try:
            svc = QueryService(eng, maintainer=mi)
            q = instantiate_template("C2", [0, 1])
            svc.insert_interest((1, 0))
            got = svc.query(q)
            assert _rows(got) == oracle.cpq_eval(fleet_graph, q)
            assert eng.backend.runtime.instructions[cl.INTEREST_BATCH] == 1
        finally:
            eng.backend.shutdown()


# ---------------------------------------------------------------------- #
# fault injection
# ---------------------------------------------------------------------- #


class TestFaultRecovery:
    def _assert_serving(self, fleet, seed):
        maint, eng = fleet["maint"], fleet["engine"]
        for q in _queries(maint.g, ["C2", "TT", "S"], seed=seed):
            assert _rows(eng.execute(q)) == oracle.cpq_eval(maint.g, q), q

    def test_hard_kill_detected_and_respawned(self, fleet):
        eng = fleet["engine"]
        runtime = eng.backend.runtime
        before = runtime.recoveries
        runtime._workers[1].proc.kill()
        time.sleep(0.2)
        self._assert_serving(fleet, seed=19)
        assert runtime.recoveries > before

    def test_crash_mid_round(self, fleet):
        # CRASH sits in rank 0's FIFO ahead of the next EXECUTE_BATCH:
        # the worker dies *inside* the round, peers block in the
        # exchange, the abort/quiesce/respawn path must re-issue
        runtime = fleet["engine"].backend.runtime
        before = runtime.recoveries
        runtime.inject_crash(0)
        self._assert_serving(fleet, seed=23)
        assert runtime.recoveries > before

    def test_crash_between_rebind_broadcast_and_ack(self, fleet):
        maint, eng = fleet["maint"], fleet["engine"]
        runtime = eng.backend.runtime
        before = runtime.recoveries
        runtime.inject_crash(1)
        # rank 1 dies before acking the FLUSH_REBIND; the instruction is
        # re-issued after recovery and survivors re-apply idempotently
        eng.rebind(maint.flush())
        self._assert_serving(fleet, seed=29)
        assert runtime.recoveries > before

    def test_crash_during_checkpoint_and_recover_from_it(self, fleet,
                                                         tmp_path):
        maint, eng = fleet["engine"].backend, fleet["engine"]
        runtime = eng.backend.runtime
        svc = QueryService(eng, maintainer=fleet["maint"])
        runtime.inject_crash(0)  # dies before the CHECKPOINT barrier ack
        step = svc.checkpoint(str(tmp_path))
        assert runtime._ckpt == (str(tmp_path), step)
        # next death respawns from the committed checkpoint base
        before = runtime.recoveries
        runtime._workers[1].proc.kill()
        time.sleep(0.2)
        self._assert_serving(fleet, seed=31)
        assert runtime.recoveries > before

    def test_no_lost_accepted_requests_across_a_crash(self, fleet):
        maint, eng = fleet["maint"], fleet["engine"]
        runtime = eng.backend.runtime
        svc = QueryService(eng, max_batch=2, auto_flush=False)
        qs = _queries(maint.g, ["C2", "TT", "S", "T", "Si", "St"], seed=37)
        reqs = [svc.submit(q) for q in qs]
        assert all(not r.shed for r in reqs)
        runtime.inject_crash(1)
        done = svc.flush()
        assert len(done) == len([r for r in reqs if not r.from_cache]) or \
            all(r.done for r in reqs)
        for q, r in zip(qs, reqs):
            assert r.done and not r.shed
            assert _rows(r.result) == oracle.cpq_eval(maint.g, q), q


# ---------------------------------------------------------------------- #
# elastic reshard (last: resizes the shared fleet and restores it)
# ---------------------------------------------------------------------- #


class TestReshard:
    def test_resize_up_down_stays_oracle_identical(self, fleet):
        maint, eng = fleet["maint"], fleet["engine"]
        qs = _queries(maint.g, ["C2", "TT", "S"], seed=41)
        truth = [oracle.cpq_eval(maint.g, q) for q in qs]
        for n in (4, 1, 2):
            eng.backend.resize(n)
            assert eng.backend.runtime.n_shards == n
            for q, t in zip(qs, truth):
                assert _rows(eng.execute(q)) == t, (n, q)

    def test_resize_past_max_workers_is_rejected(self, fleet):
        with pytest.raises(ValueError):
            fleet["engine"].backend.resize(
                fleet["engine"].backend.runtime.max_workers + 1)


class TestOneProcessPerChip:
    def test_accelerator_host_refuses_before_spawning(self, monkeypatch):
        """A chip belongs to one process: on an accelerator backend the
        runtime fails at once, before any worker or queue exists."""
        monkeypatch.setattr(cl.jax, "default_backend", lambda: "tpu")
        spawned = []
        monkeypatch.setattr(cl.ClusterRuntime, "_spawn",
                            lambda self, rank: spawned.append(rank))
        t0 = time.perf_counter()
        with pytest.raises(cl.ClusterError, match="in one process"):
            cl.ClusterRuntime(n_workers=2)
        assert spawned == []
        assert time.perf_counter() - t0 < 5.0
