"""ShardedBackend parity — in-process, over every visible device.

The whole-plan sharded executor (replicated class space, canonical
hash-partitioned pair space, OR-reduced overflow words) runs fine on a mesh of one
device — every exchange is a self-send — so the full equivalence matrix
``ShardedBackend == LocalBackend == numpy oracle`` is checked here
without subprocess machinery.  The mesh spans ``jax.device_count()``
devices: 1 in the plain tier-1 run, 8 in the CI distributed step
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — the
acceptance matrix at n_shards ∈ {1, 8}.  test_distributed.py
additionally covers the 8-device path from inside the plain suite via
subprocesses."""

import jax
import numpy as np
import pytest

from repro import compat
from repro.core import index as cindex, lifecycle, oracle
from repro.core.backend import LocalBackend
from repro.core.distributed import ShardedBackend
from repro.core.engine import Engine, QueryCaps
from repro.core.graph import LabeledGraph, example_graph
from repro.core.maintenance import MaintainableIndex
from repro.core.query import (
    TEMPLATE_ARITY,
    TEMPLATES,
    instantiate_template,
    parse,
)
from repro.core.service import QueryService

from conftest import random_graph


@pytest.fixture(scope="module")
def mesh1():
    """All visible devices on one 'engine' axis (1 normally; 8 in the
    CI distributed step)."""
    return compat.make_mesh((jax.device_count(),), ("engine",))


def _rows_set(rows):
    return {tuple(r) for r in np.asarray(rows).tolist()}


class TestShardedEngineParity:
    def test_template_suite_bit_identical(self, ex_graph, mesh1):
        """Every Fig. 5 template: the mesh engine returns the *same
        array* (values and order) as the local engine, and the right
        answer."""
        idx = cindex.build(ex_graph, 2)
        local = Engine(idx)
        sharded = Engine(idx, mesh=mesh1)
        assert isinstance(local.backend, LocalBackend)
        assert isinstance(sharded.backend, ShardedBackend)
        rng = np.random.default_rng(7)
        present = np.unique(ex_graph.lbl)
        for name in TEMPLATES:
            q = instantiate_template(
                name, rng.choice(present, TEMPLATE_ARITY[name]).tolist())
            a, b = local.execute(q), sharded.execute(q)
            assert a.shape == b.shape and np.array_equal(a, b), name
            assert _rows_set(b) == oracle.cpq_eval(ex_graph, q), name

    def test_index_leaves_placed_on_the_mesh(self, ex_graph, mesh1):
        """Every leaf carries the NamedSharding of its spec, before the
        first query and after a rebind reshards it."""
        from jax.sharding import NamedSharding

        from repro.core.sharded_index import index_specs

        idx = cindex.build(ex_graph, 2)
        eng = Engine(idx, mesh=mesh1)
        n = jax.device_count()
        for _ in range(2):
            for leaf, spec in zip(eng.backend.sharded, index_specs("engine")):
                assert isinstance(leaf.sharding, NamedSharding)
                assert leaf.sharding.spec == spec
                assert len(leaf.sharding.device_set) == n
                if spec != jax.sharding.PartitionSpec():
                    assert leaf.sharding.shard_shape(leaf.shape)[0] == 1
            eng.rebind(idx)

    def test_identity_and_parse_paths(self, ex_graph, mesh1):
        idx = cindex.build(ex_graph, 2)
        local, sharded = Engine(idx), Engine(idx, mesh=mesh1)
        for text in ("id", "l0 & id", "(l0 . l1) & id", "l0 . id . l1"):
            q = parse(text, None, ex_graph.n_labels)
            a, b = local.execute(q), sharded.execute(q)
            assert np.array_equal(a, b), text
            assert _rows_set(b) == oracle.cpq_eval(ex_graph, q), text

    def test_batch_matches_sequential(self, ex_graph, mesh1):
        idx = cindex.build(ex_graph, 2)
        sharded = Engine(idx, mesh=mesh1)
        rng = np.random.default_rng(3)
        present = np.unique(ex_graph.lbl)
        qs = [instantiate_template("T", rng.choice(present, 3).tolist())
              for _ in range(5)]
        qs += [instantiate_template("C2", rng.choice(present, 2).tolist())
               for _ in range(3)]
        batch = sharded.execute_batch(qs)
        for q, rows in zip(qs, batch):
            assert np.array_equal(rows, sharded.execute(q))

    def test_overflow_ladder_retries_to_exact(self, ex_graph, mesh1):
        """Deliberately tiny caps: the reduced sticky overflow word must
        drive the host double-and-retry to the exact answer, same as
        local."""
        idx = cindex.build(ex_graph, 2)
        sharded = Engine(idx, mesh=mesh1)
        q = parse("l0 . l1", None, ex_graph.n_labels)
        tiny = QueryCaps(class_cap=2, pair_cap=2, join_cap=2)
        rows = sharded.execute(q, caps=tiny)
        assert _rows_set(rows) == oracle.cpq_eval(ex_graph, q)

    @pytest.mark.parametrize("batched", [False, True])
    def test_only_the_overflowed_capacity_grows(self, mesh1, batched):
        """The shards' overflow words name the short capacity: a lookup
        whose pair capacity is too small climbs the pair rung alone."""
        from test_batch_exec import _heavy_graph

        g = _heavy_graph()
        eng = Engine(cindex.build(g, 2), mesh=mesh1)
        seen = []
        run = eng.backend.run
        eng.backend.run = lambda s, c, r: seen.append(c) or run(s, c, r)
        q = instantiate_template("C2", [0, 0])  # 72 answer pairs
        start = QueryCaps(64, 4, 1 << 10)
        rows = (eng.execute_batch([q], caps=start)[0] if batched
                else eng.execute(q, caps=start))
        assert _rows_set(rows) == oracle.cpq_eval(g, q)
        assert len(seen) > 1  # the ladder climbed
        assert {c.join_cap for c in seen} == {1 << 10}
        assert {c.class_cap for c in seen} == {64}
        assert seen[-1].pair_cap >= 72


class TestShardedService:
    def test_service_and_write_path_reshard(self, mesh1):
        """QueryService over a mesh engine: same serving semantics, and
        the maintenance write path (mirror batch -> flush -> rebind)
        reshards the flushed arrays — answers track the updated graph."""
        g = example_graph()
        mi = MaintainableIndex.build(g, 2)
        engine = Engine(mi.flush(), mesh=mesh1)
        svc = QueryService(engine, maintainer=mi)
        q = parse("l0 . l1", None, g.n_labels)
        before = svc.query(q)
        assert _rows_set(before) == oracle.cpq_eval(g, q)
        old_backend = engine.backend
        old_arrays = old_backend.sharded
        old_compiled = dict(old_backend._cache)

        svc.apply_updates([("insert_edge", 0, 3, 0), ("delete_edge", 0, 1, 0)])
        after = svc.query(q)  # drain applies updates, flush reshards
        # rebind resharded *into* the same backend: new arrays, but the
        # compiled plan executables survive the flush
        assert engine.backend is old_backend
        assert engine.backend.sharded is not old_arrays
        for key, fn in old_compiled.items():
            assert engine.backend._cache.get(key) is fn
        assert _rows_set(after) == oracle.cpq_eval(mi.g, q)  # updated graph
        assert svc.stats.update_batches == 1
        # epoch bumped: the pre-update cached answer is unreachable
        assert svc.graph_epoch >= 1

    def test_random_graphs_seeded_sweep(self, mesh1):
        """Deterministic cousin of the hypothesis property (which lives
        in test_sharded_properties.py): a seeded sweep of random graphs
        through a random template each, sharded == local == oracle."""
        for seed in range(4):
            g = random_graph(seed, n_max=14, m_max=36)
            idx = cindex.build(g, 2)
            local, sharded = Engine(idx), Engine(idx, mesh=mesh1)
            rng = np.random.default_rng(seed)
            present = np.unique(g.lbl)
            names = sorted(TEMPLATES)
            name = names[int(rng.integers(len(names)))]
            q = instantiate_template(
                name, rng.choice(present, TEMPLATE_ARITY[name]).tolist())
            a, b = local.execute(q), sharded.execute(q)
            assert np.array_equal(a, b), (seed, name)
            assert _rows_set(b) == oracle.cpq_eval(g, q), (seed, name)


class TestShardedCheckpointRoundTrip:
    """Elastic save/restore of the sharded layout (lifecycle satellite):
    a checkpoint taken at n shards restores at m shards bit-identically
    to resharding the live index — the restore path IS gather_index →
    shard_index, and these tests pin that equality both ways (8 → 1 and
    1 → 8) without needing an 8-device mesh."""

    def _fields_equal(self, a, b):
        from repro.core.sharded_index import ShardedIndexArrays

        for f in ShardedIndexArrays._fields:
            x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
            assert x.shape == y.shape and np.array_equal(x, y), f

    def test_same_count_restore_is_verbatim(self, ex_graph, tmp_path):
        from repro.core.sharded_index import shard_index

        idx = cindex.build(ex_graph, 2)
        sharded = shard_index(idx, 4)
        lifecycle.save_sharded(sharded, idx.n_vertices, idx.k, str(tmp_path))
        back, n_vertices, k = lifecycle.load_sharded_arrays(str(tmp_path))
        assert (n_vertices, k) == (idx.n_vertices, idx.k)
        assert back.n_shards == 4
        self._fields_equal(back, sharded)

    def test_restore_at_other_count_equals_live_reshard(self, ex_graph,
                                                        tmp_path):
        """Save at 8, restore at 1 — and re-save the 1-way, restore at
        8 — each bit-identical to gather_index → shard_index."""
        from repro.core.sharded_index import gather_index, shard_index

        idx = cindex.build(ex_graph, 2)
        eight = shard_index(idx, 8)
        d8 = str(tmp_path / "eight")
        lifecycle.save_sharded(eight, idx.n_vertices, idx.k, d8)

        one, _, _ = lifecycle.load_sharded_arrays(d8, n_shards=1)
        assert one.n_shards == 1
        # the elastic path is literally gather -> shard: pin it
        gathered = gather_index(eight)
        wrapper = cindex.CPQxIndex(
            k=idx.k, n_vertices=idx.n_vertices, arrays=gathered,
            seq_ranges=cindex._pull_seq_ranges(gathered, idx.k),
            caps=idx.caps)
        self._fields_equal(one, shard_index(wrapper, 1))

        d1 = str(tmp_path / "one")
        lifecycle.save_sharded(one, idx.n_vertices, idx.k, d1)
        eight_again, _, _ = lifecycle.load_sharded_arrays(d1, n_shards=8)
        assert eight_again.n_shards == 8
        gathered1 = gather_index(one)
        wrapper1 = cindex.CPQxIndex(
            k=idx.k, n_vertices=idx.n_vertices, arrays=gathered1,
            seq_ranges=cindex._pull_seq_ranges(gathered1, idx.k),
            caps=idx.caps)
        self._fields_equal(eight_again, shard_index(wrapper1, 8))

    def test_backend_restore_serves_identically(self, ex_graph, mesh1,
                                                tmp_path):
        """ShardedBackend.save / .restore: the restored backend answers
        bit-identically to the local engine on the same index."""
        idx = cindex.build(ex_graph, 2)
        engine = Engine(idx, mesh=mesh1)
        engine.backend.save(str(tmp_path))
        restored = ShardedBackend.restore(str(tmp_path), mesh1)
        local = Engine(idx)
        mesh_engine = Engine(idx, mesh=mesh1)
        mesh_engine.backend = restored  # serve off the restored leaves
        rng = np.random.default_rng(11)
        present = np.unique(ex_graph.lbl)
        for name in sorted(TEMPLATES)[:6]:
            q = instantiate_template(
                name, rng.choice(present, TEMPLATE_ARITY[name]).tolist())
            a, b = local.execute(q), mesh_engine.execute(q)
            assert np.array_equal(a, np.asarray(b)), name
            assert _rows_set(b) == oracle.cpq_eval(ex_graph, q), name

    def test_service_restored_on_mesh_survives_maintenance(self, tmp_path,
                                                           mesh1):
        """The promotion story end-to-end on a mesh: checkpoint a local
        service, promote a replica ONTO the mesh (restore_service(mesh=)),
        then push updates through the replica's write path — the flush
        reshards and answers track the updated graph."""
        g = example_graph()
        mi = MaintainableIndex.build(g, 2)
        svc = QueryService(Engine(mi.flush()), maintainer=mi)
        q = parse("l0 . l1", None, g.n_labels)
        svc.query(q)
        step = svc.checkpoint(str(tmp_path))

        replica = lifecycle.restore_service(str(tmp_path), step, mesh=mesh1)
        assert isinstance(replica.engine.backend, ShardedBackend)
        assert _rows_set(replica.query(q)) == oracle.cpq_eval(g, q)

        replica.apply_updates([("insert_edge", 0, 3, 0),
                               ("delete_edge", 0, 1, 0)])
        after = replica.query(q)  # drain -> mirror batch -> reshard flush
        assert _rows_set(after) == oracle.cpq_eval(replica.maintainer.g, q)
        assert replica.stats.update_batches == 1
