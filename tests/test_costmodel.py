"""The device cost model (PR 8): affine fits and their noise clamps, the
versioned JSON / checkpoint codec, cost-aware planning (plans without a
table stay byte-identical; a join-heavy table flips the C4 split),
calibrated capacity-rung selection (answers never change), online
refinement, and engine telemetry semantics across rebind."""

import numpy as np
import pytest

from repro.core import index as cindex, lifecycle, oracle
from repro.core.costmodel import DeviceCostTable, OpCost, fit_affine
from repro.core.engine import Engine
from repro.core.graph import example_graph
from repro.core.maintenance import MaintainableIndex
from repro.core.optimizer import estimate_plan, optimize_query
from repro.core.query import instantiate_template, parse, plan_query
from repro.core.service import QueryService
from repro.core.stats import IndexStats
from repro.data.graphs import skewed_labeled_graph


@pytest.fixture(scope="module")
def skewed_stats():
    g = skewed_labeled_graph(n_vertices=40, wave=12, rare_edges=10, seed=7)
    return IndexStats.from_oracle(oracle.build_index(g, 2), g.n_vertices)


def _toy_table(**overrides) -> DeviceCostTable:
    """A hand-built table with every operator priced — small enough to
    reason about, complete enough to drive the optimizer."""
    fields = dict(
        device_kind="test",
        scale=1.25,
        dispatch_floor_ns=42.0,
        ops={"lookup": OpCost(100.0, 1.0),
             "materialize": OpCost(200.0, 2.0),
             "conjoin": OpCost(150.0, 1.5),
             "join": OpCost(5_000.0, 3.0),
             "identity": OpCost(50.0, 0.5),
             "union_step": OpCost(80.0, 0.0)},
        vmem_words=123_456,
        samples={"join": [[256.0, 5768.0], [1024.0, 8072.0]]},
    )
    fields.update(overrides)
    return DeviceCostTable(**fields)


def _rows_set(rows):
    return {tuple(r) for r in np.asarray(rows).tolist()}


# ---------------------------------------------------------------------- #
# affine fitting
# ---------------------------------------------------------------------- #


class TestAffineFit:
    def test_exact_affine_data_recovered(self):
        rows = np.array([128.0, 512.0, 2048.0, 8192.0])
        cost = fit_affine(rows, 750.0 + 3.25 * rows)
        assert cost.fixed_ns == pytest.approx(750.0)
        assert cost.per_row_ns == pytest.approx(3.25)
        assert cost.ns(1000) == pytest.approx(750.0 + 3250.0)

    def test_negative_slope_collapses_to_constant(self):
        """Decreasing times are noise — never price work below zero."""
        cost = fit_affine([100, 200, 400], [900.0, 600.0, 300.0])
        assert cost.per_row_ns == 0.0
        assert cost.fixed_ns == pytest.approx(600.0)  # the mean

    def test_negative_intercept_refits_through_origin(self):
        rows = np.array([100.0, 1000.0, 10_000.0])
        cost = fit_affine(rows, 5.0 * rows - 40.0)
        assert cost.fixed_ns == 0.0
        assert cost.per_row_ns == pytest.approx(5.0, rel=0.01)

    def test_degenerate_inputs(self):
        assert fit_affine([], []) == OpCost(0.0, 0.0)
        single = fit_affine([256.0], [1234.0])
        assert single == OpCost(1234.0, 0.0)
        same_rows = fit_affine([512.0, 512.0], [100.0, 300.0])
        assert same_rows == OpCost(200.0, 0.0)


# ---------------------------------------------------------------------- #
# the artifact codec (JSON file + checkpoint leaf)
# ---------------------------------------------------------------------- #


class TestTableCodec:
    def test_json_round_trip_is_lossless(self):
        t = _toy_table()
        back = DeviceCostTable.from_json(t.to_json())
        assert back.to_json() == t.to_json()
        assert back.ops["join"] == t.ops["join"]
        assert back.vmem_words == t.vmem_words

    def test_save_load_round_trip(self, tmp_path):
        t = _toy_table()
        path = str(tmp_path / "table.json")
        t.save(path)
        assert DeviceCostTable.load(path).to_json() == t.to_json()

    def test_rejects_foreign_and_future_payloads(self):
        with pytest.raises(ValueError, match="not a cost table"):
            DeviceCostTable.from_json({"format": "something-else"})
        future = _toy_table().to_json()
        future["version"] = future["version"] + 1
        with pytest.raises(ValueError, match="newer than supported"):
            DeviceCostTable.from_json(future)

    def test_checkpoint_leaf_round_trip(self):
        """export_state is one flat uint8 array — the only shape the
        checkpoint pytree codec accepts — and decodes losslessly."""
        t = _toy_table()
        leaf = t.export_state()
        assert leaf.dtype == np.uint8 and leaf.ndim == 1
        assert DeviceCostTable.from_state(leaf).to_json() == t.to_json()

    def test_none_vmem_words_survives(self):
        t = _toy_table(vmem_words=None)
        assert DeviceCostTable.from_json(t.to_json()).vmem_words is None


# ---------------------------------------------------------------------- #
# pricing semantics
# ---------------------------------------------------------------------- #


class TestPricing:
    def test_stage_ns_applies_scale(self):
        t = _toy_table(scale=2.0)
        assert t.stage_ns("lookup", 100) == pytest.approx(2.0 * 200.0)

    def test_unknown_operator_prices_zero(self):
        """Old tables stay usable when a new operator kind appears."""
        assert _toy_table().stage_ns("hyperjoin", 1 << 20) == 0.0

    def test_dispatch_floor_caps_from_below(self):
        t = _toy_table(dispatch_floor_ns=1e9)
        assert t.plan_dispatch_ns(256) == 1e9

    def test_expected_dispatch_prices_retry_risk(self):
        t = _toy_table(dispatch_floor_ns=0.0)
        base = t.plan_dispatch_ns(1024)
        # generous headroom, sound bound: almost no retry mass
        safe = t.expected_dispatch_ns(1024, est_rows=16, risky=False)
        # same rung but join-bearing estimate near capacity: retry priced
        risky = t.expected_dispatch_ns(1024, est_rows=1000, risky=True)
        assert base <= safe < risky
        assert risky <= base + t.plan_dispatch_ns(2048)


# ---------------------------------------------------------------------- #
# cost-aware planning
# ---------------------------------------------------------------------- #


class TestCostAwarePlanning:
    # representative golden pairs from test_optimizer.TestGoldenPlans —
    # the byte-identity contract for table-less planning
    GOLDEN = [
        ("T", [0, 0, 1],
         ("conj", ("lookup", [(1,)]), ("lookup", [(0, 0)]))),
        ("C4", [1, 0, 2, 3],
         ("lookup", [(1,), (0, 2), (3,)])),
    ]

    @pytest.mark.parametrize("case", GOLDEN, ids=[c[0] for c in GOLDEN])
    def test_no_table_is_byte_identical(self, skewed_stats, case):
        """cost_table=None must reproduce the golden row-count plans
        exactly — the new cost channel defaults to inert."""
        name, labels, want = case
        q = instantiate_template(name, labels)
        assert optimize_query(q, 2, skewed_stats) == want
        assert optimize_query(q, 2, skewed_stats, cost_table=None) == want
        est = estimate_plan(want, skewed_stats)
        assert est.cost_ns == 0.0  # no table, no nanoseconds

    def test_table_populates_cost_channel(self, skewed_stats):
        q = instantiate_template("C4", [1, 0, 2, 3])
        plan = plan_query(q, 2)
        est = estimate_plan(plan, skewed_stats, cost_table=_toy_table())
        assert est.cost_ns > 0.0

    def test_join_heavy_table_flips_c4_to_two_leaves(self, skewed_stats):
        """When the fixed dispatch cost of a JOIN dwarfs per-row work
        (the calibrated CPU/interpret regime), the rare-leaf 3-segment
        split (2 joins) must lose to the greedy 2-segment split (1
        join) — the exact misprediction ISSUE 8's C4 gate closes."""
        table = _toy_table()
        table.ops["join"] = OpCost(1e9, 3.0)
        q = instantiate_template("C4", [1, 0, 2, 3])
        assert optimize_query(q, 2, skewed_stats, cost_table=table) == \
            ("lookup", [(1, 0), (2, 3)])

    def test_per_row_dominated_table_keeps_rare_leaf_split(
            self, skewed_stats):
        """With free dispatches and pure per-row pricing the cost model
        degenerates to the row-count model, so the golden 3-leaf split
        must survive."""
        table = _toy_table(scale=1.0, dispatch_floor_ns=0.0)
        table.ops = {op: OpCost(0.0, 1.0) for op in table.ops}
        q = instantiate_template("C4", [1, 0, 2, 3])
        assert optimize_query(q, 2, skewed_stats, cost_table=table) == \
            ("lookup", [(1,), (0, 2), (3,)])


# ---------------------------------------------------------------------- #
# calibrated engines: answers never change
# ---------------------------------------------------------------------- #


class TestCalibratedEngine:
    def test_answers_identical_with_and_without_table(self, ex_graph):
        """The table moves capacities and splits, never answers — the
        same contract the ladder gives misestimates."""
        idx = cindex.build(ex_graph, 2)
        plain, priced = Engine(idx), Engine(idx, cost_table=_toy_table())
        for text in ("(l0 . l0) & l0-", "l0 . l1", "l0 & id", "l1 . l0"):
            q = parse(text, None, ex_graph.n_labels)
            assert _rows_set(plain.execute(q)) == _rows_set(priced.execute(q))

    def test_calibrated_caps_stay_pow2_and_bounded(self, ex_graph):
        from repro.core.query import plan_shape

        eng = Engine(cindex.build(ex_graph, 2),
                     cost_table=_toy_table(dispatch_floor_ns=0.0))
        q = parse("l0 . l1", None, ex_graph.n_labels)
        plan = eng.plan(q)
        caps = eng.estimate_caps(eng.lookup_ranges(plan), plan_shape(plan),
                                 plan)
        cap = int(caps.pair_cap)
        assert cap & (cap - 1) == 0  # pow2 rung
        assert cap <= int(eng._default_caps.pair_cap) * 8


# ---------------------------------------------------------------------- #
# online refinement
# ---------------------------------------------------------------------- #


class TestRefinement:
    def test_refit_from_observations(self):
        t = DeviceCostTable()
        for rows in (256, 1024, 4096):
            t.observe("join", rows, 1000.0 + 2.0 * rows)
        cost = t.refit("join")
        assert cost.fixed_ns == pytest.approx(1000.0)
        assert cost.per_row_ns == pytest.approx(2.0)

    def test_refine_scale_geometric_ema_and_clamp(self):
        t = DeviceCostTable(scale=1.0)
        assert t.refine_scale(2000.0, 1000.0, weight=1.0) == pytest.approx(2.0)
        t.refine_scale(0.0, 1000.0)  # non-positive measurement: ignored
        assert t.scale == pytest.approx(2.0)
        for _ in range(40):
            t.refine_scale(1e12, 1.0, weight=1.0)
        assert t.scale == 64.0  # clamped — one corrupt row can't explode it

    def test_refine_from_telemetry_moves_dispatch_floor(self):
        t = DeviceCostTable(dispatch_floor_ns=0.0)

        class Snap:
            dispatches = 10

        t.refine_from_telemetry(Snap(), elapsed_ns=10_000.0, weight=0.5)
        assert t.dispatch_floor_ns == pytest.approx(500.0)
        t.refine_from_telemetry(Snap(), elapsed_ns=0.0)  # no-op
        assert t.dispatch_floor_ns == pytest.approx(500.0)

    def test_refine_from_trajectory_consumes_tagged_rows(self):
        t = DeviceCostTable(scale=1.0)
        payloads = [{"rows": [
            {"name": "q/cal", "us_per_call": 2.0,
             "derived": "predicted_ns=1000.0;scale=1.0"},  # measured 2000ns
            {"name": "q/other", "us_per_call": 5.0, "derived": "plain"},
        ]}]
        assert t.refine_from_trajectory(payloads, weight=1.0) == 1
        assert t.scale == pytest.approx(2.0)


# ---------------------------------------------------------------------- #
# checkpoint lifecycle
# ---------------------------------------------------------------------- #


def _service(cost_table=None):
    g = example_graph()
    mi = MaintainableIndex.build(g, 2)
    return QueryService(Engine(mi.flush(), cost_table=cost_table),
                        maintainer=mi), g


class TestCheckpointRoundTrip:
    def test_cost_table_survives_checkpoint(self, tmp_path):
        table = _toy_table()
        svc, g = _service(cost_table=table)
        step = svc.checkpoint(str(tmp_path))
        state = lifecycle.load_state(str(tmp_path), step)
        assert state.cost_table is not None
        assert state.cost_table.to_json() == table.to_json()

    def test_restored_service_answers_and_keeps_table(self, tmp_path):
        table = _toy_table()
        svc, g = _service(cost_table=table)
        step = svc.checkpoint(str(tmp_path))
        restored = lifecycle.restore_service(str(tmp_path), step)
        assert restored.engine.cost_table.to_json() == table.to_json()
        for text in ("l0 . l1", "(l0 . l0) & l0-"):
            q = parse(text, None, g.n_labels)
            assert _rows_set(restored.query(q)) == oracle.cpq_eval(g, q)

    def test_legacy_checkpoint_without_table_loads(self, tmp_path):
        """Pre-PR-8 checkpoints carry no costtable.blob leaf; they must
        restore with cost_table=None and serve unchanged."""
        svc, g = _service(cost_table=None)
        step = svc.checkpoint(str(tmp_path))
        state = lifecycle.load_state(str(tmp_path), step)
        assert state.cost_table is None
        restored = lifecycle.restore_service(str(tmp_path), step)
        assert restored.engine.cost_table is None
        q = parse("l0 . l1", None, g.n_labels)
        assert _rows_set(restored.query(q)) == oracle.cpq_eval(g, q)


# ---------------------------------------------------------------------- #
# telemetry semantics (the counters the refinement loop reads)
# ---------------------------------------------------------------------- #


class TestTelemetry:
    def test_counters_monotone_and_survive_rebind(self, ex_graph):
        table = _toy_table()
        eng = Engine(cindex.build(ex_graph, 2), cost_table=table)
        q = parse("l0 . l1", None, ex_graph.n_labels)
        eng.execute(q)
        q0, d0 = eng.telemetry.queries, eng.telemetry.dispatches
        assert q0 >= 1 and d0 >= 1
        # rebind describes a NEW index on the SAME device: lifetime
        # counters and the cost table both survive
        eng.rebind(cindex.build(ex_graph, 2))
        assert eng.telemetry.queries == q0
        assert eng.telemetry.dispatches == d0
        assert eng.cost_table is table
        eng.execute(q)
        assert eng.telemetry.queries == q0 + 1
        assert eng.telemetry.dispatches > d0

    def test_reset_zeroes_every_counter(self, ex_graph):
        eng = Engine(cindex.build(ex_graph, 2))
        eng.execute(parse("(l0 . l0) & l0-", None, ex_graph.n_labels))
        assert eng.telemetry.dispatches > 0
        eng.telemetry.reset()
        t = eng.telemetry
        assert (t.queries, t.dispatches, t.retry_rungs,
                t.default_jumps, t.union_lanes) == (0, 0, 0, 0, 0)
