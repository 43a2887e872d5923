"""The program's own spans and counters (``core.tracing``,
``LadderTelemetry``): totals and self time under nesting and across
threads, spans on the profiler's host plane, and the engine's capacity,
answer and copy counters against hand counts."""

import glob
import os
import sys
import threading
import time

import pytest

from repro.core import index as cindex
from repro.core import oracle
from repro.core import tracing
from repro.core.backend import LocalBackend
from repro.core.engine import Engine, QueryCaps
from repro.core.query import instantiate_template, plan_shape


def _wait(seconds: float) -> None:
    t = time.perf_counter() + seconds
    while time.perf_counter() < t:
        pass


def test_totals_count_seconds_self_seconds_and_calls():
    totals = tracing.SpanTotals()
    with totals.span("outer"):
        _wait(0.01)
        for _ in range(2):
            with totals.span("inner"):
                _wait(0.02)
    got = totals.snapshot()
    assert got["outer"].calls == 1 and got["inner"].calls == 2
    assert got["inner"].seconds == pytest.approx(got["inner"].self_seconds)
    assert got["inner"].seconds >= 0.04
    # the outer span's self time is its own wait, not its children's
    assert got["outer"].self_seconds == pytest.approx(
        got["outer"].seconds - got["inner"].seconds, abs=1e-9)
    assert 0.01 <= got["outer"].self_seconds < 0.02


def test_self_time_is_kept_per_thread():
    """Spans open on several threads at once: each thread's children
    come off its own parent only, and no update is lost."""
    totals = tracing.SpanTotals()
    n_threads, rounds = 8, 50
    start = threading.Barrier(n_threads)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            start.wait(timeout=10)
            for _ in range(rounds):
                with totals.span("parent"):
                    with totals.span("child"):
                        _wait(1e-4)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    got = totals.snapshot()
    assert got["parent"].calls == got["child"].calls == n_threads * rounds
    # a parent's self time never goes negative by another thread's child
    assert got["parent"].self_seconds >= 0.0
    assert got["parent"].seconds == pytest.approx(
        got["parent"].self_seconds + got["child"].seconds, rel=1e-9)


def test_delta_is_the_window_between_two_snapshots():
    totals = tracing.SpanTotals()
    with totals.span("a"):
        pass
    before = totals.snapshot()
    with totals.span("a"):
        _wait(0.005)
    with totals.span("b"):
        pass
    d = tracing.delta(before, totals.snapshot())
    assert set(d) == {"a", "b"}
    assert d["a"].calls == 1 and d["a"].seconds >= 0.005
    assert tracing.delta(before, before) == {}


def test_spans_are_on_the_profilers_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("service.flush", round=7):
            with tracing.span("engine.harvest"):
                jax.numpy.arange(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    profile = ProfileData.from_file(max(files, key=os.path.getmtime))
    found = {}
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    found[e.name] = (e.start_ns, e.end_ns, dict(e.stats))
    assert set(found) == {"cpqx.service.flush", "cpqx.engine.harvest"}
    flush, harvest = found["cpqx.service.flush"], found["cpqx.engine.harvest"]
    assert flush[2] == {"round": 7}
    assert flush[0] <= harvest[0] <= harvest[1] <= flush[1]


def _joins(shape) -> bool:
    if shape[0] == "lookup":
        return shape[1] > 1
    return shape[0] == "join" or any(_joins(s) for s in shape[1:]
                                     if isinstance(s, tuple))


@pytest.fixture(scope="module")
def engine_and_graph(ex_graph):
    return Engine(cindex.build(ex_graph, 2)), ex_graph


def test_counters_match_hand_counts_through_the_ladder(engine_and_graph,
                                                       monkeypatch):
    """Two lanes of a join plan started at capacities too small for
    their answers: every dispatch, retry rungs included, is sized at
    ``join_cap`` per lane; harvest copies the overflow words of every
    lane, and the count and ``pair_cap`` columns of a batch with a lane
    that fit."""
    eng, g = engine_and_graph
    queries = [instantiate_template("C4", [0, 0, 2, 2]),
               instantiate_template("C4", [1, 3, 0, 2])]
    # each plan joins in pair space: a JOIN, or a LOOKUP of two segments
    assert all(_joins(plan_shape(eng.plan(q))) for q in queries)
    dispatched, copied = [], []
    real_async, real_harvest = (LocalBackend.run_batch_async,
                                LocalBackend.harvest_batch)

    def run_batch_async(self, shape, caps, ranges):
        dispatched.append((caps, len(ranges)))
        return real_async(self, shape, caps, ranges)

    def harvest_batch(self, handle):
        rows, overflow = real_harvest(self, handle)
        lanes, cap = handle[1].cols[0].shape  # (lanes, pair_cap) columns
        fit = any(r is not None for r in rows)
        copied.append(4 * lanes + fit * (4 * lanes + 2 * 4 * lanes * cap))
        return rows, overflow

    monkeypatch.setattr(LocalBackend, "run_batch_async", run_batch_async)
    monkeypatch.setattr(LocalBackend, "harvest_batch", harvest_batch)
    before = eng.telemetry.snapshot()
    got = eng.execute_batch(queries, caps=QueryCaps(4, 8, 16), min_bucket=1)
    t = eng.telemetry
    assert t.retry_rungs - before.retry_rungs >= 1
    assert len(dispatched) == t.dispatches - before.dispatches >= 2
    for q, rows in zip(queries, got):
        assert {tuple(r) for r in rows.tolist()} == oracle.cpq_eval(g, q)
    assert t.cap_rows - before.cap_rows == sum(
        lanes * caps.join_cap for caps, lanes in dispatched)
    assert t.answer_pairs - before.answer_pairs == sum(len(r) for r in got)
    assert t.d2h_bytes - before.d2h_bytes == sum(copied)


def test_counters_of_a_single_query(engine_and_graph):
    """``execute`` counts one lane per attempt: a class-space plan is
    sized at ``pair_cap``, and its copy is the overflow word, the count
    and both ``pair_cap`` columns."""
    eng, g = engine_and_graph
    q = instantiate_template("C2", [0, 1])
    caps = QueryCaps(16, 64, 128)
    before = eng.telemetry.snapshot()
    rows = eng.execute(q, caps=caps)
    t = eng.telemetry
    assert t.dispatches - before.dispatches == 1
    assert {tuple(r) for r in rows.tolist()} == oracle.cpq_eval(g, q)
    assert t.cap_rows - before.cap_rows == caps.pair_cap
    assert t.answer_pairs - before.answer_pairs == len(rows)
    assert t.d2h_bytes - before.d2h_bytes == 4 + 4 + 2 * 4 * caps.pair_cap


def test_reset_clears_every_counter(engine_and_graph):
    eng, _ = engine_and_graph
    eng.execute(instantiate_template("C2", [0, 1]))
    eng.telemetry.reset()
    assert all(v == 0 for v in vars(eng.telemetry).values())


def _op_names(compiled) -> list:
    import re

    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


def test_a_recursive_primitive_leaves_no_scope_behind():
    """``prefix_sum`` recurses; the ops after it carry no ``compact``."""
    import jax
    import jax.numpy as jnp

    from repro.core import relational as R

    def f(x):
        return R.prefix_sum(x) * 3

    names = _op_names(jax.jit(f).lower(jnp.ones(4096, jnp.int32)).compile())
    assert any("/compact/" in n for n in names)
    assert {n for n in names if n.endswith("/mul")} == {"jit(f)/mul"}


def test_walker_ops_carry_their_operator_and_primitive_scopes(
        engine_and_graph):
    """A join plan's program names every primitive under its operator,
    and never a sort or an expansion inside a compaction."""
    eng, _ = engine_and_graph
    q = instantiate_template("C4", [0, 0, 2, 2])
    plan = eng.plan(q)
    ranges = eng.lookup_ranges(plan)[None]
    names = _op_names(eng.backend.compile_batch(
        plan_shape(plan), QueryCaps(16, 64, 128), ranges))
    for scope in ("vmap(join)/sort/", "vmap(join)/search/",
                  "vmap(join)/compact/", "vmap(join)/expand/",
                  "vmap(materialize)/", "vmap(lookup)/", "vmap(recap)/"):
        assert any(scope in n for n in names), scope
    assert not [n for n in names if "compact/sort" in n
                or "compact/expand" in n]
