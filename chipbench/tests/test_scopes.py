"""The program's scopes and spans in a trace: ``op_name`` read from the
trace's metadata plane, device time attributed to walker scopes, idle
time to program spans, and the older reduction left as it was."""

import glob
import os

import pytest

from chipbench import opnames, scopes, trace
from chipbench.tests.test_trace import _xspace


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, payload) -> bytes:
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _hlo(op_names: dict) -> bytes:
    """A serialized ``HloProto`` of one computation whose instructions
    carry ``op_names`` (``{instruction: op_name}``)."""
    comp = b"".join(_field(2, _field(1, name) + _field(7, _field(2, op)))
                    for name, op in op_names.items())
    return _field(1, _field(1, "jit_run_plan_batch") + _field(3, comp))


WALKER = "jit(run_plan_batch)/vmap(join)"
OP_NAMES = {
    # a sort loop and the sort in its body: one scope, counted once
    "while.1": f"{WALKER}/sort/while",
    "fusion.2": f"{WALKER}/sort/while/body/closed_call/jit(sort)/sort",
    # a binary search inside compaction: the innermost primitive wins
    "while.3": f"{WALKER}/compact/search/jit(searchsorted)/vmap()/while",
    # no primitive scope: the plan operator's
    "fusion.4": f"{WALKER}/gt",
    "copy.5": "a.c2p_u",
}


def _synthetic(tmp_path, with_metadata=True):
    """A traced window of 0..20 us: a walker module (program 42) over
    1..13 us and another over 15..17 us, their ops, the harness's window
    and wave spans, and the program's spans over the idle gaps."""
    def ev(mid, start_us, dur_us):
        return (f"events {{ metadata_id: {mid} offset_ps: "
                f"{int(start_us * 1e6)} duration_ps: {int(dur_us * 1e6)} }}")

    ops = [(1, 1, 4), (2, 2, 2), (3, 6, 2), (4, 9, 1), (5, 11, 2),
           (6, 15, 2)]
    hlo = "".join(f"\\{b:03o}" for b in _hlo(OP_NAMES))
    meta = f"""
    planes {{ id: 4 name: "/host:metadata"
      event_metadata {{ key: 42 value {{ id: 42
        name: "jit_run_plan_batch(42)"
        stats {{ metadata_id: 1 bytes_value: "{hlo}" }} }} }}
      stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
    }}""" if with_metadata else ""
    text = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
        {" ".join(ev(m, s, d) for m, s, d in ops)} }}
      lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
        {ev(20, 1, 12)} {ev(21, 15, 2)} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "%while.1 = (s32[]) while(s32[] %a)" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.2 = s32[4] fusion(s32[4] %b), kind=kCustom" }} }}
      event_metadata {{ key: 3 value {{ id: 3 name: "%while.3 = (s32[]) while(s32[] %c)" }} }}
      event_metadata {{ key: 4 value {{ id: 4 name: "%fusion.4 = s32[4] fusion(s32[4] %d), kind=kLoop" }} }}
      event_metadata {{ key: 5 value {{ id: 5 name: "%copy.5 = s32[4] copy(s32[4] %e)" }} }}
      event_metadata {{ key: 6 value {{ id: 6 name: "%sort.1 = s32[4] sort(s32[4] %f)" }} }}
      event_metadata {{ key: 20 value {{ id: 20 name: "jit_run_plan_batch(42)" }} }}
      event_metadata {{ key: 21 value {{ id: 21 name: "jit_other(43)" }} }}
    }}
    planes {{ id: 2 name: "/host:CPU"
      lines {{ id: 1 name: "python" timestamp_ns: 0
        {ev(1, 0, 20)} {ev(2, 0, 20)} {ev(3, 0, 19)} {ev(4, 5, 1.5)}
        {ev(5, 7.5, 6)} {ev(6, 7.5, 1)} {ev(7, 12, 2)} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "chipbench.window" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "chipbench.wave" }} }}
      event_metadata {{ key: 3 value {{ id: 3 name: "cpqx.service.flush" }} }}
      event_metadata {{ key: 4 value {{ id: 4 name: "cpqx.engine.dispatch" }} }}
      event_metadata {{ key: 5 value {{ id: 5 name: "cpqx.engine.harvest" }} }}
      event_metadata {{ key: 6 value {{ id: 6 name: "cpqx.harvest.wait" }} }}
      event_metadata {{ key: 7 value {{ id: 7 name: "cpqx.harvest.copy" }} }}
    }}{meta}"""
    from jax.profiler import ProfileData

    out = tmp_path / "plugins" / "profile" / "1"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


@pytest.mark.parametrize("op_name,scope", [
    (f"{WALKER}/sort/while/body/closed_call/jit(sort)/sort", "sort"),
    (f"{WALKER}/compact/search/jit(searchsorted)/vmap()/while", "search"),
    ("jit(run_plan_batch)/vmap(conj_id)/compact/compact/compact/"
     "jit(cumsum)/prefix_sum/reduce_window_sum", "compact"),
    (f"{WALKER}/expand/gather", "expand"),
    (f"{WALKER}/gt", "join"),
    ("jit(run_plan_batch)/vmap(materialize)/sort", "materialize"),
    ("jit(run_plan_batch)/vmap()/slice", "unscoped"),
    ("a.c2p_u", "unscoped"),
    ("", "unscoped"),
])
def test_scope_is_the_innermost_primitive_then_the_operator(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_scope_names_are_the_programs():
    """The reduction keeps its own copy of the walker's scope names; each
    is a scope in the ``op_name``s of compiled walker programs whose
    plans run every operator."""
    import re

    from repro.core import index as cindex
    from repro.core.engine import Engine, QueryCaps
    from repro.core.graph import example_graph
    from repro.core.query import Identity, instantiate_template, plan_shape

    eng = Engine(cindex.build(example_graph(), 2))
    queries = [
        instantiate_template("TC", [0, 1, 2, 0, 1]),  # join, conj_classes
        instantiate_template("C2i", [0, 1]),  # conj_id
        Identity(),  # identity
        instantiate_template("C4", [0, 0, 2, 2])
        & instantiate_template("C2", [0, 1]),  # conj_pairs
    ]
    written = set()
    for q in queries:
        plan = eng.plan(q)
        text = eng.backend.compile_batch(
            plan_shape(plan), QueryCaps(16, 64, 128),
            eng.lookup_ranges(plan)[None]).as_text()
        for op_name in re.findall(r'op_name="([^"]*)"', text):
            written.update(
                p[5:-1] if p.startswith("vmap(") and p.endswith(")") else p
                for p in op_name.split("/")[:-1])
    names = scopes.PRIMITIVE_SCOPES + scopes.OPERATOR_SCOPES
    assert len(set(names)) == len(names)
    assert set(names) <= written, set(names) - written


def test_op_names_come_from_the_metadata_plane(tmp_path):
    path = glob.glob(os.path.join(_synthetic(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    with open(path, "rb") as f:
        assert opnames.program_op_names(f.read()) == {
            "jit_run_plan_batch(42)": OP_NAMES}


def test_op_names_match_the_compiled_program_on_a_real_trace(tmp_path):
    """On a real (CPU) trace, the metadata plane's HLO carries the same
    ``op_name`` per instruction as the compiled program's text."""
    import re

    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("sort"):
            x = jnp.sort(x)
        with jax.named_scope("search"):
            return jnp.searchsorted(x, x)

    x = jnp.arange(64)[::-1]
    text = f.lower(x).compile().as_text()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    with open(path, "rb") as fh:
        got = opnames.program_op_names(fh.read())
    (names,) = [v for k, v in got.items() if k.startswith("jit_f(")]
    want = dict(re.findall(r'%([\w.\-]+) = .*op_name="([^"]*)"', text))
    assert want and {k: names[k] for k in want} == want
    assert {scopes.scope_of(v) for v in want.values()} >= {"sort", "search"}


def test_tracecheck_reads_the_clock_checks(tmp_path):
    from chipbench.tools import tracecheck

    got = tracecheck.checks(_synthetic(tmp_path), walker_s=12e-6)
    assert got["flush_outside_wave_ms"] == 0.0
    # engine.harvest 7.5..13.5 us: wait 7.5..8.5 and copy 12..13.5 inside
    assert got["harvest_split"] == pytest.approx(2.5 / 6)
    assert got["coverage"]["scoped_share"] == pytest.approx(7 / 12)
    assert got["coverage"]["unscoped_share"] == pytest.approx(2 / 12)


@pytest.fixture
def any_coverage(monkeypatch):
    """Per-scope seconds reported whatever share the scopes cover."""
    monkeypatch.setattr(scopes, "MIN_SCOPE_COVERAGE", 0.0)


def test_walker_time_goes_to_scopes_with_loops_counted_once(tmp_path,
                                                           any_coverage):
    red = scopes.reduce_program(_synthetic(tmp_path))
    # while.1 (1..5 us) holds fusion.2 (2..4 us): sort is 4 us, not 6
    assert red["scopes"] == pytest.approx(
        {"sort": 4e-6, "search": 2e-6, "join": 1e-6, "unscoped": 2e-6})
    # the op at 15..17 us runs in another module, not the walker's
    assert red["walker_ops_s"] == pytest.approx(9e-6)
    assert red["scoped_s"] == pytest.approx(7e-6)
    assert red["unscoped_s"] == pytest.approx(2e-6)
    # the walker module runs 1..13 us
    assert red["scope_coverage"] == pytest.approx(7 / 12)
    assert red["op_scopes"]["while.1"] == pytest.approx({"sort": 4e-6})
    assert red["op_scopes"]["fusion.2 kCustom"] == pytest.approx(
        {"sort": 2e-6})
    assert "sort.1" not in red["op_scopes"]


def test_idle_time_goes_to_the_innermost_program_span(tmp_path):
    gaps = dict(scopes.reduce_program(_synthetic(tmp_path))["program_gaps"])
    # idle: 0..1, 5..6, 8..9, 10..11, 13..15, 17..20 us
    assert gaps["engine.dispatch"] == pytest.approx(1e-6)  # 5..6
    assert gaps["harvest.wait"] == pytest.approx(0.5e-6)  # 8..8.5
    assert gaps["engine.harvest"] == pytest.approx(0.5e-6 + 1e-6)
    assert gaps["harvest.copy"] == pytest.approx(1e-6)  # 13..14
    assert gaps["service.flush"] == pytest.approx(1e-6 + 1e-6 + 2e-6)
    assert gaps[scopes.OUTSIDE_SPANS] == pytest.approx(1e-6)  # 19..20
    assert sum(gaps.values()) == pytest.approx(9e-6)


def test_innermost_takes_the_span_opened_last():
    spans = [(0.0, 10.0, "flush"), (2.0, 6.0, "harvest"),
             (3.0, 4.0, "wait"), (12.0, 13.0, "plan")]
    out = scopes.innermost(spans, [(1.0, 5.0), (9.0, 14.0)])
    assert out == pytest.approx({"flush": 1.0 + 1.0, "harvest": 2.0,
                                 "wait": 1.0, "plan": 1.0,
                                 scopes.OUTSIDE_SPANS: 3.0})


def test_a_trace_without_program_metadata_reads_unscoped(tmp_path,
                                                        any_coverage):
    red = scopes.reduce_program(_synthetic(tmp_path, with_metadata=False))
    assert set(red["scopes"]) == {"unscoped"}
    assert red["scoped_s"] == 0.0
    assert red["unscoped_s"] == pytest.approx(red["walker_ops_s"])
    assert red["scope_coverage"] == 0.0


@pytest.mark.parametrize("floor,reported", [
    (None, False), (0.5, True), (0.6, False)])
def test_scopes_fall_silent_under_the_coverage_floor(tmp_path, monkeypatch,
                                                     floor, reported):
    """Scopes cover 7 of the walker's 12 us: under the floor (the
    reduction's own, 95%, where ``floor`` is None) the per-scope seconds
    are left out."""
    if floor is not None:
        monkeypatch.setattr(scopes, "MIN_SCOPE_COVERAGE", floor)
    red = scopes.reduce_program(_synthetic(tmp_path))
    assert bool(red["scopes"]) == reported
    assert red["scope_coverage"] == pytest.approx(7 / 12)


def test_old_reduction_is_unchanged_on_its_synthetic_trace():
    red = trace.reduce(_xspace())
    assert list(red) == ["window_s", "busy_s", "walker_s", "device_ops",
                         "idle_gaps"]
    assert red["window_s"] == pytest.approx(10e-6)
    assert red["busy_s"] == pytest.approx(6e-6)
    assert red["walker_s"] == pytest.approx(4e-6)
    assert [k for k, _ in red["device_ops"]] == ["sort.1", "fusion.2"]
    assert [v for _, v in red["device_ops"]] == pytest.approx([4e-6, 3e-6])
    gaps = dict(red["idle_gaps"])
    assert gaps.pop("harvest") == pytest.approx(2e-6)
    assert gaps.pop("between_waves") == pytest.approx(2e-6)
    assert set(gaps) <= set(trace.GAP_SPANS)
    assert all(v == 0.0 for v in gaps.values())

