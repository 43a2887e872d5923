"""The trace reduction on synthetic intervals and a synthetic trace."""

import pytest

from chipbench import trace


def test_union_merges_overlaps_and_clips():
    iv = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (-1.0, 0.5), (9.0, 12.0)]
    assert trace.union(iv, 0.0, 10.0) == [(0.0, 3.0), (5.0, 6.0),
                                          (9.0, 10.0)]
    assert trace.busy(iv, 0.0, 10.0) == pytest.approx(5.0)
    assert trace.busy(iv, 2.5, 5.5) == pytest.approx(1.0)
    assert trace.busy([(20.0, 30.0)], 0.0, 10.0) == 0.0


def test_gaps_are_the_complement_in_the_window():
    u = trace.union([(1.0, 2.0), (4.0, 5.0)], 0.0, 6.0)
    assert trace.gaps(u, 0.0, 6.0) == [(0.0, 1.0), (2.0, 4.0), (5.0, 6.0)]
    assert trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_idle_goes_to_the_innermost_host_span():
    spans = [(0.0, 10.0, "wave"), (1.0, 3.0, "dispatch"),
             (1.5, 2.5, "group_batch"), (6.0, 8.0, "harvest")]
    out = trace.attribute_gaps([(1.0, 4.0), (7.0, 12.0)], spans)
    assert out["group_batch"] == pytest.approx(1.0)
    assert out["dispatch"] == pytest.approx(1.0)
    assert out["wave"] == pytest.approx(1.0 + 2.0)
    assert out["harvest"] == pytest.approx(1.0)
    assert out["between_waves"] == pytest.approx(2.0)


def _xspace(device=True):
    ops = """
      events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
      events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
      events { metadata_id: 1 offset_ps: 8000000 duration_ps: 4000000 }
    """
    dev = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }}
      lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
        events {{ metadata_id: 3 offset_ps: 1000000 duration_ps: 4000000 }}
        events {{ metadata_id: 4 offset_ps: 8000000 duration_ps: 4000000 }}
      }}
      event_metadata {{ key: 1 value {{ id: 1 name: "sort.1" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "fusion.2" }} }}
      event_metadata {{ key: 3 value {{ id: 3 name: "jit_run_plan_batch(7)" }} }}
      event_metadata {{ key: 4 value {{ id: 4 name: "jit_other(8)" }} }}
    }}""" if device else ""
    host = """
    planes { id: 3 name: "/device:CUSTOM:Megascale Trace" }
    planes { id: 2 name: "/host:CPU"
      lines { id: 1 name: "python" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
        events { metadata_id: 2 offset_ps: 5000000 duration_ps: 2000000 }
        events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
      }
      event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
      event_metadata { key: 2 value { id: 2 name: "chipbench.harvest" } }
      event_metadata { key: 3 value { id: 3 name: "other" } }
    }"""
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(dev + host)


def test_reduce_reads_busy_walker_ops_and_gaps():
    red = trace.reduce(_xspace())
    # window 0..10 us; ops cover 1..5 and 8..10 (clipped from 8..12)
    assert red["window_s"] == pytest.approx(10e-6)
    assert red["busy_s"] == pytest.approx(6e-6)
    assert red["walker_s"] == pytest.approx(4e-6)
    assert dict(red["device_ops"]) == pytest.approx(
        {"sort.1": 4e-6, "fusion.2": 3e-6})
    gaps = dict(red["idle_gaps"])
    assert gaps["harvest"] == pytest.approx(2e-6)
    assert gaps["between_waves"] == pytest.approx(2e-6)


def test_reduce_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce(_xspace(device=False))


def test_op_label_keeps_the_instruction_name_and_fusion_kind():
    assert trace.op_label(
        "%fusion.253 = s32[4194304]{0} fusion(s32[4,1048576]{1,0} %a), "
        "kind=kCustom, calls=%fused_computation.4") == "fusion.253 kCustom"
    assert trace.op_label("%while.90 = (s32[]) while((s32[]) %t)") == "while.90"
    assert trace.op_label("sort.1") == "sort.1"
