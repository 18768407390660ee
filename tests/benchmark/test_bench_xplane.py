"""The reduction from a profiler trace to device numbers, on hand-built
events with known answers and on a small xplane file written from text."""

import pytest

from benchmark.harness import xplane
from benchmark.harness.xplane import Op

COLLECTIVE = r"^(collective-permute|all-reduce|all-gather|reduce-scatter)(-start|-done)?(\.|$)"


def op(start, end, name, text=None):
    return Op(float(start), float(end), name, text or name)


# One device, two "rounds". A while loop (the layer scan) spans its body:
#   0..100   while.1        body: fusion.1 10..30, all-reduce-start.1 30..40,
#                                 custom-call.7 (a kernel) 40..70, all-reduce-done.1 70..90
#   120..130 copy.1         (after an idle gap of 20)
#   130..150 all-gather.3   synchronous collective, nothing under it
OPS = [
    op(0, 100, "while.1"),
    op(10, 30, "fusion.1"),
    op(30, 40, "all-reduce-start.1"),
    op(40, 70, "custom-call.7", "custom-call.7 jit(round)/pallas_call[name=acco_fused_attn_fwd]"),
    op(70, 90, "all-reduce-done.1"),
    op(120, 130, "copy.1"),
    op(130, 150, "all-gather.3"),
]


def test_flatten_gives_each_instant_to_the_deepest_op():
    segs = [(s.start, s.end, s.op.name) for s in xplane.flatten(OPS)]
    assert segs == [
        (0, 10, "while.1"), (10, 30, "fusion.1"), (30, 40, "all-reduce-start.1"),
        (40, 70, "custom-call.7"), (70, 90, "all-reduce-done.1"), (90, 100, "while.1"),
        (120, 130, "copy.1"), (130, 150, "all-gather.3"),
    ]


def test_busy_idle_and_self_times():
    segs = xplane.flatten(OPS)
    assert xplane.busy_ns(segs) == 130  # the loop is not counted twice
    assert xplane.span_ns(OPS) == 150
    assert xplane.idle_gaps(segs) == [(100, 20)]
    assert xplane.top_ops(segs, 3) == [("custom-call.7", 30), ("while.1", 20), ("fusion.1", 20)]
    # a kernel's own name shows in the event's stats, not in the HLO name
    assert xplane.self_time_ns(segs, "acco_fused_attn") == 30
    assert xplane.self_time_ns(segs, "acco_fused_attn", "name") == 0
    assert xplane.self_time_ns(segs, r"^fusion", "name") == 20


def test_collectives_in_flight_and_exposed():
    segs = xplane.flatten(OPS)
    assert xplane.collective_intervals(OPS, COLLECTIVE) == [(30, 90), (130, 150)]
    in_flight, exposed = xplane.collective_times_ns(OPS, segs, COLLECTIVE)
    assert in_flight == 80
    # under 30..90 only custom-call.7 (30) computes; all-gather.3 hides nothing
    assert exposed == 50


@pytest.mark.parametrize(
    "ops,expected",
    [
        # two async pairs of one kind in flight together: first started, first done
        ([op(0, 1, "all-gather-start.1"), op(2, 3, "all-gather-start.2"),
          op(10, 11, "all-gather-done.1"), op(20, 21, "all-gather-done.2")], [(0, 21)]),
        # a done whose start fell outside the trace, a start whose done did
        ([op(5, 6, "collective-permute-done.4"), op(50, 51, "collective-permute-start.9")],
         [(5, 6), (50, 51)]),
        # the name alone decides: a fusion is no collective
        ([op(0, 9, "fusion.all-reduce")], []),
    ],
)
def test_collective_pairing(ops, expected):
    assert xplane.collective_intervals(ops, COLLECTIVE) == expected


def test_instruction_names_from_the_tpus_hlo_text():
    """The TPU's ops line names an event by the whole instruction."""
    text = ("%acco_fused_attn_fwd.3 = (bf16[8,12,1024,64]{3,2,1,0:T(8,128)(2,1)}, f32[8,12,1024]"
            "{2,1,0}) custom-call(bf16[8,12,1024,64]{3,2,1,0} %bitcast.1), "
            'custom_call_target="tpu_custom_call"')
    assert xplane.instruction_name(text) == "acco_fused_attn_fwd.3"
    assert xplane.instruction_name("%fusion = bf16[8]{0} fusion(), kind=kLoop") == "fusion"
    assert xplane.instruction_name("dot_general.1") == "dot_general.1"


def test_op_label_adds_the_result_type_without_layouts():
    text = ("%acco_banded_attn_dkv.1 = (f32[8,12,1024,64]{3,2,1,0:T(8,128)}, f32[8,12,1024,64]"
            "{3,2,1,0:T(8,128)}) custom-call(bf16[8,12,1024,64]{3,2,1,0:T(8,128)(2,1)} %gte.234)")
    assert xplane.op_label(op(0, 1, "acco_banded_attn_dkv.1", text)) == (
        "acco_banded_attn_dkv.1 (f32[8,12,1024,64], f32[8,12,1024,64])")
    ce = "%select_add_fusion = bf16[8,1023,50257]{1,2,0:T(8,128)(2,1)} fusion(f32[8,1023,50257] %x)"
    assert xplane.op_label(op(0, 1, "select_add_fusion", ce)) == "select_add_fusion bf16[8,1023,50257]"
    assert xplane.op_label(op(0, 1, "dot_general.1")) == "dot_general.1"
    assert len(xplane.op_label(op(0, 1, "f", "%f = (" + "f32[8], " * 40 + "f32[8]) fusion()"))) == 96


def test_a_done_is_paired_with_the_start_it_names():
    """Two permutes of one kind in flight, done in the other order: the HLO
    text of each done names its start."""
    ops = [
        op(0, 1, "collective-permute-start.1"), op(2, 3, "collective-permute-start.2"),
        op(10, 11, "collective-permute-done.2",
           "%collective-permute-done.2 = f32[8]{0} collective-permute-done((f32[8]{0}, f32[8]{0}) "
           "%collective-permute-start.2)"),
        op(40, 41, "collective-permute-done.1",
           "%collective-permute-done.1 = f32[8]{0} collective-permute-done((f32[8]{0}, f32[8]{0}) "
           "%collective-permute-start.1)"),
        op(20, 30, "fusion.1"),
    ]
    raw = xplane.collective_intervals(ops, COLLECTIVE)
    assert raw == [(0, 41)]  # 2..11 lies inside 0..41
    in_flight, exposed = xplane.collective_times_ns(ops, xplane.flatten(ops), COLLECTIVE)
    assert (in_flight, exposed) == (41, 31)


def test_device_trace_averages_over_devices_and_rounds():
    shifted = [Op(o.start + 1000, o.end + 1000, o.name, o.text) for o in OPS]
    trace = xplane.DeviceTrace.from_ops({"/device:TPU:0": OPS, "/device:TPU:1": shifted,
                                         "/device:TPU:2": []}, rounds=2)
    assert trace.devices == 2  # a plane with no op is no device of the run
    assert trace.busy_s() == pytest.approx(130e-9)
    assert trace.window_s() == pytest.approx(150e-9)
    assert trace.op_ms_per_round("acco_fused_attn") == pytest.approx(30e-6 / 2)
    total, exposed = trace.collective_ms_per_round(COLLECTIVE)
    assert (total, exposed) == (pytest.approx(80e-6 / 2), pytest.approx(50e-6 / 2))
    assert trace.collective_ms_per_round("^nothing$") is None
    breakdown = trace.breakdown()
    assert breakdown["device_ops"][0] == ["custom-call.7", pytest.approx(30e-9)]
    assert breakdown["idle_gaps"][0] == ["unattributed", pytest.approx(20e-9)]
    assert len(breakdown["device_ops"]) <= 10 and len(breakdown["idle_gaps"]) <= 5


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 20000
             stats { metadata_id: 1 str_value: "pallas_call[name=acco_banded_attn_fwd]" } }
    events { metadata_id: 3 offset_ps: 120000 duration_ps: 10000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 130000 } }
  event_metadata { key: 1 value { id: 1 name: "while.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.2" } }
  event_metadata { key: 3 value { id: 3 name: "copy.1" } }
  event_metadata { key: 4 value { id: 4 name: "jit_round(1)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 999000 } }
  event_metadata { key: 1 value { id: 1 name: "train" } } }
"""


def test_read_ops_from_an_xplane_file(tmp_path):
    """The reader on a real ``.xplane.pb``: only the device's ops line, the
    host plane and the modules line left out, string stats in ``text``."""
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ops = xplane.read_ops(str(path))
    assert list(ops) == ["/device:TPU:0"]
    got = sorted((o.start, o.end, o.name) for o in ops["/device:TPU:0"])
    assert got == [(1000, 1100, "while.1"), (1010, 1030, "custom-call.2"), (1120, 1130, "copy.1")]
    trace = xplane.DeviceTrace.from_ops(ops, rounds=1)
    assert trace.busy_s() == pytest.approx(110e-9) and trace.window_s() == pytest.approx(130e-9)
    assert trace.op_ms_per_round("acco_banded_attn") == pytest.approx(20e-6)
