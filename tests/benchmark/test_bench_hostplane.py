"""Idle gaps of the device laid against the program's host spans
(``harness/hostplane.py``, ``reducers/idle_by_host_span.py``) and device time
by the program's scopes (``reducers/scope_op_time.py``), on hand-built spans
and on small xplane files written from text, with known answers; the
per-layer metrics PR 23 added resolve in every cell and share no scope."""

import json
import os
import re

import pytest

from _paths import ROOT
from benchmark.harness import hostplane, xplane
from benchmark.harness.manifest import Manifest
from benchmark.harness.xplane import Op

US = 1000.0  # ns


def span(start_us, end_us, name):
    return Op(start_us * US, end_us * US, name, name)


def gap(start_us, end_us):
    return (start_us * US, end_us * US)


#  host, us:  |-- loader 0..200 --|-- dispatch 200..700 --|      |-- sync 1000..3000 --|-- host 3000..3600 --|
#                                                                      |- eval 1500..2000 -|  (nested in sync)
SPANS = [
    span(0, 200, "loader/next_block"),
    span(200, 700, "train/dispatch"),
    span(1000, 3000, "train/log_boundary_sync"),
    span(1500, 2000, "train/eval"),
    span(3000, 3600, "train/log_boundary_host"),
]


@pytest.mark.parametrize(
    "gaps, expected",
    [
        # under one span
        ([gap(300, 600)], {"train/dispatch": 300}),
        # under nested spans: the innermost owns what it covers
        ([gap(1200, 2400)], {"train/log_boundary_sync": 700, "train/eval": 500}),
        # under none
        ([gap(700, 1000)], {"none": 300}),
        # straddling two, with a piece that no span covers between them
        ([gap(500, 1100)], {"train/dispatch": 200, "none": 300, "train/log_boundary_sync": 100}),
        # straddling two adjacent spans
        ([gap(2900, 3200)], {"train/log_boundary_sync": 100, "train/log_boundary_host": 200}),
        # a gap under 100 us is between two ops of one program: none, whatever the host does
        ([gap(300, 399)], {"none": 99}),
        ([gap(300, 400)], {"train/dispatch": 100}),
        # several gaps, in any order, past the last span too
        ([gap(3500, 3800), gap(100, 300)],
         {"train/log_boundary_host": 100, "none": 200, "loader/next_block": 100,
          "train/dispatch": 100}),
        ([], {}),
    ],
)
def test_each_instant_of_a_gap_goes_to_the_innermost_span(gaps, expected):
    got = hostplane.attribute(gaps, SPANS)
    assert {k: v for k, v in got.items() if v} == {k: v * US for k, v in expected.items()}
    assert sum(got.values()) == pytest.approx(sum(hi - lo for lo, hi in gaps))


def test_gaps_are_every_gap_between_segments_and_covering_names_them():
    ops = [Op(0, 100 * US, "while.1", ""), Op(10 * US, 30 * US, "fusion.1", ""),
           Op(400 * US, 500 * US, "copy.1", ""), Op(1100 * US, 1200 * US, "fusion.2", "")]
    gaps = hostplane.device_gaps(xplane.flatten(ops))
    assert gaps == [gap(100, 400), gap(500, 1100)]  # the loop's body leaves no gap in it
    assert hostplane.covering(gaps[0], SPANS) == ["loader/next_block", "train/dispatch"]
    assert hostplane.covering(gaps[1], SPANS) == ["train/dispatch", "train/log_boundary_sync"]
    assert hostplane.covering(gap(700, 1000), SPANS) == []


# Two devices and the trainer's thread, times in ps from the line's timestamp.
#   TPU:0  ops 0..1000 us, 1400..2000 us           gap 1000..1400 (400 us)
#   TPU:1  ops 0..900 us, 950..2000 us             gap 900..950 (50 us: short)
#   host   dispatch 0..1100 us, sync 1100..1300 us, host 1300..1350 us, then nothing
XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 1400000000 duration_ps: 600000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 900000000 }
    events { metadata_id: 2 offset_ps: 950000000 duration_ps: 1050000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 7 name: "prefetch" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 0 duration_ps: 2000000000 } }
  lines { id: 8 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1100000000 }
    events { metadata_id: 2 offset_ps: 1100000000 duration_ps: 200000000 }
    events { metadata_id: 3 offset_ps: 1300000000 duration_ps: 50000000 }
    events { metadata_id: 9 offset_ps: 100000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "train/dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "train/log_boundary_sync" } }
  event_metadata { key: 3 value { id: 3 name: "train/log_boundary_host" } }
  event_metadata { key: 9 value { id: 9 name: "$queue.py:122 put" } } }
"""
NAMES = ["train/dispatch", "train/log_boundary_sync", "train/log_boundary_host",
         "loader/next_block"]


def write_profile(tmp_path, text=XSPACE):
    from jax.profiler import ProfileData

    folder = tmp_path / "profile" / "plugins" / "profile" / "2026_01_01"
    folder.mkdir(parents=True)
    path = folder / "vm.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_read_host_spans_keeps_the_loops_line_and_the_programs_names(tmp_path):
    path = write_profile(tmp_path)
    spans = hostplane.read_host_spans(path, NAMES)
    assert sorted((s.start, s.end, s.name) for s in spans) == [
        (0, 1100 * US, "train/dispatch"),
        (1100 * US, 1300 * US, "train/log_boundary_sync"),
        (1300 * US, 1350 * US, "train/log_boundary_host"),
    ]
    # a program that annotates nothing leaves nothing to read, and nothing raises
    assert hostplane.read_host_spans(path, ["serve/request"]) == []
    assert hostplane.read_host_spans(path, NAMES, loop_span="loader/next_block") == []


def reducer_ctx(tmp_path, said, other_data):
    path = write_profile(tmp_path)
    return {
        "device_trace": xplane.DeviceTrace.from_ops(xplane.read_ops(path), rounds=2),
        "trace": {"traceEvents": [], "otherData": other_data},
        "say": said.append,
    }


def test_idle_by_host_span_on_two_devices(tmp_path):
    """400 us under dispatch 100 / sync 200 / host 50 / nothing 50 on one
    device, a 50 us gap on the other: per round (2) and device (2)."""
    reduce = Manifest().reducer("idle_by_host_span")
    said = []
    ctx = reducer_ctx(tmp_path, said, {"profile_dir": str(tmp_path / "profile")})
    got = {name: reduce(ctx, {"span": name}) for name in [*NAMES, "none"]}
    per = 1e-3 / 4  # us -> ms, over two devices and two rounds
    assert got == {
        "train/dispatch": pytest.approx(100 * per),
        "train/log_boundary_sync": pytest.approx(200 * per),
        "train/log_boundary_host": pytest.approx(50 * per),
        "loader/next_block": 0.0,
        "none": pytest.approx((50 + 50) * per),
    }
    # the five add up to the trace's own idle: span minus busy, per round
    trace = ctx["device_trace"]
    idle_ms = (trace.window_s() - trace.busy_s()) * 1e3 / trace.rounds
    assert sum(got.values()) == pytest.approx(idle_ms)
    # the longest gaps are printed with the spans that cover them, once
    table = [line for line in said if line.startswith("idle gap of")]
    assert len(table) == 2
    assert "0.400 ms at 1.000 ms" in table[0] and "/device:TPU:0" in table[0]
    assert table[0].endswith(
        "under train/dispatch, train/log_boundary_sync, train/log_boundary_host"
    )
    assert "0.050 ms" in table[1] and table[1].endswith("under train/dispatch")


@pytest.mark.parametrize(
    "other_data",
    [{}, {"profile_dir": None}, {"profile_dir": "/nowhere/at/all"}],
)
def test_idle_by_host_span_reads_nothing_from_a_program_that_names_no_profile(
    tmp_path, other_data
):
    """The parent commit's trace has no ``profile_dir``: None, and no raise."""
    reduce = Manifest().reducer("idle_by_host_span")
    ctx = reducer_ctx(tmp_path, [], other_data)
    assert reduce(ctx, {"span": "train/dispatch"}) is None
    assert reduce({**ctx, "device_trace": None}, {"span": "none"}) is None


def test_idle_by_host_span_reads_nothing_where_no_span_is_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData

    text = XSPACE.replace('name: "train/', 'name: "unnamed/')
    folder = tmp_path / "p" / "plugins" / "profile" / "x"
    folder.mkdir(parents=True)
    (folder / "vm.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    reduce = Manifest().reducer("idle_by_host_span")
    ctx = reducer_ctx(tmp_path, [], {"profile_dir": str(tmp_path / "p")})
    assert reduce(ctx, {"span": "train/dispatch"}) is None


# -- the metrics PR 23 added ---------------------------------------------------

IDLE_METRICS = {
    "idle_in_boundary_sync_ms": "train/log_boundary_sync",
    "idle_in_boundary_host_ms": "train/log_boundary_host",
    "idle_in_loader_ms": "loader/next_block",
    "idle_in_dispatch_ms": "train/dispatch",
    "idle_unattributed_ms": "none",
}
SCOPE_METRICS = ["block_ms", "lm_head_ce_ms", "embed_ms", "flat_staging_ms", "optimizer_ms",
                 "guard_ms", "unscoped_device_ms"]


@pytest.mark.parametrize("cell_name", Manifest().cell_names())
@pytest.mark.parametrize("metric", [*IDLE_METRICS, *SCOPE_METRICS])
def test_each_new_metric_resolves_in_every_cell(metric, cell_name):
    manifest = Manifest()
    spec = next(m for m in manifest.layer_metrics(cell_name) if m["name"] == metric)
    assert spec["unit"] == "ms/round" and spec["better"] == "lower"
    assert spec["source"] == "device_trace" and spec["moves"] == "tokens_per_s_per_chip"
    assert "workloads" not in spec  # read in every cell that reports the rate
    manifest.reducer(spec["reducer"])
    if metric in IDLE_METRICS:
        assert spec["reducer"] == "idle_by_host_span"
        assert spec["args"] == {"span": IDLE_METRICS[metric]}
    else:
        assert spec["reducer"] == "scope_op_time"
        assert spec["args"]["scopes"] and re.compile(spec["args"]["except_ops"])


def scope_args() -> dict:
    out = {}
    for metric in SCOPE_METRICS:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", f"{metric}.json")) as f:
            out[metric] = json.load(f)["args"]
    return out


def test_every_scope_has_one_owner_among_the_scope_metrics():
    """The seven metrics partition the program's scopes and ``""`` (no
    scope), and all leave the same ops (the collectives) out: with the
    collectives' own self time they add up to the device's busy time."""
    from acco_tpu.telemetry import DEVICE_SCOPES

    args = scope_args()
    owned = [scope for a in args.values() for scope in a["scopes"]]
    assert sorted(owned) == sorted([*DEVICE_SCOPES, ""])
    assert len({a["except_ops"] for a in args.values()}) == 1
    collective = re.compile(args["guard_ms"]["except_ops"])
    for name in ("collective-permute-start.3", "collective-permute-done", "all-reduce.1",
                 "all-gather-start.12", "reduce-scatter"):
        assert collective.search(name)
    for name in ("fusion.all-reduce", "dynamic-update-slice.68", "scatter.7", "copy-start.4"):
        assert not collective.search(name)


# One device, two captured rounds of two programs whose instruction names
# clash: fusion.1 is the optimizer's in round_even and the guard's in round_odd.
#   module A 0..1000 us: fusion.1 0..400, collective-permute-start.1 400..500,
#                        fusion.2 500..900 (no scope), while.1 900..1000 with fusion.3 920..980
#   module B 1000..2000 us: fusion.1 1000..1600, copy.9 1600..2000 (not in B's table)
SCOPES_XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 0 duration_ps: 1000000000 }
    events { metadata_id: 11 offset_ps: 1000000000 duration_ps: 1000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 400000000 }
    events { metadata_id: 2 offset_ps: 400000000 duration_ps: 100000000 }
    events { metadata_id: 3 offset_ps: 500000000 duration_ps: 400000000 }
    events { metadata_id: 4 offset_ps: 900000000 duration_ps: 100000000 }
    events { metadata_id: 5 offset_ps: 920000000 duration_ps: 60000000 }
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 600000000 }
    events { metadata_id: 6 offset_ps: 1600000000 duration_ps: 400000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%collective-permute-start.1 = (f32[8]) collective-permute-start(%x)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = f32[8]{0} fusion(%p), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4 name: "%while.1 = (f32[8]) while(%t)" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.3 = bf16[8]{0} fusion(%p), kind=kOutput" } }
  event_metadata { key: 6 value { id: 6 name: "%copy.9 = f32[8]{0} copy(%p)" } }
  event_metadata { key: 10 value { id: 10 name: "jit__lambda(111)" } }
  event_metadata { key: 11 value { id: 11 name: "jit__lambda(222)" } } }
"""
TABLES = {
    "round_even": {"scopes": {"fusion.1": "acco/optimizer", "while.1": "acco/accumulate",
                              "fusion.3": "model/mlp", "copy.9": "acco/cast",
                              "collective-permute-start.1": "acco/reduce_scatter"},
                   "inferred": ["copy.9"], "mixed": {}},
    "round_odd": {"scopes": {"fusion.1": "acco/guard"},
                  "mixed": {"fusion.1": ["acco/guard", "acco/optimizer"]}},
    "seed": {"scopes": {"fusion.1": "model/embed"}, "mixed": {}},
}


def scopes_ctx(tmp_path, said, programs, tables=TABLES, text=SCOPES_XSPACE):
    path = write_profile(tmp_path, text)
    table_path = tmp_path / "device_scopes.json"
    table_path.write_text(json.dumps(tables))
    return {
        "device_trace": xplane.DeviceTrace.from_ops(xplane.read_ops(path), rounds=2),
        "trace": {"otherData": {"profile_dir": str(tmp_path / "profile"),
                                "scope_table": str(table_path),
                                "profiled_programs": programs}},
        "say": said.append,
    }


def test_scope_op_time_joins_each_op_on_the_program_that_ran_it(tmp_path):
    reduce = Manifest().reducer("scope_op_time")
    said = []
    ctx = scopes_ctx(tmp_path, said, ["round_even", "round_odd"])
    args = scope_args()
    got = {metric: reduce(ctx, a) for metric, a in args.items()}
    per = 1e-3 / 2  # us -> ms, one device, two rounds
    assert got == {
        "block_ms": pytest.approx(60 * per),  # fusion.3, inside the loop
        "lm_head_ce_ms": 0.0,
        "embed_ms": 0.0,  # the seed program's table is not this capture's
        "flat_staging_ms": 0.0,  # copy.9 ran in round_odd, whose table does not name it
        "optimizer_ms": pytest.approx(400 * per),  # fusion.1 of round_even
        "guard_ms": pytest.approx(600 * per),  # fusion.1 of round_odd
        # fusion.2, the loop's own 40 us, copy.9
        "unscoped_device_ms": pytest.approx((400 + 40 + 400) * per),
    }
    # with the collective's own 100 us: the whole busy time
    trace = ctx["device_trace"]
    assert sum(got.values()) + 100 * per == pytest.approx(trace.busy_s() * 1e3 / trace.rounds)
    assert any(line.startswith("device self time by scope") for line in said)
    # nothing inferred ran: copy.9 is round_even's, and ran in round_odd
    assert any("of it 0.000 in instructions with no op_name" in line for line in said)
    # a fusion that mixes scopes goes by its own op_name, and says what it fuses
    assert any(
        "acco/guard" in line and "fusion.1 f32[8]  [fuses acco/guard, acco/optimizer]" in line
        for line in said
    )


def test_scope_op_time_needs_no_module_line_for_one_program(tmp_path):
    """DDP runs one program: the join is on the instruction's name alone."""
    text = SCOPES_XSPACE.replace('name: "XLA Modules"', 'name: "Something Else"')
    reduce = Manifest().reducer("scope_op_time")
    said = []
    ctx = scopes_ctx(tmp_path, said, ["round_even", "round_even"], text=text)
    assert reduce(ctx, scope_args()["optimizer_ms"]) == pytest.approx(1000 * 1e-3 / 2)
    assert reduce(ctx, scope_args()["flat_staging_ms"]) == pytest.approx(400 * 1e-3 / 2)
    # copy.9 has no op_name of its own: owned by inference, and marked so
    assert any("of it 0.200 in instructions with no op_name" in line for line in said)
    assert any("~acco/cast" in line and "copy.9" in line for line in said)


@pytest.mark.parametrize(
    "change",
    [
        {"scope_table": None},
        {"scope_table": "/nowhere/device_scopes.json"},
        {"profiled_programs": []},
        {"profiled_programs": None},
        "no module line",
    ],
    ids=str,
)
def test_scope_op_time_reads_nothing_where_the_program_left_no_table(tmp_path, change):
    """The parent commit writes neither key; a capture of two programs
    with no module line cannot say which ran when. None, and no raise."""
    reduce = Manifest().reducer("scope_op_time")
    if change == "no module line":
        text = SCOPES_XSPACE.replace('name: "XLA Modules"', 'name: "Something Else"')
        ctx = scopes_ctx(tmp_path, [], ["round_even", "round_odd"], text=text)
    else:
        ctx = scopes_ctx(tmp_path, [], ["round_even", "round_odd"])
        ctx["trace"]["otherData"].update(change)
    assert reduce(ctx, scope_args()["guard_ms"]) is None
    ctx["trace"].pop("otherData")
    assert reduce(ctx, scope_args()["guard_ms"]) is None
