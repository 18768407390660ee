"""The set-up metrics (PR 37): each new reader on a small recorded trace of its
own, the eight parts tiling ``setup_s``, two children summing, and a program
that recorded no ``setup/*`` span reading nothing."""

import json
import os

import pytest

from _paths import FIXTURES, ROOT

from benchmark import run as bench_run
from benchmark.harness import window as win
from benchmark.harness.manifest import Manifest, load_module

TILING = ("setup_launch_s", "setup_build_s", "setup_tokenize_s", "setup_trainer_s",
          "warmup_join_s", "setup_first_rounds_s", "setup_profile_s", "setup_unnamed_s")
BESIDE = ("compile_lower_wall_s", "compile_backend_s", "lazy_compile_s",
          "compile_cache_fill_pct")
CELL = "neo125m-acco-1chip"


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.fixture(scope="module")
def specs(manifest):
    return {s["name"]: s for s in manifest.layer_metrics(CELL)}


def ctx_of(fixture: str, manifest, **over) -> dict:
    with open(os.path.join(FIXTURES, fixture)) as f:
        trace = json.load(f)
    other = trace["otherData"]
    ctx = {
        "trace": trace,
        "window": win.measure_window(trace, other.get("warmup_rounds", 10)),
        "quantities": {"setup_s": other.get("setup_s", 30.0)},
        "counters": {"compile_cache_dir_bytes": 188.0 * 2**20,
                     "compile_cache_max_bytes": 192.0 * 2**20},
        "warmup_report": {"cache": {"hits": 0, "misses": 3}, "programs": {
            "seed": {"lower_ms": 4000.0, "compile_ms": 3000.0},
            "round_even": {"lower_ms": 4100.0, "compile_ms": 3500.0},
            "round_odd": {"lower_ms": 4200.0, "compile_ms": None}}},
        "cell": manifest.cell(CELL),
        "said": [],
    }
    ctx["say"] = ctx["said"].append
    ctx.update(over)
    return ctx


def read(manifest, specs, ctx, name):
    spec = specs[name]
    return manifest.reducer(spec["reducer"])(ctx, spec.get("args", {}))


def test_the_twelve_are_in_the_manifest_as_the_issue_lists_them(manifest, specs):
    entries = {m["name"]: m for m in manifest.data["per_layer"]}
    assert [m["name"] for m in manifest.data["per_layer"][-12:]] == list(TILING + BESIDE)
    for name in TILING + BESIDE:
        entry = entries[name]
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert "workloads" not in entry  # every cell reports setup_s
        assert entry["unit"] == ("%" if name == "compile_cache_fill_pct" else "s")
        assert entry["source"] == (
            "program_counter" if name == "compile_cache_fill_pct" else "program_span")
        assert specs[name]["what"]
    # the layers PERF.md section 3 files them under
    assert {n: entries[n]["layer"] for n in TILING} == {
        "setup_launch_s": "process", "setup_build_s": "model",
        "setup_tokenize_s": "input pipeline", "setup_trainer_s": "trainer loop",
        "warmup_join_s": "compile", "setup_first_rounds_s": "trainer loop",
        "setup_profile_s": "device", "setup_unnamed_s": "trainer loop",
    }
    assert {entries[n]["layer"] for n in BESIDE} == {"compile"}


def test_each_reader_on_the_recorded_set_up(manifest, specs):
    """Read off the file (a CPU rehearsal, so seconds of a CPU: the numbers say
    the readers read the right events, nothing about a chip)."""
    ctx = ctx_of("trace_setup.json", manifest)
    got = {name: read(manifest, specs, ctx, name) for name in TILING + BESIDE}
    spans = {}
    for e in ctx["trace"]["traceEvents"]:
        if e["ph"] == "X":
            spans.setdefault(e["name"], []).append(e)

    def s(name):
        return sum(e["dur"] for e in spans[name]) / 1e6

    fence = ctx["window"].first.end_us / 1e6
    assert got["setup_launch_s"] == pytest.approx(
        spans["setup/config"][0]["ts"] / 1e6 - (fence - ctx["quantities"]["setup_s"]))
    assert got["setup_launch_s"] > 1.0  # the harness imported jax before main.run
    assert got["setup_build_s"] == pytest.approx(
        s("setup/config") + s("setup/imports") + s("setup/build_model") + s("setup/load_data"))
    assert got["setup_tokenize_s"] == pytest.approx(s("setup/tokenize"))
    assert got["setup_trainer_s"] == pytest.approx(
        s("setup/trainer_init") - s("setup/tokenize") + s("setup/state_init") + s("setup/seed"))
    assert got["warmup_join_s"] == pytest.approx(s("compile/warmup_join"))
    profile = s("train/profile_start") + s("train/profile_stop")
    assert got["setup_profile_s"] == pytest.approx(s("setup/scope_table") + profile)
    first_block = min(e["ts"] for e in spans["loader/next_block"]) / 1e6
    assert got["setup_first_rounds_s"] == pytest.approx(fence - first_block - profile)
    assert 0 <= got["setup_unnamed_s"] < 0.05  # nothing of this run lies outside a span
    # three threads lowered at once: the union is what one of them took, the sum three times that
    lowered = [e["dur"] / 1e6 for e in spans["compile/lower"]]
    assert len(lowered) == 3
    assert max(lowered) <= got["compile_lower_wall_s"] < 0.5 * sum(lowered)
    main = next(e["tid"] for e in ctx["trace"]["traceEvents"]
                if e["ph"] == "M" and e["args"]["name"] == "MainThread")
    lazy = [e for e in spans["compile/backend"] if e["tid"] == main]
    assert 0 < len(lazy) < len(spans["compile/backend"])  # the warmup threads' do not count
    assert got["lazy_compile_s"] == pytest.approx(sum(e["dur"] for e in lazy) / 1e6)
    assert got["compile_backend_s"] == pytest.approx(6.5)  # a program that failed has none
    assert got["compile_cache_fill_pct"] == pytest.approx(100 * 188 / 192)
    assert sum("set-up" in line for line in ctx["said"]) == 1  # one line a child, not eight


def test_the_eight_tile_setup_s(manifest, specs):
    ctx = ctx_of("trace_setup.json", manifest)
    parts = [read(manifest, specs, ctx, name) for name in TILING]
    assert sum(parts) == pytest.approx(ctx["quantities"]["setup_s"], abs=1e-6)
    # whatever setup_s the harness read: a later launch moves launch and nothing else
    later = ctx_of("trace_setup.json", manifest, quantities={"setup_s": 40.0})
    moved = [read(manifest, specs, later, name) for name in TILING]
    assert sum(moved) == pytest.approx(40.0, abs=1e-6)
    assert moved[1:-1] == parts[1:-1] and moved[-1] == pytest.approx(parts[-1], abs=1e-6)


def test_two_children_sum_like_setup_s(manifest, specs):
    """``run.py`` sums a metric over a cell's children, as it sums ``setup_s``;
    the cache's fill is a share, and its reader makes the sum a mean."""
    cell = manifest.cell("neo27b-l4-dp4")
    assert len(cell["schedules"]) == 2
    children = {}
    for schedule, setup_s in zip(cell["schedules"], (30.0, 45.0)):
        ctx = ctx_of("trace_setup.json", manifest, cell=cell, quantities={"setup_s": setup_s})
        children[schedule["name"]] = {
            "device": {}, "memory_peak_bytes": None, "busy_s": 1.0, "window_s": 1.0,
            "correct": True, "attempted": 1, "failed": 0, "breakdown": {},
            "quantities": ctx["quantities"],
            "layer_metrics": {n: read(manifest, specs, ctx, n) for n in TILING + BESIDE},
        }
    line = bench_run.combine(manifest, cell, children, trace=True)
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert sum(values[n] for n in TILING) == pytest.approx(75.0, abs=1e-6)
    one = next(iter(children.values()))["layer_metrics"]
    assert values["warmup_join_s"] == pytest.approx(2 * one["warmup_join_s"])
    assert values["compile_cache_fill_pct"] == pytest.approx(100 * 188 / 192)


def test_a_program_without_the_set_up_spans_reads_nothing(manifest, specs):
    """Every commit before this one: the recorded trace of PR 22 has
    ``compile/warmup_join`` and the loop's spans, no ``setup/*``. The span
    readers and the gauges' reader return None and do not raise; the
    ``WarmupReport``'s field is as old as the report."""
    ctx = ctx_of("trace_recorded.json", manifest, counters={"train_rounds_total": 45.0})
    for name in TILING + ("compile_lower_wall_s", "lazy_compile_s", "compile_cache_fill_pct"):
        assert read(manifest, specs, ctx, name) is None, name
    assert read(manifest, specs, ctx, "compile_backend_s") == pytest.approx(6.5)
    assert ctx["said"] == []


@pytest.mark.parametrize("counters", [
    {"compile_cache_dir_bytes": 1.0, "compile_cache_max_bytes": None},
    {"compile_cache_dir_bytes": None, "compile_cache_max_bytes": None},
], ids=["no_cap", "no_dir"])
def test_a_cache_without_a_cap_is_0_full_not_absent(manifest, specs, counters):
    """A gauge nobody set reads None in the registry's snapshot. The program
    that declares both gauges always gives the share a reading, so a machine
    that sets no cap (jax never evicts) does not drop the metric from the
    line: the check refuses a line that lacks a metric its cell lists."""
    unset = ctx_of("trace_setup.json", manifest, counters=counters)
    assert read(manifest, specs, unset, "compile_cache_fill_pct") == 0.0


def test_the_union_of_overlapping_intervals():
    union_us = load_module(
        os.path.join(ROOT, "benchmark", "reducers", "span_before_fence.py")).union_us
    # three that overlap, one inside another, one apart
    assert union_us([(0.0, 10.0), (5.0, 12.0), (11.0, 15.0)]) == 15.0
    assert union_us([(0.0, 10.0), (2.0, 3.0), (20.0, 21.0)]) == 11.0
    assert union_us([]) == 0.0
