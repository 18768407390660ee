"""Telemetry subsystem (ISSUE 19, 23): registry, tracer, serve.

Proof obligations, all tier-1 fast:

- the metrics registry is **closed-world** (undeclared names raise; the
  declared surface round-trips through scalar/snapshot/Prometheus);
- the tracer emits a **valid Chrome trace** (nonnegative durations,
  proper per-track nesting — checked by the same ``validate_trace`` the
  smoke run uses), its disabled form records nothing, and a span
  enters the **injected annotation** once (what puts the trainer's
  spans on a ``jax.profiler`` capture's host plane) and carries what
  its block put into the yielded args;
- the serve ``/metrics`` endpoint scrapes as parseable Prometheus
  0.0.4 text with the scheduler's counters in it;
- the **zero-added-syncs contract**: the telemetry package never
  imports jax and carries zero host-lint findings, so
  ``telemetry.enabled=false`` cannot add a device fetch.
"""

from __future__ import annotations

import json
import threading
import urllib.request

import pytest

from acco_tpu.telemetry import (
    DEVICE_SCOPES,
    SPAN_NAMES,
    Tracer,
    UndeclaredMetricError,
    UndeclaredSpanError,
    setup_phases,
    validate_trace,
)
from acco_tpu.telemetry import test_duration_records as duration_records  # noqa: E501  (aliased so pytest does not collect it)
from acco_tpu.telemetry.metrics import DECLARED, MetricsRegistry

# -- registry: closed world ---------------------------------------------------


def _registry() -> MetricsRegistry:
    return MetricsRegistry(DECLARED)


def test_registry_rejects_undeclared_names():
    reg = _registry()
    with pytest.raises(UndeclaredMetricError):
        reg.emit("not_a_declared_metric", 1.0)
    with pytest.raises(UndeclaredMetricError):
        reg.emit_many({"train_loss": 1.0, "nope": 2.0})


def test_counter_accumulates_and_rejects_negative():
    reg = _registry()
    reg.emit("train_rounds_total", 2)
    reg.emit("train_rounds_total", 3)
    assert reg.value("train_rounds_total") == 5
    with pytest.raises(ValueError):
        reg.emit("train_rounds_total", -1)


def test_gauge_last_write_wins_and_unset_reads_none():
    reg = _registry()
    assert reg.scalar("serve_slots_free") is None
    reg.emit("serve_slots_free", 4)
    reg.emit("serve_slots_free", 2)
    assert reg.scalar("serve_slots_free") == 2
    # the snapshot holds every declared name, never-emitted gauges as None
    snap = reg.snapshot()
    assert snap["serve_slots_free"] == 2 and snap["serve_waiting"] is None


def test_histogram_p50_and_prometheus_text():
    reg = _registry()
    for v in (10.0, 20.0, 30.0, 40.0):
        reg.emit("train_log_sync_ms", v)
    p50 = reg.scalar("train_log_sync_ms")
    assert 10.0 <= p50 <= 40.0
    text = reg.to_prometheus_text()
    assert "# TYPE acco_train_log_sync_ms histogram" in text
    assert 'acco_train_log_sync_ms_bucket{le="+Inf"} 4' in text
    assert "acco_train_log_sync_ms_count 4" in text
    # every exposition line is a comment or "name[{labels}] value"
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        assert name and float(value) is not None


def test_every_declared_spec_is_well_formed():
    kinds = {"counter", "gauge", "histogram"}
    names = [s.name for s in DECLARED]
    assert len(names) == len(set(names)), "duplicate metric declared"
    for spec in DECLARED:
        assert spec.kind in kinds, spec
        assert spec.help, f"{spec.name}: missing help text"


# -- tracer: valid Chrome trace ----------------------------------------------


def test_span_names_are_closed_world():
    tr = Tracer()
    with pytest.raises(UndeclaredSpanError):
        tr.complete_event("made/up", 1.0)
    with pytest.raises(UndeclaredSpanError):
        with tr.span("also/made/up"):
            pass
    # the "test" category is the one open namespace
    tr.complete_event("tests/x.py::test_y", 1.0, cat="test")


def test_trace_is_valid_and_nests(tmp_path):
    tr = Tracer(process_name="unit")
    with tr.span("train/round", rounds=1):
        with tr.span("loader/next_block"):
            pass
        tr.complete_event("train/dispatch", 0.01)
    tr.instant("ckpt/snapshot")
    path = tr.write(str(tmp_path / "trace.json"), other_data={"k": "v"})
    with open(path, encoding="utf-8") as f:
        trace = json.load(f)
    assert validate_trace(trace) == []
    assert trace["otherData"]["k"] == "v"
    assert trace["otherData"]["dropped_events"] == 0
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    assert set(names) == {"train/round", "loader/next_block", "train/dispatch"}
    assert all(n in SPAN_NAMES for n in names)


def test_validate_trace_catches_straddle_and_negative_dur():
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "ts": 0, "dur": 100, "pid": 1, "tid": 1},
        {"ph": "X", "name": "b", "ts": 50, "dur": 100, "pid": 1, "tid": 1},
        {"ph": "X", "name": "c", "ts": 0, "dur": -1, "pid": 1, "tid": 1},
    ]}
    problems = validate_trace(bad)
    assert any("straddles" in p for p in problems)
    assert any("negative dur" in p for p in problems)


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("train/round"):
        tr.complete_event("train/dispatch", 1.0)
        tr.instant("train/eval")
    assert tr.events() == []


def test_tracer_bounded_memory_drops_not_grows():
    tr = Tracer(max_events=3)
    for _ in range(10):
        tr.complete_event("train/dispatch", 0.001)
    events = tr.events()
    assert len(events) == 3  # thread-name metadata + 2 complete events
    assert sum(1 for e in events if e["ph"] == "X") == 2
    assert tr.dropped == 8
    assert tr.to_dict()["otherData"]["dropped_events"] == 8


def test_test_duration_records_bridge():
    tr = Tracer()
    tr.complete_event("t.py::fast", 1500.0, cat="test", args={"slow": False})
    tr.complete_event("t.py::slow", 40_000.0, cat="test", args={"slow": True})
    tr.complete_event("train/dispatch", 1.0)  # non-test: excluded
    recs = duration_records(tr.events())
    assert recs == {
        "t.py::fast": {"duration": 1.5, "slow": False},
        "t.py::slow": {"duration": 40.0, "slow": True},
    }


# -- tracer: the injected annotation and the yielded args ---------------------


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts enters and
    exits per name."""

    def __init__(self):
        self.entered, self.exited = [], []

    def __call__(self, name):
        outer = self

        class _Ctx:
            def __enter__(self):
                outer.entered.append(name)

            def __exit__(self, *exc):
                outer.exited.append(name)

        return _Ctx()


@pytest.mark.parametrize("enabled", [True, False])
def test_span_enters_the_injected_annotation_once_per_span(enabled):
    notes = _Annotations()
    tr = Tracer(enabled=enabled, annotate=notes)
    with tr.span("train/round"):
        with tr.span("train/dispatch"):
            pass
    with pytest.raises(RuntimeError):
        with tr.span("loader/next_block"):
            raise RuntimeError("the block failed")
    # complete_event and instant are after-the-fact: never annotated
    tr.complete_event("train/eval", 1.0)
    tr.instant("ckpt/snapshot")
    expected = (
        ["train/round", "train/dispatch", "loader/next_block"]
        if enabled
        else []
    )
    assert notes.entered == expected
    assert sorted(notes.exited) == sorted(expected)
    spans = [e["name"] for e in tr.events() if e.get("ph") == "X"]
    assert sorted(spans) == sorted(expected + (["train/eval"] if enabled else []))


def test_span_without_annotation_factory_still_records():
    tr = Tracer()
    with tr.span("train/dispatch"):
        pass
    assert [e["name"] for e in tr.events() if e["ph"] == "X"] == ["train/dispatch"]


def test_span_yields_args_the_block_may_fill():
    tr = Tracer()
    with tr.span("train/log_boundary_sync", round=7) as fence:
        fence.update(loss=2.5, committed=56.0)
    with tr.span("train/log_boundary_host") as host:
        assert host == {}
    events = {e["name"]: e for e in tr.events() if e["ph"] == "X"}
    assert events["train/log_boundary_sync"]["args"] == {
        "round": 7, "loss": 2.5, "committed": 56.0,
    }
    assert "args" not in events["train/log_boundary_host"]
    # a disabled tracer still hands the block a dict to fill
    off = Tracer(enabled=False)
    with off.span("train/log_boundary_sync") as fence:
        fence["loss"] = 1.0
    assert off.events() == []


def test_thread_name_event_counts_against_the_bound():
    """A new thread's name event used to land past ``max_events``."""
    tr = Tracer(max_events=2)
    tr.complete_event("train/dispatch", 0.001)  # name event + this one
    assert len(tr.events()) == 2

    def other_thread():
        tr.complete_event("ckpt/commit", 0.001)

    t = threading.Thread(target=other_thread)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert len(tr.events()) == 2 and tr.dropped == 1
    # exactly one slot left: the thread's name takes it, its event drops
    tr = Tracer(max_events=1)
    tr.complete_event("train/dispatch", 0.001)
    assert [e["ph"] for e in tr.events()] == ["M"] and tr.dropped == 1


# -- one clock from the launch (ISSUE 37) --------------------------------------


def test_events_recorded_before_the_late_facts_survive_them():
    """main.run makes the tracer before rank, telemetry.enabled and the
    annotation factory are known; telling it later loses nothing and the
    early spans stand at ts >= 0 on the clock the loop's spans use."""
    ann = _Annotations()
    tr = Tracer()
    with tr.span("setup/config", cat="setup"):
        pass
    with tr.span("setup/trainer_init", cat="setup") as init:
        tr.configure(enabled=True, process_name="acco-ddp", max_events=64,
                     annotate=ann)
        with tr.span("setup/tokenize", cat="setup", rows=3):
            pass
        init["method"] = "ddp"
    with tr.span("train/dispatch"):
        pass
    trace = tr.to_dict()
    assert validate_trace(trace) == []
    assert trace["otherData"]["process"] == "acco-ddp" and tr.max_events == 64
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in spans] == [
        "setup/config", "setup/tokenize", "setup/trainer_init", "train/dispatch",
    ]
    assert all(e["ts"] >= 0 for e in spans)
    assert spans[0]["ts"] + spans[0]["dur"] <= spans[2]["ts"] + 0.2
    assert spans[2]["args"] == {"method": "ddp"}
    # only the spans that began after the factory arrived entered it
    assert ann.entered == ["setup/tokenize", "train/dispatch"]
    assert setup_phases(trace["traceEvents"]).keys() == {
        "config", "trainer_init", "tokenize",
    }


@pytest.mark.parametrize("while_open", [True, False])
def test_a_tracer_told_it_is_disabled_holds_nothing(while_open):
    """telemetry.enabled=false, or a rank that writes no trace: what was
    recorded since the launch goes, and so does a span that was open when
    the word came (setup/trainer_init is)."""
    tr = Tracer()
    with tr.span("setup/config", cat="setup"):
        pass
    if while_open:
        with tr.span("setup/trainer_init", cat="setup"):
            tr.configure(enabled=False)
    else:
        tr.configure(enabled=False)
    tr.complete_event("train/dispatch", 0.01)
    with tr.span("setup/state_init", cat="setup"):
        pass
    assert tr.events() == [] and tr.dropped == 0
    assert setup_phases(tr.events()) == {}


def test_a_backend_compile_event_lands_on_the_compiling_threads_track():
    """compile/cache.py's duration listener writes one compile/backend span
    per jax backend-compile event: duration from the event, end = now,
    on the track of whichever thread compiled."""
    from acco_tpu.compile import cache

    tr = Tracer()
    cache.trace_compiles(tr)
    try:
        def warmup_thread():
            with tr.span("compile/compile", cat="compile", program="round"):
                cache._on_duration(cache._BACKEND_EVENT, 0.0)

        t = threading.Thread(target=warmup_thread, name="acco-compile_0")
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with tr.span("setup/state_init", cat="setup"):
            cache._on_duration(cache._BACKEND_EVENT, 0.0)  # a lazy compile
        cache._on_duration("/jax/some/other_duration", 1.0)
    finally:
        cache.trace_compiles(None)
    cache._on_duration(cache._BACKEND_EVENT, 0.0)  # nobody listens any more
    trace = tr.to_dict()
    assert validate_trace(trace) == []
    track = {e["tid"]: e["args"]["name"] for e in trace["traceEvents"] if e["ph"] == "M"}
    backend = [e for e in trace["traceEvents"] if e.get("name") == "compile/backend"]
    assert sorted(track[e["tid"]] for e in backend) == ["MainThread", "acco-compile_0"]
    assert all(e["cat"] == "compile" and e["ts"] >= 0 for e in backend)


def test_a_compile_left_behind_by_an_earlier_run_stays_on_its_own_track():
    """An earlier run's abandoned warmup fires its backend event into the
    new run's tracer: the event is cut at the clock's zero, not pushed
    past now, and the dead thread's ident, handed to a new thread, does
    not put the two on one track."""
    from acco_tpu.compile import cache

    tr = Tracer()
    cache.trace_compiles(tr)
    try:
        cache._on_duration(cache._BACKEND_EVENT, 5.0)  # began 5 s ago: before zero
    finally:
        cache.trace_compiles(None)
    (ev,) = [e for e in tr.events() if e["ph"] == "X"]
    assert ev["ts"] == 0.0 and ev["dur"] <= tr.now_us()
    # a thread object the tracer has not seen, under an ident it has
    tr._tids[threading.get_ident()] = (0, threading.Thread())
    tr.complete_event("train/dispatch", 0.0)
    tids = [e["tid"] for e in tr.events() if e["ph"] == "X"]
    assert tids == [0, 1] and validate_trace(tr.to_dict()) == []


def test_device_scopes_are_declared_once_and_distinct():
    assert len(DEVICE_SCOPES) == len(set(DEVICE_SCOPES))
    assert all(isinstance(s, str) and "/" in s for s in DEVICE_SCOPES)
    assert not set(DEVICE_SCOPES) & SPAN_NAMES


# -- serve /metrics ----------------------------------------------------------


class _IdTokenizer:
    eos_token_id = 0

    def __call__(self, text, **kw):
        return {"input_ids": [ord(c) % 32 for c in text]}

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


@pytest.fixture
def stub_server():
    from acco_tpu.serve.engine import StubEngine
    from acco_tpu.serve.scheduler import ContinuousBatchingScheduler
    from acco_tpu.serve.server import ServingLoop, serve_http
    from acco_tpu.telemetry import REGISTRY

    REGISTRY.reset()
    eng = StubEngine(max_slots=2, num_pages=32)
    sched = ContinuousBatchingScheduler(eng, tracer=Tracer())
    loop = ServingLoop(sched).start()
    httpd = serve_http(loop, _IdTokenizer(), host="127.0.0.1", port=0)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        yield httpd.server_address[1], sched
    finally:
        httpd.shutdown()
        httpd.server_close()
        loop.stop()
        REGISTRY.reset()


def test_serve_metrics_scrape_parses(stub_server):
    port, sched = stub_server
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps({"tokens": [1, 2], "max_new_tokens": 3}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.status == 200
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ) as resp:
        assert resp.status == 200
        assert "text/plain" in resp.headers["Content-Type"]
        text = resp.read().decode()
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        samples[name] = float(value)
    assert samples["acco_serve_requests_total"] == 1.0
    assert samples["acco_serve_completed_total"] == 1.0
    assert samples["acco_serve_tokens_total"] == 3.0
    # latency histograms observed at least the one request
    assert samples["acco_serve_request_latency_ms_count"] >= 1.0
    assert samples["acco_serve_ttft_ms_count"] >= 1.0
    # the scheduler's tracer saw the request's spans
    names = {e["name"] for e in sched.tracer.events() if e.get("ph") == "X"}
    assert {"serve/prefill", "serve/request"} <= names


# -- zero-added-syncs contract -----------------------------------------------


def test_telemetry_package_never_imports_jax():
    import ast
    import glob
    import os

    pkg = os.path.dirname(
        os.path.abspath(__import__("acco_tpu.telemetry", fromlist=["x"]).__file__)
    )
    files = glob.glob(os.path.join(pkg, "*.py"))
    assert files
    for path in files:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            for mod in mods:
                assert not (mod == "jax" or mod.startswith("jax.")), (
                    f"{path}: the telemetry package is jax-free by "
                    "contract — a jax import could add device syncs"
                )


def test_telemetry_package_is_host_lint_clean():
    """The sync gate: zero host-lint findings (no host-sync-in-loop, no
    unjoined threads) across the telemetry sources — with no jax import
    possible (above), telemetry.enabled=false adds zero device syncs."""
    import os

    from acco_tpu.analysis.host_lint import lint_paths

    pkg = os.path.dirname(
        os.path.abspath(__import__("acco_tpu.telemetry", fromlist=["x"]).__file__)
    )
    assert lint_paths([pkg]) == []
