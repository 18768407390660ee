"""Test environment: force JAX onto 8 virtual CPU devices.

This is the cluster-free SPMD strategy from SURVEY.md §4.2: the reference
could not test its NCCL collectives without GPUs, but JAX lets the whole
mesh/collective stack (psum, psum_scatter, all_gather, shard_map) run on
fake CPU devices, so ACCO's algorithmic semantics are testable in CI.

``JAX_PLATFORMS=cpu`` in the environment is enough here. Setting it
below (for the children tests start) and the ``jax.config`` update (for
this process) are the tests' own forcing, so that the suite also runs
from a shell that did not set it. XLA_FLAGS must be set before the CPU
client spins up.

The persistent compile cache is left alone: it is wherever
``JAX_COMPILATION_CACHE_DIR`` says, if the caller set it, and off for
this process otherwise. Tests that need a cache take the
``compile_cache_dir`` fixture of test_compile_cache.py.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu_aot: compiles for a described TPU in a child process (no "
        "chip needed; 2-10 s a compile, timed on jax 0.9.0); deselect "
        "with -m 'not tpu_aot'",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running test excluded from the tier-1 window "
        "(-m 'not slow'); run explicitly with -m slow",
    )


def _memory_maps() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux: no such limit to watch
        return 0


#: Linux's default ``vm.max_map_count`` is 65530; a test adds 3,000-6,000.
#: Dropping the caches costs the worker its compiled fixtures, so not sooner.
_MAP_BUDGET = 50_000


@pytest.fixture(autouse=True)
def executables_within_the_map_limit():
    """Every compiled CPU executable a worker keeps is a few memory maps,
    a whole model's program about 3,000, and jax's caches keep them all.
    A worker that the scheduler hands the benchmark's reference checks and
    then tests/test_laguna.py's wrong-variant cases (a model each) reaches
    the kernel's limit, and the next compile dies with a segmentation
    fault inside XLA (PR 37: three whole runs of three, always in
    test_every_assignment_to_a_held_expert_is_computed_whatever_the_imbalance).
    So a worker within 15,000 of the limit drops its caches after the test."""
    yield
    if _memory_maps() > _MAP_BUDGET:
        import gc

        jax.clear_caches()  # a collection alone frees none of them
        gc.collect()


@pytest.fixture(scope="session")
def eight_devices():
    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 virtual CPU devices, got {devices}"
    assert devices[0].platform == "cpu"
    return devices


# -- duration recording for the slow-marker audit ----------------------------
# Every call-phase duration is recorded through the telemetry tracer
# (acco_tpu/telemetry, jax-free) as a cat="test" complete event — pytest
# nodeids are the one open span namespace (FREE_CATEGORIES). At session
# end the events are written as outputs/test_trace.json (loadable in
# Perfetto: the suite as a flame chart) AND projected back into
# outputs/test_durations.json via telemetry.test_duration_records, so
# `tools/lint.py --ci` keeps one evidence format for proving that
# anything slower than the threshold carries @pytest.mark.slow.
# Recording must never break a test run: everything is best-effort.

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_test_tracer = None


def _tracer():
    global _test_tracer
    if _test_tracer is None:
        from acco_tpu.telemetry import Tracer

        _test_tracer = Tracer(process_name="pytest", max_events=100_000)
    return _test_tracer


def pytest_runtest_logreport(report):
    if report.when != "call":
        return
    try:
        _tracer().complete_event(
            report.nodeid,
            report.duration * 1e3,
            cat="test",
            args={"slow": "slow" in report.keywords},
        )
    except Exception as exc:  # recording is evidence, not a gate
        print(f"# test-duration recording failed: {exc}")


def pytest_sessionfinish(session, exitstatus):
    if _test_tracer is None:
        return
    try:
        from acco_tpu.analysis.slow_markers import merge_records
        from acco_tpu.telemetry import test_duration_records

        records = test_duration_records(_test_tracer.events())
        if records:
            merge_records(
                os.path.join(_REPO_ROOT, "outputs", "test_durations.json"),
                records,
            )
        _test_tracer.write(
            os.path.join(_REPO_ROOT, "outputs", "test_trace.json")
        )
    except Exception as exc:  # recording is evidence, not a gate
        print(f"# test-duration recording failed: {exc}")
