"""``benchmark/run.py`` as a command: the rehearsal end to end, and the ways
in which a run must end with no result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from _paths import ROOT

RUN = os.path.join(ROOT, "benchmark", "run.py")


def run(*argv, cwd=ROOT, script=RUN, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one CPU device, whatever the test session forced for itself
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return subprocess.run([sys.executable, script, *argv], cwd=cwd, env=env, timeout=timeout,
                          capture_output=True, text=True)


def result_lines(stdout: str) -> list:
    """Lines of standard output that are a result: a JSON object with the
    contract's keys."""
    found = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if {"correct", "metrics", "device"} <= set(obj):
                found.append(obj)
    return found


def test_rehearsal_runs_end_to_end_and_prints_no_result_line():
    """The whole path at a tiny size on the CPU: ``main.run`` with a printed
    command line, the sampler's stop through the preemption path, the window
    from the program's trace, the profiler trace reduced, the reference
    check. A rehearsal never prints the result line."""
    done = run("--workload", "neo125m-ddp-1chip", "--seed", "5", "--seconds", "1",
               "--trace", "1", "--rehearse")
    out = done.stdout
    assert done.returncode == 0, out[-3000:] + done.stderr[-3000:]
    assert "command line: python main.py train=ddp data=synthetic" in out
    assert "seed=5 data.synthetic_seed=5" in out and "train.profile_steps=" in out
    assert "window of" in out and "NOT CORRECT" not in out
    assert "float32 reference" in out and ": agree" in out
    assert "rehearsal passed" in out and "no result line" in out
    assert result_lines(out) == []
    would = json.loads(out.split("what the line would hold: ", 1)[1].splitlines()[0])
    # spans, the warmup report and the profiler's trace were all read
    assert {"dispatch_ms", "loader_wait_ms", "log_sync_ms", "compile_lower_wall_s",
            "round_device_ms", "device_idle_pct"} <= set(would)
    assert "mfu_pct" not in would  # no peak on record for a CPU: no share of it


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_measurement_path_refuses_a_cpu(trace):
    done = run("--workload", "neo125m-ddp-1chip", "--seed", "1", "--seconds", "1",
               "--trace", trace, timeout=120)
    assert done.returncode != 0
    assert "no TPU" in done.stdout
    assert result_lines(done.stdout) == []


def test_unknown_cell_fails_before_any_child():
    done = run("--workload", "no-such-cell", "--seconds", "1", timeout=60)
    assert done.returncode != 0 and "no-such-cell" in done.stdout
    assert result_lines(done.stdout) == []


def test_a_breach_of_the_rule_is_met_before_any_child(tmp_path):
    """A cell listed under a metric whose feature its configuration does not
    run: the parent says so in a sentence and starts no child, where the driver
    would have met a line that lacks the metric."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("main.py", "acco_tpu", "config"):  # the program, as it stands
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    next(m for m in data["per_layer"] if m["name"] == "moe_held_share_pct")["workloads"].append(
        "neo125m-ddp-1chip")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    done = run("--workload", "neo125m-acco-1chip", "--seed", "1", "--seconds", "1", "--rehearse",
               cwd=str(tmp_path), script=str(tmp_path / "benchmark" / "run.py"), timeout=60)
    assert done.returncode != 0 and "--- schedule" not in done.stdout
    assert ("R1: cell `neo125m-ddp-1chip` does not run `held_experts`, which `moe_held_share_pct` needs: "
            "take it out of that metric's `workloads` in BENCHMARK.json") in done.stdout
    assert result_lines(done.stdout) == []


def test_a_directory_with_the_benchmark_alone_fails(tmp_path):
    """``BENCHMARK.json`` and the files under ``paths`` and nothing else of
    the repo: the benchmark measures the repo, not itself."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(ROOT, "tests", "benchmark"), tmp_path / "tests" / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    done = run("--workload", "neo125m-ddp-1chip", "--seed", "1", "--seconds", "1",
               cwd=str(tmp_path), script=str(tmp_path / "benchmark" / "run.py"), timeout=60)
    assert done.returncode != 0
    assert "main.py" in done.stdout
    assert result_lines(done.stdout) == []
