"""chip_smoke.py off the chip: it refuses, fast and silently as far as
results go; and the smoke's phase functions, called here at a
tiny size, do what they will do on the chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (jax-free at import, like its parent process)


def _run(script_dir, script, *args, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(script_dir, script), *args],
        capture_output=True, text=True, timeout=120, env=env, cwd=script_dir,
    )


def _no_result(stdout: str) -> None:
    """Neither the smoke's success line nor a benchmark's metric line."""
    for line in stdout.splitlines():
        try:
            record = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(record, dict) and (record.get("ok") or "metric" in record)), line
    assert '"ok": true' not in stdout


def test_no_tpu_is_a_failure_without_a_result():
    proc = _run(REPO, "chip_smoke.py")
    assert proc.returncode != 0
    _no_result(proc.stdout)
    assert "TPU" in proc.stdout + proc.stderr


def test_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run(str(tmp_path), "chip_smoke.py")
    assert proc.returncode != 0
    _no_result(proc.stdout)
    assert "not beside chip_smoke.py" in proc.stdout


def test_smoke_refuses_interpret_mode():
    proc = _run(REPO, "chip_smoke.py", ACCO_FUSED_ATTN_INTERPRET="1")
    assert proc.returncode != 0
    _no_result(proc.stdout)
    assert "interpret-mode switch" in proc.stdout


def test_rehearsal_never_prints_the_success_line(monkeypatch, capsys):
    """``--rehearse`` may pass, on any device, and still is no chip run."""
    monkeypatch.setattr(
        chip_smoke, "run_child",
        lambda job, args, deadline: {
            "first_loss": 5.0, "last_loss": 4.0, "setup_s": 1.0, "cache_misses": 0,
            "cache_hits": 3, "compile_ms": {"seed": 1, "round_even": 1, "round_odd": 1},
            "cache_dir": "x", "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        },
    )
    assert chip_smoke.main(["--rehearse"]) == 0
    _no_result(capsys.readouterr().out)


def test_device_gate_wants_a_tpu_and_the_right_count(eight_devices):
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.device_gate(chips=1, rehearse=False)
    with pytest.raises(chip_smoke.SmokeFailure, match="expected 4"):
        chip_smoke.device_gate(chips=4, rehearse=True)


def test_agree_holds_two_runs_to_the_printed_tolerance():
    a = {"first_loss": 10.0, "last_loss": 4.0}
    chip_smoke.agree("same", a, {"first_loss": 10.0 + 1e-4, "last_loss": 4.0 - 1e-3})
    with pytest.raises(chip_smoke.SmokeFailure, match="last_loss"):
        chip_smoke.agree("apart", a, {"first_loss": 10.0, "last_loss": 4.1})


def test_mosaic_kernels_reads_names_off_custom_calls_only():
    hlo = "\n".join([
        '%acco_fused_attn_fwd.1 = bf16[8] custom-call(%a), custom_call_target="tpu_custom_call"',
        '%x = f32[8] fusion(%b), metadata={op_name="jit(f)/acco_banded_attn_fwd/mul"}',
    ])
    assert chip_smoke.mosaic_kernels(hlo) == ["acco_fused_attn_fwd"]


def test_kernels_job_at_a_tiny_size(monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    monkeypatch.setenv("ACCO_FUSED_ATTN_INTERPRET", "1")
    report = chip_smoke.kernels_job(chip_smoke.rehearsal_size(1), rehearse=True)
    assert set(report["kernel_errors"]) == {
        "fused (global)", "banded (window 64, steps from the shape) at L=128 D=64",
    }


def test_train_job_at_a_tiny_size(eight_devices, monkeypatch, tmp_path, capsys):
    """The trainer's own loop through ``main.run`` on the eight virtual
    devices, with every check of a ``--chips`` job: losses, checkpoint,
    compile records, optimizer shards, collective census."""
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    monkeypatch.chdir(tmp_path)
    size = chip_smoke.rehearsal_size(8)
    argv = chip_smoke.train_overrides(
        size, 8, "ddp", "ddp",
        # einsum attention compiles fastest here; and a test run neither
        # writes into the checkout's compile cache nor reads it
        ("train.use_pallas_attention=false", "train.compile_cache_dir="),
    )
    result = chip_smoke.train_job(
        argv, rehearse=True, min_rounds=size["rounds"], expect_kernels=False,
        expect_shards=8,
    )
    assert result["rounds"] == size["rounds"]
    assert result["last_loss"] < result["first_loss"]
    assert result["checkpoint_bytes"] > 0
    printed = capsys.readouterr().out
    assert "8 addressable shards on devices [0, 1, 2, 3, 4, 5, 6, 7]" in printed
    assert "collective census of step: 2 large collectives" in printed
    # the job leaves no checkpoint behind
    assert not os.path.exists(os.path.join(tmp_path, "runs", "ddp", "checkpoints"))
