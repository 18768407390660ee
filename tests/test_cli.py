"""CLI entry-point integration: ``main.py train=... data=synthetic`` runs
end-to-end (SURVEY.md §4.3; reference surface `/root/reference/main.py` +
`README.md:54-81`) and the standalone scripts keep their parity surface.
"""

import os

import numpy as np
import pytest

# Import the entry-point modules before any test chdir()s away from the
# repo root (sys.path[''] resolves against the cwd at import time).
import dl_dataset
import main as main_mod


def _run_main(tmp_path, monkeypatch, overrides):
    monkeypatch.chdir(tmp_path)  # outputs/ land in the tmp dir
    # These mains run IN-PROCESS: the configs' persistent compile cache
    # stays off, so a test run neither writes into the checkout's
    # outputs/compile_cache nor depends on what an earlier run left there.
    return main_mod.main(["train.compile_cache_dir="] + overrides)


@pytest.mark.parametrize("method", ["ddp", "acco"])
def test_main_end_to_end(eight_devices, tmp_path, monkeypatch, method):
    summary = _run_main(
        tmp_path,
        monkeypatch,
        [
            f"train={method}",
            "data=synthetic",
            "model=tiny",
            "data.synthetic_num_docs=64",
            "train.nb_steps_tot=16",
            "train.batch_size=1",
            "train.max_length=16",
            "train.use_mixed_precision=False",
            "train.save=False",
            "train.eval=False",
            "train.warmup=0",
        ],
    )
    assert summary["method"] == method
    assert np.isfinite(summary["final_loss"])
    # Hydra-parity run dir with the resolved config inside.
    out_days = os.listdir(tmp_path / "outputs")
    assert len(out_days) == 1
    run_dirs = os.listdir(tmp_path / "outputs" / out_days[0])
    cfg_path = tmp_path / "outputs" / out_days[0] / run_dirs[0] / "config.yaml"
    assert cfg_path.exists()
    import yaml

    cfg = yaml.safe_load(open(cfg_path))
    assert cfg["train"]["method_name"] == method
    assert cfg["train"]["nb_steps_tot"] == 16


def test_main_tensor_parallel_mesh(eight_devices, tmp_path, monkeypatch):
    """CLI-level tensor parallelism: train.mesh_shape={dp, tp} flows
    through main.py's model construction — including the automatic
    Megatron vocab padding (tiny's odd 257 -> a tp-divisible size) — and
    trains end-to-end on the dp x tp mesh."""
    summary = _run_main(
        tmp_path,
        monkeypatch,
        [
            "train=acco",
            "data=synthetic",
            "model=tiny",
            "data.synthetic_num_docs=64",
            "train.nb_steps_tot=8",
            "train.batch_size=1",
            "train.max_length=16",
            "train.use_mixed_precision=False",
            "train.save=False",
            "train.eval=False",
            "train.warmup=0",
            "train.mesh_shape={dp: 4, tp: 2}",
        ],
    )
    assert summary["method"] == "acco"
    assert np.isfinite(summary["final_loss"])


def test_dl_dataset_pretokenize_then_train(eight_devices, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    out_dir = dl_dataset.main(
        [
            "data=synthetic",
            "model=tiny",
            "train=acco",
            "train.max_length=16",
            "data.synthetic_num_docs=64",
            f"+output_dir={tmp_path}/tok",
        ]
    )
    import datasets as hf_datasets

    ds = hf_datasets.load_from_disk(os.path.join(out_dir, "train"))
    assert "input_ids" in ds.column_names
    assert all(len(r) == 16 for r in ds["input_ids"][:4])


def test_perplexity_eval_compute(eight_devices):
    import jax

    from acco_tpu.data.tokenizer import ByteTokenizer
    from acco_tpu.models import LlamaConfig, LlamaModel
    from perplexity_eval import compute

    cfg = LlamaConfig(
        vocab_size=257, hidden_size=32, intermediate_size=64, num_layers=1,
        num_heads=2, num_kv_heads=2, max_position_embeddings=64,
    )
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    out = compute(
        model, params, ByteTokenizer(),
        ["hello world this is a test", "another longer document goes here"],
        batch_size=2, max_length=32,
    )
    assert len(out["perplexities"]) == 2
    assert np.isfinite(out["mean_perplexity"])
    # random init on a 257-vocab: ppl should be near exp(uniform NLL)
    assert 10 < out["mean_perplexity"] < 5000


def test_launch_scripts_are_valid_bash():
    """The L6 launch layer (launch/tpu_pod.sh, launch/acco.slurm) must at
    least parse — gcloud/sbatch can't run here, but syntax errors in the
    scripts the README tells users to run should fail CI."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for script in ("launch/tpu_pod.sh", "launch/acco.slurm"):
        path = os.path.join(root, script)
        assert os.path.exists(path), script
        proc = subprocess.run(["bash", "-n", path], capture_output=True, text=True)
        assert proc.returncode == 0, f"{script}: {proc.stderr}"


def test_main_pallas_fused_ce(eight_devices, tmp_path, monkeypatch):
    """CLI-level fused_loss='pallas': the VMEM lm-head+CE kernel
    (interpreter mode) carries a real train run end-to-end — the
    tiny128 model config exists exactly for this (hidden % 128 == 0,
    the kernel envelope's smallest CPU-runnable shape)."""
    monkeypatch.setenv("ACCO_FUSED_CE_INTERPRET", "1")
    summary = _run_main(
        tmp_path,
        monkeypatch,
        [
            "train=acco",
            "data=synthetic",
            "model=tiny128",
            "data.synthetic_num_docs=32",
            "train.nb_steps_tot=8",
            "train.batch_size=1",
            "train.max_length=16",
            "train.fused_loss=pallas",
            "train.save=False",
            "train.eval=False",
            "train.warmup=0",
        ],
    )
    assert np.isfinite(summary["final_loss"])
