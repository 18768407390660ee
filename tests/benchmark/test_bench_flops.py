"""The benchmark's FLOP and byte counts against counts worked by hand."""

import json
import os

import pytest

from _paths import ROOT

from benchmark.harness import flops
from benchmark.harness.manifest import Manifest, ManifestError, family_module, load_peaks
from benchmark.reference import gpt_neo_flops


def model(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name, "model.json")) as f:
        return json.load(f)


def test_mean_keys_per_query():
    assert flops.mean_keys_per_query(1024, 0) == 512.5  # (1 + ... + 1024) / 1024
    # window 256: rows 0..255 see 1..256 keys (32896), the other 768 rows see 256
    assert flops.mean_keys_per_query(1024, 256) == (32896 + 768 * 256) / 1024 == 224.125
    assert flops.mean_keys_per_query(2048, 256) == (32896 + 1792 * 256) / 2048 == 240.0625
    assert flops.mean_keys_per_query(128, 256) == 64.5  # a window wider than the sequence


# By hand, forward FLOPs per token, then x 3:
#   a layer's weight matmuls: 2 x (3 D^2 + D^2 + 2 D F) = 2 x 12 D^2 at F = 4 D
#   attention: 4 x D x mean keys (QK^T and PV, 2 D keys each)
#   head: 2 D V
HAND = {
    # D=768: 24 D^2 = 14,155,776 a layer x 12; attention 6 global x 4*768*512.5
    # + 6 local x 4*768*224.125; head 2*768*50257
    ("gpt-neo-125m", 1024): 3 * (
        12 * 14_155_776 + 6 * 1_574_400 + 6 * 688_512 + 77_194_752
    ),
    # D=2560: 24 D^2 = 157,286,400 a layer x 4; attention 2 global x 4*2560*1024.5
    # + 2 local x 4*2560*240.0625; head 2*2560*50257
    ("gpt-neo-2.7b-l4", 2048): 3 * (
        4 * 157_286_400 + 2 * 10_490_880 + 2 * 2_458_240 + 257_315_840
    ),
}


@pytest.mark.parametrize("name,seq", sorted(HAND))
def test_train_flops_per_token(name, seq):
    assert gpt_neo_flops.train_flops_per_token(model(name), seq) == HAND[name, seq]
    # and by the name the configuration's own file gives, as the harness finds it
    config = Manifest().config(name)
    assert family_module(config, "flops").train_flops_per_token(config["model"], seq) == HAND[name, seq]


def test_the_programs_count_is_the_full_block_and_larger():
    """``acco_tpu/utils/flops.py`` counts the whole [L, L] block for causal
    and window layers: the reason the benchmark keeps its own."""
    full_block = 3 * (12 * 14_155_776 + 12 * 4 * 1024 * 768 + 77_194_752)
    assert full_block > HAND["gpt-neo-125m", 1024]


def test_a_configuration_that_names_no_count_is_an_error():
    with pytest.raises(ManifestError, match="flops"):
        family_module({"name": "mamba", "meta": {}, "root": ROOT}, "flops")


def test_attention_kernel_work():
    cfg = model("gpt-neo-125m")
    # B=8, L=1024: 12 D keys per token; bytes: 12 tensors of B L D bf16 a layer
    f, b = gpt_neo_flops.attention_kernel_work(cfg, 1024, 8, {"global"})
    assert f == 6 * 12 * 768 * 512.5 * 8192
    assert b == 6 * 12 * 8192 * 768 * 2
    f2, b2 = gpt_neo_flops.attention_kernel_work(cfg, 1024, 8, {"global", "local"})
    assert f2 == f + 6 * 12 * 768 * 224.125 * 8192 and b2 == 2 * b
    assert gpt_neo_flops.attention_kernel_work(cfg, 1024, 8, set()) == (0.0, 0.0)


def test_peaks_and_roofline():
    peaks = load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ManifestError):
        load_peaks("cpu")
    with pytest.raises(ManifestError):
        load_peaks("_comment")
    assert flops.roofline(197e12, 1.0, peaks) == (1.0, "compute")
    assert flops.roofline(1.0, 819e9, peaks) == (1.0, "memory")
    # 80,000 tokens/s at 782 MFLOP/token on a 197 TFLOP/s chip
    assert flops.mfu_pct(80_000, HAND["gpt-neo-125m", 1024], peaks) == pytest.approx(
        100 * 80_000 * HAND["gpt-neo-125m", 1024] / 197e12
    )
