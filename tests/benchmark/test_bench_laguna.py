"""The Laguna configuration (one chip's share of a 32-chip layer), its count and
its reference, as the harness finds them: the files against the source's
numbers written out here, the parameter count and the FLOP count against hand
counts, the cell and its metrics, and the reference check on a tiny Laguna
brought as files only. The program against the reference leaf by leaf, in
float32, and the shares adding up to the uncut layer are ``tests/test_laguna.py``."""

import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from _paths import ROOT

from benchmark.harness import refcheck
from benchmark.harness.manifest import Manifest, load_module, load_peaks

PERIOD = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"]
# poolside/Laguna-S-2.1 config.json (the catalog row's ``config``)
PUBLISHED = dict(
    model_type="laguna", vocab_size=100352, hidden_size=3072, intermediate_size=12288,
    num_hidden_layers=48, num_attention_heads=48, num_key_value_heads=8, head_dim=128,
    max_position_embeddings=1048576, attention_bias=False, rms_norm_eps=1e-06,
    num_experts=256, num_experts_per_tok=10, moe_intermediate_size=1024,
    shared_expert_intermediate_size=1024, norm_topk_prob=True, decoder_sparse_step=1,
    mlp_only_layers=[0], tie_word_embeddings=False, gating="per-head", sliding_window=512,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
            "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
    },
    layer_types=PERIOD * 12, moe_apply_router_weight_on_input=False,
    mlp_layer_types=["dense"] + ["sparse"] * 47, gating_types=["per_head"] * 48,
    moe_routed_scaling_factor=2.5, num_attention_heads_per_layer=[48, 72, 72, 72] * 12,
    moe_router_logit_softcapping=0,
)
# the chip's share: the two published keys that change, and the repo's own keys
HELD = dict(vocab_size=12544, num_experts=8)
OWN = dict(num_layers=5, num_routed_experts=256, first_held_expert=0, kv_groups_held=1)
ASSUMED = dict(initializer_range=0.02, router_aux_loss_coef=0.0, router_z_loss_coef=0.0)

D, DH = 3072, 128


def attention_parameters(query_heads: int, kv_heads: int) -> int:
    return 2 * D * query_heads * DH + 2 * D * kv_heads * DH + query_heads * D  # q, o; k, v; the gate


def parameters(layers: int, experts: int, kv_heads: int, vocab: int) -> int:
    """Of the first ``layers`` layers with ``experts`` routed experts and
    ``kv_heads`` of the 8 K/V heads (each with its query group) held."""
    total = 2 * vocab * D + D  # untied embedding and head, final norm
    for i in range(layers):
        heads = (48 if i % 4 == 0 else 72) * kv_heads // 8
        total += attention_parameters(heads, kv_heads) + 2 * D  # two norms
        if i == 0:
            total += 3 * D * 12288
        else:
            total += 256 * D + 3 * D * 1024 + experts * 3 * D * 1024  # router, shared, routed
    return total


@pytest.fixture(scope="module")
def config():
    return Manifest().config("laguna-s-2.1-l5")


def test_the_benchmarks_file_holds_what_the_source_publishes(config):
    model = config["model"]
    changed = {k: (PUBLISHED[k], model[k]) for k in PUBLISHED if model[k] != PUBLISHED[k]}
    assert changed == {k: (PUBLISHED[k], held) for k, held in HELD.items()}
    assert {k: model[k] for k in OWN} == OWN and {k: model[k] for k in ASSUMED} == ASSUMED
    reduced = ["num_layers", "num_experts", "kv_groups_held", "vocab_size"]
    assert config["entry"]["reduced"] == config["meta"]["reduced"] == reduced
    assert set(config["meta"]["reduced_detail"]) == set(reduced)
    assert config["entry"]["source"] == config["meta"]["source"]
    assert "poolside/Laguna-S-2.1/blob/main/config.json" in config["entry"]["source"]
    assert set(config["meta"]["assumed"]) == set(config["meta"]["assumed_detail"])
    assert {"no_qk_norm", "router_score_function", "attention_gate", "shared_expert_ungated",
            "router_aux_loss_coef", "initializer_range", "bos_eos_token_id",
            "reference_check_router_scale", "reference_check_qk_scale"} <= set(config["meta"]["assumed"])
    assert "32 chips" in config["meta"]["stands_for"]
    # ISSUE 32's arithmetic, per layer at the 8-way head share
    assert attention_parameters(6, 1) == 5_523_456 and attention_parameters(9, 1) == 7_891_968
    assert 256 * D == 786_432 and 3 * D * 1024 == 9_437_184 and 8 * 3 * D * 1024 == 75_497_472
    assert 3 * D * 12288 == 113_246_208 and 2 * 12544 * D == 77_070_336
    assert parameters(1, 8, 1, 0) - D == 118_775_808  # layer 0
    assert parameters(2, 8, 1, 0) - parameters(1, 8, 1, 0) == 93_619_200  # a window sparse layer
    assert parameters(5, 8, 1, 0) - parameters(4, 8, 1, 0) == 91_250_688  # the full sparse layer
    assert config["meta"]["param_count"] == parameters(5, 8, 1, 12544) == 567_957_504
    # what does not fit at 22.06 B a parameter: the same five layers with every head kept
    assert parameters(5, 8, 8, 12544) == 811_017_216


def test_the_users_file_holds_the_source_at_full_depth():
    with open(os.path.join(ROOT, "config", "model", "laguna-s-2.1.json")) as f:
        model = json.load(f)
    assert {k: model[k] for k in PUBLISHED} == PUBLISHED
    assert {k: model[k] for k in ASSUMED} == ASSUMED
    assert not set(OWN) & set(model)


@pytest.mark.parametrize("path, held", [
    ("benchmark/configs/laguna-s-2.1-l5/model.json", (5, 8, 1, 12544)),
    ("config/model/laguna-s-2.1.json", (48, 256, 8, 100352)),
])
def test_the_program_builds_what_the_file_says(path, held):
    from acco_tpu.models.registry import build_model

    model = build_model({"config_path": path}, repo_root=ROOT)
    cfg = model.config
    layers, experts, kv_heads, vocab = held
    assert (cfg.num_layers, cfg.num_experts, cfg.kv_heads_here, cfg.vocab_size) == held
    assert (cfg.num_kv_heads, cfg.head_dim, cfg.num_experts_per_tok, cfg.sliding_window) == (8, 128, 10, 512)
    assert (cfg.norm_topk_prob, cfg.moe_routed_scaling_factor, cfg.gating) == (True, 2.5, "per-head")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == parameters(*held)
    runs = shapes["layers"]
    assert len(runs) == len(cfg.layer_runs()) and "router" not in runs[0]
    assert runs[0]["w_gate"].shape == (1, D, 12288)
    assert runs[1]["wq"].shape == (3, D, 9 * kv_heads * DH) and runs[1]["wk"].shape == (3, D, kv_heads * DH)
    assert runs[1]["attn_gate"].shape == (3, 9 * kv_heads, D)
    assert runs[2]["wq"].shape == (1, D, 6 * kv_heads * DH)
    assert runs[2]["router"].shape == (1, 256, D) and runs[2]["w_down"].shape == (1, experts, 1024, D)
    assert runs[2]["shared_up"].shape == (1, D, 1024)


def test_the_count_against_a_hand_count(config):
    flops = load_module(os.path.join(ROOT, config["meta"]["flops"]))
    model = config["model"]
    L = 8192
    head = 2 * D * 12544  # 77,070,336
    full = 2 * D * DH * (2 * 6 + 2) + 2 * D * 6 + 4 * 6 * DH * (L + 1) / 2  # 11,010,048 + 36,864 + 12,584,448
    keys = (512 * 513 / 2 + (L - 512) * 512) / L  # 496.03125 keys a query under the window
    window = 2 * D * DH * (2 * 9 + 2) + 2 * D * 9 + 4 * 9 * DH * keys  # 15,728,640 + 55,296 + 2,285,712
    dense = 2 * 3 * D * 12288  # 226,492,416
    routed = 2 * 3 * D * 1024 * 10 * 8 / 256  # 5,898,240: 10 x 8 / 256 of an expert a token
    sparse = 2 * D * 256 + 2 * 3 * D * 1024 + routed  # router 1,572,864 + shared 18,874,368 + routed
    assert (full, keys, window, sparse) == (23_631_360.0, 496.03125, 18_069_648.0, 26_345_472.0)
    forward = head + (full + dense) + 3 * (window + sparse) + (full + sparse)
    assert flops.train_flops_per_token(model, L) == 3.0 * forward == 1_531_248_912.0
    # 12.5 TFLOP a round of 8192 tokens (ISSUE 32 expected 12-13)
    assert 3.0 * forward * L == pytest.approx(12.54e12, rel=1e-3)
    assert dense / forward == pytest.approx(0.4437, abs=1e-4)  # layer 0's MLP is the largest term
    # the published file counts every expert, head and row held: 10 whole experts a token
    published = {k: v for k, v in model.items() if k not in OWN} | {"vocab_size": 100352, "num_experts": 256}
    whole_sparse = 2 * D * 256 + 2 * 3 * D * 1024 * 11
    whole = lambda heads, keys: 2 * D * DH * (2 * heads + 16) + 2 * D * heads + 4 * heads * DH * keys
    assert flops.train_flops_per_token(published, L) == 3.0 * (
        2 * D * 100352 + 12 * whole(48, (L + 1) / 2) + 36 * whole(72, keys) + dense + 47 * whole_sparse)
    # the kernels: 12 x heads x head_dim x keys a query and token; q-sized tensors 7 times, K/V-sized 5
    assert flops.attention_kernel_work(model, L, 1, {"global"}) == (
        2 * 12 * 6 * DH * (L + 1) / 2 * L, 2 * (7 * 6 + 5) * DH * L * 2)
    assert flops.attention_kernel_work(model, L, 1, {"local"}) == (
        3 * 12 * 9 * DH * keys * L, 3 * (7 * 9 + 5) * DH * L * 2)
    both = flops.attention_kernel_work(model, L, 1, {"global", "local"})
    assert both[0] == sum(flops.attention_kernel_work(model, L, 1, {k})[0] for k in ("global", "local"))
    # the held experts: 2,560 expected rows a layer, four sparse layers, 8 experts' weights
    rows = L * 10 * 8 / 256
    assert rows == 2560 == flops.expected_expert_rows(model, L, 1)
    assert flops.expert_matmul_work(model, L, 1) == (
        4 * 3 * 2 * rows * 3 * D * 1024, 4 * 9 * (rows * (D + 1024) + 8 * D * 1024) * 2)
    assert flops.shared_expert_work(model, L, 1) == (
        4 * 3 * 2 * L * 3 * D * 1024, 4 * 9 * (L * (D + 1024) + D * 1024) * 2)
    from benchmark.harness.flops import roofline

    peaks = load_peaks("TPU v5 lite")
    # 320 rows an expert: the weights are the traffic, and the bound
    assert roofline(*flops.expert_matmul_work(model, L, 1), peaks)[1] == "memory"
    assert roofline(*flops.shared_expert_work(model, L, 1), peaks)[1] == "compute"


def test_the_cell_is_what_the_issue_defined():
    m = Manifest()
    cell = m.cell("laguna-l5-acco-1chip")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("laguna-s-2.1-l5", "acco-seq8192-bs1", 1)
    assert (cell["seq_len"], cell["batch_per_chip"]) == (8192, 1)
    assert "train.remat=dots" in cell["overrides"] and "train.n_grad_accumulation=1" in cell["overrides"]
    # the byte tokenizer's rows, as every cell (the harness passes model.tokenizer=byte): no override of it
    assert not any(o.startswith(("model.tokenizer", "train.moe", "model.")) for o in cell["overrides"])
    assert cell["schedules"] == [{"name": "main", "overrides": ["train=acco"], "reference_check": True}]
    # the issue's window, from round 20; about 100 rounds of 0.19 s follow in 20 s (PERF.md section 7 on
    # what the router does from round 90)
    assert (cell["warmup_rounds"], cell["ref_round"], cell["trace_rounds"]) == (20, 100, 10)
    assert len(cell["why"]) <= 200 and "320 rows" in cell["why"] and "5 of 48" in cell["why"]
    names = {s["name"] for s in m.layer_metrics("laguna-l5-acco-1chip")}
    assert {"moe_router_ms", "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
            "moe_shared_ms", "moe_shared_roofline", "moe_held_share_pct", "attn_kernel_ms",
            "attn_kernel_roofline", "flash_attn_kernel_ms", "flash_attn_kernel_roofline",
            "block_ms", "mfu_pct"} <= names
    # what the configuration says it runs: both kinds of kernel, a chip's share of the experts beside
    # a shared one. Which cells list what follows from that (tests/benchmark/test_bench_rules.py).
    assert m.features("laguna-l5-acco-1chip") == {
        "own_attention_kernels", "stock_flash_kernels", "experts", "shared_expert", "held_experts"}
    assert {s["needs"] for s in m.layer_metrics("laguna-l5-acco-1chip") if "needs" in s} == m.features(
        "laguna-l5-acco-1chip")


def test_the_new_metrics_read_what_the_program_writes(config):
    """The shared expert's scope has one owner among the scope metrics and its
    roofline reads the same ops; the gauge is read off the boundary span's
    arguments over the window, and a program that writes none gives nothing."""
    from benchmark.harness.window import Fence, Window

    m = Manifest()
    spec = {s["name"]: s for s in m.layer_metrics("laguna-l5-acco-1chip")}
    shared, roofline = spec["moe_shared_ms"]["args"], spec["moe_shared_roofline"]["args"]
    assert shared["scopes"] == roofline["scopes"] == ["model/moe_shared"]
    assert roofline["work"] == "shared_expert_work"
    assert shared["except_ops"] == spec["block_ms"]["args"]["except_ops"] == roofline["except_ops"]
    assert spec["moe_shared_ms"]["needs"] == spec["moe_shared_roofline"]["needs"] == "shared_expert"
    # the stock flash kernels of the full layers are read under the one name the benchmark has for them
    flash, share = spec["flash_attn_kernel_ms"]["args"], spec["flash_attn_kernel_roofline"]["args"]
    assert flash["regex"] == "flash_attention|flash_mha_bwd" and share["kernels"] == {flash["regex"]: "global"}
    assert spec["attn_kernel_roofline"]["args"]["kernels"] == {"acco_fused_attn": "global", "acco_banded_attn": "local"}

    def boundary(ts, **args):
        return {"ph": "X", "name": "train/log_boundary_sync", "ts": ts, "dur": 5.0, "args": args}

    fences = [Fence(round=r, start_us=r * 100.0, end_us=r * 100.0 + 5.0) for r in (20, 30, 40)]
    events = [boundary(2000.0, moe_held_share=0.9), boundary(3000.0, moe_held_share=0.030),
              boundary(4000.0, moe_held_share=0.034), boundary(5000.0, moe_held_share=0.5),
              {"ph": "X", "name": "train/dispatch", "ts": 3500.0, "dur": 1.0, "args": {"moe_held_share": 1.0}}]
    read = m.reducer(spec["moe_held_share_pct"]["reducer"])
    ctx = {"trace": {"traceEvents": events}, "window": Window(fences[0], fences[-1], 3)}
    # the boundaries after the window's first fence, its last included: the second and the third
    assert read(ctx, spec["moe_held_share_pct"]["args"]) == pytest.approx(3.2)
    ctx["trace"] = {"traceEvents": [boundary(3000.0, moe_lb_loss=1.0)]}
    assert read(ctx, spec["moe_held_share_pct"]["args"]) is None


# -- the reference check on a tiny Laguna share, brought as files only ------------------

TINY = {
    "model_type": "laguna", "vocab_size": 96, "hidden_size": 64, "intermediate_size": 48,
    "num_hidden_layers": 5, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6, "attention_bias": False,
    "num_experts": 4, "num_experts_per_tok": 5, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                           "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 32,
                           "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
    },
    "layer_types": PERIOD + ["full_attention"], "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4], "initializer_range": 0.02,
    "num_routed_experts": 16, "first_held_expert": 4, "kv_groups_held": 1,
}
SEQ = 64
GROUPS = {"loss", "embedding", "lm_head", "layer0", "window_layer", "full_sparse_layer", "router",
          "held_experts", "shared_expert", "attn_gate"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark with one more configuration, as a later PR
    would add it: two files and a manifest entry, no code."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    (root / "benchmark" / "configs" / "tiny-laguna").mkdir()
    (root / "benchmark" / "configs" / "tiny-laguna" / "model.json").write_text(json.dumps(TINY))
    (root / "benchmark" / "configs" / "tiny-laguna" / "config.json").write_text(json.dumps(
        {"reference": "benchmark/reference/laguna_ref.py", "flops": "benchmark/reference/laguna_flops.py"}))
    data["configs"].append({"name": "tiny-laguna", "source": "https://example.org/tiny", "reduced": [],
                            "file": "benchmark/configs/tiny-laguna/model.json", "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return Manifest(root=str(root)).config("tiny-laguna")


def program(config: dict, dtype, **changes):
    from acco_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = dataclasses.replace(LlamaConfig.from_json(config["model_path"]), **changes)
    return LlamaModel(cfg, param_dtype=dtype, attention="xla")


def test_the_program_passes_the_check_on_files_alone(tiny):
    """In float32 every group agrees to roundoff; in bfloat16 the groups that no
    top-10 decides stand at bf16's own level, inside the tolerance. (The router's
    and the held experts' groups of a [2, 64] batch are a few dozen rows: ONE
    token that rounds into another tenth expert is percents of them, which is
    the chip's check at [2, 8192] to hold, PERF.md section 6.)"""
    said = []
    result = refcheck.compare(program(tiny, jnp.float32), False, tiny, SEQ, 11, say=said.append)
    assert result["ok"], said
    assert set(result["errors"]) == GROUPS and result["qk_scale"] == 0.5
    assert max(result["errors"].values()) < 1e-4
    assert "query and key projections scaled by 0.500" in said[-1] and "agree" in said[-1]
    result = refcheck.compare(program(tiny, jnp.bfloat16), False, tiny, SEQ, 11, say=said.append)
    for group in GROUPS - {"loss", "router", "held_experts"}:
        assert refcheck.U_BF16 / 2 < result["errors"][group] < refcheck.GRAD_RTOL, (group, result["errors"])
    assert result["errors"]["loss"] < refcheck.LOSS_RTOL


@pytest.mark.parametrize("changes, caught_by", [
    (dict(moe_routed_scaling_factor=1.0), "held_experts"),
    (dict(num_experts_per_tok=4), "router"),
    (dict(first_held_expert=5), "held_experts"),
    (dict(sliding_window=7), "window_layer"),
    (dict(gating=""), "full_sparse_layer"),
])
def test_the_check_refuses_a_wrong_program(tiny, changes, caught_by):
    """Float32 programs that depart from the layer equations in one thing:
    refused, by the group that holds the thing at the least."""
    model = program(tiny, jnp.float32, **changes)
    if not model.config.gating:  # the tree of a gateless model lacks the leaf: give the check the sound tree
        sound = program(tiny, jnp.float32)
        model.init = sound.init
    result = refcheck.compare(model, False, tiny, SEQ, 11, say=lambda line: None)
    assert not result["ok"]
    assert result["errors"][caught_by] > refcheck.GRAD_RTOL, result["errors"]


def test_the_checks_conditioning_touches_the_router_and_the_scores_alone(tiny, capsys):
    """``well_conditioned`` scales the router's weights by 4 and the query and key
    projections by 0.5 in every layer, says so, and reports the second as the
    harness's scale of the query and key projections; nothing else moves."""
    reference = refcheck.reference_of(tiny)
    params = program(tiny, jnp.bfloat16).init(jax.random.PRNGKey(0))
    tempered, qk_scale = reference.well_conditioned(params, tiny["model"])
    assert (qk_scale, reference.CHECK_QK_SCALE, reference.CHECK_ROUTER_SCALE) == (0.5, 0.5, 4.0)
    out = capsys.readouterr().out
    assert "router weights scaled by 4.0" in out and "query and key projections by 0.5" in out
    seen = set()
    for (path, before), after in zip(jax.tree_util.tree_leaves_with_path(params),
                                     jax.tree.leaves(tempered)):
        name = jax.tree_util.keystr(path)
        scale = 4.0 if "router" in name else 0.5 if "'wq'" in name or "'wk'" in name else 1.0
        seen.add(scale)
        assert after.dtype == before.dtype
        assert jnp.array_equal(after.astype(jnp.float32),
                               (before.astype(jnp.float32) * scale).astype(before.dtype).astype(jnp.float32))
    assert seen == {0.5, 1.0, 4.0}


def test_the_groups_are_the_ones_the_issue_named(tiny):
    reference = refcheck.reference_of(tiny)
    shapes = jax.eval_shape(program(tiny, jnp.float32).init, jax.random.PRNGKey(0))
    grads = jax.tree.map(lambda a: jnp.ones(a.shape, jnp.float32), shapes)
    groups = reference.compared_groups(grads)
    assert set(groups) == GROUPS - {"loss"}
    h, dh = 64, 16
    attention = lambda heads: 2 * h * heads * dh + 2 * h * dh  # q, o; k, v (one K/V head held)
    assert groups["embedding"].size == groups["lm_head"].size == 96 * h
    assert groups["layer0"].size == attention(2) + 2 * h + 2 * h + 3 * h * 48  # + the gate, two norms, the dense MLP
    # a sparse block's group is its attention and its norms: what has a group of its own is apart,
    # and holds ALL the layers' leaves of its kind (four sparse layers, five gated ones)
    assert groups["window_layer"].size == attention(3) + 2 * h
    assert groups["full_sparse_layer"].size == attention(2) + 2 * h
    assert groups["router"].size == 4 * 16 * h and groups["held_experts"].size == 4 * 4 * 3 * h * 16
    assert groups["shared_expert"].size == 4 * 3 * h * 16
    assert groups["attn_gate"].size == (2 + 3 * 3 + 2) * h
