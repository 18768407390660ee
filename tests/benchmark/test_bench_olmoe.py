"""The OLMoE configuration, its count and its reference, as the harness finds
them: the files against the source's numbers written out here, the count
against a hand count, and the reference check on a tiny OLMoE brought as files
only. The program against the reference leaf by leaf, in float32, is
``tests/test_moe.py``."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from _paths import ROOT

from benchmark.harness import refcheck
from benchmark.harness.manifest import Manifest, load_module

# allenai/OLMoE-1B-7B-0125-Instruct config.json (the catalog row's ``config``)
PUBLISHED = dict(
    attention_bias=False, clip_qkv=None, hidden_act="silu", hidden_size=2048,
    intermediate_size=1024, max_position_embeddings=4096, model_type="olmoe",
    norm_topk_prob=False, num_attention_heads=16, num_experts=64, num_experts_per_tok=8,
    num_hidden_layers=16, num_key_value_heads=16, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=10000, tie_word_embeddings=False, vocab_size=50304,
)
# what the published file lacks and the configuration states under ``assumed``
ASSUMED = dict(qk_norm=True, router_aux_loss_coef=0.01, router_z_loss_coef=0.001,
               initializer_range=0.02)


def parameters(m: dict, layers: int) -> int:
    D, F, E, V = m["hidden_size"], m["intermediate_size"], m["num_experts"], m["vocab_size"]
    attention = 4 * D * D + 2 * D  # q, k, v, o; q_norm and k_norm over the whole projection
    experts = E * 3 * D * F
    layer = attention + 2 * D + E * D + experts  # + two norms + the router
    return 2 * V * D + D + layers * layer  # untied embedding and head, final norm


@pytest.fixture(scope="module")
def config():
    return Manifest().config("olmoe-1b-7b-l1")


def test_the_benchmarks_file_holds_what_the_source_publishes(config):
    model = config["model"]
    assert {k: model[k] for k in PUBLISHED} == PUBLISHED
    assert {k: model[k] for k in ASSUMED} == ASSUMED
    assert model["num_layers"] == 1  # the one cut, under the repo's own key
    assert config["entry"]["reduced"] == config["meta"]["reduced"] == ["num_layers"]
    assert config["entry"]["source"] == config["meta"]["source"]
    assert "OLMoE-1B-7B-0125-Instruct/blob/main/config.json" in config["entry"]["source"]
    assert config["meta"]["param_count"] == parameters(model, 1) == 625_616_896
    assert set(config["meta"]["assumed"]) == set(config["meta"]["assumed_detail"])
    assert set(ASSUMED) <= set(config["meta"]["assumed"])
    assert parameters(model, 2) == 1_045_186_560  # what does not fit one chip
    # one layer is 419,569,664 parameters, 402,653,184 of them experts
    assert parameters(model, 2) - parameters(model, 1) == 419_569_664
    assert 64 * 3 * 2048 * 1024 == 402_653_184


def test_the_users_file_holds_the_source_at_full_depth():
    with open(os.path.join(ROOT, "config", "model", "olmoe-1b-7b.json")) as f:
        model = json.load(f)
    assert {k: model[k] for k in PUBLISHED} == PUBLISHED
    assert {k: model[k] for k in ASSUMED} == ASSUMED
    assert "num_layers" not in model
    assert parameters(model, model["num_hidden_layers"]) == 6_919_161_856


@pytest.mark.parametrize("path", ["benchmark/configs/olmoe-1b-7b-l1/model.json",
                                  "config/model/olmoe-1b-7b.json"])
def test_the_program_builds_what_the_file_says(path):
    """``LlamaConfig`` reads HF's names, the repo's depth key wins, and the
    parameter tree has the file's count."""
    from acco_tpu.models.registry import build_model

    model = build_model({"config_path": path}, repo_root=ROOT)
    cfg = model.config
    with open(os.path.join(ROOT, path)) as f:
        raw = json.load(f)
    depth = raw.get("num_layers", raw["num_hidden_layers"])
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (depth, 16, 16, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.norm_topk_prob, cfg.qk_norm) == (64, 8, False, True)
    assert not cfg.tie_word_embeddings and cfg.max_position_embeddings == 4096
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == parameters(raw, depth)
    assert shapes["layers"]["w_gate"].shape == (depth, 64, 2048, 1024)
    assert shapes["layers"]["w_down"].shape == (depth, 64, 1024, 2048)
    assert shapes["layers"]["router"].shape == (depth, 64, 2048)


def test_the_count_against_a_hand_count(config):
    flops = load_module(os.path.join(ROOT, config["meta"]["flops"]))
    model = config["model"]
    L = 4096
    projections = 2 * 4 * 2048 * 2048  # 33,554,432
    scores = 4 * 2048 * (L + 1) / 2  # 16,781,312: a query reads (L + 1) / 2 keys on average
    router = 2 * 2048 * 64  # 262,144
    experts = 2 * 8 * 3 * 2048 * 1024  # 100,663,296: 8 of 64 experts
    head = 2 * 2048 * 50304  # 206,045,184
    forward = projections + scores + router + experts + head
    assert flops.train_flops_per_token(model, L) == 3.0 * forward == 1_071_919_104.0
    assert head / forward == pytest.approx(0.5767, abs=1e-4)
    assert experts / forward == pytest.approx(0.2817, abs=1e-4)
    full = {k: v for k, v in model.items() if k != "num_layers"}  # the published depth
    assert flops.train_flops_per_token(full, L) == 3.0 * (16 * (forward - head) + head)
    # the grouped matmuls of one [1, 4096] round: 32,768 rows through nine matmuls
    rows = 1 * L * 8
    work = flops.expert_matmul_work(model, L, 1)
    assert work[0] == 3 * 2 * rows * 3 * 2048 * 1024 == 3 * 4096 * experts
    assert work[1] == 9 * (rows * (2048 + 1024) + 64 * 2048 * 1024) * 2
    # compute bound on a v5e: 6.28 ms against 5.16 ms of traffic
    from benchmark.harness.flops import roofline
    from benchmark.harness.manifest import load_peaks

    least, bound = roofline(*work, load_peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(6.279e-3, rel=1e-3)
    # attention, as the GPT-Neo count has it: 12 x hidden x keys per query and token
    assert flops.attention_kernel_work(model, L, 1, {"global"}) == (
        12 * 2048 * (L + 1) / 2 * L, 12 * L * 2048 * 2)
    assert flops.attention_kernel_work(model, L, 1, {"local"}) == (0.0, 0.0)


def test_the_cells_are_what_the_issue_defined():
    m = Manifest()
    olmoe = m.cell("olmoe-l1-acco-1chip")
    assert (olmoe["config"], olmoe["traffic"], olmoe["chips"]) == ("olmoe-1b-7b-l1", "acco-seq4096-bs1", 1)
    assert (olmoe["seq_len"], olmoe["batch_per_chip"]) == (4096, 1)
    assert "train.remat=dots" in olmoe["overrides"] and "train.n_grad_accumulation=1" in olmoe["overrides"]
    assert olmoe["schedules"] == [{"name": "main", "overrides": ["train=acco"], "reference_check": True}]
    assert (olmoe["warmup_rounds"], olmoe["ref_round"], olmoe["trace_rounds"]) == (20, 100, 10)
    # the DPU cell is the ACCO cell's file with the schedule changed and nothing else
    dpu, acco = m.cell("neo125m-dpu-1chip"), m.cell("neo125m-acco-1chip")
    assert dpu["schedules"][0]["overrides"] == ["train=dpu"]
    same = set(acco) - {"name", "traffic", "why", "schedules"}
    assert {k: dpu[k] for k in same} == {k: acco[k] for k in same}
    names = [s["name"] for s in m.layer_metrics("olmoe-l1-acco-1chip")]
    assert {"moe_router_ms", "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
            "block_ms", "lm_head_ce_ms", "mfu_pct"} <= set(names)
    assert "moe_experts_ms" not in [s["name"] for s in m.layer_metrics("neo125m-dpu-1chip")]


def test_the_configuration_says_which_kernels_and_scopes_it_runs(config):
    """The stock flash kernel and the three expert scopes, and not this repo's
    own kernels, a shared expert or a chip's share: what the cell lists follows
    from that (``tests/benchmark/test_bench_rules.py`` holds every cell to it)."""
    assert config["meta"]["runs"] == ["stock_flash_kernels", "experts"]
    m = Manifest()
    for cell in m.cell_names():
        if m.cell(cell)["config"] == config["name"]:
            assert m.features(cell) == {"stock_flash_kernels", "experts"}
            assert {"flash_attn_kernel_ms", "flash_attn_kernel_roofline"} <= {
                s["name"] for s in m.layer_metrics(cell)}


def test_the_flash_kernels_are_read_by_the_names_the_chip_gives_them(config):
    """The instruction names of the traced OLMoE run (my chip run, PR 25) and
    their self times: the two new metrics read the four flash kernels and
    nothing else, the accepted ones read none of them, and the roofline is
    the count's least time over what the kernels took."""
    from benchmark.harness import flops, xplane
    from benchmark.harness.manifest import load_peaks

    took_ms = {
        "flash_mha_bwd_dkv_block_q_major_128_block_q_128_block_k_major_128_block_k_128.2": 5.866,
        "flash_attention.11": 4.355,
        "flash_attention.10": 4.182,
        "flash_mha_bwd_dq_block_q_major_128_block_k_major_128_block_k_128.2": 3.703,
        "fusion.133": 5.47,
        "gmm.5": 1.2,
    }

    class Trace:
        def __init__(self):
            self.segments, t = [], 0.0
            for name, ms in took_ms.items():
                text = f"%{name} = bf16[1,16,4096,128]{{3,2,1,0}} custom-call(%p.1)"
                self.segments.append(xplane.Segment(t, t + ms * 1e6, xplane.Op(t, t + ms * 1e6, name, text)))
                t += ms * 1e6

        def op_ms_per_round(self, regex, field="text"):
            return xplane.self_time_ns(self.segments, regex, field) / 1e6

    m = Manifest()
    said = []
    ctx = {"device_trace": Trace(), "peaks": load_peaks("TPU v5 lite"), "config": config,
           "cell": m.cell("olmoe-l1-acco-1chip"), "say": said.append}
    spec = {s["name"]: s for s in m.layer_metrics("olmoe-l1-acco-1chip")}
    read = lambda name, path=None: m.reducer(spec[name]["reducer"])(ctx, spec[name]["args"])
    flash_ms = 5.866 + 4.355 + 4.182 + 3.703
    assert read("flash_attn_kernel_ms") == pytest.approx(flash_ms)
    least_s, bound = flops.roofline(12 * 2048 * 4097 / 2 * 4096, 12 * 4096 * 2048 * 2, ctx["peaks"])
    assert bound == "compute"
    assert read("flash_attn_kernel_roofline") == pytest.approx(100 * least_s * 1e3 / flash_ms)
    assert 5.0 < read("flash_attn_kernel_roofline") < 7.0  # 1.05 ms of work in 18.1 ms of kernels
    own = {s["name"]: s for s in m.layer_metrics("neo125m-dpu-1chip")}
    for name in ("attn_kernel_ms", "attn_kernel_roofline"):
        assert m.reducer(own[name]["reducer"])(ctx, own[name]["args"]) is None


def test_the_expert_scopes_have_one_owner_each_among_the_expert_metrics(config):
    """The three scopes ``ops/moe.py`` gives an expert layer's ops have one
    metric each, all three need the feature this configuration runs, and the
    roofline counts the grouped matmuls' work over the experts' scope. (That
    no scope has two owners, and that a cell's scope metrics add up, is rule
    R2 of ``tests/benchmark/test_bench_rules.py``.)"""
    m = Manifest()
    spec = {s["name"]: s for s in m.layer_metrics("olmoe-l1-acco-1chip")}
    new = {name: spec[name] for name in ("moe_router_ms", "moe_dispatch_ms", "moe_experts_ms")}
    assert {name: s["args"]["scopes"] for name, s in new.items()} == {
        "moe_router_ms": ["model/moe_router"], "moe_dispatch_ms": ["model/moe_dispatch"],
        "moe_experts_ms": ["model/moe_experts"]}
    assert {s["needs"] for s in new.values()} == {"experts"} <= set(config["meta"]["runs"])
    assert {s["args"]["except_ops"] for s in new.values()} == {spec["block_ms"]["args"]["except_ops"]}
    roofline = spec["moe_experts_roofline"]["args"]
    assert roofline["scopes"] == new["moe_experts_ms"]["args"]["scopes"] and roofline["work"] == "expert_matmul_work"


# -- the reference check on a tiny OLMoE, brought as files only -----------------

TINY = {
    "model_type": "olmoe", "vocab_size": 257, "hidden_size": 64, "intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
    "max_position_embeddings": 128, "rope_theta": 10000, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": False, "qk_norm": True, "router_aux_loss_coef": 0.01,
    "router_z_loss_coef": 0.001, "initializer_range": 0.02,
}
SEQ = 64


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark with one more configuration, as a later PR
    would add it: two files and a manifest entry, no code."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    (root / "benchmark" / "configs" / "tiny-olmoe").mkdir()
    (root / "benchmark" / "configs" / "tiny-olmoe" / "model.json").write_text(json.dumps(TINY))
    (root / "benchmark" / "configs" / "tiny-olmoe" / "config.json").write_text(json.dumps(
        {"reference": "benchmark/reference/olmoe_ref.py", "flops": "benchmark/reference/olmoe_flops.py"}))
    data["configs"].append({"name": "tiny-olmoe", "source": "https://example.org/tiny", "reduced": [],
                            "file": "benchmark/configs/tiny-olmoe/model.json", "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return Manifest(root=str(root)).config("tiny-olmoe")


def program(config: dict, dtype=jnp.bfloat16, **changes):
    import dataclasses

    from acco_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = dataclasses.replace(LlamaConfig.from_json(config["model_path"]), **changes)
    return LlamaModel(cfg, param_dtype=dtype, attention="xla")


def test_the_program_in_bf16_passes_the_check_on_files_alone(tiny):
    said = []
    result = refcheck.compare(program(tiny), False, tiny, SEQ, 11, say=said.append)
    assert result["ok"], said
    assert set(result["errors"]) == {"loss", "embedding", "lm_head", "first_block", "last_block"}
    for group, error in result["errors"].items():
        if group != "loss":  # bf16's own level, not float32's
            assert refcheck.U_BF16 / 2 < error < refcheck.GRAD_RTOL, (group, error)
    assert "lm_head" in said[-1] and "agree" in said[-1]


def test_the_checks_conditioning_touches_the_router_alone(tiny, capsys):
    """``well_conditioned`` scales the router's weights (a token's last
    expert, which no bf16 program chooses as float32 does, then carries a
    hundredth of the first one's gate) and says so; nothing else moves, and the
    harness's scale of the query and key projections stays 1."""
    reference = refcheck.reference_of(tiny)
    params = program(tiny).init(jax.random.PRNGKey(0))
    tempered, qk_scale = reference.well_conditioned(params, tiny["model"])
    assert qk_scale == 1.0 and reference.CHECK_ROUTER_SCALE == 4.0
    assert "router weights scaled by 4.0" in capsys.readouterr().out
    for (path, before), after in zip(jax.tree_util.tree_leaves_with_path(params),
                                     jax.tree.leaves(tempered)):
        scale = 4.0 if "router" in jax.tree_util.keystr(path) else 1.0
        assert after.dtype == before.dtype
        assert jnp.array_equal(after.astype(jnp.float32), before.astype(jnp.float32) * scale)


def test_one_block_is_compared_as_one_vector(tiny):
    reference = refcheck.reference_of(tiny)
    shapes = jax.eval_shape(program(tiny).init, jax.random.PRNGKey(0))
    grads = jax.tree.map(lambda a: jnp.ones(a.shape, jnp.float32), shapes)
    groups = reference.compared_groups(grads)
    per_layer = sum(a.size for a in jax.tree.leaves(shapes["layers"])) // 2
    assert groups["first_block"].size == groups["last_block"].size == per_layer
    assert groups["lm_head"].size == groups["embedding"].size == 257 * 64
    one_layer = jax.tree.map(lambda a: a[:1], grads["layers"])
    assert "last_block" not in reference.compared_groups({**grads, "layers": one_layer})


WRONG = {
    # name: (changes of the program's configuration, refused by the check)
    "renormalised_gates": ({"norm_topk_prob": True}, True),
    "auxiliary_terms_left_out": ({"router_aux_loss_coef": 0.0, "router_z_loss_coef": 0.0}, True),
    "no_qk_norm": ({"qk_norm": False}, True),
    # The limit, stated in PERF.md section 7: at initialisation (weights of 0.02, gates
    # near 1/8 un-renormalised) a token's LAST expert adds less to a block's gradient
    # than bf16's own allowance, so the check does not tell one expert a token from two
    # at THIS size (2.8e-2 against 4.7e-2). At the published widths on the chip it does
    # tell top-7 from top-8 (6.9e-2 to 7.7e-2 with the router x 4; PERF.md section 6,
    # PR 25). Held where both sides are float32 whatever the size: tests/test_moe.py.
    "one_expert_a_token_for_two": ({"num_experts_per_tok": 1}, False),
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_what_the_checks_tolerance_tells_apart(tiny, variant):
    """The program in float32 with one thing changed, against the reference,
    at the chip's own (bf16-level) tolerance: refused by the gradients or,
    for the auxiliary terms, by the loss."""
    changes, refused = WRONG[variant]
    wrong = program(tiny, jnp.float32, **changes)
    if variant == "no_qk_norm":  # the same parameter tree, the norms not applied
        wrong.init = program(tiny, jnp.float32).init
    said = []
    result = refcheck.compare(wrong, False, tiny, SEQ, 11, say=said.append)
    assert result["ok"] is not refused, said
    assert ("DISAGREE" in said[-1]) is refused


def test_float32_passes_by_orders_of_magnitude(tiny):
    result = refcheck.compare(program(tiny, jnp.float32), False, tiny, SEQ, 11, say=lambda _: None)
    assert result["ok"] and max(result["errors"].values()) < 1e-4
