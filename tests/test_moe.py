"""Sparse experts in ``LlamaModel`` (``model_type: "olmoe"``): the program in
float32 against the plain reference (``benchmark/reference/olmoe_ref.py``) at a
small size, through the model alone and through the three schedules' round
programs; what the comparison tells apart; what an expert model refuses.

Tolerance: float32 on both sides, precision ``highest``. The two sides add the
same numbers in another order (a grouped matmul over sorted rows against a
dense product over all experts, a scan against a loop), so an element differs
by a few float32 roundoffs (6e-8) times the length of its sums: measured 3e-7 to
8e-7 relative L2 on every leaf at this size. 1e-5 leaves a factor of ten for
another backend's ordering and is four orders under what any of the wrong
variants below gives (a token's eighth expert changed is 1e-2 and more).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from acco_tpu.models.llama import LlamaConfig, LlamaModel
from acco_tpu.ops import moe
from acco_tpu.ops.losses import model_ce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 1e-5

# h=64, 8 experts top-2, 2 layers, QK-norm, untied head: OLMoE's shape, small
MODEL_JSON = {
    "model_type": "olmoe", "vocab_size": 97, "hidden_size": 64, "intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
    "max_position_embeddings": 64, "rope_theta": 10000, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": False, "qk_norm": True, "router_aux_loss_coef": 0.01,
    "router_z_loss_coef": 0.001, "initializer_range": 0.02,
}
SEQ = 32


@pytest.fixture(scope="module")
def ref():
    from benchmark.harness.manifest import load_module

    return load_module(os.path.join(ROOT, "benchmark", "reference", "olmoe_ref.py"))


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("olmoe") / "model.json"
    path.write_text(json.dumps(MODEL_JSON))
    return str(path)


def build(model_path, **changes) -> LlamaModel:
    cfg = dataclasses.replace(LlamaConfig.from_json(model_path), **changes)
    return LlamaModel(cfg, param_dtype=jnp.float32, attention="xla")


def seeded(model, key=3):
    """Seeded random weights with every leaf made to count: the norms start
    at one and the router near uniform, so each gets its own noise."""
    params = model.init(jax.random.PRNGKey(key))
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(key + 1), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [a + 0.1 * jax.random.normal(k, a.shape, a.dtype) for a, k in zip(leaves, keys)]
    )


def program_loss(model, params, ids, mask=None, with_terms=False):
    return model_ce(model, params, ids, mask, ids, label_smoothing=0.0, fused=False,
                    with_terms=with_terms)


def count_primitive(fn, *args, name: str) -> int:
    """Equations of the traced ``fn`` whose primitive's name starts with
    ``name``, through every nested jaxpr at each place it is used, but not
    inside the grouped matmul (``megablox``'s jitted ``gmm`` / ``tgmm``: the
    kernel's body and the tile-to-group metadata around it are its own): the
    kernel is counted as its ``pallas_call``."""
    from jax.extend import core

    def sub_jaxprs(value):
        if isinstance(value, core.ClosedJaxpr):
            yield value.jaxpr
        elif isinstance(value, core.Jaxpr):
            yield value
        elif isinstance(value, (tuple, list)):
            for v in value:
                yield from sub_jaxprs(v)

    def count(jaxpr, inside_kernel_call=False) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            is_kernel = eqn.primitive.name == "pallas_call"
            if eqn.primitive.name.startswith(name) and (is_kernel or not inside_kernel_call):
                n += 1
            if is_kernel:
                continue
            inside = inside_kernel_call or eqn.params.get("name") in ("gmm", "tgmm")
            for value in eqn.params.values():
                n += sum(count(j, inside) for j in sub_jaxprs(value))
        return n

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


@pytest.fixture(scope="module")
def both_sides(ref, model_path):
    """Loss, terms and gradients of the program and of the reference on the
    same weights and a [3, 32] batch."""
    model = build(model_path)
    params = seeded(model)
    ids = jax.random.randint(jax.random.PRNGKey(5), (3, SEQ), 0, MODEL_JSON["vocab_size"], jnp.int32)
    with jax.default_matmul_precision("highest"):
        (loss, terms), grads = jax.value_and_grad(
            lambda p: program_loss(model, p, ids, with_terms=True), has_aux=True
        )(params)
        logits = model.apply(params, ids, None)
        want_logits, _, _ = ref.forward(params, ids, MODEL_JSON)
        want_terms = ref.loss_terms(params, ids, MODEL_JSON)
    want_loss, want_grads = ref.loss_and_grads(params, ids, MODEL_JSON)
    return dict(model=model, params=params, ids=ids, loss=loss, terms=terms, grads=grads,
                logits=logits, want_logits=want_logits, want_terms=want_terms,
                want_loss=want_loss, want_grads=want_grads)


def test_the_json_reads_as_an_expert_model(model_path):
    cfg = LlamaConfig.from_json(model_path)
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads) == (2, 4, 4)  # HF's names
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.qk_norm) == (8, 2, True)
    assert (cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (0.01, 0.001)
    shapes = jax.eval_shape(build(model_path).init, jax.random.PRNGKey(0))["layers"]
    assert shapes["router"].shape == (2, 8, 64)  # [layers, experts, hidden], as HF's gate.weight
    assert shapes["w_gate"].shape == shapes["w_up"].shape == (2, 8, 64, 32)
    assert shapes["w_down"].shape == (2, 8, 32, 64)
    assert shapes["q_norm"].shape == shapes["k_norm"].shape == (2, 64)  # the whole projection


def test_the_repos_key_wins_over_hfs(tmp_path):
    """A benchmark configuration keeps the published ``num_hidden_layers``
    beside the ``num_layers`` it runs."""
    path = tmp_path / "model.json"
    path.write_text(json.dumps({**MODEL_JSON, "num_hidden_layers": 16, "num_layers": 1}))
    assert LlamaConfig.from_json(str(path)).num_layers == 1


@pytest.mark.parametrize("key", ["attention_bias", "clip_qkv", "rope_scaling"])
def test_a_json_asking_for_what_the_model_lacks_is_refused(tmp_path, key):
    path = tmp_path / "model.json"
    path.write_text(json.dumps({**MODEL_JSON, key: 8.0}))
    with pytest.raises(ValueError, match=key):
        LlamaConfig.from_json(str(path))


def test_logits_match_the_reference(both_sides):
    assert rel_l2(both_sides["logits"], both_sides["want_logits"]) < RTOL


def test_the_objective_and_each_of_its_terms_match_the_reference(both_sides):
    s = both_sides
    lb, z = float(s["terms"]["moe_lb_loss"]), float(s["terms"]["moe_z_loss"])
    assert lb == pytest.approx(float(s["want_terms"]["lb"]), rel=RTOL)
    assert z == pytest.approx(float(s["want_terms"]["z"]), rel=RTOL)
    ce = float(s["loss"]) - 0.01 * lb - 0.001 * z
    assert ce == pytest.approx(float(s["want_terms"]["ce"]), rel=RTOL)
    assert float(s["loss"]) == pytest.approx(float(s["want_loss"]), rel=RTOL)
    # the terms are there to be seen: the router is not uniform on these weights
    assert lb > 1.0 and z > 1.0 and 1.0 < float(s["terms"]["moe_max_load"]) <= 8 / 2


LEAVES = ["wte", "lm_head", "final_norm"] + [
    f"layers/{name}" for name in ("attn_norm", "k_norm", "mlp_norm", "q_norm", "router", "w_down",
                                  "w_gate", "w_up", "wk", "wo", "wq", "wv")
]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(both_sides, leaf):
    """Router and experts on their own, not inside a block's norm."""
    def pick(tree):
        for part in leaf.split("/"):
            tree = tree[part]
        return tree

    assert set(LEAVES) == {
        "/".join(str(k.key) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(both_sides["grads"])
    }
    assert rel_l2(pick(both_sides["grads"]), pick(both_sides["want_grads"])) < RTOL


@pytest.mark.parametrize("remat", [True, "dots"])
def test_remat_changes_nothing(both_sides, remat):
    """``dots`` saves the grouped matmuls' outputs by name; either way the
    gradients are the un-rematerialised ones."""
    s = both_sides
    model = LlamaModel(s["model"].config, param_dtype=jnp.float32, attention="xla", remat=remat)
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: program_loss(model, p, s["ids"]))(s["params"])
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(s["grads"])):
        assert rel_l2(got, want) < RTOL


def test_dots_saves_the_grouped_matmuls(both_sides):
    """Under ``remat=dots`` the backward pass does not run the three forward
    grouped matmuls again: 9 kernel calls a layer, 12 under ``True``."""
    s = both_sides

    def grouped_matmuls(remat):
        model = LlamaModel(s["model"].config, param_dtype=jnp.float32, attention="xla", remat=remat)
        return count_primitive(
            jax.grad(lambda p: program_loss(model, p, s["ids"])), s["params"], name="pallas_call"
        )

    # in the layer scan's body: 3 forward + 6 backward, and 3 more where the forward runs again
    assert (grouped_matmuls(False), grouped_matmuls("dots"), grouped_matmuls(True)) == (9, 9, 12)


def test_all_tokens_to_one_expert_is_exact_and_drops_nothing(ref, model_path):
    """An extreme imbalance through the whole model: a router whose rows for
    experts 5 and 2 are 3 and 2 (times the sum of its input) and zero for the
    rest sends a token to {5, 2} where that sum is positive and, the other
    six logits tying at zero, to {0, 1} where it is not. Four experts get
    every row between them, four get none, and the result is the reference's,
    in which every expert sees every token."""
    model = build(model_path)
    params = seeded(model)
    router = np.zeros((2, 8, 64), np.float32)
    router[:, 5], router[:, 2] = 3.0, 2.0
    params["layers"]["router"] = jnp.asarray(router)
    ids = jax.random.randint(jax.random.PRNGKey(9), (2, SEQ), 0, MODEL_JSON["vocab_size"], jnp.int32)
    with jax.default_matmul_precision("highest"):
        (loss, terms), grads = jax.value_and_grad(
            lambda p: program_loss(model, p, ids, with_terms=True), has_aux=True
        )(params)
    want_loss, want_grads = ref.loss_and_grads(params, ids, MODEL_JSON)
    assert float(loss) == pytest.approx(float(want_loss), rel=RTOL)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert rel_l2(got, want) < RTOL
    # two experts took at least a quarter of a sequence's assignments each, not an eighth
    assert float(terms["moe_max_load"]) >= 2.0


def test_one_group_of_every_row_and_the_rest_empty():
    """``dropless_experts`` itself under total imbalance: all T x k rows in
    one group, against a dense product with that expert's weights."""
    T, k, E, D, F = 24, 2, 4, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    h = jax.random.normal(keys[0], (T, D))
    w_gate, w_up = (jax.random.normal(kk, (E, D, F)) for kk in keys[1:3])
    w_down = jax.random.normal(keys[3], (E, F, D))
    gates = jax.random.uniform(keys[4], (T, k))
    experts = jnp.full((T, k), 3, jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = moe.dropless_experts(h, gates, experts, w_gate, w_up, w_down)
        one = (jax.nn.silu(h @ w_gate[3]) * (h @ w_up[3])) @ w_down[3]
    np.testing.assert_allclose(got, gates.sum(axis=1, keepdims=True) * one, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "tiles, seq",
    [((16, 32, 16), 28), ((8, 48, 32), 30), ((256, 1024, 1024), 31)],
    ids=["rows-8-of-16", "irregular-k-and-n", "one-tile"],
)
def test_the_kernel_tiles_unbalanced_groups_like_the_reference(ref, tmp_path, monkeypatch, tiles, seq):
    """The grouped matmul's kernel (``megablox.gmm`` and its VJP, interpreted
    here, compiled on the chip) where its tiling has work to do: several row
    tiles a group and groups that start and end inside a tile, a row count
    that is no multiple of the configured row tile (3 x 28 x 2 = 168 rows go
    in tiles of gcd(168, 16) = 8), contraction and columns in several tiles of
    which the last is partial (width 40 in tiles of 16 or 32, hidden 64 in
    tiles of 48), and a router that sends a quarter of all rows to one expert.
    Loss and every gradient leaf against the reference, which
    applies every expert to every token and has no groups at all."""
    cfg_json = {**MODEL_JSON, "intermediate_size": 40}
    path = tmp_path / "model.json"
    path.write_text(json.dumps(cfg_json))
    monkeypatch.setattr(moe, "GMM_TILES", tiles)
    model = build(str(path))
    params = seeded(model)
    router = np.array(params["layers"]["router"])  # [layers, E, D]
    router[:, 5] = 30.0  # tops every other logit wherever the sum of its input is positive
    params["layers"]["router"] = jnp.asarray(router)
    ids = jax.random.randint(jax.random.PRNGKey(11), (3, seq), 0, cfg_json["vocab_size"], jnp.int32)
    with jax.default_matmul_precision("highest"):
        (loss, terms), grads = jax.value_and_grad(
            lambda p: program_loss(model, p, ids, with_terms=True), has_aux=True
        )(params)
    want_loss, want_grads = ref.loss_and_grads(params, ids, cfg_json)
    assert float(loss) == pytest.approx(float(want_loss), rel=RTOL)
    for (name, got), want in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        assert rel_l2(got, want) < RTOL, jax.tree_util.keystr(name)
    # balanced is 1.0; expert 5 takes one of the two assignments of about half the tokens
    assert float(terms["moe_max_load"]) >= 2.0


def test_no_scatter_in_the_dispatch_or_its_transpose(both_sides):
    """Into expert order and back are gathers both ways (``_permute``)."""
    s = both_sides
    layer = jax.tree.map(lambda a: a[0], s["params"]["layers"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))

    def expert_half(h, layer):
        return s["model"]._expert_mlp(h, layer, None)[0].sum()

    grad = jax.grad(expert_half, argnums=(0, 1))
    assert count_primitive(grad, h, layer, name="gather") == 4  # in and back, and their transposes
    assert count_primitive(grad, h, layer, name="pallas_call") == 9  # 3 forward, 6 backward
    # no row of activations is scattered: the two that remain add counts into
    # [E] (bincount) and top_k's gate gradients into [B, L, E]
    assert count_primitive(grad, h, layer, name="scatter") == 2


def test_padding_counts_in_no_statistic(model_path):
    """A padded sequence's statistics are those of its real tokens: under a
    causal mask they are the statistics of the truncated sequence."""
    model = build(model_path)
    params = seeded(model)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, MODEL_JSON["vocab_size"], jnp.int32)
    mask = jnp.asarray(np.arange(SEQ)[None, :] < np.array([[SEQ], [20]]), jnp.int32)
    _, padded = model.hidden(params, ids, mask, with_aux=True)
    _, full = model.hidden(params, ids[:1], None, with_aux=True)
    _, cut = model.hidden(params, ids[1:, :20], None, with_aux=True)
    for name in ("moe_lb_loss", "moe_z_loss", "moe_max_load"):
        want = (float(full[name]) + float(cut[name])) / 2
        assert float(padded[name]) == pytest.approx(want, rel=1e-5), name


# -- what the comparison tells apart ------------------------------------------


def capacity_limit_dropping_one_token(monkeypatch):
    """A capacity limit that drops one assignment: the last row of the most
    loaded expert's group gets no output."""
    dropless = moe.dropless_experts

    def limited(h, gates, experts, *weights, **kwargs):
        flat = experts.reshape(-1)
        counts = jnp.bincount(flat, length=weights[0].shape[0])
        last = jnp.max(jnp.where(flat == jnp.argmax(counts), jnp.arange(flat.size), -1))
        gates = gates.reshape(-1).at[last].set(0.0).reshape(gates.shape)
        return dropless(h, gates, experts, *weights, **kwargs)

    monkeypatch.setattr(moe, "dropless_experts", limited)
    return {}


def statistics_per_microbatch(monkeypatch):
    """The router statistics over all tokens of the microbatch, as the
    published code takes them, where per sequence is stated."""
    route = moe.route

    def pooled(h, router, top_k, norm, mask=None):
        B, L, D = h.shape
        gates, experts, stats = route(h.reshape(1, B * L, D), router, top_k, norm, None)
        stats = jax.tree.map(lambda s: jnp.broadcast_to(s, (B,)), stats)
        return gates.reshape(B, L, top_k), experts.reshape(B, L, top_k), stats

    monkeypatch.setattr(moe, "route", pooled)
    return {}


WRONG = {
    # name: config changes of the program's side, or a patch of ops/moe.py
    "top_1_for_top_2": lambda mp: {"num_experts_per_tok": 1},  # top-7 for top-8 at the published size
    "renormalised_gates": lambda mp: {"norm_topk_prob": True},
    "auxiliary_terms_left_out": lambda mp: {"router_aux_loss_coef": 0.0, "router_z_loss_coef": 0.0},
    "capacity_limit_drops_one_token": capacity_limit_dropping_one_token,
    "statistics_per_microbatch": statistics_per_microbatch,
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_a_wrong_variant_fails_the_comparison(both_sides, model_path, monkeypatch, variant):
    """Each departs from the published layer in one thing, and the gradients
    leave the tolerance by orders of magnitude (and the loss, where the
    change reaches it)."""
    s = both_sides
    model = build(model_path, **WRONG[variant](monkeypatch))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: program_loss(model, p, s["ids"]))(s["params"])
    worst = max(
        rel_l2(got, want)
        for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(s["want_grads"]))
    )
    assert worst > 100 * RTOL, (variant, worst)
    # one assignment of 192, or statistics pooled over three sequences, move the loss
    # in its fifth or sixth digit only: those two are told apart by the gradients
    if variant in ("top_1_for_top_2", "renormalised_gates", "auxiliary_terms_left_out"):
        assert abs(float(loss) - float(s["want_loss"])) > 10 * RTOL * float(s["want_loss"])


# -- through the three schedules' round programs -------------------------------

DP, N_ACC, PER_CHIP = 2, 2, 2
LR, WD, B1, B2, EPS = 3e-3, 0.1, 0.9, 0.95, 1e-8


def _round_batches(r):
    ids = jax.random.randint(
        jax.random.PRNGKey(40 + r), (N_ACC, DP * PER_CHIP, SEQ), 0, MODEL_JSON["vocab_size"], jnp.int32
    )
    return {"input_ids": ids, "attention_mask": jnp.ones_like(ids), "labels": ids,
            "valid": jnp.ones((N_ACC, DP), jnp.float32)}


class Replay:
    """The schedules in plain JAX on one device: microbatch means of the
    objective, AdamW on the whole vector."""

    def __init__(self, model, params):
        self.model = model
        self.flat, self.unravel = ravel_pytree(params)
        self.opt_p, self.mu, self.nu, self.t = self.flat, 0 * self.flat, 0 * self.flat, 0
        self.micro = jax.jit(jax.value_and_grad(
            lambda f, ids: program_loss(model, self.unravel(f), ids, with_terms=True), has_aux=True
        ))

    def grads(self, flat, batches):
        """Sum over the round's microbatches (each chip's rows of each
        accumulation step) of the gradient of the microbatch's objective, the
        count, and the mean of each term."""
        total, count, seen = 0 * flat, 0, []
        for a in range(N_ACC):
            for d in range(DP):
                ids = batches["input_ids"][a, d * PER_CHIP : (d + 1) * PER_CHIP]
                (loss, terms), g = self.micro(flat, ids)
                total, count = total + g, count + 1
                seen.append({"loss": loss, **terms})
        return total, count, {k: float(np.mean([s[k] for s in seen])) for k in seen[0]}

    def adamw(self, g):
        t = self.t + 1
        mu = B1 * self.mu + (1 - B1) * g
        nu = B2 * self.nu + (1 - B2) * g * g
        p = self.opt_p * (1 - LR * WD) - LR * (mu / (1 - B1**t)) / (jnp.sqrt(nu / (1 - B2**t)) + EPS)
        return p, mu, nu, t


def _step(kind, model):
    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.acco import AccoTrainStep
    from acco_tpu.parallel.ddp import DDPTrainStep
    from acco_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": DP}, devices=jax.devices()[:DP])
    sched = get_schedule("constant", LR, 0, 1000)
    common = dict(weight_decay=WD, beta1=B1, beta2=B2, label_smoothing=0.0, param_dtype=jnp.float32)
    if kind == "ddp":
        return DDPTrainStep(model, mesh, sched, **common)
    return AccoTrainStep(model, mesh, sched, mode=kind, **common)


@pytest.mark.parametrize("kind", ["acco", "dpu", "ddp"])
def test_two_rounds_through_the_round_programs_match_a_plain_replay(eight_devices, model_path, kind):
    """dp=2, two microbatches a round of two sequences each: parameters after
    each round, the logged objective and the three terms at the boundary."""
    model = build(model_path)
    params = seeded(model)
    step, replay = _step(kind, model), Replay(model, params)
    state = step.init_state(params)
    n = step.geom.n_params

    start = np.asarray(replay.flat)

    def check(state, metrics, want_flat, want_terms):
        # the round's update as a whole, not element by element: AdamW's first steps
        # divide each element by its own magnitude, so an element whose gradient is at
        # roundoff level (an expert few tokens chose) moves by +-lr on either side
        moved = np.asarray(state.flat_params)[:n] - start
        assert rel_l2(moved, np.asarray(want_flat) - start) < 1e-3
        assert float(metrics.loss) == pytest.approx(want_terms["loss"], rel=1e-5)
        assert set(metrics.terms) == {"moe_lb_loss", "moe_z_loss", "moe_max_load"}
        for name, value in metrics.terms.items():
            assert float(value) == pytest.approx(want_terms[name], rel=1e-5), name

    if kind == "ddp":
        run = step.step_fn()
        for r in range(2):
            batches = _round_batches(r)
            g, count, terms = replay.grads(replay.opt_p, batches)
            replay.opt_p, replay.mu, replay.nu, replay.t = replay.adamw(g / count)
            state, metrics = run(state, batches)
            check(state, metrics, replay.opt_p, terms)
        return
    # ACCO / DPU: a seed round stages gradients, then each round applies the
    # staged ones while computing the next at the parameters it started from
    seed = _round_batches(99)
    state, _ = step.seed_fn()(state, seed)
    pending, pending_count, _ = replay.grads(replay.flat, seed)
    working = replay.flat
    run = step.round_fn()
    for r in range(2):
        batches = _round_batches(r)
        speculative = kind == "acco" and r % 2 == 0
        new_p, mu, nu, t = replay.adamw(pending / pending_count)
        g, count, terms = replay.grads(working, batches)
        if speculative:  # the estimate moves the working copy only; gradients accumulate on
            pending, pending_count = pending + g, pending_count + count
        else:
            replay.opt_p, replay.mu, replay.nu, replay.t = new_p, mu, nu, t
            pending, pending_count = g, count
        working = new_p
        state, metrics = run(state, batches)
        check(state, metrics, working, terms)


def test_a_dense_model_carries_no_terms(eight_devices):
    """The round programs of a model whose objective is the cross-entropy
    alone hand the boundary an empty dict: nothing is added to them."""
    cfg = LlamaConfig(vocab_size=32, hidden_size=16, intermediate_size=32, num_layers=1,
                      num_heads=2, num_kv_heads=2, max_position_embeddings=16)
    model = LlamaModel(cfg, param_dtype=jnp.float32)
    step = _step("acco", model)
    state = step.init_state(model.init(jax.random.PRNGKey(0)))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, DP, 8), 0, 32, jnp.int32)
    batches = {"input_ids": ids, "attention_mask": jnp.ones_like(ids), "labels": ids,
               "valid": jnp.ones((1, DP), jnp.float32)}
    state, _ = step.seed_fn()(state, batches)
    _, metrics = step.round_fn()(state, batches)
    assert metrics.terms == {} and np.isfinite(float(metrics.loss))


# -- a dense Llama is what it was -------------------------------------------------

DENSE_HLO_SHA256 = {
    # sha256 of jax.jit(value_and_grad(model_ce)).lower(...).as_text() for
    # config/model/llama-125M.json at [2, 128], einsum attention, taken on the
    # commit before the expert block went into LlamaModel (16cc9f0, jax 0.9.0).
    # The text carries no source locations and no scope names. A PR that means
    # to change the dense block replaces these; one that does not must not.
    False: "d3f0f6911b6fd877a35efa1c3a69357dcc8321df44ae52d2d9d83ade4638df7c",
    "dots": "92e7a35aacccda652fafa7b8c52dc6c3545854e220c635765ab8bd295c247073",
}


@pytest.mark.parametrize("remat", [False, "dots"])
def test_a_dense_llama_lowers_to_the_same_hlo_as_before(remat):
    import hashlib

    from acco_tpu.models.registry import build_model

    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    model = build_model({"config_path": "config/model/llama-125M.json"}, repo_root=ROOT,
                        remat=remat, attention="xla")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def loss(p, ids):  # the text holds the function's name
        return model_ce(model, p, ids, None, ids, label_smoothing=0.0, fused=False)

    text = jax.jit(jax.value_and_grad(loss)).lower(params, ids).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == DENSE_HLO_SHA256[remat]


# -- what an expert model refuses ---------------------------------------------------

EXPERTS = dict(vocab_size=32, hidden_size=16, intermediate_size=8, num_layers=2, num_heads=2,
               num_kv_heads=2, max_position_embeddings=16, num_experts=4, num_experts_per_tok=2)
REFUSED = "no rule for the expert leaves"


@pytest.mark.parametrize(
    "kind, axes, model_kw, wanted",
    [
        ("tp", {"tensor_axis": "tp"}, {"tensor_axis": "tp"}, "tensor parallelism"),
        ("pp", {"pipeline_axis": "pp"}, {}, "pipeline parallelism"),
        ("sp", {"seq_axis": "sp"}, {"attention": "ring", "sequence_axis": "sp"}, "context parallelism"),
    ],
)
@pytest.mark.parametrize("step", ["acco", "ddp"])
def test_a_step_over_another_axis_than_dp_is_refused_at_construction(
    eight_devices, step, kind, axes, model_kw, wanted
):
    """The one place every train path's layout is decided
    (sharding/layout.shard_layout) names what is missing."""
    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.acco import AccoTrainStep
    from acco_tpu.parallel.ddp import DDPTrainStep
    from acco_tpu.parallel.mesh import make_mesh
    from acco_tpu.sharding.rules import ShardingRuleError

    model = LlamaModel(LlamaConfig(**EXPERTS), param_dtype=jnp.float32, **model_kw)
    mesh = make_mesh({"dp": 2, kind: 2}, devices=jax.devices()[:4])
    build_step = AccoTrainStep if step == "acco" else DDPTrainStep
    with pytest.raises(ShardingRuleError, match=wanted + ".*" + REFUSED):
        build_step(model, mesh, get_schedule("constant", LR, 0, 10), weight_decay=WD,
                   beta1=B1, beta2=B2, **axes)


def test_serving_is_refused():
    from acco_tpu.sharding.rules import ShardingRuleError

    model = LlamaModel(LlamaConfig(**EXPERTS), param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ShardingRuleError, match="serving.*" + REFUSED):
        model.prefill(params, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ShardingRuleError, match="serving.*" + REFUSED):
        model._check_serve()


def test_experts_per_token_must_fit_the_experts():
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        LlamaConfig(**{**EXPERTS, "num_experts_per_tok": 5})
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        LlamaConfig(**{**EXPERTS, "num_experts_per_tok": 0})
