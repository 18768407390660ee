"""Pipeline parallelism: pp x dp training must match plain dp exactly.

The pp design (parallel/pp.py: TpLayout over layer-stage splits, the
GPipe tick loop whose autodiff is the backward pipeline, the tp-recipe
gradient correction) is validated the way tensor parallelism was
(SURVEY §4.2 equivalence): the same model, microbatch block, and
optimizer on a ``dp``-only mesh and on a ``dp x pp`` mesh must produce
the same losses and the same parameters after several updates — for DDP
and for the speculative/commit ACCO rounds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acco_tpu.models.llama import LlamaConfig, LlamaModel
from acco_tpu.ops.schedules import get_schedule
from acco_tpu.parallel.acco import AccoTrainStep
from acco_tpu.parallel.ddp import DDPTrainStep
from acco_tpu.parallel.mesh import DATA_AXIS, make_mesh

CFG = LlamaConfig(
    vocab_size=64,
    hidden_size=32,
    intermediate_size=48,
    num_layers=4,  # pp=4 stages of 1 / pp=2 stages of 2
    num_heads=4,
    num_kv_heads=2,
    max_position_embeddings=32,
)
OPT = dict(weight_decay=0.1, beta1=0.9, beta2=0.95, param_dtype=jnp.float32)
SCHED = lambda: get_schedule("cosine", 1e-2, 2, 50)
N_ACC, SEQ = 4, 16  # n_acc microbatches ARE the pipeline microbatches


def _params():
    return LlamaModel(CFG, param_dtype=jnp.float32).init(jax.random.PRNGKey(0))


def _batches(key, ws_dp):
    ids = jax.random.randint(
        key, (N_ACC, ws_dp, SEQ), 0, CFG.vocab_size, dtype=jnp.int32
    )
    return {
        "input_ids": ids,
        "attention_mask": jnp.ones_like(ids),
        "labels": ids,
        "valid": jnp.ones((N_ACC, ws_dp), jnp.float32),
    }


def _assert_trees_close(a, b, rtol=2e-5, atol=1e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
        )


def _steps(step_cls, dp, pp, **kw):
    model = LlamaModel(CFG, param_dtype=jnp.float32)
    mesh_dp = make_mesh({DATA_AXIS: dp}, devices=jax.devices()[:dp])
    mesh_2d = make_mesh({DATA_AXIS: dp, "pp": pp})
    ref = step_cls(model, mesh_dp, SCHED(), **OPT, **kw)
    ppstep = step_cls(model, mesh_2d, SCHED(), **OPT, pipeline_axis="pp", **kw)
    return ref, ppstep, _params()


def _dense(step, state):
    flat = np.asarray(jax.device_get(state.flat_params))
    return step.unravel(jnp.asarray(flat[: step.geom.n_params]))


def _pp_dense(step, state):
    stack = np.asarray(jax.device_get(state.flat_params)).reshape(
        step.tp, step.geom.padded_size
    )
    return step.tp_layout.gather_params(stack)


@pytest.mark.parametrize("dp,pp", [(2, 4), (4, 2)])
def test_ddp_pp_matches_dp(eight_devices, dp, pp):
    ref, ppstep, params = _steps(DDPTrainStep, dp, pp)
    s_ref, s_pp = ref.init_state(params), ppstep.init_state(params)
    assert ppstep.num_shards == dp  # ZeRO-1 shards within the pp group
    fr, fp = ref.step_fn(), ppstep.step_fn()
    for i in range(3):
        b = _batches(jax.random.PRNGKey(60 + i), dp)
        s_ref, m_ref = fr(s_ref, b)
        s_pp, m_pp = fp(s_pp, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_pp.loss), rtol=1e-5, atol=1e-6
        )
        assert float(m_ref.grads_this_step) == float(m_pp.grads_this_step)
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(ppstep, s_pp))


@pytest.mark.parametrize("mode", ["acco", "dpu"])
def test_acco_pp_matches_dp(eight_devices, mode):
    dp, pp = 2, 4
    ref, ppstep, params = _steps(AccoTrainStep, dp, pp, mode=mode)
    s_ref, s_pp = ref.init_state(params), ppstep.init_state(params)
    seed = _batches(jax.random.PRNGKey(59), dp)
    s_ref, _ = ref.seed_fn()(s_ref, seed)
    s_pp, _ = ppstep.seed_fn()(s_pp, seed)
    fr, fp = ref.round_fn(), ppstep.round_fn()
    for i in range(4):
        b = _batches(jax.random.PRNGKey(70 + i), dp)
        s_ref, m_ref = fr(s_ref, b)
        s_pp, m_pp = fp(s_pp, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_pp.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(ppstep, s_pp))


def test_ddp_pp_matches_dp_untied_vocab_split(eight_devices):
    """Untied embeddings take the vocab-split wte path (V/pp rows per
    stage + uniform psum'd lookup, model.pp_param_specs) — the Llama-3
    configuration; gradient-exactness must survive the extra psum."""
    import dataclasses

    cfg = dataclasses.replace(CFG, tie_word_embeddings=False)
    model = LlamaModel(cfg, param_dtype=jnp.float32)
    dp, pp = 2, 4
    mesh_dp = make_mesh({DATA_AXIS: dp}, devices=jax.devices()[:dp])
    mesh_2d = make_mesh({DATA_AXIS: dp, "pp": pp})
    ref = DDPTrainStep(model, mesh_dp, SCHED(), **OPT)
    ppstep = DDPTrainStep(model, mesh_2d, SCHED(), **OPT, pipeline_axis="pp")
    params = model.init(jax.random.PRNGKey(0))
    assert model.pp_param_specs()["wte"] == 0  # vocab-split active
    s_ref, s_pp = ref.init_state(params), ppstep.init_state(params)
    fr, fp = ref.step_fn(), ppstep.step_fn()
    for i in range(3):
        b = _batches(jax.random.PRNGKey(80 + i), dp)
        s_ref, m_ref = fr(s_ref, b)
        s_pp, m_pp = fp(s_pp, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_pp.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(ppstep, s_pp))


def test_pp_rejects_bad_pairings(eight_devices):
    model = LlamaModel(CFG, param_dtype=jnp.float32)
    # tp x pp needs a model built WITH the tensor axis (its block psums
    # run inside the pipeline stages)
    mesh_3d = make_mesh({DATA_AXIS: 2, "pp": 2, "tp": 2})
    with pytest.raises(ValueError, match="must be built with"):
        DDPTrainStep(
            model, mesh_3d, SCHED(), **OPT, pipeline_axis="pp",
            tensor_axis="tp",
        )
    mesh8 = make_mesh({DATA_AXIS: 1, "pp": 8})  # 8 does not divide 4 layers
    with pytest.raises(ValueError, match="divide num_layers"):
        DDPTrainStep(model, mesh8, SCHED(), **OPT, pipeline_axis="pp")


def test_trainer_pp_end_to_end(eight_devices, tmp_path):
    """Full DecoupledTrainer run on the dp x pp mesh: training, the pp
    eval path (pipelined shard_map loss), and the checkpoint's dense
    params.npz export reassembled from the per-stage stack."""
    import os

    from acco_tpu.configuration import config_from_dict
    from acco_tpu.data.tokenizer import ByteTokenizer
    from acco_tpu.trainer import DecoupledTrainer

    rng = np.random.default_rng(0)
    docs = [
        {"input_ids": rng.integers(0, 64, size=16).tolist()} for _ in range(64)
    ]
    args = config_from_dict(
        dict(
            method_name="acco",
            batch_size=2,
            n_grad_accumulation=4,  # >= pp: pipeline microbatches
            learning_rate=1e-3,
            weight_decay=0.0,
            adam_beta1=0.9,
            adam_beta2=0.95,
            nb_steps_tot=32,
            max_length=16,
            scheduler_name="constant",
            warmup=0,
            use_mixed_precision=False,
            eval=True,
            eval_step=16,
            save=True,
            const_len_batch=True,
            checkpoint_every_s=10_000,
            mesh_shape={"dp": 2, "pp": 4},
            run_name="pp",
        )
    )
    from acco_tpu.parallel.tp import pad_vocab

    model = LlamaModel(
        LlamaConfig(
            vocab_size=257, hidden_size=32, intermediate_size=64,
            num_layers=4, num_heads=2, num_kv_heads=2,
            max_position_embeddings=16,
        ),
        param_dtype=jnp.float32,
        # the pp embedding/head are vocab-parallel: pad 257 -> a pp
        # multiple (Megatron convention, automatic through main.py)
        vocab_pad_to=pad_vocab(257, 4),
    )
    t = DecoupledTrainer(
        model, ByteTokenizer(), docs, docs[:16], args, seed=0,
        run_dir=str(tmp_path),
    )
    assert t.pipeline_axis == "pp" and t.world_size == 2
    summary = t.train()
    assert np.isfinite(summary["final_loss"])
    assert np.isfinite(t.evaluate(t.final_state.flat_params))
    from acco_tpu.utils.checkpoint import latest_checkpoint

    path = latest_checkpoint(
        os.path.join(str(tmp_path), "checkpoints", "pp")
    )
    assert path is not None
    npz = np.load(os.path.join(path, "params.npz"))["flat_params"]
    # export strips the Megatron vocab padding -> UNPADDED dense size
    plain = LlamaModel(model.config, param_dtype=jnp.float32)
    n_dense = sum(
        int(np.prod(l.shape))
        for l in jax.tree.leaves(plain.init(jax.random.PRNGKey(0)))
    )
    assert npz.size == n_dense and np.isfinite(npz).all()


def test_pp_eval_matches_dp_eval(eight_devices, tmp_path):
    """The pipelined eval path (multi-microbatch block with token-count
    valid weights) must compute the SAME global token mean as the plain
    jit eval on identical parameters and eval data (const-len packed —
    the only data shape pp serves)."""
    from acco_tpu.configuration import config_from_dict
    from acco_tpu.data.tokenizer import ByteTokenizer
    from acco_tpu.parallel.tp import pad_vocab
    from acco_tpu.trainer import DecoupledTrainer

    rng = np.random.default_rng(7)
    docs = [
        {"input_ids": rng.integers(0, 64, size=16).tolist()}
        for _ in range(64)
    ]

    def build(mesh_shape, run):
        args = config_from_dict(
            dict(
                method_name="acco", batch_size=8, n_grad_accumulation=4,
                learning_rate=1e-3, weight_decay=0.0, adam_beta1=0.9,
                adam_beta2=0.95, nb_steps_tot=0, max_length=16,
                scheduler_name="constant", warmup=0,
                use_mixed_precision=False, eval=False, save=False,
                const_len_batch=True, checkpoint_every_s=10_000,
                mesh_shape=mesh_shape, run_name=run,
            )
        )
        model = LlamaModel(
            LlamaConfig(
                vocab_size=257, hidden_size=32, intermediate_size=64,
                num_layers=4, num_heads=2, num_kv_heads=2,
                max_position_embeddings=16,
            ),
            param_dtype=jnp.float32,
            vocab_pad_to=pad_vocab(257, 4),
        )
        return DecoupledTrainer(
            model, ByteTokenizer(), docs, docs, args, seed=0,
            run_dir=str(tmp_path / run),
        )

    t_dp = build({"dp": 8}, "dp")
    t_pp = build({"dp": 2, "pp": 4}, "pp")
    # zero training steps: final_state is the seed-0 init on both, so the
    # two trainers hold identical parameters in their own layouts
    t_dp.train()
    t_pp.train()
    loss_dp = t_dp.evaluate(t_dp.final_state.flat_params)
    loss_pp = t_pp.evaluate(t_pp.final_state.flat_params)
    np.testing.assert_allclose(loss_dp, loss_pp, rtol=2e-5, atol=1e-6)

from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel

NEO_CFG = GPTNeoConfig(
    vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
    max_position_embeddings=32, window_size=8,
    attention_layers=["global", "local", "global", "local"],
)


@pytest.mark.parametrize("dp,pp", [(2, 4), (4, 2)])
def test_gptneo_ddp_pp_matches_dp(eight_devices, dp, pp):
    """GPT-Neo pipeline stages: the absolute-layer-indexed window pattern
    must land on the right stage slice (dynamic_slice at stage_index), the
    tied vocab-split wte must serve both the lookup and the CE."""
    model = GPTNeoModel(NEO_CFG, param_dtype=jnp.float32)
    mesh_dp = make_mesh({DATA_AXIS: dp}, devices=jax.devices()[:dp])
    mesh_2d = make_mesh({DATA_AXIS: dp, "pp": pp})
    ref = DDPTrainStep(model, mesh_dp, SCHED(), **OPT)
    ppstep = DDPTrainStep(model, mesh_2d, SCHED(), **OPT, pipeline_axis="pp")
    params = model.init(jax.random.PRNGKey(1))
    s_ref, s_pp = ref.init_state(params), ppstep.init_state(params)
    fr, fp = ref.step_fn(), ppstep.step_fn()
    for i in range(3):
        b = _batches(jax.random.PRNGKey(90 + i), dp)
        s_ref, m_ref = fr(s_ref, b)
        s_pp, m_pp = fp(s_pp, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_pp.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(ppstep, s_pp))


def test_gptneo_acco_pp_matches_dp(eight_devices):
    dp, pp = 2, 4
    model = GPTNeoModel(NEO_CFG, param_dtype=jnp.float32)
    mesh_dp = make_mesh({DATA_AXIS: dp}, devices=jax.devices()[:dp])
    mesh_2d = make_mesh({DATA_AXIS: dp, "pp": pp})
    ref = AccoTrainStep(model, mesh_dp, SCHED(), **OPT, mode="acco")
    ppstep = AccoTrainStep(
        model, mesh_2d, SCHED(), **OPT, mode="acco", pipeline_axis="pp"
    )
    params = model.init(jax.random.PRNGKey(1))
    s_ref, s_pp = ref.init_state(params), ppstep.init_state(params)
    seed = _batches(jax.random.PRNGKey(89), dp)
    s_ref, _ = ref.seed_fn()(s_ref, seed)
    s_pp, _ = ppstep.seed_fn()(s_pp, seed)
    fr, fp = ref.round_fn(), ppstep.round_fn()
    for i in range(4):
        b = _batches(jax.random.PRNGKey(95 + i), dp)
        s_ref, m_ref = fr(s_ref, b)
        s_pp, m_pp = fp(s_pp, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_pp.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(ppstep, s_pp))


# -- tp x pp composition ----------------------------------------------------

def _composed_steps(step_cls, **kw):
    dp, pp, tp = 2, 2, 2
    dense = LlamaModel(CFG, param_dtype=jnp.float32)
    tp_model = LlamaModel(CFG, param_dtype=jnp.float32, tensor_axis="tp")
    mesh_dp = make_mesh({DATA_AXIS: dp}, devices=jax.devices()[:dp])
    mesh_3d = make_mesh({DATA_AXIS: dp, "pp": pp, "tp": tp})
    ref = step_cls(dense, mesh_dp, SCHED(), **OPT, **kw)
    comp = step_cls(
        tp_model, mesh_3d, SCHED(), **OPT,
        pipeline_axis="pp", tensor_axis="tp", **kw,
    )
    return ref, comp, dense.init(jax.random.PRNGKey(0))


def test_ddp_tp_pp_composed_matches_dp(eight_devices):
    """dp x pp x tp: stages hold head/ffn slices of their layers, the
    vocab splits over the combined (pp, tp) index, ZeRO-1 shards within
    each (stage, tp-shard)'s dp slice, and the two-segment gradient
    correction (ComposedLayout + zero1) reproduces plain dp exactly."""
    ref, comp, params = _composed_steps(DDPTrainStep)
    s_ref, s_c = ref.init_state(params), comp.init_state(params)
    assert comp.tp == 4 and comp.num_shards == 2
    lay = comp.tp_layout
    assert 0 < lay.n_repl_both < lay.n_repl < lay.n_local
    fr, fc = ref.step_fn(), comp.step_fn()
    for i in range(3):
        b = _batches(jax.random.PRNGKey(100 + i), 2)
        s_ref, m_ref = fr(s_ref, b)
        s_c, m_c = fc(s_c, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_c.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(comp, s_c))


def test_acco_tp_pp_composed_matches_dp(eight_devices):
    ref, comp, params = _composed_steps(AccoTrainStep, mode="acco")
    s_ref, s_c = ref.init_state(params), comp.init_state(params)
    seed = _batches(jax.random.PRNGKey(99), 2)
    s_ref, _ = ref.seed_fn()(s_ref, seed)
    s_c, _ = comp.seed_fn()(s_c, seed)
    fr, fc = ref.round_fn(), comp.round_fn()
    for i in range(4):
        b = _batches(jax.random.PRNGKey(110 + i), 2)
        s_ref, m_ref = fr(s_ref, b)
        s_c, m_c = fc(s_c, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_c.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(comp, s_c))


def test_gptneo_tp_pp_composed_matches_dp(eight_devices):
    """GPT-Neo on the dp x pp x tp mesh: stage-sliced windows + head-split
    fused qkv + sublayer psums inside pipeline stages (review finding:
    stage_blocks must honor tensor_axis, not silently skip the psums)."""
    dense = GPTNeoModel(NEO_CFG, param_dtype=jnp.float32)
    tp_model = GPTNeoModel(
        NEO_CFG, param_dtype=jnp.float32, tensor_axis="tp"
    )
    dp, pp, tp = 2, 2, 2
    mesh_dp = make_mesh({DATA_AXIS: dp}, devices=jax.devices()[:dp])
    mesh_3d = make_mesh({DATA_AXIS: dp, "pp": pp, "tp": tp})
    ref = DDPTrainStep(dense, mesh_dp, SCHED(), **OPT)
    comp = DDPTrainStep(
        tp_model, mesh_3d, SCHED(), **OPT,
        pipeline_axis="pp", tensor_axis="tp",
    )
    params = dense.init(jax.random.PRNGKey(2))
    s_ref, s_c = ref.init_state(params), comp.init_state(params)
    fr, fc = ref.step_fn(), comp.step_fn()
    for i in range(3):
        b = _batches(jax.random.PRNGKey(120 + i), dp)
        s_ref, m_ref = fr(s_ref, b)
        s_c, m_c = fc(s_c, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_c.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(comp, s_c))


# -- pp x sp composition ----------------------------------------------------

@pytest.mark.parametrize("zigzag", [False, True])
def test_ddp_pp_sp_composed_matches_dp(eight_devices, zigzag):
    """dp x pp x sp: ring attention runs INSIDE every pipeline stage (the
    sequence sharded over sp, activations flowing stages over pp), the
    loss is the psum'd global token mean of pre-shifted labels; must
    reproduce plain dp exactly, both sequence layouts."""
    dense = LlamaModel(CFG, param_dtype=jnp.float32)
    ring = LlamaModel(
        CFG, param_dtype=jnp.float32, attention="ring", sequence_axis="sp",
        zigzag=zigzag,
    )
    dp, pp, sp = 2, 2, 2
    mesh_dp = make_mesh({DATA_AXIS: dp}, devices=jax.devices()[:dp])
    mesh_3d = make_mesh({DATA_AXIS: dp, "pp": pp, "sp": sp})
    ref = DDPTrainStep(dense, mesh_dp, SCHED(), **OPT)
    comp = DDPTrainStep(
        ring, mesh_3d, SCHED(), **OPT, pipeline_axis="pp", seq_axis="sp"
    )
    assert comp.num_shards == dp * sp  # ZeRO-1 over dp x sp per stage
    params = dense.init(jax.random.PRNGKey(3))
    s_ref, s_c = ref.init_state(params), comp.init_state(params)
    fr, fc = ref.step_fn(), comp.step_fn()
    for i in range(3):
        b = _batches(jax.random.PRNGKey(130 + i), dp)
        s_ref, m_ref = fr(s_ref, b)
        s_c, m_c = fc(s_c, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_c.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(comp, s_c))


def test_acco_pp_sp_composed_matches_dp(eight_devices):
    dense = LlamaModel(CFG, param_dtype=jnp.float32)
    ring = LlamaModel(
        CFG, param_dtype=jnp.float32, attention="ring", sequence_axis="sp",
        zigzag=True,
    )
    dp = 2
    mesh_dp = make_mesh({DATA_AXIS: dp}, devices=jax.devices()[:dp])
    mesh_3d = make_mesh({DATA_AXIS: dp, "pp": 2, "sp": 2})
    ref = AccoTrainStep(dense, mesh_dp, SCHED(), **OPT, mode="acco")
    comp = AccoTrainStep(
        ring, mesh_3d, SCHED(), **OPT, mode="acco",
        pipeline_axis="pp", seq_axis="sp",
    )
    params = dense.init(jax.random.PRNGKey(3))
    s_ref, s_c = ref.init_state(params), comp.init_state(params)
    seed = _batches(jax.random.PRNGKey(129), dp)
    s_ref, _ = ref.seed_fn()(s_ref, seed)
    s_c, _ = comp.seed_fn()(s_c, seed)
    fr, fc = ref.round_fn(), comp.round_fn()
    for i in range(4):
        b = _batches(jax.random.PRNGKey(140 + i), dp)
        s_ref, m_ref = fr(s_ref, b)
        s_c, m_c = fc(s_c, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_c.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(comp, s_c))


@pytest.mark.parametrize("zigzag", [False, True])
def test_gptneo_ddp_pp_sp_composed_matches_dp(eight_devices, zigzag):
    """GPT-Neo pp x sp (the reference's flagship pretrain model on the
    full composition matrix): windowed ring attention runs inside every
    pipeline stage with the stage-sliced window pattern, and the learned
    position table is looked up at the sequence shard's absolute
    positions in pp_embed — both layouts."""
    dense = GPTNeoModel(NEO_CFG, param_dtype=jnp.float32)
    ring = GPTNeoModel(
        NEO_CFG, param_dtype=jnp.float32, attention="ring",
        sequence_axis="sp", zigzag=zigzag,
    )
    dp, pp, sp = 2, 2, 2
    mesh_dp = make_mesh({DATA_AXIS: dp}, devices=jax.devices()[:dp])
    mesh_3d = make_mesh({DATA_AXIS: dp, "pp": pp, "sp": sp})
    ref = DDPTrainStep(dense, mesh_dp, SCHED(), **OPT)
    comp = DDPTrainStep(
        ring, mesh_3d, SCHED(), **OPT, pipeline_axis="pp", seq_axis="sp"
    )
    params = dense.init(jax.random.PRNGKey(5))
    s_ref, s_c = ref.init_state(params), comp.init_state(params)
    fr, fc = ref.step_fn(), comp.step_fn()
    for i in range(3):
        b = _batches(jax.random.PRNGKey(150 + i), dp)
        s_ref, m_ref = fr(s_ref, b)
        s_c, m_c = fc(s_c, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_c.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(comp, s_c))


def test_gptneo_acco_pp_sp_composed_matches_dp(eight_devices):
    dense = GPTNeoModel(NEO_CFG, param_dtype=jnp.float32)
    ring = GPTNeoModel(
        NEO_CFG, param_dtype=jnp.float32, attention="ring",
        sequence_axis="sp", zigzag=True,
    )
    dp = 2
    mesh_dp = make_mesh({DATA_AXIS: dp}, devices=jax.devices()[:dp])
    mesh_3d = make_mesh({DATA_AXIS: dp, "pp": 2, "sp": 2})
    ref = AccoTrainStep(dense, mesh_dp, SCHED(), **OPT, mode="acco")
    comp = AccoTrainStep(
        ring, mesh_3d, SCHED(), **OPT, mode="acco",
        pipeline_axis="pp", seq_axis="sp",
    )
    params = dense.init(jax.random.PRNGKey(5))
    s_ref, s_c = ref.init_state(params), comp.init_state(params)
    seed = _batches(jax.random.PRNGKey(149), dp)
    s_ref, _ = ref.seed_fn()(s_ref, seed)
    s_c, _ = comp.seed_fn()(s_c, seed)
    fr, fc = ref.round_fn(), comp.round_fn()
    for i in range(4):
        b = _batches(jax.random.PRNGKey(160 + i), dp)
        s_ref, m_ref = fr(s_ref, b)
        s_c, m_c = fc(s_c, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_c.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(comp, s_c))


def test_ddp_four_axis_composition(eight_devices):
    """All four axes at once — dp x pp x tp x sp (1x2x2x2): tensor-split
    ring-attention stages over a sequence-sharded pipeline. The layout
    machinery composes (model_axis=(pp,tp), ZeRO over dp x sp); must
    still reproduce plain-dp math exactly."""
    dense = LlamaModel(CFG, param_dtype=jnp.float32)
    ring_tp = LlamaModel(
        CFG, param_dtype=jnp.float32, attention="ring", sequence_axis="sp",
        zigzag=True, tensor_axis="tp",
    )
    mesh_dp = make_mesh({DATA_AXIS: 1}, devices=jax.devices()[:1])
    mesh_4d = make_mesh({DATA_AXIS: 1, "pp": 2, "tp": 2, "sp": 2})
    ref = DDPTrainStep(dense, mesh_dp, SCHED(), **OPT)
    comp = DDPTrainStep(
        ring_tp, mesh_4d, SCHED(), **OPT,
        pipeline_axis="pp", tensor_axis="tp", seq_axis="sp",
    )
    params = dense.init(jax.random.PRNGKey(4))
    s_ref, s_c = ref.init_state(params), comp.init_state(params)
    fr, fc = ref.step_fn(), comp.step_fn()
    for i in range(3):
        b = _batches(jax.random.PRNGKey(150 + i), 1)
        s_ref, m_ref = fr(s_ref, b)
        s_c, m_c = fc(s_c, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_c.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(comp, s_c))


def test_acco_four_axis_composition(eight_devices):
    """The ACCO round itself on all four axes — dp x pp x tp x sp
    (1x2x2x2): the speculative/commit trajectory with grads-at-θ̃
    carry-in must reproduce the plain-dp ACCO rounds exactly through the
    composed layout (the DDP four-axis case alone does not exercise the
    two-program parity specialization or the round-state plumbing)."""
    dense = LlamaModel(CFG, param_dtype=jnp.float32)
    ring_tp = LlamaModel(
        CFG, param_dtype=jnp.float32, attention="ring", sequence_axis="sp",
        zigzag=True, tensor_axis="tp",
    )
    mesh_dp = make_mesh({DATA_AXIS: 1}, devices=jax.devices()[:1])
    mesh_4d = make_mesh({DATA_AXIS: 1, "pp": 2, "tp": 2, "sp": 2})
    ref = AccoTrainStep(dense, mesh_dp, SCHED(), **OPT, mode="acco")
    comp = AccoTrainStep(
        ring_tp, mesh_4d, SCHED(), **OPT, mode="acco",
        pipeline_axis="pp", tensor_axis="tp", seq_axis="sp",
    )
    params = dense.init(jax.random.PRNGKey(4))
    s_ref, s_c = ref.init_state(params), comp.init_state(params)
    seed = _batches(jax.random.PRNGKey(169), 1)
    s_ref, _ = ref.seed_fn()(s_ref, seed)
    s_c, _ = comp.seed_fn()(s_c, seed)
    fr, fc = ref.round_fn(), comp.round_fn()
    for i in range(4):
        b = _batches(jax.random.PRNGKey(170 + i), 1)
        s_ref, m_ref = fr(s_ref, b)
        s_c, m_c = fc(s_c, b)
        np.testing.assert_allclose(
            float(m_ref.loss), float(m_c.loss), rtol=1e-5, atol=1e-6
        )
    _assert_trees_close(_dense(ref, s_ref), _pp_dense(comp, s_c))


def test_trainer_pp_sp_end_to_end(eight_devices, tmp_path):
    """DecoupledTrainer on the dp x pp x sp mesh: pipelined ring-attention
    training plus the composed eval path (chunked pre-shifted labels
    through the pipelined loss)."""
    from acco_tpu.configuration import config_from_dict
    from acco_tpu.data.tokenizer import ByteTokenizer
    from acco_tpu.parallel.tp import pad_vocab
    from acco_tpu.trainer import DecoupledTrainer

    rng = np.random.default_rng(1)
    docs = [
        {"input_ids": rng.integers(0, 64, size=16).tolist()} for _ in range(64)
    ]
    args = config_from_dict(
        dict(
            method_name="acco",
            batch_size=2,
            n_grad_accumulation=2,
            learning_rate=1e-3,
            weight_decay=0.0,
            adam_beta1=0.9,
            adam_beta2=0.95,
            nb_steps_tot=16,
            max_length=16,
            scheduler_name="constant",
            warmup=0,
            use_mixed_precision=False,
            eval=True,
            eval_step=8,
            save=False,
            const_len_batch=True,
            checkpoint_every_s=10_000,
            mesh_shape={"dp": 2, "pp": 2, "sp": 2},
            run_name="ppsp",
        )
    )
    model = LlamaModel(
        LlamaConfig(
            vocab_size=257, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=2, num_kv_heads=2,
            max_position_embeddings=16,
        ),
        param_dtype=jnp.float32,
        attention="ring",
        sequence_axis="sp",
        zigzag=True,
        vocab_pad_to=pad_vocab(257, 2),
    )
    t = DecoupledTrainer(
        model, ByteTokenizer(), docs, docs[:16], args, seed=0,
        run_dir=str(tmp_path),
    )
    assert t.pipeline_axis == "pp" and t.seq_axis == "sp"
    summary = t.train()
    assert np.isfinite(summary["final_loss"])
    assert np.isfinite(t.evaluate(t.final_state.flat_params))
