"""Tensor parallelism: tp x dp training must match plain dp exactly.

The tp recipe (parallel/tp.py: per-shard local flat vectors, Megatron
head/ffn splits, the measured check_vma=False gradient correction) is
validated end-to-end: the same model, batches, and optimizer run on a
``dp``-only mesh and on a ``dp x tp`` mesh must produce the same losses
and the same parameters after several optimizer updates — for DDP, for
the speculative/commit ACCO rounds, and combined with context
parallelism (dp x sp x tp).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acco_tpu.models.llama import LlamaConfig, LlamaModel
from acco_tpu.ops.schedules import get_schedule
from acco_tpu.parallel.acco import AccoTrainStep
from acco_tpu.parallel.common import synthetic_block
from acco_tpu.parallel.ddp import DDPTrainStep
from acco_tpu.parallel.mesh import DATA_AXIS, make_mesh
from acco_tpu.parallel.tp import TpLayout

CFG = LlamaConfig(
    vocab_size=64,
    hidden_size=32,
    intermediate_size=48,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    max_position_embeddings=32,
)
OPT = dict(weight_decay=0.1, beta1=0.9, beta2=0.95, param_dtype=jnp.float32)
SCHED = lambda: get_schedule("cosine", 1e-2, 2, 50)


def _params():
    return LlamaModel(CFG, param_dtype=jnp.float32).init(jax.random.PRNGKey(0))


def _dense_pytree(step, state):
    flat = np.asarray(jax.device_get(state.flat_params))
    return step.unravel(jnp.asarray(flat[: step.geom.n_params]))


def _tp_pytree(step, state):
    stack = np.asarray(jax.device_get(state.flat_params)).reshape(
        step.tp, step.geom.padded_size
    )
    return step.tp_layout.gather_params(stack)


def _assert_trees_close(a, b, rtol=2e-5, atol=1e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
        )


# Parameter-trajectory comparisons use a loose atol: AdamW's
# mu_hat/(sqrt(nu_hat)+eps) is sign-like for near-zero gradients, so
# float32 reduction-order noise on a tiny-gradient element legitimately
# produces O(lr) divergence. The *gradient*-level test below carries the
# precision burden (f32-noise tolerance, no optimizer amplification).
TRAJ_TOL = dict(rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_acco_tp_gradients_match_dp(eight_devices, smoothing):
    """The staged gradient vector after the seed round, mapped back to the
    parameter pytree, must match the dp-only gradients to float32 noise —
    this pins the check_vma=False tp correction (sharded /tp, replicated
    pmean) AND the vocab-parallel CE (psum'd lse / label logit / smoothing
    term) without AdamW's near-zero amplification."""
    params = _params()
    grads = {}
    for tag, mesh_shape, tp_axis in (
        ("dp", {DATA_AXIS: 2}, None),
        ("tp", {DATA_AXIS: 2, "tp": 2}, "tp"),
    ):
        n_dev = int(np.prod(list(mesh_shape.values())))
        mesh = make_mesh(mesh_shape, devices=eight_devices[:n_dev])
        model = LlamaModel(CFG, param_dtype=jnp.float32, tensor_axis=tp_axis)
        step = AccoTrainStep(
            model, mesh, SCHED(), mode="acco", tensor_axis=tp_axis,
            label_smoothing=smoothing, **OPT
        )
        state = step.init_state(params)
        state, _ = step.seed_fn()(
            state, synthetic_block(mesh, DATA_AXIS, CFG.vocab_size, 1, 2, 16, seed=7)
        )
        pending = np.asarray(jax.device_get(state.pending_grads))
        Pp = step.geom.padded_size
        if tp_axis:
            # [tp, dp, Pp]: sum the dp partials, then apply the recipe —
            # sharded segment /tp, replicated prefix mean over tp.
            g = pending.reshape(step.tp, step.num_shards, Pp).sum(1)
            nr = step.tp_layout.n_repl
            fixed = np.concatenate(
                [np.broadcast_to(g[:, :nr].mean(0), (step.tp, nr)), g[:, nr:] / step.tp],
                axis=1,
            )
            grads[tag] = step.tp_layout.gather_params(fixed)
        else:
            g = pending.reshape(step.num_shards, Pp).sum(0)
            grads[tag] = step.unravel(jnp.asarray(g[: step.geom.n_params]))
    _assert_trees_close(grads["dp"], grads["tp"], rtol=2e-5, atol=1e-6)


def test_tp_layout_roundtrip(eight_devices):
    params = _params()
    layout = TpLayout(params, LlamaModel(CFG).tp_param_specs(), 2)
    stack = layout.stack_flat(params)
    rec = layout.gather_params(stack)
    _assert_trees_close(rec, params, rtol=0, atol=0)
    assert 0 < layout.n_repl < layout.n_local
    # Dense reassembly must stay on host: at tp's target scale the full
    # model does not fit one chip, so no leaf may become a jax.Array.
    assert all(isinstance(l, np.ndarray) for l in jax.tree.leaves(rec))


@pytest.mark.parametrize("steps", [3])
def test_ddp_tp_matches_dp(eight_devices, steps):
    params = _params()
    batches = {}
    losses = {}
    finals = {}
    for tag, mesh_shape, tp_axis in (
        ("dp", {DATA_AXIS: 2}, None),
        ("tp", {DATA_AXIS: 2, "tp": 2}, "tp"),
    ):
        n_dev = int(np.prod(list(mesh_shape.values())))
        mesh = make_mesh(mesh_shape, devices=eight_devices[:n_dev])
        model = LlamaModel(CFG, param_dtype=jnp.float32, tensor_axis=tp_axis)
        step = DDPTrainStep(
            model, mesh, SCHED(), tensor_axis=tp_axis, **OPT
        )
        state = step.init_state(params)
        fn = step.step_fn()
        ls = []
        for i in range(steps):
            block = synthetic_block(mesh, DATA_AXIS, CFG.vocab_size, 2, 2, 16, seed=i)
            state, m = fn(state, block)
            ls.append(float(m.loss))
        losses[tag] = ls
        finals[tag] = (
            _tp_pytree(step, state) if tp_axis else _dense_pytree(step, state)
        )
    np.testing.assert_allclose(losses["dp"], losses["tp"], rtol=1e-5)
    _assert_trees_close(finals["dp"], finals["tp"], **TRAJ_TOL)


def test_acco_tp_matches_dp(eight_devices):
    params = _params()
    losses = {}
    finals = {}
    for tag, mesh_shape, tp_axis in (
        ("dp", {DATA_AXIS: 2}, None),
        ("tp", {DATA_AXIS: 2, "tp": 2}, "tp"),
    ):
        n_dev = int(np.prod(list(mesh_shape.values())))
        mesh = make_mesh(mesh_shape, devices=eight_devices[:n_dev])
        model = LlamaModel(CFG, param_dtype=jnp.float32, tensor_axis=tp_axis)
        step = AccoTrainStep(
            model, mesh, SCHED(), mode="acco", tensor_axis=tp_axis, **OPT
        )
        state = step.init_state(params)
        state, _ = step.seed_fn()(
            state, synthetic_block(mesh, DATA_AXIS, CFG.vocab_size, 1, 2, 16, seed=99)
        )
        fns = [step.round_fn(parity=True), step.round_fn(parity=False)]
        ls = []
        for i in range(4):
            block = synthetic_block(mesh, DATA_AXIS, CFG.vocab_size, 1, 2, 16, seed=i)
            state, m = fns[i % 2](state, block)
            ls.append(float(m.loss))
        losses[tag] = ls
        finals[tag] = (
            _tp_pytree(step, state) if tp_axis else _dense_pytree(step, state)
        )
    np.testing.assert_allclose(losses["dp"], losses["tp"], rtol=1e-5)
    _assert_trees_close(finals["dp"], finals["tp"], **TRAJ_TOL)


def test_acco_tp_with_context_parallelism(eight_devices):
    """dp x sp x tp (8 devices) vs dp x sp: ring attention composes with
    tensor parallelism (sequence sharded over sp, heads over tp)."""
    params = _params()
    losses = {}
    finals = {}
    for tag, mesh_shape, tp_axis in (
        ("cp", {DATA_AXIS: 2, "sp": 2}, None),
        ("cp+tp", {DATA_AXIS: 2, "sp": 2, "tp": 2}, "tp"),
    ):
        n_dev = int(np.prod(list(mesh_shape.values())))
        mesh = make_mesh(mesh_shape, devices=eight_devices[:n_dev])
        model = LlamaModel(
            CFG,
            param_dtype=jnp.float32,
            attention="ring",
            sequence_axis="sp",
            tensor_axis=tp_axis,
        )
        step = AccoTrainStep(
            model,
            mesh,
            SCHED(),
            mode="acco",
            seq_axis="sp",
            tensor_axis=tp_axis,
            **OPT,
        )
        state = step.init_state(params)
        fns = [step.round_fn(parity=True), step.round_fn(parity=False)]
        state, _ = step.seed_fn()(
            state,
            synthetic_block(
                mesh, DATA_AXIS, CFG.vocab_size, 1, 2, 16, seed=99, seq_axis="sp"
            ),
        )
        ls = []
        for i in range(2):
            block = synthetic_block(
                mesh, DATA_AXIS, CFG.vocab_size, 1, 2, 16, seed=i, seq_axis="sp"
            )
            state, m = fns[i % 2](state, block)
            ls.append(float(m.loss))
        losses[tag] = ls
        finals[tag] = (
            _tp_pytree(step, state) if tp_axis else _dense_pytree(step, state)
        )
    np.testing.assert_allclose(losses["cp"], losses["cp+tp"], rtol=1e-5)
    _assert_trees_close(finals["cp"], finals["cp+tp"], **TRAJ_TOL)


def test_trainer_tp_end_to_end(eight_devices, tmp_path):
    """Full DecoupledTrainer run on a dp x tp mesh: warmup DPU rounds +
    handover (the warm step must inherit tp_layout or the replicated-
    prefix grad psum silently vanishes), the tp eval path (shard_map loss
    with the tp flat spec), the cross-tp-shard consistency of replicated
    parameters, and the dense params.npz export."""
    from acco_tpu.configuration import config_from_dict
    from acco_tpu.data.tokenizer import ByteTokenizer
    from acco_tpu.trainer import DecoupledTrainer

    rng = np.random.default_rng(0)
    docs = [
        {"input_ids": rng.integers(0, 64, size=24).tolist()} for _ in range(64)
    ]
    args = config_from_dict(
        dict(
            method_name="acco",
            batch_size=1,
            n_grad_accumulation=1,
            learning_rate=1e-3,
            weight_decay=0.0,
            adam_beta1=0.9,
            adam_beta2=0.95,
            nb_steps_tot=16,
            max_length=16,
            scheduler_name="constant",
            warmup=0,
            n_warmup_steps=2,
            use_mixed_precision=False,
            eval=True,
            eval_step=8,
            save=True,
            mesh_shape={DATA_AXIS: 4, "tp": 2},
            run_name="tp",
        )
    )
    model = LlamaModel(
        LlamaConfig(
            # 258 = ByteTokenizer's 257 padded to a tp=2 multiple (the
            # Megatron vocab-padding convention the layout requires)
            vocab_size=258, hidden_size=32, intermediate_size=64, num_layers=1,
            num_heads=2, num_kv_heads=2, max_position_embeddings=16,
        ),
        param_dtype=jnp.float32,
        tensor_axis="tp",
    )
    t = DecoupledTrainer(
        model, ByteTokenizer(), docs, docs[:16], args, seed=0,
        run_dir=str(tmp_path),
    )
    assert t.tensor_axis == "tp" and t.world_size == 4
    summary = t.train()
    assert np.isfinite(summary["final_loss"])
    assert np.isfinite(t.evaluate(t.final_state.flat_params))

    # Replicated-prefix consistency: after warmup + decoupled rounds, the
    # "replicated" leaves (wte, norms) must be bit-identical on every tp
    # shard — they diverge if any round skips the tp grad psum.
    step = t.step_obj
    stacked = np.asarray(jax.device_get(t.final_state.flat_params)).reshape(
        step.tp, step.geom.padded_size
    )
    nr = step.tp_layout.n_repl
    np.testing.assert_array_equal(stacked[0, :nr], stacked[1, :nr])

    # params.npz must hold the DENSE layout (not tp shard 0's local vector).
    import glob

    from jax.flatten_util import ravel_pytree

    npz = sorted(glob.glob(str(tmp_path) + "/checkpoints/tp/step_*/params.npz"))
    assert npz, "params.npz not written"
    flat = np.load(npz[-1])["flat_params"]
    dense = ravel_pytree(step.tp_layout.gather_params(stacked))[0]
    np.testing.assert_allclose(flat, np.asarray(dense, np.float32), rtol=1e-6)


def test_padded_vocab_tp_matches_unpadded_dense(eight_devices):
    """Odd vocab under tp (Megatron padding, parallel/tp.pad_vocab):
    tp2 with vocab 63 padded to 64 must reproduce the UNPADDED dense
    model's gradients exactly — padded positions are excluded from the
    softmax and the smoothing mean, carry ~zero gradient, and unpad_vocab
    strips them for export."""
    from acco_tpu.parallel.tp import pad_vocab

    assert pad_vocab(50257, 2) == pad_vocab(50257, 4) == 50304
    assert pad_vocab(64, 2) == 64  # already divisible: no padding

    odd_cfg = LlamaConfig(
        vocab_size=63, hidden_size=32, intermediate_size=48, num_layers=2,
        num_heads=4, num_kv_heads=2, max_position_embeddings=32,
    )
    dense_model = LlamaModel(odd_cfg, param_dtype=jnp.float32)
    params = dense_model.init(jax.random.PRNGKey(0))
    grads = {}
    for tag, mesh_shape, tp_axis in (
        ("dp", {DATA_AXIS: 2}, None),
        ("tp", {DATA_AXIS: 2, "tp": 2}, "tp"),
    ):
        n_dev = int(np.prod(list(mesh_shape.values())))
        mesh = make_mesh(mesh_shape, devices=eight_devices[:n_dev])
        pad_to = pad_vocab(odd_cfg.vocab_size, 2) if tp_axis else None
        model = LlamaModel(
            odd_cfg, param_dtype=jnp.float32, tensor_axis=tp_axis,
            vocab_pad_to=pad_to,
        )
        p = params
        if pad_to:
            p = dict(params)
            p["wte"] = jnp.pad(params["wte"], ((0, pad_to - 63), (0, 0)))
        step = AccoTrainStep(
            model, mesh, SCHED(), mode="acco", tensor_axis=tp_axis,
            label_smoothing=0.1, **OPT
        )
        state = step.init_state(p)
        state, _ = step.seed_fn()(
            state, synthetic_block(mesh, DATA_AXIS, 63, 1, 2, 16, seed=7)
        )
        pending = np.asarray(jax.device_get(state.pending_grads))
        Pp = step.geom.padded_size
        if tp_axis:
            g = pending.reshape(step.tp, step.num_shards, Pp).sum(1)
            nr = step.tp_layout.n_repl
            fixed = np.concatenate(
                [np.broadcast_to(g[:, :nr].mean(0), (step.tp, nr)), g[:, nr:] / step.tp],
                axis=1,
            )
            padded_tree = step.tp_layout.gather_params(fixed)
            # padded rows must carry (numerically) zero gradient
            pad_grads = np.asarray(padded_tree["wte"])[63:]
            assert np.abs(pad_grads).max() < 1e-6, pad_grads
            grads[tag] = model.unpad_vocab(padded_tree)
        else:
            g = pending.reshape(step.num_shards, Pp).sum(0)
            grads[tag] = step.unravel(jnp.asarray(g[: step.geom.n_params]))
    _assert_trees_close(grads["dp"], grads["tp"], rtol=2e-5, atol=1e-6)


def test_gpt_neo_tp_gradients_match_dp(eight_devices):
    """GPT-Neo tensor parallelism (3-way-split fused qkv, sharded-ffn
    biases, post-psum replicated biases, vocab-parallel tied head, the
    alternating local/global windows): staged gradients on dp x tp must
    match plain dp to float32 noise."""
    from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel

    neo_cfg = GPTNeoConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_position_embeddings=32, window_size=8,
        attention_layers=["global", "local"],
    )
    params = GPTNeoModel(neo_cfg, param_dtype=jnp.float32).init(
        jax.random.PRNGKey(0)
    )
    grads = {}
    for tag, mesh_shape, tp_axis in (
        ("dp", {DATA_AXIS: 2}, None),
        ("tp", {DATA_AXIS: 2, "tp": 2}, "tp"),
    ):
        n_dev = int(np.prod(list(mesh_shape.values())))
        mesh = make_mesh(mesh_shape, devices=eight_devices[:n_dev])
        model = GPTNeoModel(neo_cfg, param_dtype=jnp.float32, tensor_axis=tp_axis)
        step = AccoTrainStep(
            model, mesh, SCHED(), mode="acco", tensor_axis=tp_axis, **OPT
        )
        state = step.init_state(params)
        state, _ = step.seed_fn()(
            state,
            synthetic_block(mesh, DATA_AXIS, neo_cfg.vocab_size, 1, 2, 16, seed=7),
        )
        pending = np.asarray(jax.device_get(state.pending_grads))
        Pp = step.geom.padded_size
        if tp_axis:
            g = pending.reshape(step.tp, step.num_shards, Pp).sum(1)
            nr = step.tp_layout.n_repl
            fixed = np.concatenate(
                [np.broadcast_to(g[:, :nr].mean(0), (step.tp, nr)), g[:, nr:] / step.tp],
                axis=1,
            )
            grads[tag] = step.tp_layout.gather_params(fixed)
        else:
            g = pending.reshape(step.num_shards, Pp).sum(0)
            grads[tag] = step.unravel(jnp.asarray(g[: step.geom.n_params]))
    _assert_trees_close(grads["dp"], grads["tp"], rtol=2e-5, atol=1e-6)


def test_tp_axis_mismatch_rejected(eight_devices):
    mesh = make_mesh({DATA_AXIS: 2, "tp": 2}, devices=eight_devices[:4])
    model = LlamaModel(CFG, param_dtype=jnp.float32)  # no tensor_axis
    with pytest.raises(ValueError, match="tensor_axis"):
        DDPTrainStep(model, mesh, SCHED(), tensor_axis="tp", **OPT)
