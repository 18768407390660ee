"""DDP mode end-to-end on the 8-device CPU mesh (SURVEY.md §4.3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acco_tpu.models import LlamaConfig, LlamaModel
from acco_tpu.ops.schedules import get_schedule
from acco_tpu.parallel.common import make_flat_loss_fn
from acco_tpu.parallel.ddp import DDPTrainStep
from acco_tpu.parallel.mesh import make_mesh

CFG = LlamaConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=4, max_position_embeddings=32,
)
N_ACC, GLOBAL_BS, SEQ = 2, 8, 16
WD, B1, B2 = 0.1, 0.9, 0.95


def _batches(key, n_acc=N_ACC, bs=GLOBAL_BS, seq=SEQ):
    ids = jax.random.randint(key, (n_acc, bs, seq), 0, CFG.vocab_size, dtype=jnp.int32)
    return {
        "input_ids": ids,
        "attention_mask": jnp.ones_like(ids),
        "labels": ids,
        "valid": jnp.ones((n_acc, 8), jnp.float32),
    }


@pytest.fixture(scope="module")
def trainer(eight_devices):
    mesh = make_mesh()
    model = LlamaModel(CFG, param_dtype=jnp.float32)
    sched = get_schedule("cosine", 3e-3, 0, 100_000)
    t = DDPTrainStep(
        model, mesh, sched, weight_decay=WD, beta1=B1, beta2=B2,
        label_smoothing=0.0, param_dtype=jnp.float32,
    )
    params = model.init(jax.random.PRNGKey(0))
    state = t.init_state(params)
    return t, state


def test_loss_decreases(trainer):
    t, state = trainer
    step = t.step_fn()
    # deterministic next-token structure: ids[b, l] = (3*b + l) % vocab
    b_idx = jnp.arange(GLOBAL_BS)[:, None]
    l_idx = jnp.arange(SEQ)[None, :]
    ids = ((3 * b_idx + l_idx) % CFG.vocab_size).astype(jnp.int32)
    ids = jnp.broadcast_to(ids, (N_ACC, GLOBAL_BS, SEQ))
    batch = {
        "input_ids": ids,
        "attention_mask": jnp.ones_like(ids),
        "labels": ids,
        "valid": jnp.ones((N_ACC, 8), jnp.float32),
    }
    first = last = None
    for _ in range(30):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics.loss)
        last = float(metrics.loss)
    assert last < first * 0.7, (first, last)


def test_grad_count_and_schedule_bookkeeping(trainer):
    t, _ = trainer
    model = t.model
    state = t.init_state(model.init(jax.random.PRNGKey(3)))
    step = t.step_fn()
    state, metrics = step(state, _batches(jax.random.PRNGKey(2)))
    assert float(metrics.grads_this_step) == 8 * N_ACC
    # default LR accounting is reference-faithful: one scheduler step per
    # update (the reference's _step_count bump is a torch no-op — see
    # acco_tpu/ops/schedules.py)
    assert int(state.zero1.sched_grads) == 1
    assert int(state.zero1.opt.count) == 1


def test_lr_grad_accounting_optin(trainer):
    t_ref, _ = trainer
    t = DDPTrainStep(
        t_ref.model, t_ref.mesh, t_ref.schedule, weight_decay=WD, beta1=B1,
        beta2=B2, param_dtype=jnp.float32, lr_grad_accounting=True,
    )
    state = t.init_state(t_ref.model.init(jax.random.PRNGKey(3)))
    state, _ = t.step_fn()(state, _batches(jax.random.PRNGKey(2)))
    # opt-in: scheduler advances by the all-reduced micro-grad count
    assert int(state.zero1.sched_grads) == 8 * N_ACC


def test_one_step_matches_unsharded_math(trainer):
    """The sharded step == plain single-device grad + AdamW math."""
    t, _ = trainer
    model = t.model
    params = model.init(jax.random.PRNGKey(5))
    state = t.init_state(params)
    batch = _batches(jax.random.PRNGKey(6))
    step = t.step_fn()
    new_state, metrics = step(state, batch)

    # Hand-compute: average grad over all ws*n_acc microbatches at params.
    flat = t.layout.ravel(params)  # the step's own order, not ravel_pytree's
    loss_fn = make_flat_loss_fn(model, t.unravel, flat.size, 0.0)
    flat_padded = t.geom.pad_flat(flat)
    total_g = np.zeros(t.geom.padded_size, np.float32)
    for a in range(N_ACC):
        for d in range(8):
            bs_per = GLOBAL_BS // 8
            mb = {
                "input_ids": batch["input_ids"][a, d * bs_per : (d + 1) * bs_per],
                "attention_mask": batch["attention_mask"][a, d * bs_per : (d + 1) * bs_per],
                "labels": batch["labels"][a, d * bs_per : (d + 1) * bs_per],
            }
            total_g += np.asarray(jax.grad(loss_fn)(flat_padded, mb), np.float32)
    g_avg = total_g / (8 * N_ACC)
    lr = float(t.schedule(jnp.int32(0)))
    # first AdamW step: bias corrections cancel, so mu_hat=g, nu_hat=g^2
    expected = np.asarray(flat_padded, np.float32)
    expected = expected * (1 - lr * WD) - lr * g_avg / (np.sqrt(g_avg**2) + 1e-8)
    mask = np.arange(t.geom.padded_size) < t.geom.n_params
    expected = np.where(mask, expected, np.asarray(flat_padded))
    # atol 1e-5: the health guard's where/psum change XLA's fusions, so
    # f32 reductions re-associate at the ULP level vs the hand math —
    # identical semantics, not identical bits (same caveat as
    # test_acco.test_parity_specialized_rounds_match_generic).
    np.testing.assert_allclose(
        np.asarray(new_state.flat_params), expected, rtol=5e-4, atol=1e-5
    )


def test_heterogeneous_microbatch_mask(trainer):
    """Masking device 3's second microbatch: count drops and the update
    equals the count-weighted average (trainer_decoupled.py:85-98)."""
    t, _ = trainer
    model = t.model
    params = model.init(jax.random.PRNGKey(7))
    batch = _batches(jax.random.PRNGKey(8))
    valid = np.ones((N_ACC, 8), np.float32)
    valid[1, 3] = 0.0
    batch_h = dict(batch, valid=jnp.asarray(valid))
    step = t.step_fn()

    state = t.init_state(params)
    new_state, metrics = step(state, batch_h)
    assert float(metrics.grads_this_step) == 8 * N_ACC - 1

    # equivalent dense computation: drop that microbatch, weight by count
    flat = t.layout.ravel(params)  # the step's own order, not ravel_pytree's
    loss_fn = make_flat_loss_fn(model, t.unravel, flat.size, 0.0)
    flat_padded = t.geom.pad_flat(flat)
    total_g = np.zeros(t.geom.padded_size, np.float32)
    for a in range(N_ACC):
        for d in range(8):
            if valid[a, d] == 0.0:
                continue
            bs_per = GLOBAL_BS // 8
            mb = {
                k: batch[k][a, d * bs_per : (d + 1) * bs_per]
                for k in ("input_ids", "attention_mask", "labels")
            }
            total_g += np.asarray(jax.grad(loss_fn)(flat_padded, mb), np.float32)
    g_avg = total_g / (8 * N_ACC - 1)
    lr = float(t.schedule(jnp.int32(0)))
    expected = np.asarray(flat_padded, np.float32)
    expected = expected * (1 - lr * WD) - lr * (g_avg / (np.sqrt(g_avg**2) + 1e-8))
    mask = np.arange(t.geom.padded_size) < t.geom.n_params
    expected = np.where(mask, expected, np.asarray(flat_padded))
    # atol 1e-5: the health guard's where/psum change XLA's fusions, so
    # f32 reductions re-associate at the ULP level vs the hand math —
    # identical semantics, not identical bits (same caveat as
    # test_acco.test_parity_specialized_rounds_match_generic).
    np.testing.assert_allclose(
        np.asarray(new_state.flat_params), expected, rtol=5e-4, atol=1e-5
    )
