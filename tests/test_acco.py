"""ACCO/DPU round-program semantics (SURVEY.md §4.2 equivalence tests).

The guardrail: a pure-numpy simulator of the reference's round semantics
(speculative even / real odd, accumulate-across-half-rounds, count-weighted
averaging — trainer_decoupled.py:431-598) is stepped against the compiled
shard_map round on the 8-device CPU mesh; trajectories must match.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acco_tpu.models import LlamaConfig, LlamaModel
from acco_tpu.ops.schedules import get_schedule
from acco_tpu.parallel.acco import AccoTrainStep
from acco_tpu.parallel.common import make_flat_loss_fn
from acco_tpu.parallel.mesh import make_mesh

CFG = LlamaConfig(
    vocab_size=32, hidden_size=16, intermediate_size=32, num_layers=1,
    num_heads=2, num_kv_heads=2, max_position_embeddings=16,
)
WS, N_ACC, SEQ = 8, 1, 8
WD, B1, B2, EPS = 0.1, 0.9, 0.95, 1e-8
LR = 3e-3


def _batch(key, n_acc=N_ACC):
    ids = jax.random.randint(key, (n_acc, WS, SEQ), 0, CFG.vocab_size, dtype=jnp.int32)
    return {
        "input_ids": ids,
        "attention_mask": jnp.ones_like(ids),
        "labels": ids,
        "valid": jnp.ones((n_acc, WS), jnp.float32),
    }


def _make(mode, lr_grad_accounting=False):
    mesh = make_mesh()
    model = LlamaModel(CFG, param_dtype=jnp.float32)
    sched = get_schedule("constant", LR, 0, 1000)
    t = AccoTrainStep(
        model, mesh, sched, weight_decay=WD, beta1=B1, beta2=B2,
        label_smoothing=0.0, param_dtype=jnp.float32, mode=mode,
        lr_grad_accounting=lr_grad_accounting,
    )
    params = model.init(jax.random.PRNGKey(0))
    state = t.init_state(params)
    return t, state, params


class _Sim:
    """Numpy re-derivation of the reference's ACCO/DPU round semantics."""

    def __init__(self, flat0, grad_fn, geom, mode):
        self.grad_fn = grad_fn  # (flat_padded, micro) -> flat grad
        self.geom = geom
        self.mode = mode
        self.params = np.asarray(flat0, np.float64)  # working params (padded)
        self.opt_p = self.params.copy()
        self.mu = np.zeros_like(self.opt_p)
        self.nu = np.zeros_like(self.opt_p)
        self.t = 0
        self.grad = np.zeros_like(self.opt_p)
        self.count = 0.0
        self.pending = np.zeros_like(self.opt_p)
        self.pending_count = 0.0
        self.r = 0
        self.mask = (np.arange(geom.padded_size) < geom.n_params).astype(np.float64)

    def _adamw(self, g, lr):
        t = self.t + 1
        mu = B1 * self.mu + (1 - B1) * g
        nu = B2 * self.nu + (1 - B2) * g * g
        mu_hat = mu / (1 - B1**t)
        nu_hat = nu / (1 - B2**t)
        p = self.opt_p * (1 - lr * WD * self.mask) - (
            lr * mu_hat / (np.sqrt(nu_hat) + EPS)
        ) * self.mask
        return p, mu, nu, t

    def seed(self, micros):
        for mb in micros:
            self.grad += self.grad_fn(self.params, mb)
            self.count += 1
        self.pending = self.grad.copy()
        self.pending_count = self.count
        if self.mode == "dpu":  # one-round staleness: seed commits once
            self.grad = np.zeros_like(self.grad)
            self.count = 0.0

    def round(self, micros):
        speculative = (self.r % 2 == 0) if self.mode == "acco" else False
        zero_after = (self.r % 2 == 0) if self.mode == "acco" else True
        # comm branch on pending
        g_avg = self.pending / max(self.pending_count, 1.0)
        new_p, mu, nu, t = self._adamw(g_avg, LR)
        if not speculative:
            self.opt_p, self.mu, self.nu, self.t = new_p, mu, nu, t
        # compute branch at current params
        for mb in micros:
            self.grad += self.grad_fn(self.params, mb)
            self.count += 1
        # swap
        self.params = new_p.copy()
        self.pending = self.grad.copy()
        self.pending_count = self.count
        if zero_after:
            self.grad = np.zeros_like(self.grad)
            self.count = 0.0
        self.r += 1


def _micros_for(batch):
    """Split a global batch into the ws*n_acc per-device microbatches."""
    out = []
    for a in range(batch["input_ids"].shape[0]):
        for d in range(WS):
            out.append(
                {
                    "input_ids": batch["input_ids"][a, d : d + 1],
                    "attention_mask": batch["attention_mask"][a, d : d + 1],
                    "labels": batch["labels"][a, d : d + 1],
                }
            )
    return out


@pytest.mark.parametrize("mode", ["acco", "dpu"])
def test_trajectory_matches_simulator(eight_devices, mode):
    t, state, params = _make(mode)
    flat = t.layout.ravel(params)  # the step's own order, not ravel_pytree's
    loss_fn = make_flat_loss_fn(t.model, t.unravel, t.geom.n_params, 0.0)
    grad_fn = lambda fp, mb: np.asarray(
        jax.grad(loss_fn)(jnp.asarray(fp, jnp.float32), mb), np.float64
    )
    sim = _Sim(t.geom.pad_flat(flat), grad_fn, t.geom, mode)

    seed_batch = _batch(jax.random.PRNGKey(100))
    state, _ = t.seed_fn()(state, seed_batch)
    sim.seed(_micros_for(seed_batch))
    np.testing.assert_allclose(
        np.asarray(state.flat_params), sim.params, rtol=1e-5, atol=1e-6
    )

    rnd = t.round_fn()
    for r in range(6):
        batch = _batch(jax.random.PRNGKey(200 + r))
        state, metrics = rnd(state, batch)
        sim.round(_micros_for(batch))
        np.testing.assert_allclose(
            np.asarray(state.flat_params), sim.params, rtol=2e-4, atol=2e-6,
            err_msg=f"round {r} ({mode})",
        )
        assert bool(metrics.is_real_update) == (
            (r % 2 == 1) if mode == "acco" else True
        )
    # after 6 rounds: acco committed 3 real updates, dpu 6 (+the seed none)
    assert int(state.zero1.opt.count) == (3 if mode == "acco" else 6)


def test_speculative_rollback_preserves_opt_state(eight_devices):
    """Even round: params become θ̃ but optimizer state is untouched —
    the reference's snapshot/rollback (trainer_decoupled.py:79-84,113-126)
    expressed functionally."""
    t, state, _ = _make("acco")
    state, _ = t.seed_fn()(state, _batch(jax.random.PRNGKey(1)))
    before_opt = jax.tree.map(np.asarray, state.zero1.opt)
    before_params = np.asarray(state.flat_params)
    before_sched = int(state.zero1.sched_grads)

    state, metrics = t.round_fn()(state, _batch(jax.random.PRNGKey(2)))
    assert not bool(metrics.is_real_update)
    for a, b in zip(jax.tree.leaves(before_opt), jax.tree.leaves(
        jax.tree.map(np.asarray, state.zero1.opt)
    )):
        np.testing.assert_array_equal(a, b)
    assert int(state.zero1.sched_grads) == before_sched
    # ...but the working params did move to the estimate
    assert not np.allclose(np.asarray(state.flat_params), before_params)


def test_parity_specialized_rounds_match_generic(eight_devices):
    """round_fn(parity=...) compiles rollback/zeroing-free programs; their
    trajectory must be identical to the generic traced-parity program."""
    t1, s1, params = _make("acco")
    t2 = AccoTrainStep(
        t1.model, t1.mesh, t1.schedule, weight_decay=WD, beta1=B1, beta2=B2,
        label_smoothing=0.0, param_dtype=jnp.float32, mode="acco",
    )
    s2 = t2.init_state(params)
    seed = _batch(jax.random.PRNGKey(7))
    s1, _ = t1.seed_fn()(s1, seed)
    s2, _ = t2.seed_fn()(s2, seed)
    generic = t1.round_fn()
    for r in range(4):
        batch = _batch(jax.random.PRNGKey(300 + r))
        s1, m1 = generic(s1, batch)
        s2, m2 = t2.round_fn(parity=(r % 2 == 0))(s2, batch)
        assert bool(m1.is_real_update) == bool(m2.is_real_update) == (r % 2 == 1)
    # Folding the selects changes XLA's fusions, so reductions re-associate
    # at the ULP level — identical semantics, not identical bits.
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )


def test_acco_learns(eight_devices):
    t, state, _ = _make("acco")
    b_idx = jnp.arange(WS)[:, None]
    l_idx = jnp.arange(SEQ)[None, :]
    ids = jnp.broadcast_to(
        ((b_idx + l_idx) % CFG.vocab_size).astype(jnp.int32), (N_ACC, WS, SEQ)
    )
    batch = {
        "input_ids": ids,
        "attention_mask": jnp.ones_like(ids),
        "labels": ids,
        "valid": jnp.ones((N_ACC, WS), jnp.float32),
    }
    state, _ = t.seed_fn()(state, batch)
    rnd = t.round_fn()
    losses = []
    for _ in range(60):
        state, m = rnd(state, batch)
        losses.append(float(m.loss))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_heterogeneous_counts_flow_through(eight_devices):
    t, state, _ = _make("acco")
    state, _ = t.seed_fn()(state, _batch(jax.random.PRNGKey(3), n_acc=2))
    valid = np.ones((2, WS), np.float32)
    valid[1, :4] = 0.0  # 4 slow workers skip their 2nd microbatch
    batch = dict(_batch(jax.random.PRNGKey(4), n_acc=2), valid=jnp.asarray(valid))
    state, m = t.round_fn()(state, batch)
    # round 0's comm consumed the seed counts (all valid)
    assert float(m.round_grads) == 2 * WS
    state, m = t.round_fn()(state, _batch(jax.random.PRNGKey(5), n_acc=2))
    # round 1 consumed seed(16) + round-0 compute (16 - 4 masked) = 28
    assert float(m.round_grads) == 2 * WS + (2 * WS - 4)
