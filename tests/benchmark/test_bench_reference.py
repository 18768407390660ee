"""The plain float32 reference against ``GPTNeoModel`` at ``tiny_neo`` size,
and against facts that hold for the architecture whatever the program does."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _paths import ROOT

from benchmark.reference import gpt_neo_ref


@pytest.fixture(scope="module")
def tiny():
    from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel

    path = os.path.join(ROOT, "config", "model", "tiny_neo.json")
    cfg = GPTNeoConfig.from_json(path)
    # float32 parameters and plain einsum attention: the program's own oracle
    model = GPTNeoModel(cfg, param_dtype=jnp.float32, attention="xla")
    params = model.init(jax.random.PRNGKey(3))
    # biases and norm offsets start at zero: make every leaf count
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(
        tree, [a + 0.02 * jax.random.normal(k, a.shape, a.dtype) for a, k in zip(leaves, keys)]
    )
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 48), 0, cfg.vocab_size, jnp.int32)
    import json

    with open(path) as f:
        return model, params, ids, json.load(f)


def test_logits_match_the_program(tiny):
    model, params, ids, cfg = tiny
    with jax.default_matmul_precision("highest"):
        want = gpt_neo_ref.forward(params, ids, cfg)
        got = model.apply(params, ids, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_loss_and_gradients_match_the_program(tiny):
    """Float32 on both sides, so the tolerance is float32's: a few 1e-5."""
    from acco_tpu.ops.losses import model_ce

    model, params, ids, cfg = tiny

    def program_loss(p):
        return model_ce(model, p, ids, None, ids, label_smoothing=0.0, fused=False)

    with jax.default_matmul_precision("highest"):
        loss_p, grads_p = jax.value_and_grad(program_loss)(params)
    loss_r, grads_r = gpt_neo_ref.loss_and_grads(params, ids, cfg)
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    flat_p = jax.tree_util.tree_leaves_with_path(grads_p)
    flat_r = dict(jax.tree_util.tree_leaves_with_path(grads_r))
    assert len(flat_p) == len(flat_r) == 15
    for path, g in flat_p:
        r = np.asarray(flat_r[path])
        err = np.linalg.norm(np.asarray(g) - r) / np.linalg.norm(r)
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def test_window_layers_do_not_see_past_their_window(tiny):
    """Changing a token more than ``window`` positions back changes a local
    layer's output only through the global layers: with every layer local,
    the last position's logits do not move at all."""
    _, params, ids, cfg = tiny
    local = {**cfg, "attention_layers": ["local"] * cfg["num_layers"]}
    reach = cfg["num_layers"] * (cfg["window_size"] - 1)  # receptive field of the stack
    L = reach + 8
    row = jax.random.randint(jax.random.PRNGKey(6), (1, L), 0, cfg["vocab_size"], jnp.int32)
    other = row.at[0, 0].set((row[0, 0] + 1) % cfg["vocab_size"])
    a = gpt_neo_ref.forward(params, row, local)[0, -1]
    b = gpt_neo_ref.forward(params, other, local)[0, -1]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the published pattern has global layers, and they do see it
    c = gpt_neo_ref.forward(params, row, cfg)[0, -1]
    d = gpt_neo_ref.forward(params, other, cfg)[0, -1]
    assert float(jnp.abs(c - d).max()) > 0


def test_causal_and_window_masks():
    m = np.asarray(gpt_neo_ref.allowed_mask(6, 0))
    assert (m == np.tril(np.ones((6, 6), bool))).all()
    w = np.asarray(gpt_neo_ref.allowed_mask(6, 2))
    assert w.sum(axis=1).tolist() == [1, 2, 2, 2, 2, 2]  # itself and one back
    assert w[5].tolist() == [False, False, False, False, True, True]


def test_loss_of_uniform_logits_is_log_vocab(tiny):
    _, params, ids, cfg = tiny
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    assert float(gpt_neo_ref.loss(zero, ids, cfg)) == pytest.approx(np.log(cfg["vocab_size"]), rel=1e-6)
