"""Attention-impl resolution and selective remat (CPU-testable parts;
flash-kernel numerics are validated on TPU — see acco_tpu/ops/attention.py
docstrings for the measured crossover)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel
from acco_tpu.models.llama import LlamaConfig, LlamaModel
from acco_tpu.ops.attention import resolve_attention_impl

CFG = LlamaConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=2, num_kv_heads=2, max_position_embeddings=32,
)


def test_resolve_forced():
    assert resolve_attention_impl("flash", 64, "cpu") == "flash"
    assert resolve_attention_impl("xla", 8192, "tpu") == "xla"
    assert resolve_attention_impl(True, 64, "cpu") == "flash"
    assert resolve_attention_impl(False, 8192, "tpu") == "xla"


def test_resolve_auto():
    # CPU never gets the pallas kernel
    assert resolve_attention_impl("auto", 8192, "cpu") == "xla"
    # TPU: only long, block-aligned sequences
    assert resolve_attention_impl("auto", 1024, "tpu") == "xla"
    assert resolve_attention_impl("auto", 2048, "tpu") == "flash"
    assert resolve_attention_impl("auto", 2048 + 128, "tpu") == "xla"  # misaligned


def test_resolve_auto_is_remat_aware():
    # Where the fused kernel does not apply (no head_dim given here): under
    # a remat policy the flash kernel waits for 4096, a line no chip run
    # stands behind (the resolver's docstring).
    assert resolve_attention_impl("auto", 2048, "tpu", remat="dots") == "xla"
    assert resolve_attention_impl("auto", 4096, "tpu", remat="dots") == "flash"
    assert resolve_attention_impl("auto", 2048, "tpu", remat=False) == "flash"


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("seq_len", [512, 1536, 2048, 4096, 8192])
def test_flash_block_sizes_fit_the_shape(seq_len, head_dim):
    """The tiles are a function of the shape: every size divides the
    sequence, every minor divides its major (``BlockSizes`` checks that
    itself, at construction), the backward kernels have theirs, and no
    kernel is left at the stock 128 x 128."""
    import dataclasses

    from acco_tpu.ops.attention import flash_block_sizes

    sizes = flash_block_sizes(seq_len, head_dim)
    assert sizes.has_backward_blocks and sizes.block_b == 1
    tiles = {k: v for k, v in dataclasses.asdict(sizes).items() if k != "block_b"}
    assert len(tiles) == 10
    for name, size in tiles.items():
        assert seq_len % size == 0 and size % 128 == 0, (name, size)
        assert size >= min(seq_len, 512), (name, size)
    for major, minor in [
        ("block_k_major", "block_k"),
        ("block_q_major_dkv", "block_q_dkv"),
        ("block_k_major_dkv", "block_k_dkv"),
        ("block_k_major_dq", "block_k_dq"),
    ]:
        assert tiles[major] % tiles[minor] == 0, (major, minor)
    # the measured cell: [1, 16, 4096, 128], and the rule's other lengths
    if seq_len % 1024 == 0:
        assert (sizes.block_q, sizes.block_k_major, sizes.block_k) == (1024,) * 3
        assert (
            sizes.block_q_major_dkv, sizes.block_q_dkv,
            sizes.block_k_major_dkv, sizes.block_k_dkv,
        ) == (1024, 512, 1024, 1024)
        assert (sizes.block_q_dq, sizes.block_k_major_dq, sizes.block_k_dq) == (1024, 512, 512)


def test_flash_block_sizes_refuse_an_untiled_length():
    from acco_tpu.ops.attention import flash_block_sizes

    with pytest.raises(ValueError, match="by 128"):
        flash_block_sizes(3000, 128)


def test_resolve_rejects_unknown():
    with pytest.raises(ValueError, match="auto/flash/fused/xla"):
        resolve_attention_impl("splash", 64, "cpu")


def test_remat_typos_rejected():
    """Genuine typos still fail loudly; case/int/bool-string spellings
    normalize (ops.attention.normalize_remat — the one shared map, so
    'Dots' means 'dots' here exactly as remat=1 means True on the CLI)."""
    from acco_tpu.models.layers import wrap_remat

    with pytest.raises(ValueError, match="remat must be"):
        wrap_remat(lambda c, x: (c, x), "dot")
    model = LlamaModel(CFG, param_dtype=jnp.float32, remat="dotz")
    with pytest.raises(ValueError, match="remat must be"):
        model.apply(
            model.init(jax.random.PRNGKey(0)),
            jnp.zeros((1, 8), jnp.int32),
            jnp.ones((1, 8), jnp.int32),
        )
    # case-variant spelling now normalizes instead of raising
    ok = LlamaModel(CFG, param_dtype=jnp.float32, remat="Dots")
    ok.apply(
        ok.init(jax.random.PRNGKey(0)),
        jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), jnp.int32),
    )


def test_gpt_neo_rejects_flash():
    with pytest.raises(ValueError, match="sliding-window"):
        GPTNeoModel(GPTNeoConfig(num_layers=2, attention_layers=["global", "local"]),
                    attention="flash")


@pytest.mark.parametrize("remat", [True, "dots"])
def test_remat_modes_match_no_remat(remat):
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64, dtype=jnp.int32)
    am = jnp.ones((2, 16), jnp.int32)
    params = LlamaModel(CFG, param_dtype=jnp.float32).init(jax.random.PRNGKey(1))

    def loss(model, p):
        return model.apply(p, ids, am).astype(jnp.float32).sum()

    base = LlamaModel(CFG, param_dtype=jnp.float32, remat=False)
    test = LlamaModel(CFG, param_dtype=jnp.float32, remat=remat)
    np.testing.assert_allclose(
        float(loss(base, params)), float(loss(test, params)), rtol=1e-6
    )
    gb = jax.grad(lambda p: loss(base, p))(params)
    gt = jax.grad(lambda p: loss(test, p))(params)
    for a, b in zip(jax.tree.leaves(gb), jax.tree.leaves(gt)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_dots_probs_remat_matches_dots(eight_devices):
    """remat='dots+probs' changes what the backward stores, not the math:
    losses and grads match remat='dots' (the probs are saved in the same
    bf16/f32 dtype the recompute would produce)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from acco_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
        num_heads=4, num_kv_heads=2, max_position_embeddings=16,
    )
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64, jnp.int32)
    labels = ids

    def loss_for(remat):
        model = LlamaModel(cfg, param_dtype=jnp.float32, remat=remat)
        params = model.init(jax.random.PRNGKey(0))

        def loss(p):
            logits = model.apply(p, ids, jnp.ones_like(ids))
            from acco_tpu.ops.losses import causal_lm_loss

            return causal_lm_loss(logits, labels, 0.0)

        l, g = jax.value_and_grad(loss)(params)
        return float(l), g

    l_dots, g_dots = loss_for("dots")
    l_probs, g_probs = loss_for("dots+probs")
    np.testing.assert_allclose(l_dots, l_probs, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_dots), jax.tree.leaves(g_probs)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )
