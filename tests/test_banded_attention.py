"""Banded sliding-window attention kernel (ops/banded_attention.py).

Parity against the einsum reference (the same oracle the full fused
kernel tests use), the GPT-Neo model-level cond dispatch and the envelope
gate. The interpreter accepts layouts Mosaic rejects, so the kernels are
also compiled for the chip at the real GPT-Neo pretrain dims, in
tests/test_tpu_compile.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acco_tpu.ops.attention import attention_mask_bias, dot_product_attention
from acco_tpu.ops.banded_attention import (
    banded_dot_product_attention,
    supports_banded_attention,
)


def _qkv(key, L=256, B=1, H=2, D=64, dtype=jnp.float32):
    return tuple(
        jax.random.normal(jax.random.fold_in(key, i), (B, H, L, D)).astype(
            dtype
        )
        for i in range(3)
    )


@pytest.mark.parametrize(
    "L,window",
    [
        # Fast representative set (stays in tier-1): nprev=1, a
        # non-QB-multiple window, and the W%128==1 off-by-one widths.
        (256, 128),
        (256, 200),
        (384, 129),
        (512, 257),
        # Heaviest widths (3-5 s each of interpret-mode grad checks):
        # marked slow so this file stays small inside the tier-1 window
        # even on a cold cache — the shapes above already cover every
        # nprev band count and boundary case these re-exercise at size.
        pytest.param(384, 100, marks=pytest.mark.slow),
        pytest.param(512, 256, marks=pytest.mark.slow),
        pytest.param(512, 300, marks=pytest.mark.slow),
        pytest.param(640, 384, marks=pytest.mark.slow),
    ],
)
def test_forward_and_grads_match_einsum(L, window):
    """Band widths covering nprev = 1, 2, 3 and non-QB-multiple windows;
    forward and all three gradients against the einsum+bias oracle."""
    q, k, v = _qkv(jax.random.PRNGKey(0), L=L)
    bias = attention_mask_bias(L, window, None)

    def ref(q, k, v):
        return dot_product_attention(q, k, v, bias, scale=0.125)

    def got(q, k, v):
        return banded_dot_product_attention(
            q, k, v, window=window, scale=0.125, interpret=True
        )

    np.testing.assert_allclose(
        got(q, k, v), ref(q, k, v), atol=2e-5, rtol=2e-5
    )
    gr = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(lambda *a: (got(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gb):
        np.testing.assert_allclose(b, a, atol=5e-4, rtol=5e-4, err_msg=name)


def test_nprev_band_count():
    """ceil((W-1)/QB), not ceil(W/QB): the lowest in-window key for row i
    is i-W+1, so a window one past a block multiple must NOT cost an
    extra (fully masked) KV block per grid cell (round-5 ADVICE #3)."""
    from acco_tpu.ops.banded_attention import _QB, _nprev

    assert _nprev(1) == 0  # diagonal-only window
    assert _nprev(_QB) == 1
    assert _nprev(_QB + 1) == 1  # the off-by-one width: was 2
    assert _nprev(2 * _QB) == 2
    assert _nprev(2 * _QB + 1) == 2  # was 3
    assert _nprev(256) == 2  # shipped GPT-Neo width: unchanged


def test_bf16_inputs():
    q, k, v = _qkv(jax.random.PRNGKey(4), dtype=jnp.bfloat16)
    got = banded_dot_product_attention(q, k, v, window=128, interpret=True)
    bias = attention_mask_bias(256, 128, None)
    want = dot_product_attention(q, k, v, bias)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), atol=3e-2, rtol=3e-2
    )


def test_envelope_gate():
    assert supports_banded_attention(1024, 64, 256)
    assert supports_banded_attention(8192, 64, 256)  # past the full
    # kernel's L=2048 VMEM wall: the band never grows with L
    assert not supports_banded_attention(1024, 64, 0)  # global: full kernel
    assert not supports_banded_attention(256, 64, 256)  # window >= L
    assert not supports_banded_attention(1000, 64, 256)  # L % QB
    assert not supports_banded_attention(1024, 96, 256)  # head_dim % 64
    assert not supports_banded_attention(1024, 64, 1000)  # band > 8 blocks
    with pytest.raises(ValueError, match="MHA-only"):
        q = jnp.zeros((1, 4, 256, 64), jnp.bfloat16)
        kv = jnp.zeros((1, 2, 256, 64), jnp.bfloat16)
        banded_dot_product_attention(q, kv, kv, window=128, interpret=True)


def test_gptneo_model_banded_matches_xla(monkeypatch):
    """The model-level lax.cond dispatch (global -> full kernel, local ->
    banded): logits and parameter gradients match the einsum model."""
    from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel

    monkeypatch.setenv("ACCO_FUSED_ATTN_INTERPRET", "1")
    cfg = GPTNeoConfig(
        vocab_size=128, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=2, max_position_embeddings=128,
        window_size=64, attention_layers=["global", "local"],
    )
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 128), 0, 128)

    def loss_and_grad(model):
        params = model.init(jax.random.PRNGKey(3))

        def loss(p):
            return jnp.mean(model.apply(p, ids).astype(jnp.float32) ** 2)

        return loss(params), jax.grad(loss)(params)

    l_fused, g_fused = loss_and_grad(
        GPTNeoModel(cfg, param_dtype=jnp.float32, attention="fused")
    )
    l_xla, g_xla = loss_and_grad(
        GPTNeoModel(cfg, param_dtype=jnp.float32, attention="xla")
    )
    np.testing.assert_allclose(l_fused, l_xla, rtol=2e-5)
    for pa, pb in zip(jax.tree.leaves(g_fused), jax.tree.leaves(g_xla)):
        np.testing.assert_allclose(pa, pb, atol=2e-4, rtol=2e-3)


def test_gptneo_einsum_plan_banded_local_matches_xla(monkeypatch):
    """The einsum plan's banded-local dispatch (attention='auto' where
    'auto' does NOT pick the full-tile kernel — the CPU here, L > 2048 on
    the chip): global layers keep the pure einsum path, local layers take
    the banded kernel; logits match the explicit-'xla' model (which must
    stay the untouched einsum oracle)."""
    from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel

    monkeypatch.setenv("ACCO_FUSED_ATTN_INTERPRET", "1")
    cfg = GPTNeoConfig(
        vocab_size=128, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=2, max_position_embeddings=128,
        window_size=64, attention_layers=["global", "local"],
    )
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 128), 0, 128)

    def logits(model):
        params = model.init(jax.random.PRNGKey(6))
        return model.apply(params, ids, None)

    auto = GPTNeoModel(cfg, param_dtype=jnp.float32, attention="auto")
    xla = GPTNeoModel(cfg, param_dtype=jnp.float32, attention="xla")
    # the auto model really took the banded-local plan
    assert auto._dense_attn_plan(128, None)[1] is True
    assert xla._dense_attn_plan(128, None)[1] is False
    np.testing.assert_allclose(
        logits(auto), logits(xla), atol=2e-4, rtol=2e-4
    )
