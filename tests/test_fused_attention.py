"""Bespoke VMEM-resident attention kernel vs the einsum reference path.

Runs the Pallas kernel in interpreter mode on CPU (``interpret=True``)
— the same kernel code the TPU compiles — and checks forward and
gradients against ``ops.attention.dot_product_attention`` at float32
tolerance, across the mask surface the models use: causal, sliding
window (traced scalar, as in GPT-Neo's layer scan), key padding, GQA,
and GPT-Neo's unscaled-score quirk (scale=1.0).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acco_tpu.ops.attention import attention_mask_bias, dot_product_attention
from acco_tpu.ops.fused_attention import (
    fused_dot_product_attention,
    supports_fused_attention,
)

B, H, L, D = 2, 4, 128, 64


def _qkv(key, hkv=H, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, L, D), dtype)
    k = jax.random.normal(kk, (B, hkv, L, D), dtype)
    v = jax.random.normal(kv, (B, hkv, L, D), dtype)
    return q, k, v


def _ref(q, k, v, window=0, pad_mask=None, scale=None):
    bias = attention_mask_bias(L, window, pad_mask)
    return dot_product_attention(q, k, v, bias, scale=scale)


@pytest.mark.parametrize("window", [0, 32])
@pytest.mark.parametrize("scale", [None, 1.0])
def test_forward_matches_einsum(window, scale):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    got = fused_dot_product_attention(
        q, k, v, window=window, scale=scale, interpret=True
    )
    want = _ref(q, k, v, window=window, scale=scale)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_forward_padding_mask():
    q, k, v = _qkv(jax.random.PRNGKey(1))
    pad = jnp.ones((B, L), jnp.int32).at[:, L // 2 :].set(0)
    got = fused_dot_product_attention(q, k, v, pad_mask=pad, interpret=True)
    want = _ref(q, k, v, pad_mask=pad)
    # compare only real-token query rows; pad rows are don't-care
    np.testing.assert_allclose(
        got[:, :, : L // 2], want[:, :, : L // 2], atol=2e-5, rtol=2e-5
    )


def test_forward_gqa():
    q, k, v = _qkv(jax.random.PRNGKey(2), hkv=2)
    got = fused_dot_product_attention(q, k, v, interpret=True)
    want = _ref(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [0, 32])
def test_gradients_match_einsum(window):
    q, k, v = _qkv(jax.random.PRNGKey(3))
    t = jax.random.normal(jax.random.PRNGKey(4), (B, H, L, D))

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, k, v) * t)  # weighted sum: dense cotangent

        return jax.grad(f, argnums=(0, 1, 2))

    fused = functools.partial(
        fused_dot_product_attention, window=window, interpret=True
    )
    ref = functools.partial(_ref, window=window)
    for g, w in zip(loss(fused)(q, k, v), loss(ref)(q, k, v)):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


def test_gradients_gqa_accumulate():
    # dK/dV accumulate across the q-head grid steps sharing a KV head
    q, k, v = _qkv(jax.random.PRNGKey(5), hkv=1)
    t = jax.random.normal(jax.random.PRNGKey(6), (B, H, L, D))

    def mk(fn):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * t), argnums=(0, 1, 2)
        )

    fused = functools.partial(fused_dot_product_attention, interpret=True)
    for g, w in zip(mk(fused)(q, k, v), mk(_ref)(q, k, v)):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


def test_gradients_padding_mask():
    q, k, v = _qkv(jax.random.PRNGKey(7))
    pad = jnp.ones((B, L), jnp.int32).at[:, 3 * L // 4 :].set(0)
    t = jax.random.normal(jax.random.PRNGKey(8), (B, H, L, D))
    t = t * pad[:, None, :, None]  # loss ignores pad query rows, as the CE does

    def mk(fn):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v, pad) * t), argnums=(0, 1, 2)
        )

    fused = functools.partial(fused_dot_product_attention, interpret=True)
    ref = lambda q, k, v, pad: _ref(q, k, v, pad_mask=pad)
    for g, w in zip(mk(fused)(q, k, v), mk(ref)(q, k, v)):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


def test_traced_window_under_scan():
    # GPT-Neo's layer scan feeds window as scanned data: one compiled
    # body must serve global (0) and local layers
    q, k, v = _qkv(jax.random.PRNGKey(9))
    windows = jnp.asarray([0, 32], jnp.int32)

    @jax.jit
    def scan_fused(q, k, v):
        def body(x, w):
            return x, fused_dot_product_attention(
                q, k, v, window=w, interpret=True
            )

        _, outs = jax.lax.scan(body, 0, windows)
        return outs

    outs = scan_fused(q, k, v)
    for idx, w in enumerate([0, 32]):
        np.testing.assert_allclose(
            outs[idx], _ref(q, k, v, window=w), atol=2e-5, rtol=2e-5
        )


def test_shape_gate():
    assert supports_fused_attention(1024, 64)
    assert supports_fused_attention(2048, 128)
    assert not supports_fused_attention(4096, 64)  # scores exceed VMEM
    assert not supports_fused_attention(1000, 64)  # unaligned
    assert not supports_fused_attention(64, 64)  # sub-tile
    q, k, v = _qkv(jax.random.PRNGKey(10))
    with pytest.raises(ValueError, match="VMEM envelope"):
        fused_dot_product_attention(q[:, :, :64], k[:, :, :64], v[:, :, :64])


def test_llama_model_fused_matches_xla():
    # full model: logits AND parameter gradients through the kernel
    from acco_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=2, num_kv_heads=2,
        max_position_embeddings=128,
    )
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 128), 0, 128)

    def loss_fn(model):
        params = model.init(jax.random.PRNGKey(1))

        def loss(p):
            logits = model.apply(p, ids)
            return jnp.mean(
                jnp.sum(jax.nn.log_softmax(logits) ** 2, axis=-1)
            )

        return loss(params), jax.grad(loss)(params)

    import os

    os.environ["ACCO_FUSED_ATTN_INTERPRET"] = "1"
    try:
        l_fused, g_fused = loss_fn(
            LlamaModel(cfg, param_dtype=jnp.float32, attention="fused")
        )
    finally:
        del os.environ["ACCO_FUSED_ATTN_INTERPRET"]
    l_xla, g_xla = loss_fn(
        LlamaModel(cfg, param_dtype=jnp.float32, attention="xla")
    )
    np.testing.assert_allclose(l_fused, l_xla, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4),
        g_fused,
        g_xla,
    )


def test_gptneo_model_fused_matches_xla():
    # alternating global/local windows ride through the scan as traced
    # SMEM scalars; the unscaled-score quirk is preserved
    from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel

    cfg = GPTNeoConfig(
        vocab_size=128, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=2, max_position_embeddings=128,
        window_size=32, attention_layers=["global", "local"],
    )
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 128), 0, 128)

    def logits_of(model):
        params = model.init(jax.random.PRNGKey(3))
        return model.apply(params, ids)

    import os

    os.environ["ACCO_FUSED_ATTN_INTERPRET"] = "1"
    try:
        got = logits_of(
            GPTNeoModel(cfg, param_dtype=jnp.float32, attention="fused")
        )
    finally:
        del os.environ["ACCO_FUSED_ATTN_INTERPRET"]
    want = logits_of(
        GPTNeoModel(cfg, param_dtype=jnp.float32, attention="xla")
    )
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_auto_resolution_picks_fused_on_tpu():
    from acco_tpu.ops.attention import resolve_attention_impl

    assert resolve_attention_impl("auto", 1024, "tpu", head_dim=64) == "fused"
    assert (
        resolve_attention_impl("auto", 1024, "tpu", remat="dots", head_dim=64)
        == "fused"
    )
    # the envelope's ceiling, under the 2.7B cells' policy: measured 18-19%
    # faster than the einsum there (PR 29), so no length line inside it
    assert (
        resolve_attention_impl("auto", 2048, "tpu", remat="dots", head_dim=128)
        == "fused"
    )
    assert resolve_attention_impl("auto", 1536, "tpu", head_dim=64) == "fused"
    # outside the VMEM envelope: previous crossover logic
    assert resolve_attention_impl("auto", 4096, "tpu", head_dim=64) == "flash"
    assert resolve_attention_impl("auto", 2048 + 512, "tpu", head_dim=128) == "flash"
    assert (
        resolve_attention_impl("auto", 2048 + 512, "tpu", remat="dots", head_dim=128)
        == "xla"
    )
    # CPU never gets pallas kernels
    assert resolve_attention_impl("auto", 1024, "cpu", head_dim=64) == "xla"


def test_bf16_inputs():
    q, k, v = _qkv(jax.random.PRNGKey(11), dtype=jnp.bfloat16)
    got = fused_dot_product_attention(q, k, v, interpret=True)
    want = _ref(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), atol=3e-2, rtol=3e-2
    )
