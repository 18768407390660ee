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
    BandedStep,
    banded_block_sizes,
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


# (L, window, heads, head_dim, tiles): ``None`` leaves the tiles to
# ``banded_block_sizes``, as the model does.
_RULE_CASES = [
    # Fast representative set (stays in tier-1): a band pad of 128 and of
    # 256, a non-128-multiple window, and the W % 128 == 1 widths whose
    # pad must not grow by a block (window - 1 a multiple of the 128 lanes).
    (256, 128, 2, 64, None),
    (256, 200, 2, 64, None),
    (384, 129, 2, 64, None),
    (512, 257, 2, 64, None),
    # a head count no group above one divides: one head a step
    (256, 128, 3, 64, None),
    (256, 128, 5, 64, None),
    # the tile sets the rule returns at the shapes in its table, (L, D, H) =
    # (1024, 64, 12) and (2048, 128, 20), at as many heads as one step takes
    (1024, 256, 2 * banded_block_sizes(1024, 256, 64, 12).heads, 64, None),
    (2048, 256, 2 * banded_block_sizes(2048, 256, 128, 20).heads, 128, None),
    # past 2048 a step takes a row block and as many heads as divide H
    (4096, 256, 4, 64, None),
]
_EDGE_CASES = [
    # rows < L: offsets computed from the grid index. The first row block's
    # band is clamped at key 0, the last key block's at the last query row.
    (512, 256, 2, 64, BandedStep(128, 1, 128)),
    (512, 256, 2, 64, BandedStep(256, 2, 256)),  # rows = W: band = previous + own
    (512, 256, 4, 64, BandedStep(256, 2, 128)),  # two tiles a step, two head groups
    (768, 256, 2, 64, BandedStep(256, 1, 256)),  # three row blocks: a middle one
    (512, 200, 2, 64, BandedStep(128, 2, 128)),  # window % rows != 0
    (512, 129, 2, 64, BandedStep(256, 1, 128)),  # window - 1 = one band block
    (512, 257, 3, 64, BandedStep(128, 3, 128)),  # window - 1 = two band blocks
    (640, 384, 2, 64, BandedStep(128, 1, 128)),  # a band of four blocks
    # rows = L: every offset static, the first tiles' bands cut at the
    # diagonal and the last key tiles' at the last row
    (512, 256, 2, 64, BandedStep(512, 2, 128)),
    (512, 256, 2, 64, BandedStep(512, 1, 512)),  # one tile: band = L
    (512, 300, 2, 64, BandedStep(512, 2, 256)),
    (384, 100, 2, 64, BandedStep(384, 1, 128)),
    (512, 256, 4, 64, BandedStep(512, 4, 256)),  # every head in one step
]
_SLOW_CASES = [
    # Heaviest widths (3-5 s each of interpret-mode grad checks): marked
    # slow so this file stays small inside the tier-1 window even on a
    # cold cache — the shapes above already cover every band pad and
    # boundary case these re-exercise at size.
    (384, 100, 2, 64, None),
    (512, 256, 2, 64, None),
    (512, 300, 2, 64, None),
    (640, 384, 2, 64, None),
]


def _case_id(case):
    L, window, H, D, sizes = case
    tiles = "rule" if sizes is None else sizes.tag()
    return f"L{L}-W{window}-H{H}-D{D}-{tiles}"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "case",
    _RULE_CASES + _EDGE_CASES
    + [pytest.param(c, marks=pytest.mark.slow) for c in _SLOW_CASES],
    ids=_case_id,
)
def test_forward_and_grads_match_einsum(case, dtype):
    """Forward and all three gradients against the einsum+bias oracle, at
    the tiles the rule chooses and at every edge the grid creates. float32
    at the tolerances the kernel has always had; bfloat16 at
    ``test_bf16_inputs``' 3e-2, gradients relative to the tensor's scale."""
    L, window, H, D, sizes = case
    q, k, v = _qkv(jax.random.PRNGKey(0), L=L, H=H, D=D, dtype=dtype)
    bias = attention_mask_bias(L, window, None)

    def ref(q, k, v):
        return dot_product_attention(q, k, v, bias, scale=0.125)

    def got(q, k, v):
        return banded_dot_product_attention(
            q, k, v, window=window, scale=0.125, interpret=True,
            block_sizes=sizes,
        )

    def loss(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum()

    out, want = got(q, k, v), ref(q, k, v)
    assert out.dtype == dtype
    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(loss(got), argnums=(0, 1, 2))(q, k, v)
    f32 = dtype == jnp.float32
    np.testing.assert_allclose(
        out.astype(np.float32), want.astype(np.float32),
        atol=2e-5 if f32 else 3e-2, rtol=2e-5 if f32 else 3e-2,
    )
    for name, a, b in zip("qkv", gr, gb):
        assert b.dtype == dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = 1.0 if f32 else max(1.0, float(np.abs(a).max()))
        np.testing.assert_allclose(
            b / scale, a / scale, atol=5e-4 if f32 else 3e-2,
            rtol=5e-4 if f32 else 3e-2, err_msg=name,
        )


def test_band_arithmetic():
    """What a tile's band is. The pad is ceil((W-1)/128) blocks, not
    ceil(W/128): the lowest in-window key for row i is i-W+1, so a window
    one past a block multiple must NOT cost an extra (fully masked) block a
    tile (round-5 ADVICE #3). Every band, static or computed from the grid
    index, covers every (query, key) pair the window allows, is never wider
    than tile + pad, and stays inside the sequence."""
    from acco_tpu.ops.banded_attention import (
        _band_pad, _key_band, _query_band,
    )

    assert _band_pad(1) == 0  # diagonal-only window
    assert _band_pad(128) == 128
    assert _band_pad(129) == 128  # the off-by-one width: was 256
    assert _band_pad(256) == 256  # shipped GPT-Neo width
    assert _band_pad(257) == 256  # was 384
    # rows = L = 1024, tile 256, W 256: cut at the diagonal and the last row
    kw = (256, 256, 1024)  # tile, pad, L
    assert [_key_band(r, *kw) for r in (0, 256, 512, 768)] == [
        (0, 256), (0, 512), (256, 512), (512, 512),
    ]
    assert [_query_band(c, *kw) for c in (0, 256, 512, 768)] == [
        (0, 512), (256, 512), (512, 512), (768, 256),
    ]
    for L, window, tile in [
        (512, 256, 128), (512, 129, 256), (640, 384, 128), (512, 300, 512),
        (384, 100, 128), (1024, 257, 256),
    ]:
        pad = _band_pad(window)
        for first in range(0, L, tile):
            for traced in (False, True):
                at = jnp.int32(first) if traced else first
                k0, kw_ = (int(x) for x in _key_band(at, tile, pad, L))
                q0, qw_ = (int(x) for x in _query_band(at, tile, pad, L))
                for start, width in ((k0, kw_), (q0, qw_)):
                    assert start % 128 == 0 and width % 128 == 0
                    assert 0 <= start and start + width <= L
                    assert width <= tile + pad
                # keys rows [first, first+tile) see; rows that see those keys
                assert k0 <= max(first - window + 1, 0) and first + tile <= k0 + kw_
                assert q0 <= first and min(first + tile + window - 1, L) <= q0 + qw_


def test_block_sizes_rule():
    """The rule's choices at the shapes the cells run, and what it does
    with shapes off its table: every step it returns tiles its shape."""
    from acco_tpu.ops.banded_attention import _check_step

    for L, D, H in [
        (1024, 64, 12), (2048, 128, 20), (384, 64, 2), (4096, 64, 2),
        (8192, 128, 7), (128 * 9, 64, 6),
    ]:
        _check_step(banded_block_sizes(L, 256 if L > 256 else 100, D, H), L, H)
    # what the cells run: a whole head a step, 128 rows at a time
    assert banded_block_sizes(1024, 256, 64, 12) == BandedStep(1024, 1, 128)
    assert banded_block_sizes(2048, 256, 128, 20) == BandedStep(2048, 1, 128)
    assert banded_block_sizes(4096, 256, 64, 12) == BandedStep(2048, 4, 256)
    assert banded_block_sizes(4096, 256, 64, 7) == BandedStep(2048, 1, 256)
    with pytest.raises(ValueError, match="tiles the sequence by 128"):
        banded_block_sizes(1000, 256, 64, 12)
    with pytest.raises(ValueError, match="does not tile"):
        q = jnp.zeros((1, 3, 256, 64), jnp.float32)
        banded_dot_product_attention(  # a group of 2 in 3 heads
            q, q, q, window=128, interpret=True, block_sizes=BandedStep(256, 2, 128)
        )


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
    assert not supports_banded_attention(1000, 64, 256)  # L % 128
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
