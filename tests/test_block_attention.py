"""Block-attention Pallas kernel (ring/CP path) vs the jnp block.

Interpreter mode on CPU: the partial ``(o, m, l)`` and its custom VJP —
including the ``m``/``l`` cotangents the ring's online-softmax merge
produces — against the jnp formulation at float32 tolerance, then the
full ring functions with ``block_impl='fused'`` against ``'xla'``
through a real 4-device shard_map (forward AND gradients).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from acco_tpu.ops.block_attention import block_attention_partial
from acco_tpu.ops.ring_attention import (
    ring_attention,
    zigzag_ring_attention,
)

B, H, Lc, D = 2, 4, 32, 64


def _qkv(key, hkv=H, lk=Lc):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, Lc, D), jnp.float32)
    k = jax.random.normal(kk, (B, hkv, lk, D), jnp.float32)
    v = jax.random.normal(kv, (B, hkv, lk, D), jnp.float32)
    return q, k, v


def _ref_partial(q, k, v, diag=False, scale=None):
    n_rep = q.shape[1] // k.shape[1]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=1)
        v = jnp.repeat(v, n_rep, axis=1)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if diag:
        i = jnp.arange(s.shape[2])[:, None]
        j = jnp.arange(s.shape[3])[None, :]
        s = jnp.where(j <= i, s, -1e9)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    return jnp.einsum("bhqk,bhkd->bhqd", p, v), m, p.sum(-1)


@pytest.mark.parametrize("diag", [False, True])
@pytest.mark.parametrize("hkv", [H, 2])
def test_partial_forward(diag, hkv):
    q, k, v = _qkv(jax.random.PRNGKey(0), hkv=hkv)
    got = block_attention_partial(q, k, v, diag=diag, interpret=True)
    want = _ref_partial(q, k, v, diag=diag)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("diag", [False, True])
@pytest.mark.parametrize("hkv", [H, 1])
def test_partial_gradients_with_merge_cotangents(diag, hkv):
    # random cotangents on ALL THREE outputs — exactly what the ring's
    # merge produces via corr_blk = exp(m_blk - m_new) etc.
    q, k, v = _qkv(jax.random.PRNGKey(1), hkv=hkv)
    kt = jax.random.split(jax.random.PRNGKey(2), 3)
    t_o = jax.random.normal(kt[0], (B, H, Lc, D))
    t_m = jax.random.normal(kt[1], (B, H, Lc))
    t_l = jax.random.normal(kt[2], (B, H, Lc))

    def loss(fn):
        def f(q, k, v):
            o, m, l = fn(q, k, v)
            return (
                jnp.sum(o * t_o) + jnp.sum(m * t_m) + jnp.sum(l * t_l)
            )

        return jax.grad(f, argnums=(0, 1, 2))

    fused = lambda q, k, v: block_attention_partial(
        q, k, v, diag=diag, interpret=True
    )
    ref = lambda q, k, v: _ref_partial(q, k, v, diag=diag)
    for g, w in zip(loss(fused)(q, k, v), loss(ref)(q, k, v)):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


def _mesh4():
    devs = jax.devices()[:4]
    return Mesh(np.array(devs), ("sp",))


@pytest.mark.parametrize(
    "ring_fn", [ring_attention, zigzag_ring_attention],
    ids=["contiguous", "zigzag"],
)
def test_ring_fused_matches_xla_through_shard_map(monkeypatch, ring_fn):
    """The full ring with the Pallas block (interpret) vs the jnp block,
    forward and parameter gradients, on a real 4-device CPU mesh."""
    monkeypatch.setenv("ACCO_FUSED_ATTN_INTERPRET", "1")
    mesh = _mesh4()
    ws = 4
    L = Lc * ws
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (B, H, L, D), jnp.float32)
    k = jax.random.normal(kk, (B, 2, L, D), jnp.float32)
    v = jax.random.normal(kv, (B, 2, L, D), jnp.float32)
    t = jax.random.normal(jax.random.PRNGKey(4), (B, H, L, D))

    def run(block_impl):
        def body(q, k, v):
            return ring_fn(q, k, v, "sp", block_impl=block_impl)

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None, "sp"), P(None, None, "sp"),
                      P(None, None, "sp")),
            out_specs=P(None, None, "sp"),
            check_vma=False,  # as every production shard_map in parallel/
        )

        def loss(q, k, v):
            return jnp.sum(fn(q, k, v) * t)

        out = fn(q, k, v)
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return out, grads

    out_x, g_x = run("xla")
    out_f, g_f = run("fused")
    np.testing.assert_allclose(out_f, out_x, atol=2e-5, rtol=2e-5)
    for gf, gx in zip(g_f, g_x):
        np.testing.assert_allclose(gf, gx, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("window", [0, 24])
def test_partial_positional_mask(window):
    """The positional variant (windowed ring's mask from absolute
    positions + traced window) vs the jnp formulation, fwd and grads
    with merge cotangents."""
    q, k, v = _qkv(jax.random.PRNGKey(20), hkv=2)
    qp = jnp.arange(32, 32 + Lc, dtype=jnp.int32)  # this shard's tokens
    kp = jnp.arange(0, Lc, dtype=jnp.int32)  # an earlier chunk's tokens

    def ref(q, k, v):
        n_rep = q.shape[1] // k.shape[1]
        kk = jnp.repeat(k, n_rep, axis=1)
        vv = jnp.repeat(v, n_rep, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * (D ** -0.5)
        allowed = (kp[None, :] <= qp[:, None]) & (
            (window == 0) | (kp[None, :] > qp[:, None] - window)
        )
        s = jnp.where(allowed[None, None], s, -1e9)
        m = s.max(-1)
        p = jnp.exp(s - m[..., None])
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv), m, p.sum(-1)

    fused = lambda q, k, v: block_attention_partial(
        q, k, v, interpret=True,
        q_positions=qp, kv_positions=kp, window=jnp.int32(window),
    )
    got, want = fused(q, k, v), ref(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)

    kt = jax.random.split(jax.random.PRNGKey(21), 3)
    t_o = jax.random.normal(kt[0], (B, H, Lc, D))
    t_m = jax.random.normal(kt[1], (B, H, Lc))
    t_l = jax.random.normal(kt[2], (B, H, Lc))

    def loss(fn):
        def f(q, k, v):
            o, m, l = fn(q, k, v)
            return jnp.sum(o * t_o) + jnp.sum(m * t_m) + jnp.sum(l * t_l)

        return jax.grad(f, argnums=(0, 1, 2))

    for g, w in zip(loss(fused)(q, k, v), loss(ref)(q, k, v)):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


def test_windowed_ring_fused_matches_xla(monkeypatch):
    """GPT-Neo's windowed ring with the positional kernel vs the jnp
    block through a real 4-device shard_map, both window modes, fwd and
    gradients."""
    from acco_tpu.ops.ring_attention import windowed_ring_attention

    monkeypatch.setenv("ACCO_FUSED_ATTN_INTERPRET", "1")
    mesh = _mesh4()
    ws = 4
    L = Lc * ws
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(22), 3)
    q = jax.random.normal(kq, (B, H, L, D), jnp.float32)
    k = jax.random.normal(kk, (B, H, L, D), jnp.float32)
    v = jax.random.normal(kv, (B, H, L, D), jnp.float32)
    t = jax.random.normal(jax.random.PRNGKey(23), (B, H, L, D))

    def run(block_impl, window):
        def body(q, k, v):
            idx = lax.axis_index("sp")
            return windowed_ring_attention(
                q, k, v, "sp", jnp.int32(window),
                idx * Lc + jnp.arange(Lc),
                lambda src: src * Lc + jnp.arange(Lc),
                block_impl=block_impl,
            )

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"),
            check_vma=False,
        )
        out = fn(q, k, v)
        grads = jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * t), argnums=(0, 1, 2)
        )(q, k, v)
        return out, grads

    for window in (0, 40):
        out_x, g_x = run("xla", window)
        out_f, g_f = run("fused", window)
        np.testing.assert_allclose(out_f, out_x, atol=2e-5, rtol=2e-5)
        for gf, gx in zip(g_f, g_x):
            np.testing.assert_allclose(gf, gx, atol=2e-4, rtol=2e-4)
