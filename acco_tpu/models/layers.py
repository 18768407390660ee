"""Shared transformer building blocks (pure functions over param pytrees).

Models here are *pure pytrees + functions*, not framework modules: ACCO's
machinery lives on the flat 1-D parameter vector (ZeRO-1 slice geometry,
reduce-scatter/all-gather staging — `/root/reference/trainer_base.py:
284-332`), and a plain dict pytree is what `parallel/flat_layout.py` needs
to be the bridge between the two views (it reads the leaves' shapes alone).

TPU-first layout choices:
- **stacked layers**: every per-layer leaf carries a leading ``n_layers``
  axis and the forward pass is one ``lax.scan`` over that axis — one block
  compilation regardless of depth, and the natural hook for
  ``jax.checkpoint`` rematerialisation;
- parameters and activations in ``param_dtype`` (bfloat16 by default, the
  reference's mixed-precision mode `trainer_base.py:164-169`), with
  norm statistics and softmax in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def normal_init(key: jax.Array, shape: tuple, stddev: float, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


_MOE_DOTS = ("moe_gate", "moe_up", "moe_down")  # ops/moe.py's grouped matmuls


def wrap_remat(block, remat):
    """Apply the configured rematerialisation mode to a scan block.

    ``False`` — store all activations; ``True`` — full-block
    ``jax.checkpoint``; ``'dots'`` — checkpoint with the dots-saveable
    policy (projection/MLP matmul outputs stored, attention scores and
    elementwise recomputed); ``'dots+probs'`` — dots plus the bf16
    attention probabilities (ops/attention.py names them), trading
    ~B*H*L^2*2 bytes of storage per layer for the backward not recomputing
    the float32 scores and softmax of the einsum path. Whether that trade
    wins a round is unmeasured: no benchmark cell sets it (ROADMAP D2).
    Anything else is a config error.

    The 'dots' policy additionally saves the attention kernels' named
    outputs (attn_out + attn_lse: ops/fused_attention.py,
    ops/banded_attention.py and, for the stock flash kernel, whose own
    ``custom_vjp`` names nothing, ops/attention._named_flash): a
    pallas_call is not a dot, so
    without the names the backward re-traces and reruns the forward
    kernel once per layer purely to regenerate its residuals. On the
    einsum path the names never occur and the policy is unchanged.
    Likewise the sparse experts' grouped matmuls (ops/moe.py: ``moe_gate``,
    ``moe_up``, ``moe_down``): a grouped matmul through a kernel is a matmul
    the stock dots policy does not recognise, and 'dots' means their outputs
    are stored.

    Spellings are normalized through ops.attention.normalize_remat (the
    one normalizer every surface shares), so YAML/CLI forms like
    ``remat: 1`` / ``train.remat=0`` / ``'true'`` work here exactly as
    they do in the proof tools.
    """
    from acco_tpu.ops.attention import normalize_remat

    remat = normalize_remat(remat)
    if remat == "dots":
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "attn_out", "attn_lse", *_MOE_DOTS
            ),
        )
        return jax.checkpoint(block, policy=policy)
    if remat == "dots+probs":
        # attn_out/attn_lse included here too: under the fused kernel
        # this knob must never mean "rerun the forward kernel" — that
        # would invert its documented purpose (save memory traffic, not
        # re-pay the attention stream).
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "attn_probs", "attn_out", "attn_lse", *_MOE_DOTS
            ),
        )
        return jax.checkpoint(block, policy=policy)
    if remat is True:
        return jax.checkpoint(block)
    if remat is False:
        return block
    raise ValueError(  # unreachable after normalize_remat; backstop
        f"remat must be False, True, 'dots', or 'dots+probs'; got {remat!r}"
    )


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layer_norm(
    x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float
) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    normed = (xf - mean) * jax.lax.rsqrt(var + eps)
    return normed.astype(x.dtype) * scale + bias


def gelu_new(x: jax.Array) -> jax.Array:
    """GPT-Neo's 'gelu_new' (tanh approximation)."""
    return jax.nn.gelu(x, approximate=True)


def rope_angles(
    seq_len: int, head_dim: int, theta: float, offset=0, positions=None
) -> tuple[jax.Array, jax.Array]:
    """Rotary position-embedding cos/sin tables, float32 [L, D/2].

    ``offset`` shifts the absolute positions — under sequence parallelism
    each shard's chunk starts at ``axis_index * chunk_len`` (may be a
    traced scalar). ``positions`` overrides with explicit per-token
    absolute positions [L] (zig-zag sequence sharding: a shard's tokens
    are not contiguous)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if positions is None:
        positions = offset + jnp.arange(seq_len, dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Half-rotation RoPE on [B, H, L, D] (HF/NeoX convention)."""
    d_half = x.shape[-1] // 2
    x1, x2 = x[..., :d_half], x[..., d_half:]
    cos = cos[None, None, :, :].astype(x.dtype)
    sin = sin[None, None, :, :].astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


def apply_rope_at(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Half-rotation RoPE on [R, H, 1, D] with per-ROW angle tables
    [R, D/2] — the decode-step variant of :func:`apply_rope`, where each
    batch slot sits at its own absolute position (continuous batching:
    every request is at a different depth of its sequence)."""
    d_half = x.shape[-1] // 2
    x1, x2 = x[..., :d_half], x[..., d_half:]
    cos = cos[:, None, None, :].astype(x.dtype)
    sin = sin[:, None, None, :].astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


def vocab_parallel_embed(
    wte: jax.Array,  # [V/tp, D] this shard's vocab rows
    input_ids: jax.Array,  # [B, L] int32 GLOBAL ids
    tensor_axis: str,
) -> jax.Array:
    """Token embedding lookup with the vocab dim sharded over
    ``tensor_axis`` (Megatron vocab-parallel): each shard gathers its
    in-range ids (out-of-range -> row 0, masked to zero) and one psum
    assembles the full [B, L, D] embedding. Shared by every
    tensor-parallel model family."""
    v_local = wte.shape[0]
    v0 = jax.lax.axis_index(tensor_axis) * v_local
    loc = input_ids - v0
    ok = (loc >= 0) & (loc < v_local)
    rows = wte[jnp.where(ok, loc, 0)]
    return jax.lax.psum(
        jnp.where(ok[..., None], rows, jnp.zeros((), rows.dtype)), tensor_axis
    )


def split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    """[B, L, H*D] -> [B, H, L, D]"""
    b, l, _ = x.shape
    return x.reshape(b, l, n_heads, -1).transpose(0, 2, 1, 3)


def merge_heads(x: jax.Array) -> jax.Array:
    """[B, H, L, D] -> [B, L, H*D]"""
    b, h, l, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, l, h * d)
