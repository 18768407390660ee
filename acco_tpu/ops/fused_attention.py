"""Bespoke fused attention kernel: full-tile, VMEM-resident scores.

The einsum attention path materializes [B, H, L, L] float32 scores in
HBM (at L=1024, D=64, 12 heads and a per-chip batch of 8, 403 MB a
layer for each pass over them). The stock Pallas flash kernel removes
that traffic with online-softmax blocks over L. How the three compare
in a round at these lengths is unmeasured: the benchmark's 125M cells
run this kernel (``attn_kernel_ms`` 18.08 of a 97.55 ms round, at
``attn_kernel_roofline`` 12.2%: ledger, PR 24) and nothing runs the
other two beside it (ROADMAP S1).

This kernel is the third point in that design space, tuned for the
L≤2048 regime where one head's entire [L, L] float32 score tile fits in
VMEM (4 MB at L=1024, 16 MB at L=2048 — v5e VMEM is 128 MB):

* grid = (batch, q_head); each program instance computes one head's
  attention **in full** — no L-blocking, no online softmax, no running
  rescale. Scores live and die in VMEM; HBM sees only Q/K/V/O ([B, H,
  L, D] bf16, ~50 MB/layer) and the [B, H, L] log-sum-exp.
* the backward pass is the standard flash-style recompute (one extra
  QKᵀ) — dQ, dK, dV in one kernel, with the [L, L] intermediates again
  VMEM-resident.
* masking (causal, sliding window, key padding) is generated in-kernel
  from iota — the [L, L] mask never exists in HBM either. ``window`` is
  a *traced* scalar in SMEM, so one compiled body serves GPT-Neo's
  alternating global/local layers inside a ``lax.scan`` over layers
  (same contract as ops/attention.py's ``attention_mask_bias``).
* grouped-query attention indexes the KV head as ``h // n_rep`` in the
  BlockSpec index maps (no repeat_kv materialization); dK/dV accumulate
  across the ``n_rep`` consecutive q-head grid steps that share a KV
  block (TPU grids iterate the trailing axis fastest, so the revisited
  output block stays resident).

Reference frame: the reference gets fused attention implicitly from HF
transformers' SDPA/cuDNN path (`/root/reference/trainer_decoupled.py`);
this kernel is the TPU-native equivalent for the pretrain shape.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e9  # matches ops/attention.py's additive-bias mask value


def _mask(seq_len: int, window, pad_row) -> jax.Array:
    """[L, L] bool: causal AND (global OR in-window) AND key-not-pad.

    ``window`` is a traced int32 scalar (0 = global); ``pad_row`` is a
    traced [L] int32 row (1 = real token) or None.
    """
    i = jax.lax.broadcasted_iota(jnp.int32, (seq_len, seq_len), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (seq_len, seq_len), 1)
    allowed = jnp.logical_and(
        j <= i, jnp.logical_or(window == 0, (i - j) < window)
    )
    if pad_row is not None:
        allowed = jnp.logical_and(allowed, (pad_row != 0)[None, :])
    return allowed


def _fwd_kernel(win_ref, q_ref, k_ref, v_ref, *rest, scale, has_pad):
    if has_pad:
        pad_ref, o_ref, lse_ref = rest
        pad_row = pad_ref[0, 0]
    else:
        (o_ref, lse_ref), pad_row = rest, None
    q = q_ref[0, 0]  # [L, D] bf16
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * scale
    s = jnp.where(_mask(q.shape[0], win_ref[0, 0], pad_row), s, _NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    # normalize in f32, cast to the activation dtype for the MXU PV
    # matmul — the same rounding the einsum path applies to its probs
    pn = (p / l).astype(o_ref.dtype)
    o = jax.lax.dot_general(
        pn, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0, 0] = o.astype(o_ref.dtype)
    lse_ref[0, 0, 0] = (m + jnp.log(l))[:, 0]


def _bwd_kernel(
    win_ref, q_ref, k_ref, v_ref, *rest, scale, has_pad, n_rep
):
    if has_pad:
        (pad_ref, o_ref, lse_ref, do_ref, dq_ref, dk_ref, dv_ref) = rest
        pad_row = pad_ref[0, 0]
    else:
        (o_ref, lse_ref, do_ref, dq_ref, dk_ref, dv_ref) = rest
        pad_row = None
    q = q_ref[0, 0]  # [L, D] bf16
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    o = o_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, 0][:, None]  # [L, 1] f32
    # recompute the normalized probabilities from Q, K and the saved LSE
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * scale
    s = jnp.where(_mask(q.shape[0], win_ref[0, 0], pad_row), s, _NEG_INF)
    p = jnp.exp(s - lse)  # [L, L] f32, rows sum to 1 (0 on masked)
    pn = p.astype(do.dtype)
    # dV = Pᵀ dO ;  dP = dO Vᵀ ;  dS = P ∘ (dP − rowsum(dO ∘ O))
    dv = jax.lax.dot_general(
        pn, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=1, keepdims=True
    )
    ds = (p * (dp - delta)).astype(do.dtype)  # [L, L] bf16
    dq = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)
    dk = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dk = dk * scale
    # GQA: n_rep consecutive q-head steps share this dK/dV block — zero it
    # on the group's first visit, then accumulate (f32 output for safety).
    if n_rep == 1:
        dk_ref[0, 0] = dk
        dv_ref[0, 0] = dv
    else:
        first = pl.program_id(1) % n_rep == 0

        @pl.when(first)
        def _init():
            dk_ref[0, 0] = dk
            dv_ref[0, 0] = dv

        @pl.when(jnp.logical_not(first))
        def _acc():
            dk_ref[0, 0] += dk
            dv_ref[0, 0] += dv


def _specs(B, H, Hkv, L, D, has_pad):
    """(window, q, k, v[, pad]) input BlockSpecs for grid (B, H)."""
    n_rep = H // Hkv
    specs = [
        pl.BlockSpec((1, 1), lambda b, h: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h // n_rep, 0, 0)),
        pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h // n_rep, 0, 0)),
    ]
    if has_pad:
        # [B, 1, L] so the trailing block dims equal the array dims —
        # Mosaic requires the last two block dims be (8, 128)-aligned or
        # full; a [B, L] layout's (1, L) block violates that on real TPU.
        specs.append(pl.BlockSpec((1, 1, L), lambda b, h: (b, 0, 0)))
    return specs


def _compiler_params(bwd: bool):
    # only the backward accumulates dK/dV across q-head grid steps (GQA),
    # so only there must the head axis stay sequential. The raised vmem
    # budget covers the L=2048 end of the envelope (one [L, L] f32 tile
    # is 16 MB there — over the 16 MB default scoped budget once
    # operands and double-buffering join it; v5e VMEM is 128 MB).
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary" if bwd else "parallel"),
        vmem_limit_bytes=100 * 1024 * 1024,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _attn(q, k, v, window, pad_mask, scale, interpret):
    out, _ = _attn_fwd(q, k, v, window, pad_mask, scale, interpret)
    return out


def _attn_fwd(q, k, v, window, pad_mask, scale, interpret):
    B, H, L, D = q.shape
    Hkv = k.shape[1]
    has_pad = pad_mask is not None
    args = [window, q, k, v] + ([pad_mask] if has_pad else [])
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, has_pad=has_pad),
        grid=(B, H),
        in_specs=_specs(B, H, Hkv, L, D, has_pad),
        out_specs=[
            pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h, 0, 0)),
            # LSE as [B, H, 1, L]: trailing block dims (1, L) equal the
            # array dims, satisfying Mosaic's tiling rule (a [B, H, L]
            # layout's (1, L) block does not).
            pl.BlockSpec((1, 1, 1, L), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, L), jnp.float32),
        ],
        compiler_params=_compiler_params(bwd=False),
        interpret=interpret,
        name="acco_fused_attn_fwd",
    )(*args)
    # Named so the 'dots' remat policy (models/layers.wrap_remat) can
    # save the kernel's outputs: a pallas_call is not a "dot", so under
    # a plain dots policy the backward re-traces and RERUNS this forward
    # kernel just to regenerate its residuals. Saving out+LSE (~13 MB
    # per layer at the flagship shape) removes that recompute entirely.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, window, pad_mask, out, lse)


def _attn_bwd(scale, interpret, res, g):
    q, k, v, window, pad_mask, out, lse = res
    B, H, L, D = q.shape
    Hkv = k.shape[1]
    n_rep = H // Hkv
    has_pad = pad_mask is not None
    in_specs = _specs(B, H, Hkv, L, D, has_pad) + [
        pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h, 0, 0)),  # out
        pl.BlockSpec((1, 1, 1, L), lambda b, h: (b, h, 0, 0)),  # lse
        pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h, 0, 0)),  # d_out
    ]
    args = (
        [window, q, k, v]
        + ([pad_mask] if has_pad else [])
        + [out, lse, g]
    )
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, has_pad=has_pad, n_rep=n_rep
        ),
        grid=(B, H),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h // n_rep, 0, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h // n_rep, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, L, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, L, D), jnp.float32),
        ],
        compiler_params=_compiler_params(bwd=True),
        interpret=interpret,
        name="acco_fused_attn_bwd",
    )(*args)
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        None,  # window: integer operand, no cotangent
        None,  # pad_mask
    )


_attn.defvjp(_attn_fwd, _attn_bwd)


def supports_fused_attention(seq_len: int, head_dim: int) -> bool:
    """Shape gate: one head's [L, L] f32 score tile (plus the backward's
    second tile) must fit VMEM with room for operands — L ≤ 2048 — and
    the tile dims must be MXU/VPU-aligned."""
    return (
        128 <= seq_len <= 2048
        and seq_len % 128 == 0
        and head_dim % 64 == 0
    )


def fused_dot_product_attention(
    q: jax.Array,  # [B, H, L, D]
    k: jax.Array,  # [B, Hkv, L, D]
    v: jax.Array,  # [B, Hkv, L, D]
    pad_mask: Optional[jax.Array] = None,  # [B, L] 1=real token
    window: jax.Array | int = 0,  # traced scalar; 0 = global
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal (+window +padding) attention with VMEM-resident scores.

    Same contract as ``ops.attention.dot_product_attention`` with a
    causal mask bias, but no [L, L] HBM materialization in either
    direction. ``interpret=True`` runs the kernel in the Pallas
    interpreter; the default reads ``ACCO_FUSED_ATTN_INTERPRET`` so
    full-model CPU tests can exercise the fused code path end-to-end."""
    if interpret is None:
        import os

        interpret = bool(os.environ.get("ACCO_FUSED_ATTN_INTERPRET"))
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"q heads {q.shape[1]} not a multiple of kv heads {k.shape[1]}"
        )
    if not supports_fused_attention(q.shape[2], q.shape[3]):
        raise ValueError(
            f"shape L={q.shape[2]} D={q.shape[3]} outside the fused "
            "kernel's VMEM envelope (supports_fused_attention)"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    window = jnp.asarray(window, jnp.int32).reshape(1, 1)
    if pad_mask is not None:
        # [B, 1, L] — see _specs: the middle singleton keeps the block's
        # trailing dims full-size for Mosaic's tiling rule.
        pad_mask = pad_mask.astype(jnp.int32)[:, None, :]
    return _attn(q, k, v, window, pad_mask, float(scale), interpret)
