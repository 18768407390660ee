"""Block-attention Pallas kernel for the ring (context-parallel) path.

``ring_attention``/``zigzag_ring_attention`` (ops/ring_attention.py)
accumulate one (q-chunk x kv-chunk) attention block per ring hop with
the online-softmax merge. The block computation is the hot part — the
jnp form materializes a [B, H, Lc, Lc] float32 score tile in HBM per
hop AND runs its matmuls in float32 (the MXU's slow path). This kernel
is the block computation with the score tile VMEM-resident and the
matmuls in the activation dtype, mirroring ops/fused_attention.py for
the sharded-sequence regime (Lc ≤ 2048 per device — exactly the ring's
operating point: at sp=4 a 4k global context is Lc=1024 chunks):

* grid (batch, q_head); one head's full [Lc, Lc] block per cell;
* returns the UNNORMALIZED partial ``(o = P·V, m = rowmax, l = rowsum)``
  — the cheap O(Lc·D) merge stays jnp in the ring body, so the ring's
  autodiff-derived backward (ppermute transposition) is untouched;
* custom VJP: recomputes the tile from (q, k, m) and routes the merge's
  cotangents on ``m`` and ``l`` exactly as jnp would — including the
  even gradient split across tied maxima (``eq/cnt``), so the kernel
  is a drop-in for the differentiated jnp block at float32 tolerance;
* ``diag=True`` applies the self-hop's lower-triangular causal mask
  in-kernel from iota (the [Lc, Lc] mask never exists in HBM either);
* GQA: KV heads indexed ``h // n_rep`` in the BlockSpecs; dK/dV
  accumulate across the q-head grid steps sharing a KV head.

The ring callers select the kernel on TPU ('fused') and the jnp form on
CPU meshes ('xla'), same convention as resolve_attention_impl. The
windowed ring (GPT-Neo CP) uses the positional variant: the exact
causal + sliding-window mask is regenerated in-kernel from the shard's
absolute position vectors and the traced window scalar, so the
[Lq, Lk] mask never exists in HBM either.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e9  # matches ring_attention's mask value


def _mask_scores(s, diag, pos):
    """Apply the static diag triangle OR the position-computed causal +
    sliding-window mask (GPT-Neo's windowed ring — HF semantics:
    ``i`` attends ``j`` iff ``kj <= qi`` and, when ``window`` != 0,
    ``kj > qi - window``). ``pos`` = (q_pos [Lq], kv_pos [Lk], win_ref)
    or None. Returns ``(masked_scores, allowed | None)`` — the backward
    multiplies ``ds`` by ``allowed``, matching jnp's ``where`` exactly:
    masked positions carry NO gradient into q/k even on fully-masked
    rows (where p = exp(-1e9 − (-1e9)) = 1, not 0)."""
    if pos is not None:
        q_pos, kv_pos, win_ref = pos
        qi = q_pos[:, None]  # [Lq, 1]
        kj = kv_pos[None, :]  # [1, Lk]
        w = win_ref[0, 0]
        allowed = jnp.logical_and(
            kj <= qi, jnp.logical_or(w == 0, kj > qi - w)
        )
        return jnp.where(allowed, s, _NEG_INF), allowed
    if diag:
        i = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        allowed = j <= i
        return jnp.where(allowed, s, _NEG_INF), allowed
    return s, None


def _fwd_kernel(*refs, scale, diag, positional):
    if positional:
        win_ref, qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
        pos = (qp_ref[0, 0], kp_ref[0, 0], win_ref)
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
        pos = None
    q = q_ref[0, 0]  # [Lq, D]
    k = k_ref[0, 0]  # [Lk, D]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    s, _ = _mask_scores(s, diag, pos)
    m = jnp.max(s, axis=1, keepdims=True)  # [Lq, 1]
    p = jnp.exp(s - m)
    l_ref[0, 0, 0] = jnp.sum(p, axis=1, keepdims=True)[:, 0]
    m_ref[0, 0, 0] = m[:, 0]
    o_ref[0, 0] = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _bwd_kernel(*refs, scale, diag, n_rep, positional):
    if positional:
        (win_ref, qp_ref, kp_ref, q_ref, k_ref, v_ref, m_ref, do_ref,
         dm_ref, dl_ref, dq_ref, dk_ref, dv_ref) = refs
        pos = (qp_ref[0, 0], kp_ref[0, 0], win_ref)
    else:
        (q_ref, k_ref, v_ref, m_ref, do_ref, dm_ref, dl_ref,
         dq_ref, dk_ref, dv_ref) = refs
        pos = None
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    m = m_ref[0, 0, 0][:, None]  # [Lq, 1]
    do = do_ref[0, 0]  # [Lq, D] f32
    dm = dm_ref[0, 0, 0][:, None]
    dl = dl_ref[0, 0, 0][:, None]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    s, allowed = _mask_scores(s, diag, pos)
    p = jnp.exp(s - m)  # [Lq, Lk]
    # dp_j = do·v_j + dl ;  ds = p∘dp − w·Σp∘dp + dm·w, w = ties of max
    dp = jax.lax.dot_general(
        do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + dl
    eq = (s == m).astype(jnp.float32)
    w = eq / jnp.maximum(jnp.sum(eq, axis=1, keepdims=True), 1.0)
    common = jnp.sum(p * dp, axis=1, keepdims=True)
    ds = p * dp - w * common + dm * w
    if allowed is not None:
        ds = jnp.where(allowed, ds, 0.0)
    ds = ds.astype(q.dtype)
    dq_ref[0, 0] = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    dk = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    dv = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
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


def _row_specs(L, fn):
    # [B, H, 1, L] layout: trailing block dims equal the array dims
    # (Mosaic's tiling rule; see ops/fused_attention.py)
    return pl.BlockSpec((1, 1, 1, L), fn)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-axes type of ``like`` — the
    ring calls this kernel inside a shard_map, where pallas_call outputs
    must declare their vma explicitly."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _pos_specs(Lq, Lk):
    """(window SMEM, q_pos, kv_pos) input specs — position operands of
    the windowed (GPT-Neo CP) masking, [1, 1, L] i32 so the trailing
    block dims are full-size (Mosaic tiling rule)."""
    return [
        pl.BlockSpec((1, 1), lambda b, h: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, Lq), lambda b, h: (0, 0, 0)),
        pl.BlockSpec((1, 1, Lk), lambda b, h: (0, 0, 0)),
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _blk(q, k, v, window, q_pos, kv_pos, scale, diag, interpret):
    out, _ = _blk_fwd(q, k, v, window, q_pos, kv_pos, scale, diag, interpret)
    return out


def _blk_fwd(q, k, v, window, q_pos, kv_pos, scale, diag, interpret):
    B, H, Lq, D = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    positional = q_pos is not None
    pos_args = (
        (window.reshape(1, 1), q_pos.reshape(1, 1, Lq),
         kv_pos.reshape(1, 1, Lk))
        if positional
        else ()
    )
    o, m, l = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, diag=diag, positional=positional
        ),
        grid=(B, H),
        in_specs=(_pos_specs(Lq, Lk) if positional else []) + [
            pl.BlockSpec((1, 1, Lq, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h: (b, h // n_rep, 0, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h: (b, h // n_rep, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Lq, D), lambda b, h: (b, h, 0, 0)),
            _row_specs(Lq, lambda b, h: (b, h, 0, 0)),
            _row_specs(Lq, lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            _sds((B, H, Lq, D), jnp.float32, q),
            _sds((B, H, 1, Lq), jnp.float32, q),
            _sds((B, H, 1, Lq), jnp.float32, q),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="acco_block_attn_fwd",
    )(*pos_args, q, k, v)
    outs = (o, m.reshape(B, H, Lq), l.reshape(B, H, Lq))
    return outs, (q, k, v, window, q_pos, kv_pos, m)


def _blk_bwd(scale, diag, interpret, res, g):
    q, k, v, window, q_pos, kv_pos, m = res
    do, dm, dl = g
    B, H, Lq, D = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    zero = jnp.zeros((B, H, 1, Lq), jnp.float32)
    dm = zero if dm is None else dm.astype(jnp.float32).reshape(B, H, 1, Lq)
    dl = zero if dl is None else dl.astype(jnp.float32).reshape(B, H, 1, Lq)
    do = (
        jnp.zeros((B, H, Lq, D), jnp.float32)
        if do is None
        else do.astype(jnp.float32)
    )
    positional = q_pos is not None
    pos_args = (
        (window.reshape(1, 1), q_pos.reshape(1, 1, Lq),
         kv_pos.reshape(1, 1, Lk))
        if positional
        else ()
    )
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, diag=diag, n_rep=n_rep,
            positional=positional,
        ),
        grid=(B, H),
        in_specs=(_pos_specs(Lq, Lk) if positional else []) + [
            pl.BlockSpec((1, 1, Lq, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h: (b, h // n_rep, 0, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h: (b, h // n_rep, 0, 0)),
            _row_specs(Lq, lambda b, h: (b, h, 0, 0)),  # m
            pl.BlockSpec((1, 1, Lq, D), lambda b, h: (b, h, 0, 0)),  # do
            _row_specs(Lq, lambda b, h: (b, h, 0, 0)),  # dm
            _row_specs(Lq, lambda b, h: (b, h, 0, 0)),  # dl
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Lq, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h: (b, h // n_rep, 0, 0)),
            pl.BlockSpec((1, 1, Lk, D), lambda b, h: (b, h // n_rep, 0, 0)),
        ],
        out_shape=[
            _sds((B, H, Lq, D), jnp.float32, q),
            _sds((B, Hkv, Lk, D), jnp.float32, k),
            _sds((B, Hkv, Lk, D), jnp.float32, k),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="acco_block_attn_bwd",
    )(*pos_args, q, k, v, m, do, dm, dl)
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        None,  # window
        None,  # q_pos
        None,  # kv_pos
    )


_blk.defvjp(_blk_fwd, _blk_bwd)


def block_attention_partial(
    q: jax.Array,  # [B, H, Lq, D]
    k: jax.Array,  # [B, Hkv, Lk, D]
    v: jax.Array,  # [B, Hkv, Lk, D]
    diag: bool = False,
    scale: float | None = None,
    interpret: bool | None = None,
    q_positions: jax.Array | None = None,  # [Lq] int32 absolute positions
    kv_positions: jax.Array | None = None,  # [Lk] int32
    window=None,  # int32 scalar (traced ok); 0 = global causal
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One attention block's unnormalized partial, VMEM-resident scores.

    Returns ``(o, m, l)``: ``m = rowmax(scores)`` [B, H, Lq],
    ``l = rowsum(exp(scores - m))``, ``o = exp(scores - m) @ V`` (f32,
    unnormalized) — the operands of the ring's online-softmax merge.
    ``diag=True`` masks ``j > i`` (the self hop's causal triangle);
    passing ``q_positions``/``kv_positions`` (+ traced ``window``)
    instead generates the windowed ring's exact causal+sliding mask
    in-kernel from absolute token positions (GPT-Neo CP,
    ops/ring_attention.windowed_ring_attention — the [Lq, Lk] mask
    never exists in HBM). Differentiable (custom VJP) including the
    ``m``/``l`` cotangents the merge produces. ``interpret`` defaults
    from ``ACCO_FUSED_ATTN_INTERPRET`` like ops/fused_attention.py."""
    if interpret is None:
        import os

        interpret = bool(os.environ.get("ACCO_FUSED_ATTN_INTERPRET"))
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"q heads {q.shape[1]} not a multiple of kv heads {k.shape[1]}"
        )
    if (q_positions is None) != (kv_positions is None):
        raise ValueError("q_positions and kv_positions go together")
    if q_positions is not None and diag:
        raise ValueError("diag and positional masking are exclusive")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    win = None
    if q_positions is not None:
        win = jnp.asarray(0 if window is None else window, jnp.int32)
        q_positions = q_positions.astype(jnp.int32)
        kv_positions = kv_positions.astype(jnp.int32)
    return _blk(
        q, k, v, win, q_positions, kv_positions,
        float(scale), bool(diag), interpret,
    )
