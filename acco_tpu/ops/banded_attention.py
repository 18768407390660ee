"""Banded fused attention: key-block skipping for sliding-window layers.

GPT-Neo alternates global and local (window 256) attention layers
(`/root/reference/config/model/gpt-neo-125M.json` attention_layers;
models/gpt_neo.py preserves the pattern). The full-tile kernel
(ops/fused_attention.py) serves both through one traced SMEM window
scalar — but for a window layer at L=1024 it still computes the whole
[L, L] score tile and masks ~3/4 of it away.

This kernel computes only the band. The window is a STATIC Python int —
GPT-Neo's two per-layer window values (0 and config.window_size) are
known at trace time, so the model dispatches `lax.cond(window == 0,
full_kernel, banded_kernel)` inside its scanned layer body: one
compiled body still serves all layers, and the local branch does ~W/L
of the full branch's score work.

* grid (B, H, L/QB): one q row-block per cell, QB = 128 rows.
* the only keys a q block [qb·QB, qb·QB+QB) can see in-window live in
  blocks qb-nprev..qb with nprev = ceil(W/QB) — those nprev+1 KV blocks
  are the cell's whole working set ([QB, (nprev+1)·QB] scores; 192 KB
  f32 at W=256). Absolute key position is linear in the concatenated
  band column: j_abs = (qb-nprev)·QB + col, so the causal+window mask
  is two iota compares; columns whose source block index clamped at 0
  have j_abs < 0 and mask themselves.
* backward = two parallel passes, both banded: a dq pass mirroring the
  forward, and a dkv pass gridded over KV blocks (block kb is read by
  q blocks kb..kb+nprev only — the transpose of the forward's band).
  No accumulation across grid cells, so every grid axis is parallel.
* fwd/bwd FLOPs and HBM bytes scale with L·(W+QB) instead of L²: at
  L=1024, W=256 the band is 384 wide vs 1024 — 2.7x less score work,
  and the envelope extends past the full kernel's L=2048 VMEM wall
  (the band never grows with L).

MHA only (Hkv == H): GPT-Neo, the one windowed family here, has no GQA.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e9  # matches ops/attention.py's additive-bias mask value
_QB = 128  # q rows per grid cell; also the KV band's block unit


def _nprev(window: int) -> int:
    """KV blocks BEFORE the diagonal block a q block can reach: the
    lowest in-window key for row qb·QB is qb·QB − W + 1, i.e. W−1 keys
    back — ceil((W−1)/QB) blocks, NOT ceil(W/QB): at W % QB == 1 the
    latter loads one fully-masked extra KV view per grid cell."""
    return -(-(window - 1) // _QB)


def _view_mask(qb, t, n_band, window):
    """[QB, QB] bool for view ``t``: q rows of block ``qb`` against keys
    of block ``qb-(n_band-1)+t``, causal AND in-window. A view whose
    source block index clamped at 0 has j_abs < 0 everywhere it matters
    and masks itself — no separate validity flag needed.

    NOTE per-view structure everywhere (no jnp.concatenate of loaded
    blocks): Mosaic's concatenate lowering rejects the shapes this
    kernel would produce ("Input offsets outside of the first tile" —
    caught by the AOT canaries, invisible to the interpreter)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (_QB, _QB), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (_QB, _QB), 1)
    i_abs = qb * _QB + i
    j_abs = (qb - (n_band - 1) + t) * _QB + j
    return jnp.logical_and(
        jnp.logical_and(j_abs >= 0, j_abs <= i_abs),
        (i_abs - j_abs) < window,
    )


def _fwd_kernel(*refs, scale, window, n_band):
    q_ref = refs[0]
    k_refs = refs[1 : 1 + n_band]
    v_refs = refs[1 + n_band : 1 + 2 * n_band]
    o_ref, lse_ref = refs[1 + 2 * n_band :]
    qb = pl.program_id(2)
    q = q_ref[0, 0]  # [QB, D]
    # two passes over the (VMEM-resident) views: rowmax first, then the
    # exp/accumulate — cheaper than online rescaling at n_band ≤ 8
    ss = []
    m = jnp.full((_QB, 1), _NEG_INF, jnp.float32)
    for t in range(n_band):
        s_t = jax.lax.dot_general(
            q, k_refs[t][0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s_t = jnp.where(_view_mask(qb, t, n_band, window), s_t * scale,
                        _NEG_INF)
        ss.append(s_t)
        m = jnp.maximum(m, jnp.max(s_t, axis=1, keepdims=True))
    l = jnp.zeros((_QB, 1), jnp.float32)
    o = jnp.zeros((_QB, q.shape[1]), jnp.float32)
    for t in range(n_band):
        ss[t] = jnp.exp(ss[t] - m)  # reuse the retained tile: exp once
        l = l + jnp.sum(ss[t], axis=1, keepdims=True)
    for t in range(n_band):
        pn_t = (ss[t] / l).astype(o_ref.dtype)
        o = o + jax.lax.dot_general(
            pn_t, v_refs[t][0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    o_ref[0, 0] = o.astype(o_ref.dtype)
    lse_ref[0, 0, 0] = (m + jnp.log(l))[:, 0]


def _dq_kernel(*refs, scale, window, n_band):
    q_ref = refs[0]
    k_refs = refs[1 : 1 + n_band]
    v_refs = refs[1 + n_band : 1 + 2 * n_band]
    lse_ref, delta_ref, do_ref, dq_ref = refs[1 + 2 * n_band :]
    qb = pl.program_id(2)
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, 0][:, None]
    # delta = rowsum(dO ∘ O), precomputed ONCE per q block in jnp by
    # _banded_bwd and shared with the dkv pass (which would otherwise
    # recompute every q block's delta n_band times)
    delta = delta_ref[0, 0, 0][:, None]
    dq = jnp.zeros((_QB, q.shape[1]), jnp.float32)
    for t in range(n_band):
        k_t = k_refs[t][0, 0]
        s_t = jax.lax.dot_general(
            q, k_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        allowed = _view_mask(qb, t, n_band, window)
        s_t = jnp.where(allowed, s_t * scale, _NEG_INF)
        p_t = jnp.exp(s_t - lse)
        dp_t = jax.lax.dot_general(
            do, v_refs[t][0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_t = (p_t * (dp_t - delta)).astype(do.dtype)
        dq = dq + jax.lax.dot_general(
            ds_t, k_t, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, window, n_band, n_qblocks):
    k_ref, v_ref = refs[0], refs[1]
    q_refs = refs[2 : 2 + n_band]
    lse_refs = refs[2 + n_band : 2 + 2 * n_band]
    delta_refs = refs[2 + 2 * n_band : 2 + 3 * n_band]
    do_refs = refs[2 + 3 * n_band : 2 + 4 * n_band]
    dk_ref, dv_ref = refs[2 + 4 * n_band :]
    kb = pl.program_id(2)
    k = k_ref[0, 0]  # [QB, D]
    v = v_ref[0, 0]
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    i = jax.lax.broadcasted_iota(jnp.int32, (_QB, _QB), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (_QB, _QB), 1)
    for t in range(n_band):
        # view t: q rows of block kb+t (clamped at the top) against the
        # keys of block kb — the transpose of the forward's band
        q_t = q_refs[t][0, 0]
        do_t = do_refs[t][0, 0]
        lse_t = lse_refs[t][0, 0, 0][:, None]
        delta_t = delta_refs[t][0, 0, 0][:, None]
        i_abs = (kb + t) * _QB + i
        j_abs = kb * _QB + j
        allowed = jnp.logical_and(
            jnp.logical_and(j_abs <= i_abs, (i_abs - j_abs) < window),
            # a clamped view past the last q block repeats the last
            # block's rows; kill its contribution entirely
            (kb + t) <= (n_qblocks - 1),
        )
        s_t = jax.lax.dot_general(
            q_t, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s_t = jnp.where(allowed, s_t * scale, _NEG_INF)
        p_t = jnp.where(allowed, jnp.exp(s_t - lse_t), 0.0)
        pn_t = p_t.astype(do_t.dtype)
        dv = dv + jax.lax.dot_general(
            pn_t, do_t, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_t = jax.lax.dot_general(
            do_t, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_t = (p_t * (dp_t - delta_t)).astype(pn_t.dtype)
        dk = dk + jax.lax.dot_general(
            ds_t, q_t, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    dk_ref[0, 0] = dk * scale
    dv_ref[0, 0] = dv


def _qkv_band_specs(L, D, n_band):
    """q block + the nprev+1 clamped KV band views for grid (B, H, nQ)."""
    qspec = pl.BlockSpec((1, 1, _QB, D), lambda b, h, qb: (b, h, qb, 0))
    # view t loads block qb-(n_band-1)+t, clamped at 0 — the mask zeroes
    # clamped views via their (negative) absolute positions. Bind t as a
    # default arg so the lambdas don't all close over the loop's last t.
    kv = [
        pl.BlockSpec(
            (1, 1, _QB, D),
            (lambda off: lambda b, h, qb: (
                b, h, jnp.maximum(qb - off, 0), 0
            ))(n_band - 1 - t),
        )
        for t in range(n_band)
    ]
    return qspec, kv


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=100 * 1024 * 1024,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _banded(q, k, v, window, scale, interpret):
    out, _ = _banded_fwd(q, k, v, window, scale, interpret)
    return out


def _banded_fwd(q, k, v, window, scale, interpret):
    B, H, L, D = q.shape
    n_band = _nprev(window) + 1
    qspec, kvspecs = _qkv_band_specs(L, D, n_band)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, window=window, n_band=n_band
        ),
        grid=(B, H, L // _QB),
        in_specs=[qspec] + kvspecs + kvspecs,
        out_specs=[
            pl.BlockSpec((1, 1, _QB, D), lambda b, h, qb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, 1, _QB), lambda b, h, qb: (b, h, 0, qb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, L), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="acco_banded_attn_fwd",
    )(q, *([k] * n_band), *([v] * n_band))
    from jax.ad_checkpoint import checkpoint_name

    # same names as the full kernel: the 'dots' remat policy saves both
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _banded_bwd(window, scale, interpret, res, g):
    q, k, v, out, lse = res
    B, H, L, D = q.shape
    n_band = _nprev(window) + 1
    nQ = L // _QB
    # delta = rowsum(dO ∘ O) once per q row in plain jnp (one fused
    # elementwise pass XLA handles); both kernel passes consume it in
    # the LSE layout instead of each recomputing it per band view.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[:, :, None, :]  # [B, H, 1, L]
    qspec, kvspecs = _qkv_band_specs(L, D, n_band)
    row_spec = pl.BlockSpec((1, 1, _QB, D), lambda b, h, qb: (b, h, qb, 0))
    lse_spec = pl.BlockSpec((1, 1, 1, _QB), lambda b, h, qb: (b, h, 0, qb))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, window=window, n_band=n_band
        ),
        grid=(B, H, nQ),
        in_specs=[qspec] + kvspecs + kvspecs
        + [lse_spec, lse_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, L, D), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="acco_banded_attn_dq",
    )(q, *([k] * n_band), *([v] * n_band), lse, delta, g)

    # dkv pass: views over q blocks kb..kb+n_band-1 (clamped at the top)
    def fwd_view(t):
        return pl.BlockSpec(
            (1, 1, _QB, D),
            (lambda t_: lambda b, h, kb: (
                b, h, jnp.minimum(kb + t_, nQ - 1), 0
            ))(t),
        )

    def lse_view(t):
        return pl.BlockSpec(
            (1, 1, 1, _QB),
            (lambda t_: lambda b, h, kb: (
                b, h, 0, jnp.minimum(kb + t_, nQ - 1)
            ))(t),
        )

    kv_self = pl.BlockSpec((1, 1, _QB, D), lambda b, h, kb: (b, h, kb, 0))
    q_views = [fwd_view(t) for t in range(n_band)]
    do_views = [fwd_view(t) for t in range(n_band)]
    lse_views = [lse_view(t) for t in range(n_band)]
    delta_views = [lse_view(t) for t in range(n_band)]
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, window=window, n_band=n_band,
            n_qblocks=nQ,
        ),
        grid=(B, H, nQ),
        in_specs=[kv_self, kv_self] + q_views + lse_views + delta_views
        + do_views,
        out_specs=[kv_self, kv_self],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, L, D), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="acco_banded_attn_dkv",
    )(
        k, v, *([q] * n_band), *([lse] * n_band), *([delta] * n_band),
        *([g] * n_band),
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_banded.defvjp(_banded_fwd, _banded_bwd)


def supports_banded_attention(
    seq_len: int, head_dim: int, window: int
) -> bool:
    """Envelope: QB-tiled sequence, MXU-aligned head dim, a window that
    actually bands (0 = global → use the full kernel; a window spanning
    the whole sequence saves nothing). The band never grows with L, so
    unlike the full kernel there is no L ceiling from VMEM — cap at 8k
    as the tested range."""
    return (
        window > 0
        and window < seq_len
        and 128 <= seq_len <= 8192
        and seq_len % _QB == 0
        and head_dim % 64 == 0
        and _nprev(window) + 1 <= 8  # keep the band's VMEM working set sane
    )


def banded_dot_product_attention(
    q: jax.Array,  # [B, H, L, D]
    k: jax.Array,  # [B, H, L, D] — MHA only (no GQA families use windows)
    v: jax.Array,
    window: int,  # STATIC python int > 0
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal sliding-window attention computing only the key band.

    Same contract as ``fused_dot_product_attention(..., window=w)`` for
    static ``w > 0`` and no padding mask, at ~(W+QB)/L of its score
    work. Gradients via the banded two-pass custom VJP."""
    if interpret is None:
        import os

        interpret = bool(os.environ.get("ACCO_FUSED_ATTN_INTERPRET"))
    if q.shape[1] != k.shape[1]:
        raise ValueError(
            f"banded attention is MHA-only: q heads {q.shape[1]} != kv "
            f"heads {k.shape[1]}"
        )
    if not supports_banded_attention(q.shape[2], q.shape[3], int(window)):
        raise ValueError(
            f"shape L={q.shape[2]} D={q.shape[3]} window={window} outside "
            "the banded kernel envelope (supports_banded_attention)"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _banded(q, k, v, int(window), float(scale), interpret)
