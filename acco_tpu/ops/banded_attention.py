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

How much of the problem one grid step takes is a function of the shape
(:func:`banded_block_sizes`, with the chip sweep behind it): ``rows`` of
``heads`` heads a step, worked through in ``tile``-row pieces inside the
step. A grid step costs a fraction of a microsecond to start and one DMA
a block whatever it holds (at 128 rows of one head a step the three
kernels spent 0.83 µs a step on 0.06 µs of arithmetic), so a step takes
a whole head where the sequence allows (L <= 2048) and its arithmetic
stays at the 128-row tile, where the least of the band is masked.

* forward and dq: grid (B, H/heads, L/rows). q, o, lse (and do, delta)
  arrive as the step's ``[heads, rows, D]`` block; K and V of the step's
  heads are held WHOLE in VMEM under an index map that ignores the
  row-block index, so they are fetched once a (batch, head group) and
  every key is read from HBM once. Query rows [r, r+tile) see keys
  [r-W+1, r+tile): the tile's band is the ``tile + pad`` keys from
  ``r - pad``, ``pad`` = W-1 rounded up to the 128 lanes, sliced from the
  resident K / V at a multiple of 128 ([128, 384] scores at tile 128,
  W 256). Where ``rows == L`` every offset is a Python int and the first
  tiles' bands are cut at the diagonal; else the start is computed from
  the row-block index and clamped at 0, and the causal mask covers what
  lies past the diagonal.
* dkv: the transpose. Grid over KEY blocks, K / V / dk / dv as the
  step's block, Q / dO / lse / delta of the heads held whole. Keys
  [c, c+tile) are seen by query rows [c, c+tile+W-1): ``tile + pad`` rows
  from ``c`` (clamped at the top, cut at L where static).
* which way round a score tile lies is chosen a kernel, by the sweep.
  Forward and dkv compute it TRANSPOSED, [keys, queries]: the softmax's
  max and sum then run down the sublanes (vector ops; along the lanes
  they are cross-lane reductions, which bound the forward), lse and
  delta broadcast as the lane rows they are stored as, and dv = Pt dO,
  dk = dSt Q are plain matmuls; the forward pays one transposed-operand
  matmul (Pt' V) for it and is a quarter faster. dq keeps [queries,
  keys], where dq = dS K is the plain matmul: transposed it is a third
  slower.
* a tile's mask depends on where its band starts relative to it alone:
  computed once a step, reused by every tile and head.
* no accumulation across grid steps; dq / dk / dv leave in the input
  dtype (float32 accumulator, one rounding at the store).
* fwd/bwd FLOPs scale with L·(W+tile) instead of L², HBM bytes with L.

MHA only (Hkv == H): GPT-Neo, the one windowed family here, has no GQA.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e9  # matches ops/attention.py's additive-bias mask value
_LANES = 128  # every in-kernel offset and width is a multiple of this
_VMEM_LIMIT = 100 * 1024 * 1024
# what a step's double-buffered blocks may take of it, counted at 4 bytes
# an element; the score tiles and the compiler's scratch get the rest
_VMEM_BLOCKS = 48 * 1024 * 1024


class BandedStep(NamedTuple):
    """A grid step of the three kernels: ``rows`` (query rows for fwd and
    dq, key rows for dkv) of ``heads`` heads, worked through ``tile`` rows
    at a time."""

    rows: int
    heads: int
    tile: int

    def tag(self) -> str:
        return f"r{self.rows}_h{self.heads}_t{self.tile}"


def _band_pad(window: int) -> int:
    """Rows of band beyond a tile's own: the lowest in-window key for row
    r is r − W + 1, i.e. W−1 keys back (NOT W: at W % 128 == 1 that would
    load 128 fully-masked keys a tile), rounded up to the 128 lanes."""
    return -(-(window - 1) // _LANES) * _LANES


def banded_block_sizes(
    seq_len: int, window: int, head_dim: int, n_heads: int
) -> BandedStep:
    """The three kernels' grid step, chosen from the shape.

    The rule: a step takes the WHOLE sequence of ONE head where the
    sequence is at most 2048 long, 128 rows at a time: ``(L, 1, 128)``.
    Past 2048 it takes the largest row block of 2048, 1024, ... that
    divides L, 256 rows at a time, and up to 4 heads.

    The sweep behind it (my chip runs, PR 30: one v5e chip, bf16, window
    256, unscaled scores; each kernel jitted alone, five calls inside a
    ``jax.profiler`` trace, the median DEVICE time of the Mosaic call, ms a
    call = a layer; every set compiled here first for a described
    ``v5e:2x2``: Mosaic refused only 20 heads x 2048 rows at D = 128, out of
    VMEM). ``rows`` down, ``tile`` across, one head a step; in brackets 4
    heads and all heads a step at tile 128:

    ``[8, 12, 1024, 64]`` (the 125M cells; the parent's 128 rows of one
    head a step through ``n_band`` views: 0.639 / 0.538 / 0.753):

    =====  =====================  =====  =====  =====  =====  =====  =====  =====  =====
    rows   forward 128            256    512    dq 128  256    512   dkv 128  256   512
    =====  =====================  =====  =====  =====  =====  =====  =====  =====  =====
    128    0.635 (0.605, 0.589)                 0.450                0.573
    256    0.538 (0.546, 0.541)   0.480         0.319  0.334         0.413  0.405
    512    0.488 (0.499, 0.498)   0.441  0.386  0.243  0.260  0.350  0.314  0.320  0.435
    1024   **0.209** (0.213, 0.220)  0.247  0.262  **0.184**  0.199  0.264  **0.235**  0.258  0.347
    =====  =====================  =====  =====  =====  =====  =====  =====  =====  =====

    ``[2, 20, 2048, 128]`` (``neo27b-l4-acco-1chip``; the parent's: 0.531 /
    0.452 / 0.629; at ``[4, 20, 2048, 128]``, the dp=4 cell's, every entry
    is twice this one to 3% and the order is the same: 0.277 / 0.317 / 0.403
    at the rule against the parent's 1.061 / 0.952 / 1.314):

    =====  =====================  =====  =====  =====  =====  =====  =====  =====  =====
    rows   forward 128            256    512    dq 128  256    512   dkv 128  256   512
    =====  =====================  =====  =====  =====  =====  =====  =====  =====  =====
    128    0.550 (0.529, 0.521)                 0.409                0.491
    256    0.478 (0.469, 0.476)   0.435         0.294  0.311         0.365  0.358
    512    0.408 (0.417, 0.437)   0.369  0.331  0.242  0.262  0.323  0.314  0.320  0.387
    1024   0.289 (0.299, 0.327)   0.252  0.308  0.222  0.230  0.281  0.273  0.269  0.357
    2048   **0.140** (0.147, 0.192)  0.165  0.228  **0.161**  0.170  0.237  **0.205**  0.220  0.313
    =====  =====================  =====  =====  =====  =====  =====  =====  =====  =====

    What it says. (1) Rows a step is the lever: every halving of the step
    count is faster, and the whole sequence a step is faster again by more
    than the steps saved, because every offset is then a Python int: the
    bands of the first tiles are cut at the diagonal, those of the last
    key tiles at the last row, and one mask serves every other tile. (2)
    Heads a step buys 7-14% at 128 rows and nothing once a step holds a
    whole head (1-4 heads within 2%; all heads 5-35% SLOWER: the first
    step's DMA, which nothing hides, grows with the block). (3) Inside a
    whole-sequence step the smallest tile wins, 128 rows against 384 keys:
    what a bigger tile saves in loop overhead it loses in masked scores
    (band over needed 1.5 at 128, 2.0 at 256); where offsets are computed,
    256 or 512 win the forward. (4) Which way round the scores lie, at
    (L, 1) and tile 128 / 256 / 512: forward as [queries, keys] 0.377 /
    0.287 / 0.307 at D = 64 and 0.317 / 0.244 / 0.254 at D = 128, with the
    normalisation moved past the PV matmul 0.310 / 0.249 / 0.286, as [keys,
    queries] **0.210** / 0.247 / 0.262 and **0.141** / 0.165 / 0.229; dq as
    [keys, queries] 0.345 / 0.252 / 0.275 against **0.184** / 0.199 / 0.264.
    The control, the full-tile kernel with its window scalar at 256 in
    the same traces (``fused_dot_product_attention``): 0.411 + 0.685 =
    1.096 ms a layer at ``[8, 12, 1024, 64]``, 0.599 + 1.132 = 1.731 at
    ``[2, 20, 2048, 128]``, 1.197 + 2.250 = 3.447 at ``[4, 20, 2048, 128]``
    against this kernel's 0.628, 0.506 and 0.997. Past 2048 nothing is
    measured (no cell is that long): the rule there is the table's rows =
    1024 line at L = 2048.
    """
    del window  # enters the band's width, not the step: swept at 256 only
    if seq_len % _LANES:
        raise ValueError(
            f"the banded kernel tiles the sequence by {_LANES}; got "
            f"seq_len={seq_len}"
        )
    rows = next(
        r for r in (seq_len, 2048, 1024, 512, 256, 128)
        if r <= 2048 and seq_len % r == 0
    )
    if rows == seq_len:
        return BandedStep(rows, 1, _LANES)

    def block_bytes(heads):  # double-buffered, the dkv pass's six blocks
        return 2 * 4 * heads * head_dim * (2 * seq_len + 4 * rows)

    heads = max(
        g for g in (1, 2, 4)
        if n_heads % g == 0 and (g == 1 or block_bytes(g) <= _VMEM_BLOCKS)
    )
    return BandedStep(rows, heads, 256 if rows % 256 == 0 else _LANES)


def _key_band(r0, tile: int, pad: int, seq_len: int):
    """(start, width) of the keys query rows [r0, r0+tile) can see."""
    if isinstance(r0, int):
        start = max(r0 - pad, 0)
        return start, r0 + tile - start  # cut at the diagonal
    # clamped at 0: the band then reaches past the diagonal, masked below
    return jnp.maximum(r0 - pad, 0), min(tile + pad, seq_len)


def _query_band(c0, tile: int, pad: int, seq_len: int):
    """(start, width) of the query rows that can see keys [c0, c0+tile)."""
    if isinstance(c0, int):
        return c0, min(tile + pad, seq_len - c0)  # cut at the last row
    width = min(tile + pad, seq_len)
    # clamped at the top: the band then starts before c0, masked below
    return jnp.minimum(c0, seq_len - width), width


def _rows(start, width: int):
    """``width`` rows from ``start``, which is a multiple of 128 by
    construction (rows, tile and pad all are): said to Mosaic where
    ``start`` is computed from the grid index."""
    if not isinstance(start, int):
        start = pl.multiple_of(start, _LANES)
    return pl.ds(start, width)


def _allowed(gap, shape, window: int, q_axis: int):
    """bool ``shape``: the query at index i along ``q_axis`` sees the key
    at index j along the other axis, the first query ``gap`` positions
    after the first key: causal AND in-window."""
    qi = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kj = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    d = (qi - kj) + gap
    return jnp.logical_and(d >= 0, d < window)


def _tiles(rows, seq_len, tile, n_blocks, window, own_is_query, q_axis):
    """The step's tiles as (own rows, band rows, mask), built ONCE a step,
    outside the loop over its heads. The tile's own rows are queries and
    its band keys (fwd, dq), or keys and its band queries (dkv); ``q_axis``
    is the axis of the score tile the queries lie along (0: [queries,
    keys], dq; 1: [keys, queries], fwd and dkv). A mask depends on the
    distance between tile and band alone, the same for every tile but the
    clamped ones: a step computes one or two and every head reuses them."""
    # the Python int 0 where the block is the whole sequence, so that every
    # offset below stays static
    first = 0 if n_blocks == 1 else pl.program_id(2) * rows
    band_of = _key_band if own_is_query else _query_band
    own_axis = q_axis if own_is_query else 1 - q_axis
    masks, out = {}, []
    for t in range(rows // tile):
        own0 = first + t * tile
        band0, width = band_of(own0, tile, _band_pad(window), seq_len)
        gap = own0 - band0 if own_is_query else band0 - own0
        shape = (tile, width) if own_axis == 0 else (width, tile)
        if isinstance(gap, int) and (gap, width) in masks:
            mask = masks[gap, width]
        else:
            mask = _allowed(gap, shape, window, q_axis)
            if isinstance(gap, int):
                masks[gap, width] = mask
        out.append((pl.ds(t * tile, tile), _rows(band0, width), mask))
    return out


def _masked(s, mask, scale: float):
    # GPT-Neo's scores are unscaled (scale 1.0): no multiply then
    return jnp.where(mask, s if scale == 1.0 else s * scale, _NEG_INF)


def _dot(a, b, contract=(1, 0)):
    """``a @ b``, or with ``contract`` (1, 1) ``a @ b.T`` and (0, 0)
    ``a.T @ b``; float32 out."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _each_head(n_heads: int, body) -> None:
    if n_heads == 1:
        body(0)
    else:
        jax.lax.fori_loop(0, n_heads, lambda g, c: (body(g), c)[1], 0)


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, window, tile, n_blocks
):
    _, heads, rows, _ = q_ref.shape
    tiles = _tiles(rows, k_ref.shape[2], tile, n_blocks, window, True, 1)

    def head(g):
        for own, band, mask in tiles:
            # [keys, query rows]: the softmax reduces over sublanes (vector
            # adds, no cross-lane reduction) and lse leaves as the lane row
            # it is stored as
            st = _masked(
                _dot(k_ref[0, g, band, :], q_ref[0, g, own, :], (1, 1)), mask, scale
            )
            m = jnp.max(st, axis=0, keepdims=True)
            p = jnp.exp(st - m)
            l = jnp.sum(p, axis=0, keepdims=True)
            # normalize in f32, cast to the activation dtype for the MXU PV
            # matmul — the same rounding the einsum path applies to its probs
            pn = (p * (1.0 / l)).astype(o_ref.dtype)
            o = _dot(pn, v_ref[0, g, band, :], (0, 0))  # contracts the keys
            o_ref[0, g, own, :] = o.astype(o_ref.dtype)
            lse_ref[0, g, :, own] = m + jnp.log(l)

    _each_head(heads, head)


def _dq_kernel(
    q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref, dq_ref,
    *, scale, window, tile, n_blocks,
):
    _, heads, rows, _ = q_ref.shape
    tiles = _tiles(rows, k_ref.shape[2], tile, n_blocks, window, True, 0)

    def head(g):
        for own, band, mask in tiles:
            # [query rows, keys], so dq = dS K is a plain matmul (computed
            # transposed like the other two it is a third slower: the sweep)
            k = k_ref[0, g, band, :]
            do = do_ref[0, g, own, :]
            s = _masked(_dot(q_ref[0, g, own, :], k, (1, 1)), mask, scale)
            p = jnp.exp(s - lse_ref[0, g, 0, own][:, None])
            dp = _dot(do, v_ref[0, g, band, :], (1, 1))
            # delta = rowsum(dO ∘ O), precomputed ONCE per q row in jnp by
            # _banded_bwd and shared with the dkv pass
            ds = (p * (dp - delta_ref[0, g, 0, own][:, None])).astype(do.dtype)
            dq_ref[0, g, own, :] = (_dot(ds, k) * scale).astype(dq_ref.dtype)

    _each_head(heads, head)


def _dkv_kernel(
    k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, scale, window, tile, n_blocks,
):
    _, heads, rows, _ = k_ref.shape
    tiles = _tiles(rows, q_ref.shape[2], tile, n_blocks, window, False, 1)

    def head(g):
        for own, band, mask in tiles:
            q = q_ref[0, g, band, :]
            do = do_ref[0, g, band, :]
            # transposed scores, [keys, query rows]: lse and delta are rows
            st = _masked(_dot(k_ref[0, g, own, :], q, (1, 1)), mask, scale)
            pt = jnp.exp(st - lse_ref[0, g, :, band])
            dv_ref[0, g, own, :] = _dot(pt.astype(do.dtype), do).astype(
                dv_ref.dtype
            )
            dpt = _dot(v_ref[0, g, own, :], do, (1, 1))
            dst = (pt * (dpt - delta_ref[0, g, :, band])).astype(do.dtype)
            dk_ref[0, g, own, :] = (_dot(dst, q) * scale).astype(dk_ref.dtype)

    _each_head(heads, head)


def _check_step(step: BandedStep, seq_len: int, n_heads: int) -> None:
    if (
        seq_len % step.rows
        or step.rows % step.tile
        or step.tile % _LANES
        or n_heads % step.heads
    ):
        raise ValueError(
            f"{step} does not tile L={seq_len}, H={n_heads}: rows must divide "
            f"L, tile divide rows and be a multiple of {_LANES}, heads divide H"
        )


def _specs(step: BandedStep, seq_len: int, head_dim: int):
    """(the step's [heads, rows, D] block, the heads' whole [heads, L, D],
    the step's lse block, the heads' whole lse) for grid (B, H/heads,
    L/rows). The whole blocks' index maps ignore the row-block index:
    fetched once a (batch, head group)."""
    rows, heads, _ = step
    return (
        pl.BlockSpec((1, heads, rows, head_dim), lambda b, h, r: (b, h, r, 0)),
        pl.BlockSpec((1, heads, seq_len, head_dim), lambda b, h, r: (b, h, 0, 0)),
        pl.BlockSpec((1, heads, 1, rows), lambda b, h, r: (b, h, 0, r)),
        pl.BlockSpec((1, heads, 1, seq_len), lambda b, h, r: (b, h, 0, 0)),
    )


def _call(kernel, name, step, q, window, scale, interpret, in_specs, out_specs,
          out_shape):
    B, H, L, _ = q.shape
    _check_step(step, L, H)
    return pl.pallas_call(
        functools.partial(
            kernel, scale=scale, window=window, tile=step.tile,
            n_blocks=L // step.rows,
        ),
        grid=(B, H // step.heads, L // step.rows),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        # the chosen step rides in the name: the chip's trace and the
        # compiled program's text say which rule engaged
        name=f"{name}_{step.tag()}",
    )


def _fwd_call(q, k, v, window, scale, step, interpret):
    B, H, L, D = q.shape
    block, whole, lse_block, _ = _specs(step, L, D)
    return _call(
        _fwd_kernel, "acco_banded_attn_fwd", step, q, window, scale, interpret,
        [block, whole, whole], [block, lse_block],
        [
            jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, L), jnp.float32),
        ],
    )(q, k, v)


def _dq_call(q, k, v, lse, delta, g, window, scale, step, interpret):
    B, H, L, D = q.shape
    block, whole, lse_block, _ = _specs(step, L, D)
    return _call(
        _dq_kernel, "acco_banded_attn_dq", step, q, window, scale, interpret,
        [block, whole, whole, lse_block, lse_block, block], block,
        jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
    )(q, k, v, lse, delta, g)


def _dkv_call(q, k, v, lse, delta, g, window, scale, step, interpret):
    B, H, L, D = q.shape
    block, whole, _, lse_whole = _specs(step, L, D)
    return _call(
        _dkv_kernel, "acco_banded_attn_dkv", step, q, window, scale, interpret,
        [block, block, whole, whole, lse_whole, lse_whole], [block, block],
        [
            jax.ShapeDtypeStruct((B, H, L, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, L, D), v.dtype),
        ],
    )(k, v, q, g, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _banded(q, k, v, window, scale, step, interpret):
    out, _ = _banded_fwd(q, k, v, window, scale, step, interpret)
    return out


def _banded_fwd(q, k, v, window, scale, step, interpret):
    out, lse = _fwd_call(q, k, v, window, scale, step, interpret)
    from jax.ad_checkpoint import checkpoint_name

    # same names as the full kernel: the 'dots' remat policy saves both
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _banded_bwd(window, scale, step, interpret, res, g):
    q, k, v, out, lse = res
    # delta = rowsum(dO ∘ O) once per q row in plain jnp (one fused
    # elementwise pass XLA handles); both kernel passes consume it in
    # the LSE layout instead of each recomputing it per tile.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[:, :, None, :]  # [B, H, 1, L]
    dq = _dq_call(q, k, v, lse, delta, g, window, scale, step, interpret)
    dk, dv = _dkv_call(q, k, v, lse, delta, g, window, scale, step, interpret)
    return dq, dk, dv


_banded.defvjp(_banded_fwd, _banded_bwd)


def supports_banded_attention(
    seq_len: int, head_dim: int, window: int
) -> bool:
    """Envelope: what :func:`banded_block_sizes` can tile (a sequence of
    whole 128-lane tiles; any head count, one head a step if need be), an
    MXU-aligned head dim, and a window that actually bands (0 = global →
    use the full kernel; a window spanning the whole sequence saves
    nothing). The band never grows with L, so unlike the full kernel
    there is no L ceiling from the score tile — cap at 8k as the tested
    range (a head's whole K and V, 2 MB each in bf16 at D=128, still sit
    in VMEM there)."""
    return (
        window > 0
        and window < seq_len
        and 128 <= seq_len <= 8192
        and seq_len % _LANES == 0
        and head_dim % 64 == 0
        # keep the band's [tile, tile+pad] score tiles sane
        and _band_pad(window) <= 7 * _LANES
    )


def banded_dot_product_attention(
    q: jax.Array,  # [B, H, L, D]
    k: jax.Array,  # [B, H, L, D] — MHA only (no GQA families use windows)
    v: jax.Array,
    window: int,  # STATIC python int > 0
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    block_sizes: Optional[BandedStep] = None,
) -> jax.Array:
    """Causal sliding-window attention computing only the key band.

    Same contract as ``fused_dot_product_attention(..., window=w)`` for
    static ``w > 0`` and no padding mask, at ~(W+tile)/L of its score
    work. Gradients via the banded two-pass custom VJP. ``block_sizes``
    is for the sweep and the tests: the model leaves it to
    :func:`banded_block_sizes`."""
    if interpret is None:
        import os

        interpret = bool(os.environ.get("ACCO_FUSED_ATTN_INTERPRET"))
    if q.shape[1] != k.shape[1]:
        raise ValueError(
            f"banded attention is MHA-only: q heads {q.shape[1]} != kv "
            f"heads {k.shape[1]}"
        )
    _, H, L, D = q.shape
    if not supports_banded_attention(L, D, int(window)):
        raise ValueError(
            f"shape L={L} D={D} window={window} outside "
            "the banded kernel envelope (supports_banded_attention)"
        )
    if scale is None:
        scale = D ** -0.5
    if block_sizes is None:
        block_sizes = banded_block_sizes(L, int(window), D, H)
    return _banded(q, k, v, int(window), float(scale), block_sizes, interpret)
