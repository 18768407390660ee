"""Fused lm-head + cross-entropy Pallas kernel: no [N, V] HBM logits.

The [B, L, V] float32 logits are the train step's largest transient
([8, 1023, 50257] f32 = 1.65 GB in the 125M cells, [B, L, 128k] for
Llama-3). The materialized lm-head + CE reads ``lm_head_ce_ms`` 15.96 of
``neo125m-acco-1chip``'s round where its matmuls need 9.6 ms at the MXU's
peak (ledger, PR 24; ROADMAP S5). ``chunked_causal_lm_loss`` bounds the
*memory* with a scan and a recompute. This kernel removes the stream; it
has NEVER RUN ON THE CHIP (compiled for it only: tests/test_tpu_compile.py,
tests/test_fused_ce.py), so what it wins is unmeasured (ROADMAP S5):

* forward — grid (row_blocks, vocab_tiles), vocab innermost: one
  [RB, VT] logits tile lives in VMEM per step; a running (max, sumexp,
  true-logit, sum-logits) online-softmax state in VMEM scratch carries
  across the vocab tiles of a row block. HBM sees hidden + W (bf16)
  and three [N] f32 vectors out — never the logits.
* backward — by default ONE kernel, grid (vocab_tiles, row_blocks):
  recomputes the logits tile (the standard flash-style trade), forms
  ``dlogits = d_lse·softmax + d_true·onehot + d_sum·valid`` in VMEM,
  and contracts it twice: dW tiles accumulate in VMEM scratch across
  the inner row steps (consecutive revisits — sound); dHidden is
  emitted as per-vocab-tile PARTIALS [T, N, D] and summed outside the
  kernel (~2·T·N·D·4 B ≈ 1.3 GB of HBM at the flagship shape, ≪ the
  logits stream it replaces). An input/output-aliased running dH
  buffer would be unsound: Pallas prefetches input blocks ahead of the
  compute step, so reading a location an earlier grid step wrote races
  the pipeline. When the partials would exceed
  ``ACCO_FUSED_CE_PARTIAL_CAP`` (default 1 GiB — Llama-3-class
  vocab×hidden), the backward splits into dH-only + dW-only kernels
  whose accumulators live in VMEM scratch (one extra logits recompute,
  5 contractions instead of 4, no [T, N, D] buffer at all).
  Total matmul work is 4 (or 5) lm-head-sized contractions vs the
  materialized path's 3, the price of the removed HBM stream (the
  backward contractions run in the activation dtype on the MXU, where
  the materialized path's f32 dlogits matmuls do not).

Semantics parity with ``ops.losses._per_token_ce`` (the contract every
loss path shares): f32 log-sum-exp, IGNORE_INDEX masking, HF
LabelSmoother smoothing, and ``real_vocab`` exclusion of padded vocab
columns — the kernel masks columns ≥ v_real to -1e30 (additive-bias
convention of ops/attention.py) so lse / smoothing are bit-equivalent
to the unpadded model's.

Reference frame: the reference materializes logits inside HF models and
pays the same stream on CUDA (`/root/reference/trainer_decoupled.py:
28-34`); fused CE losses are the established fix in large-vocab
training. This is the TPU-native (Pallas, VMEM-pipelined) form.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from acco_tpu.ops.losses import IGNORE_INDEX

_NEG = -1e30  # large-negative mask (avoids -inf minus -inf NaNs)


def _fwd_kernel(
    vreal_ref,  # SMEM (1, 1) int32: real vocab size
    h_ref,  # [RB, D] activation dtype
    w_ref,  # [D, VT]
    t_ref,  # [1, RB, 1] int32 targets (safe: IGNORE already mapped to 0)
    lse_ref,  # out [1, RB, 1] f32
    tl_ref,  # out [1, RB, 1] f32 true logit
    sl_ref,  # out [1, RB, 1] f32 sum of (real-vocab) logits
    m_sc,  # scratch [RB, 1] f32 running max
    s_sc,  # scratch [RB, 1] f32 running sumexp
    tl_sc,  # scratch [RB, 1] f32
    sl_sc,  # scratch [RB, 1] f32
):
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        s_sc[...] = jnp.zeros_like(s_sc)
        tl_sc[...] = jnp.zeros_like(tl_sc)
        sl_sc[...] = jnp.zeros_like(sl_sc)

    logits = jax.lax.dot_general(
        h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [RB, VT]
    vt = logits.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + t * vt
    valid = col < vreal_ref[0, 0]
    logits = jnp.where(valid, logits, _NEG)

    m_old = m_sc[...]
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=1, keepdims=True))
    s_sc[...] = s_sc[...] * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=1, keepdims=True
    )
    m_sc[...] = m_new
    tgt = t_ref[0]  # [RB, 1]
    tl_sc[...] += jnp.sum(
        jnp.where(col == tgt, logits, 0.0), axis=1, keepdims=True
    )
    sl_sc[...] += jnp.sum(
        jnp.where(valid, logits, 0.0), axis=1, keepdims=True
    )

    @pl.when(t == nt - 1)
    def _fin():
        lse_ref[0] = m_sc[...] + jnp.log(s_sc[...])
        tl_ref[0] = tl_sc[...]
        sl_ref[0] = sl_sc[...]


def _bwd_kernel(
    vreal_ref,  # SMEM (1, 1) int32
    h_ref,  # [RB, D]
    w_ref,  # [D, VT]
    t_ref,  # [1, RB, 1] int32
    lse_ref,  # [1, RB, 1] f32
    dl_ref,  # [1, RB, 1] f32 cotangent of lse
    dt_ref,  # [1, RB, 1] f32 cotangent of true logit
    ds_ref,  # [1, RB, 1] f32 cotangent of sum-logits
    dh_ref,  # out [1, RB, D] f32: this vocab tile's dHidden partial
    dw_ref,  # out [D, VT] f32
    dw_sc,  # scratch [D, VT] f32
):
    t = pl.program_id(0)
    r = pl.program_id(1)
    nr = pl.num_programs(1)

    h = h_ref[...]
    w = w_ref[...]
    # dp in the activation dtype: on the MXU (f32 only under tests)
    dp = _dp_tile(vreal_ref, h, w, t_ref, lse_ref, dl_ref, dt_ref, ds_ref, t)

    dh_ref[0] = jax.lax.dot_general(
        dp, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    # dW accumulates across the INNER row steps in VMEM scratch.
    dw = jax.lax.dot_general(
        h, dp, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(r == 0)
    def _init():
        dw_sc[...] = dw

    @pl.when(r > 0)
    def _acc():
        dw_sc[...] += dw

    @pl.when(r == nr - 1)
    def _fin():
        dw_ref[...] = dw_sc[...]


def _dp_tile(vreal_ref, h, w, t_ref, lse_ref, dl_ref, dt_ref, ds_ref, t):
    """Shared backward tile math: recompute logits, form dlogits."""
    logits = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    vt = logits.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + t * vt
    valid = col < vreal_ref[0, 0]
    p = jnp.exp(jnp.where(valid, logits, _NEG) - lse_ref[0])
    onehot = (col == t_ref[0]).astype(jnp.float32)
    return (
        dl_ref[0] * p
        + dt_ref[0] * onehot
        + ds_ref[0] * valid.astype(jnp.float32)
    ).astype(h.dtype)


def _bwd_dh_kernel(
    vreal_ref, h_ref, w_ref, t_ref, lse_ref, dl_ref, dt_ref, ds_ref,
    dh_ref, dh_sc,
):
    """dHidden-only backward, grid (row_blocks, vocab_tiles): the vocab
    axis is INNER, so dH accumulates in VMEM scratch across consecutive
    revisits — no [T, N, D] partials (the single-kernel form's memory
    cost, prohibitive at 128k vocab)."""
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    dp = _dp_tile(
        vreal_ref, h_ref[...], w_ref[...], t_ref, lse_ref, dl_ref,
        dt_ref, ds_ref, t,
    )
    dh = jax.lax.dot_general(
        dp, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(t == 0)
    def _init():
        dh_sc[...] = dh

    @pl.when(t > 0)
    def _acc():
        dh_sc[...] += dh

    @pl.when(t == nt - 1)
    def _fin():
        dh_ref[...] = dh_sc[...]


def _bwd_dw_kernel(
    vreal_ref, h_ref, w_ref, t_ref, lse_ref, dl_ref, dt_ref, ds_ref,
    dw_ref, dw_sc,
):
    """dW-only backward, grid (vocab_tiles, row_blocks): rows INNER, dW
    tiles accumulate in VMEM scratch (same shape as _bwd_kernel's dW
    half, without the dH side)."""
    t = pl.program_id(0)
    r = pl.program_id(1)
    nr = pl.num_programs(1)
    h = h_ref[...]
    dp = _dp_tile(
        vreal_ref, h, w_ref[...], t_ref, lse_ref, dl_ref, dt_ref,
        ds_ref, t,
    )
    dw = jax.lax.dot_general(
        h, dp, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(r == 0)
    def _init():
        dw_sc[...] = dw

    @pl.when(r > 0)
    def _acc():
        dw_sc[...] += dw

    @pl.when(r == nr - 1)
    def _fin():
        dw_ref[...] = dw_sc[...]


def _pad_to(x: jax.Array, axis: int, mult: int, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _lm_head_ce(h, w, tgt, v_real, rb, vt, interpret):
    out, _ = _lm_head_ce_fwd(h, w, tgt, v_real, rb, vt, interpret)
    return out


def _lm_head_ce_fwd(h, w, tgt, v_real, rb, vt, interpret):
    N, D = h.shape
    Vp = w.shape[1]
    R, T = N // rb, Vp // vt
    tgt3 = tgt.reshape(R, rb, 1)
    # v_real may be a traced per-shard scalar (vocab-parallel path)
    vreal = jnp.asarray(v_real, jnp.int32).reshape(1, 1)
    grid = (R, T)
    row_spec = pl.BlockSpec((1, rb, 1), lambda r, t: (r, 0, 0))
    out_shape = jax.ShapeDtypeStruct((R, rb, 1), jnp.float32)
    lse, tl, sl = pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda r, t: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((rb, D), lambda r, t: (r, 0)),
            pl.BlockSpec((D, vt), lambda r, t: (0, t)),
            row_spec,
        ],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[out_shape, out_shape, out_shape],
        scratch_shapes=[pltpu.VMEM((rb, 1), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # one [RB, VT] f32 logits tile + double-buffered operands
            # exceed the 16 MB default scoped-vmem budget at the
            # production tile sizes; v5e VMEM is 128 MB
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
        name="acco_fused_ce_fwd",
    )(vreal, h, w, tgt3)
    outs = (lse.reshape(N), tl.reshape(N), sl.reshape(N))
    return outs, (h, w, tgt, v_real, lse)


def _lm_head_ce_bwd(rb, vt, interpret, res, g):
    h, w, tgt, v_real, lse = res
    d_lse, d_tl, d_sl = g
    N, D = h.shape
    Vp = w.shape[1]
    R, T = N // rb, Vp // vt
    tgt3 = tgt.reshape(R, rb, 1)
    vreal = jnp.asarray(v_real, jnp.int32).reshape(1, 1)
    cot = [
        jnp.zeros((R, rb, 1), jnp.float32) if c is None
        else c.astype(jnp.float32).reshape(R, rb, 1)
        for c in (d_lse, d_tl, d_sl)
    ]
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=100 * 1024 * 1024,  # see _lm_head_ce_fwd
    )
    cp_common = dict(interpret=interpret, compiler_params=params)
    smem = pl.BlockSpec((1, 1), lambda *_: (0, 0), memory_space=pltpu.SMEM)
    args = (vreal, h, w, tgt3, lse, *cot)

    # One fused backward kernel (4 matmul passes total) while its
    # [T, N, D] dHidden partials stay modest; past the cap (large vocab
    # x hidden — Llama-3-class heads) split into dH-only + dW-only
    # kernels (5 passes, one extra logits recompute) whose accumulators
    # live in VMEM scratch instead.
    import os

    cap = int(os.environ.get("ACCO_FUSED_CE_PARTIAL_CAP", 1 << 30))
    if T * N * D * 4 <= cap:
        row_spec = pl.BlockSpec((1, rb, 1), lambda t, r: (r, 0, 0))
        dh_part, dw = pl.pallas_call(
            _bwd_kernel,
            grid=(T, R),
            in_specs=[
                smem,
                pl.BlockSpec((rb, D), lambda t, r: (r, 0)),
                pl.BlockSpec((D, vt), lambda t, r: (0, t)),
                row_spec,
                row_spec,  # lse
                row_spec,  # d_lse
                row_spec,  # d_tl
                row_spec,  # d_sl
            ],
            out_specs=[
                pl.BlockSpec((1, rb, D), lambda t, r: (t, r, 0)),
                pl.BlockSpec((D, vt), lambda t, r: (0, t)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((T, N, D), jnp.float32),
                jax.ShapeDtypeStruct((D, Vp), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((D, vt), jnp.float32)],
            name="acco_fused_ce_bwd",
            **cp_common,
        )(*args)
        return (
            dh_part.sum(axis=0).astype(h.dtype),
            dw.astype(w.dtype),
            None,
            None,
        )

    row_rt = pl.BlockSpec((1, rb, 1), lambda r, t: (r, 0, 0))
    dh = pl.pallas_call(
        _bwd_dh_kernel,
        grid=(R, T),
        in_specs=[
            smem,
            pl.BlockSpec((rb, D), lambda r, t: (r, 0)),
            pl.BlockSpec((D, vt), lambda r, t: (0, t)),
            row_rt,
            row_rt,
            row_rt,
            row_rt,
            row_rt,
        ],
        out_specs=pl.BlockSpec((rb, D), lambda r, t: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rb, D), jnp.float32)],
        name="acco_fused_ce_bwd_dh",
        **cp_common,
    )(*args)
    row_tr = pl.BlockSpec((1, rb, 1), lambda t, r: (r, 0, 0))
    dw = pl.pallas_call(
        _bwd_dw_kernel,
        grid=(T, R),
        in_specs=[
            smem,
            pl.BlockSpec((rb, D), lambda t, r: (r, 0)),
            pl.BlockSpec((D, vt), lambda t, r: (0, t)),
            row_tr,
            row_tr,
            row_tr,
            row_tr,
            row_tr,
        ],
        out_specs=pl.BlockSpec((D, vt), lambda t, r: (0, t)),
        out_shape=jax.ShapeDtypeStruct((D, Vp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((D, vt), jnp.float32)],
        name="acco_fused_ce_bwd_dw",
        **cp_common,
    )(*args)
    return dh.astype(h.dtype), dw.astype(w.dtype), None, None


_lm_head_ce.defvjp(_lm_head_ce_fwd, _lm_head_ce_bwd)


def supports_fused_ce(n_rows: int, hidden: int, vocab: int) -> bool:
    """Envelope: MXU/VPU-aligned hidden dim; enough vocab to tile. Rows
    are padded to the row-block size internally (padded targets are
    IGNORE_INDEX, so they drop out of the loss), so no minimum row
    COUNT beyond non-emptiness — a degenerate B=1, L<=8 eval batch is
    in-envelope, and the build-time gate (losses.resolve_fused_loss)
    can answer without knowing the runtime batch shape.
    n_rows == 0 (L=1 with shift) stays out: a zero-row grid would never
    write the dW output buffer in the backward."""
    return n_rows >= 1 and hidden % 128 == 0 and vocab >= 128


def _tiles(D: int, V: int, n_rows: int, block_rows: int,
           block_vocab: int) -> tuple[int, int]:
    """Derive (rb, vt) from the VMEM budget instead of per-D point
    thresholds, so ANY hidden dim the envelope admits compiles. Analytic
    per-grid-cell bytes: double-buffered bf16 [RB, D] rows + [D, VT]
    weights, f32 [D, VT] dW scratch, ~3 f32 [RB, VT] score/prob
    temporaries, double-buffered f32 [RB, D] dH — targeted at <=45 MB
    because the measured Mosaic footprint runs ~2x the analytic sum
    (rb512 x vt1024 at D=4096 measured 105.8 MB vs ~53 MB analytic)
    against the kernels' 100 MB vmem_limit_bytes."""
    budget = 45 * 1024 * 1024
    vt = min(block_vocab, max(V, 128))
    while vt > 128 and 8 * D * vt > budget // 2:  # w db (4B/el) + dw_sc
        vt //= 2
    rb = min(block_rows, max(8, n_rows))
    while rb > 128 and rb * (12 * D + 12 * vt) > budget:
        rb //= 2
    # Align the row block to the bf16 sublane tile (16; covers f32's 8):
    # a non-power-of-2 n_rows (e.g. 400 at large D -> rb 200 after
    # halving) or a tiny batch (n_rows 9..15 -> rb = n_rows) would
    # otherwise hand Mosaic a row block it may refuse to lower on real
    # TPU even though the interpreter accepts it. Rounding
    # UP is safe — rows are padded to rb by the caller.
    rb = max(16, rb // 16 * 16)
    return rb, min(vt, max(V, 1))


def _prep(hidden, lm_head, labels, shift, block_rows, block_vocab,
          interpret):
    """Shared prologue of both public entry points: envelope check,
    interpret default, next-token shift, row/vocab padding, and the
    VMEM-budget tile sizing — ONE copy so the tensor-parallel path can
    never drift from the base path's tiling or sentinel rules."""
    if interpret is None:
        import os

        interpret = bool(os.environ.get("ACCO_FUSED_CE_INTERPRET"))
    B, L, D = hidden.shape
    V = lm_head.shape[1]
    if not supports_fused_ce(B * (L - 1 if shift else L), D, V):
        raise ValueError(
            f"shape N={B * L} D={D} V={V} outside the fused CE envelope"
        )
    if shift:
        hidden = hidden[:, :-1, :]
        targets = labels[:, 1:]
    else:
        targets = labels
    h2 = hidden.reshape(-1, D)
    t1 = targets.reshape(-1)
    rb, vt = _tiles(D, V, h2.shape[0], block_rows, block_vocab)
    h2 = _pad_to(h2, 0, rb)
    t1 = _pad_to(t1, 0, rb, value=IGNORE_INDEX)
    w = _pad_to(lm_head, 1, vt)
    return h2, t1, w, rb, vt, interpret


def fused_ce_loss(
    hidden: jax.Array,  # [B, L, D] activation dtype
    lm_head: jax.Array,  # [D, V]
    labels: jax.Array,  # [B, L] int32, IGNORE_INDEX = masked
    label_smoothing: float = 0.0,
    shift: bool = True,
    num_valid=None,
    real_vocab: Optional[int] = None,
    block_rows: int = 512,
    block_vocab: int = 2048,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``causal_lm_loss(hidden @ lm_head, labels)`` with the logits
    VMEM-resident (same contract as ops.losses.causal_lm_loss:
    next-token shift, IGNORE_INDEX mask, f32 LSE, HF smoothing,
    ``real_vocab`` Megatron-padding exclusion, ``num_valid`` denominator
    override for sequence sharding)."""
    V = lm_head.shape[1]
    h2, t1, w, rb, vt, interpret = _prep(
        hidden, lm_head, labels, shift, block_rows, block_vocab, interpret
    )
    v_real = V if real_vocab is None else real_vocab
    mask = (t1 != IGNORE_INDEX).astype(jnp.float32)
    safe = jnp.where(t1 == IGNORE_INDEX, 0, t1).astype(jnp.int32)

    lse, tl, sl = _lm_head_ce(h2, w, safe, v_real, rb, vt, interpret)
    per_tok = lse - tl
    if label_smoothing:
        per_tok = (1.0 - label_smoothing) * per_tok + label_smoothing * (
            lse - sl / v_real
        )
    denom = jnp.maximum(mask.sum() if num_valid is None else num_valid, 1.0)
    return (per_tok * mask).sum() / denom


def vocab_parallel_fused_ce_loss(
    hidden: jax.Array,  # [B, L, D] activation dtype (replicated over tp)
    lm_head_local: jax.Array,  # [D, V/tp] this shard's vocab slice
    labels: jax.Array,  # [B, L] int32 GLOBAL ids, IGNORE_INDEX = masked
    vocab_axis: str,  # mesh axis the vocab dim is sharded over
    label_smoothing: float = 0.0,
    shift: bool = True,
    num_valid=None,
    real_vocab: Optional[int] = None,
    block_rows: int = 512,
    block_vocab: int = 2048,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """:func:`fused_ce_loss` over a vocab-sharded head, inside a
    ``shard_map`` carrying ``vocab_axis`` — the tensor-parallel loss
    path (ops/losses.vocab_parallel_causal_lm_loss) without the local
    [B, L, V/tp] float32 logits.

    Per shard the kernel produces local (lse, true-logit, sum-logits)
    partials over its vocab slice; the cross-shard combination is cheap
    O(N) jnp — the global LSE is a log-sum-exp of the per-shard LSEs
    (stabilized by an all-gathered stop-grad max, the same
    pmax-has-no-autodiff workaround the materialized vp CE uses), the
    true logit and sum-logits are psums (a target id lands in exactly
    one shard's range; elsewhere the kernel's one-hot never fires).
    ``real_vocab`` excludes Megatron tp-padding: each shard masks its
    own slice of the padding via a per-shard traced v_real scalar.
    Every shard returns the same full-vocab loss value."""
    from jax import lax

    v_local = lm_head_local.shape[1]
    h2, t1, w, rb, vt, interpret = _prep(
        hidden, lm_head_local, labels, shift, block_rows, block_vocab,
        interpret,
    )

    v0 = lax.axis_index(vocab_axis) * v_local
    vocab_total = v_local * lax.axis_size(vocab_axis)
    if real_vocab is not None and real_vocab < vocab_total:
        n_real_local = jnp.clip(real_vocab - v0, 0, v_local)
        vocab_total = real_vocab
    else:
        n_real_local = jnp.int32(v_local)

    mask = (t1 != IGNORE_INDEX).astype(jnp.float32)
    # Local target index, sanitized to the -1 sentinel whenever it does
    # NOT fall in THIS shard's real column range: IGNORE rows, other
    # shards' ids, and — crucially — ids ≥ v_local that would otherwise
    # land on this shard's locally-PADDED columns (w is padded to a vt
    # multiple, so those columns exist here but their global ids belong
    # to the next shard; matching one would pick up the -1e30 masked
    # logit and blow the psum'd true-logit up to ~1e30).
    t_loc = t1.astype(jnp.int32) - v0
    safe = jnp.where(
        (t1 == IGNORE_INDEX) | (t_loc < 0) | (t_loc >= v_local), -1, t_loc
    ).astype(jnp.int32)

    lse_l, tl_l, sl_l = _lm_head_ce(h2, w, safe, n_real_local, rb, vt,
                                    interpret)
    # stabilizing max: value-only (LSE is shift-invariant in the combine)
    gmax = jnp.max(
        lax.all_gather(lax.stop_gradient(lse_l), vocab_axis), axis=0
    )
    lse = jnp.log(lax.psum(jnp.exp(lse_l - gmax), vocab_axis)) + gmax
    tl = lax.psum(tl_l, vocab_axis)
    per_tok = lse - tl
    if label_smoothing:
        sl = lax.psum(sl_l, vocab_axis)
        per_tok = (1.0 - label_smoothing) * per_tok + label_smoothing * (
            lse - sl / vocab_total
        )
    denom = jnp.maximum(mask.sum() if num_valid is None else num_valid, 1.0)
    return (per_tok * mask).sum() / denom
