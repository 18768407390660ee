"""Multi-head attention for TPU: einsum-based, mask-composable.

One attention primitive serves both model families:
- Llama: causal + RoPE + grouped-query (KV heads repeated);
- GPT-Neo: causal, alternating **global** and **local sliding-window**
  layers (window from the model JSON; reference arch config
  `/root/reference/config/model/gpt-neo-125M.json` — window_size 256).

The window is a *traced scalar*: ``window == 0`` means global. This lets a
single compiled layer body serve both layer kinds inside a ``lax.scan``
over layers (no per-layer Python control flow, one XLA compilation).

Softmax runs in float32; the QK and PV contractions stay in the activation
dtype (bfloat16 on TPU) so they hit the MXU.
:func:`flash_dot_product_attention` is the fused O(L)-memory alternative
(JAX's bundled Pallas TPU flash kernel) behind the same call contract;
:func:`resolve_attention_impl` picks between them by a rule on
platform, length and remat policy; its docstring says which of the
rule's lines a chip run stands behind (ROADMAP S1).
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

log = logging.getLogger(__name__)

_NEG_INF = -1e9  # large-negative in float32; safe pre-softmax mask value


def attention_mask_bias(
    seq_len: int,
    window: jax.Array | int,
    pad_mask: Optional[jax.Array] = None,  # [B, L] 1=real token
) -> jax.Array:
    """Additive [B, 1, L, L] (or [1, 1, L, L]) float32 bias.

    causal AND (global OR within-window) AND not-padding. ``window`` may be
    a traced int scalar; 0 selects global attention.
    """
    i = jnp.arange(seq_len)[:, None]
    j = jnp.arange(seq_len)[None, :]
    causal = j <= i
    window = jnp.asarray(window)
    in_window = jnp.logical_or(window == 0, (i - j) < window)
    allowed = jnp.logical_and(causal, in_window)[None, None, :, :]
    if pad_mask is not None:
        keyable = pad_mask[:, None, None, :].astype(bool)
        allowed = jnp.logical_and(allowed, keyable)
    return jnp.where(allowed, 0.0, _NEG_INF).astype(jnp.float32)


def resolve_attention_impl(
    impl,
    seq_len: int,
    platform: Optional[str] = None,
    remat=False,
    head_dim: Optional[int] = None,
) -> str:
    """Resolve an attention-impl request to 'xla', 'flash', or 'fused'.

    ``impl``: 'fused' / 'flash' / 'xla' force. 'auto' (the
    ``use_pallas_attention: auto`` config default) applies this rule:

    - off the TPU: 'xla' (Pallas TPU kernels don't run there);
    - 'fused', the full-tile VMEM kernel (ops/fused_attention.py, no
      [B, H, L, L] scores in HBM), when ``head_dim`` is known and the
      shape fits the kernel's envelope (L <= 2048);
    - else 'flash' (the stock online-softmax kernel, O(L) memory, at the
      tiles :func:`flash_block_sizes` chooses) when ``seq_len`` is a
      multiple of 512 and at least 2048 with remat off, or at least 4096
      under any remat policy (``remat`` is the model's policy: False |
      True | 'dots');
    - else 'xla' (the einsum).

    MEASURED (my chip runs, PR 29; ``PERF.md`` §6): at L = 2048 under
    ``remat=dots`` (GPT-Neo-2.7B at depth 4, 20 heads x 128) the fused
    kernel on the global layers against the einsum, which this rule chose
    there until then (``and seq_len <= 1024``), parent and change in one
    call at one seed: one chip, batch 2, 29,620 -> 34,889 and 29,651 ->
    34,914 tokens/s/chip (+17.8%, +17.7%); dp=4, batch 4, ACCO 31,228 ->
    37,286 (+19.4%) and DDP 30,057 -> 35,666 (+18.7%), one pair;
    ``loss_at_ref_round`` equal to 7e-4 relative or closer; the compiled dp=4
    round's peak 13.20 -> 8.75 GB. The 1024 line went on that evidence.
    Still UNMEASURED: the other two thresholds. The "4096 under any remat
    policy" branch was reasoned from "the kernel's O(L) memory is itself
    the remat, so a policy's recompute on top of it is pure overhead";
    since PR 29 the 'dots' policies save the flash kernel's residuals
    (:func:`_named_flash`), its forward runs once a layer, and that reason
    is gone. With the fused kernel taking every aligned L <= 2048 the two
    thresholds now decide only lengths past 2048 and head sizes the fused
    kernel refuses; no shape's resolution was changed on the reasoning
    alone, and the flash kernel at its new tiles (0.96 ms forward +
    backward at ``[1, 16, 2048, 128]``) has not been timed against the
    fused kernel at 2048 (ROADMAP S1).

    Sliding-WINDOW layers (GPT-Neo) have their own lane outside this
    rule: the banded kernel (ops/banded_attention.py) computes only
    the key band and is dispatched per layer by the model itself —
    inside the 'fused' plan, and as the local-layer branch of the
    einsum plan where the fused kernel's envelope ends
    (GPTNeoModel._dense_attn_plan) — so this resolver only ever decides
    the GLOBAL layers' impl.
    """
    impl = normalize_attention_impl(impl)
    remat = normalize_remat(remat)  # '0'/'false' must mean remat-OFF
    # here exactly as they do in wrap_remat — the no-remat flash
    # threshold (2048 vs 4096) depends on it
    if impl != "auto":
        return impl
    if platform is None:
        platform = jax.devices()[0].platform
    if platform != "tpu":
        return "xla"
    if head_dim is not None:
        from acco_tpu.ops.fused_attention import supports_fused_attention

        if supports_fused_attention(seq_len, head_dim):
            return "fused"
    threshold = 2048 if remat in (False, None) else 4096
    if seq_len >= threshold and seq_len % 512:
        # a long-but-unaligned sequence (e.g. 3000) would otherwise
        # silently fall back to the O(L^2)-memory einsum path in exactly
        # the regime it stops fitting HBM.
        log.warning(
            "attention 'auto': seq_len %d is past the flash crossover but "
            "not a multiple of 512 (the kernel's block size); using the "
            "O(L^2)-memory XLA path — pad/pack sequences to a 512 multiple "
            "to enable the fused kernel",
            seq_len,
        )
    return "flash" if seq_len >= threshold and seq_len % 512 == 0 else "xla"


def normalize_remat(value) -> "bool | str":
    """THE remat-spelling normalizer: config/CLI/env surfaces write the
    policy as YAML booleans, 0/1 ints, or strings ('true', 'dots', the
    README's ``train.remat=1``); every consumer (wrap_remat, the
    attention resolver, hbm_check) normalizes through this
    one function so a spelling can never mean remat-off to one of them
    and remat-on to another. Returns False | True | 'dots' |
    'dots+probs'; anything else raises."""
    if isinstance(value, str):
        value = value.lower()
    if value in (False, None, 0, "0", "false", "no", "off", ""):
        return False
    if value in (True, 1, "1", "true", "yes", "on"):
        return True
    if value in ("dots", "dots+probs"):
        return value
    raise ValueError(
        f"remat must be False, True, 'dots', or 'dots+probs' "
        f"(0/1/'true'/'false' spellings accepted); got {value!r}"
    )


def normalize_attention_impl(impl) -> str:
    """Map config-surface spellings (YAML bool/None included) to
    'auto' | 'flash' | 'fused' | 'xla' | 'ring'; reject anything else.

    'ring' is only valid on a model constructed with a ``sequence_axis``
    and applied inside a ``shard_map`` over that axis (context
    parallelism; see acco_tpu/ops/ring_attention.py)."""
    if impl in (True, "flash", "true", "True"):
        return "flash"
    if impl in (False, None, "xla", "false", "False"):
        return "xla"
    if impl in ("auto", "ring", "fused"):
        return impl
    raise ValueError(
        f"attention impl must be auto/flash/fused/xla/ring, got {impl!r}"
    )


def repeat_kv(
    q: jax.Array, k: jax.Array, v: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Grouped-query head repeat: expand [B, Hkv, L, D] K/V to q's head
    count (shared by all attention impls)."""
    n_rep = q.shape[1] // k.shape[1]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=1)
        v = jnp.repeat(v, n_rep, axis=1)
    return k, v


def flash_block_sizes(seq_len: int, head_dim: int):
    """The stock flash kernel's tile sizes, chosen from the shape.

    jax 0.9.0's ``BlockSizes.get_default`` gives 128 for all eleven ("TODO:
    select better parameters"): at L = 4096 a 32 x 32 grid of 128 x 128
    score tiles a head, each a matmul too small to fill the MXU. The rule
    here: ``b`` is the largest of 1024, 512, 256, 128 that divides
    ``seq_len``, and ``h = min(b, 512)``;

    - forward: ``block_q = block_k_major = block_k = b``;
    - dK/dV: ``block_q_major = block_k_major = block_k = b``, ``block_q = h``;
    - dQ: ``block_q = b``, ``block_k_major = block_k = h``.

    The sweep behind it (my chip run, PR 29: one v5e chip, ``[1, 16, 4096,
    128]`` bf16, causal, no segment ids; each kernel jitted alone, ms a call
    by the host's clock over 20 calls, best of 3; 99 tile sets, every one
    accepted by Mosaic). Forward as (block_q, block_k_major, block_k), dK/dV
    as (block_q_major, block_q, block_k_major, block_k), dQ as (block_q,
    block_k_major, block_k):

    ===================  ====  ========================  ====  ==================  ====
    forward              ms    dK/dV                     ms    dQ                  ms
    ===================  ====  ========================  ====  ==================  ====
    128, 128, 128        5.17  128, 128, 128, 128        5.87  128, 128, 128       4.67
    256, 512, 256        1.46  256, 256, 512, 256        1.95  256, 512, 512       1.47
    256, 1024, 1024      0.90  512, 512, 512, 512        1.36  512, 512, 512       1.25
    512, 512, 512        0.80  1024, 512, 512, 512       1.37  1024, 512, 256      1.23
    1024, 512, 512       0.80  512, 512, 1024, 1024      1.24  **1024, 512, 512**  1.20
    512, 1024, 1024      0.78  1024, 1024, 1024, 1024    1.24  1024, 1024, 512     1.36
    1024, 1024, 512      0.76  1024, 256, 1024, 1024     1.23  1024, 1024, 1024    1.37
    **1024, 1024, 1024** 0.74  **1024, 512, 1024, 1024** 1.22  512, 2048, 512      1.78
    1024, 2048, 1024     0.82  1024, 512, 2048, 1024     1.34  1024, 2048, 1024    1.76
    ===================  ====  ========================  ====  ==================  ====

    Past 128 the surface is flat (the best eight of each kernel lie within
    10%); a ``block_k_major`` of 2048 loses everywhere, because a causal
    tile on the diagonal is computed whole. Forward + backward through
    ``jax.grad``, ms, the rule against one size for all eleven:

    ====  =====  =====  =====  =====  ========
    L     128    256    512    1024   the rule
    ====  =====  =====  =====  =====  ========
    2048  3.61   1.63   0.96   1.18   0.96
    4096  15.04  6.22   3.25   3.23   3.03
    8192  68.27  25.35  12.14  10.88  10.51
    ====  =====  =====  =====  =====  ========

    At 4096 the three kernels do 206 GFLOP (1.05 ms of the chip's peak) in
    3.03 ms where the default took 15.04. ``head_dim`` was 128 throughout;
    what a tile costs in VMEM is its ``[block_q, block_k]`` float32 scores,
    which ``head_dim`` does not enter, and 64 compiles for the chip at the
    same tiles (tests/test_tpu_compile.py), unmeasured.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    del head_dim  # enters no tile's size: the docstring's last paragraph
    if seq_len % 128:
        raise ValueError(
            f"the flash kernel tiles the sequence by 128; got seq_len={seq_len}"
        )
    b = next(size for size in (1024, 512, 256, 128) if seq_len % size == 0)
    h = min(b, 512)
    return BlockSizes(
        block_q=b, block_k_major=b, block_k=b, block_b=1,
        block_q_major_dkv=b, block_q_dkv=h, block_k_major_dkv=b, block_k_dkv=b,
        block_q_dq=b, block_k_major_dq=h, block_k_dq=h,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _named_flash(q, k, v, segment_ids, scale, block_sizes):
    """The stock kernel's forward-with-residuals and backward under a
    ``custom_vjp`` of this repo's, for one reason: the residuals get the
    names the 'dots' policies save (models/layers.wrap_remat), as
    ops/fused_attention.py gives its own. The stock ``custom_vjp`` names
    nothing, so under ``remat=dots`` every layer's forward kernel ran
    twice (4.2 of 18.6 ms in ``olmoe-l1-acco-1chip``: ledger, PR 28)."""
    return _named_flash_fwd(q, k, v, segment_ids, scale, block_sizes)[0]


def _named_flash_fwd(q, k, v, segment_ids, scale, block_sizes):
    from jax.ad_checkpoint import checkpoint_name
    from jax.experimental.pallas.ops.tpu import flash_attention as stock

    # jitted under the stock entry point's name: the chip's trace names a
    # kernel by its innermost jit or scope (flash_attention.N), and that is
    # what the benchmark's flash_attn_kernel_* metrics read
    @jax.jit
    def flash_attention(q, k, v, segment_ids):
        return stock._flash_attention_impl(
            q, k, v, None, segment_ids, True, True, scale,
            block_sizes.block_b, block_sizes.block_q,
            block_sizes.block_k_major, block_sizes.block_k, False,
        )

    out, l, m = flash_attention(q, k, v, segment_ids)
    out = checkpoint_name(out, "attn_out")
    # the softmax's running sum and maximum, [B, H, L] float32 each: the
    # two halves of the log-sum-exp the repo's own kernels save
    l, m = checkpoint_name(l, "attn_lse"), checkpoint_name(m, "attn_lse")
    return out, (q, k, v, None, segment_ids, out, l, m)


def _named_flash_bwd(scale, block_sizes, residuals, d_out):
    from jax.experimental.pallas.ops.tpu import flash_attention as stock

    dq, dk, dv, _, _ = stock._flash_attention_bwd(
        False, True, scale, block_sizes, False, residuals, d_out
    )
    return dq, dk, dv, None


_named_flash.defvjp(_named_flash_fwd, _named_flash_bwd)


def flash_dot_product_attention(
    q: jax.Array,  # [B, H, L, D]
    k: jax.Array,  # [B, Hkv, L, D]
    v: jax.Array,  # [B, Hkv, L, D]
    pad_mask: Optional[jax.Array] = None,  # [B, L] 1=real token
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal attention via the fused Pallas TPU flash kernel.

    Same contract as :func:`dot_product_attention` with a causal+padding
    mask, but O(L) memory: no [L, L] bias / scores materialization — the
    online-softmax tiles stay in VMEM (pallas_guide.md; this is what makes
    long sequences fit HBM at all). Padding is expressed as segment ids
    (pad tokens get segment 0, real tokens 1, cross-segment pairs are
    masked), gradients flow through the kernel's own backward kernels
    (:func:`_named_flash`), at the tiles :func:`flash_block_sizes` gives.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import SegmentIds

    k, v = repeat_kv(q, k, v)  # the kernel wants equal head counts
    if scale is None:
        scale = q.shape[-1] ** -0.5
    seg = None
    if pad_mask is not None:
        ids = pad_mask.astype(jnp.int32)
        seg = SegmentIds(q=ids, kv=ids)
    return _named_flash(
        q, k, v, seg, float(scale), flash_block_sizes(q.shape[2], q.shape[3])
    )


def cached_attention(
    q: jax.Array,  # [R, H, 1, D] the current position's queries
    k_ctx: jax.Array,  # [R, C, Hkv, D] rows gathered from the paged cache
    v_ctx: jax.Array,  # [R, C, Hkv, D]
    k_new: jax.Array,  # [R, Hkv, 1, D] the current token's K (post-RoPE)
    v_new: jax.Array,  # [R, Hkv, 1, D]
    q_positions: jax.Array,  # [R] absolute position being decoded
    kv_positions: jax.Array,  # [C] or [R, C] absolute position per row
    window: jax.Array | int = 0,  # traced scalar; 0 = global
    scale: Optional[float] = None,
) -> jax.Array:  # [R, H, 1, D]
    """Single-position attention against gathered KV-cache rows — the
    decode-step half of the serving path (acco_tpu/serve/kv_cache.py
    holds the page pool; the models' ``decode`` calls this per layer).

    A cached row attends iff its position is STRICTLY below the query's:
    rows at or past ``q_positions`` are either unallocated, garbage tail
    of a prefill bucket, or the current position's own page slot, which
    is only written *after* this step computes — the current token
    instead rides in via ``k_new``/``v_new`` (the causal diagonal,
    always attended). ``window`` carries GPT-Neo's per-layer sliding
    window as traced data, exactly like :func:`attention_mask_bias`:
    0 = global, else rows older than ``window`` positions are masked —
    which is what lets a narrow band gather (the paged analogue of the
    banded kernel's key band) stand in for the full context on local
    layers.
    """
    R = q.shape[0]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # [R, C, Hkv, D] page-major rows -> [R, Hkv, C, D] head-major, with
    # the current token appended as the final key/value column
    k_all = jnp.concatenate([k_ctx.transpose(0, 2, 1, 3), k_new], axis=2)
    v_all = jnp.concatenate([v_ctx.transpose(0, 2, 1, 3), v_new], axis=2)
    k_all, v_all = repeat_kv(q, k_all, v_all)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k_all, preferred_element_type=jnp.float32
    )
    if kv_positions.ndim == 1:
        kv_positions = jnp.broadcast_to(kv_positions[None, :], (R, kv_positions.shape[0]))
    qp = q_positions[:, None]
    window = jnp.asarray(window)
    allowed = kv_positions < qp
    allowed &= jnp.logical_or(window == 0, (qp - kv_positions) < window)
    allowed = jnp.concatenate(
        [allowed, jnp.ones((R, 1), bool)], axis=1  # self-attention column
    )
    bias = jnp.where(allowed, 0.0, _NEG_INF).astype(jnp.float32)
    scores = scores * scale + bias[:, None, None, :]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v_all)


def dot_product_attention(
    q: jax.Array,  # [B, H, L, D]
    k: jax.Array,  # [B, Hkv, L, D]
    v: jax.Array,  # [B, Hkv, L, D]
    bias: jax.Array,  # [B or 1, 1, L, L] additive float32
    scale: Optional[float] = None,
) -> jax.Array:
    """Masked softmax(QK^T)V with float32 softmax; returns q.dtype."""
    k, v = repeat_kv(q, k, v)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale + bias
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    # Named for the 'dots+probs' remat policy (models/layers.wrap_remat):
    # saving the bf16 probabilities lets the backward skip recomputing
    # the [B, H, L, L] float32 scores + softmax, the largest buffer the
    # einsum attention path writes.
    from jax.ad_checkpoint import checkpoint_name

    probs = checkpoint_name(probs, "attn_probs")
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
