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
platform, length and remat policy whose thresholds are unmeasured
(ROADMAP S1).
"""

from __future__ import annotations

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
      [B, H, L, L] scores in HBM), when ``head_dim`` is known, the shape
      fits the kernel's envelope AND ``seq_len <= 1024``;
    - else 'flash' (the stock online-softmax kernel, O(L) memory) when
      ``seq_len`` is a multiple of 512 and at least 2048 with remat off,
      or at least 4096 under any remat policy (the kernel's O(L) memory
      is itself the remat, so a policy's recompute on top of it is pure
      overhead; ``remat`` is the model's policy: False | True | 'dots');
    - else 'xla' (the einsum).

    UNMEASURED: the three thresholds (1024, 2048, 4096) are guesses. The
    fused kernel's envelope takes L = 2048 and no cell has run it there;
    the benchmark's cells sit at L = 1024 ('fused'; ``attn_kernel_ms``
    18.08 of a 97.55 ms round at ``attn_kernel_roofline`` 12.2%: ledger,
    PR 24) and at L = 2048 under ``remat=dots`` ('xla' for the global
    layers), one side of each line only. ROADMAP S1 runs the other sides
    (cell ``neo27b-l4-seq1024``, R4); D2 then makes the choice from shape
    and deletes what loses.

    Sliding-WINDOW layers (GPT-Neo) have their own lane outside this
    rule: the banded kernel (ops/banded_attention.py) computes only
    the key band and is dispatched per layer by the model itself —
    inside the 'fused' plan at L <= 1024, and as the local-layer branch
    of the einsum plan past it (GPTNeoModel._dense_attn_plan) — so this
    resolver only ever decides the GLOBAL layers' impl.
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

        # 'auto' prefers the bespoke kernel only up to L=1024, the shape
        # class it was built for; past it the choice is unmeasured
        # (docstring; ROADMAP S1).
        if supports_fused_attention(seq_len, head_dim) and seq_len <= 1024:
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
    masked), gradients flow through the kernel's custom VJP.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds,
        flash_attention as _pallas_flash,
    )

    k, v = repeat_kv(q, k, v)  # the kernel wants equal head counts
    if scale is None:
        scale = q.shape[-1] ** -0.5
    seg = None
    if pad_mask is not None:
        ids = pad_mask.astype(jnp.int32)
        seg = SegmentIds(q=ids, kv=ids)
    return _pallas_flash(q, k, v, segment_ids=seg, causal=True, sm_scale=scale)


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
