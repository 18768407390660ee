"""Ring attention: causal attention over a sequence-sharded mesh axis.

Long-context support beyond one chip's HBM: the sequence dimension is
sharded over a mesh axis (``sp``) and K/V chunks rotate around the ring
with ``lax.ppermute`` while each device accumulates its queries' attention
with the online-softmax (running max / denominator) merge — the blockwise
formulation of Liu et al.'s Ring Attention (see PAPERS.md). Every hop
rides a neighbor ICI link and XLA overlaps the ppermute with the local
block's matmuls, so the ring adds bandwidth-bound time only when compute
per block is too small to hide it.

The reference has no sequence parallelism (its max context is a tokenizer
truncation constant, SURVEY.md §5 'long-context') — this module is part of
the designed TPU-native scale-out surface, not a parity port.

Differentiation: the body is pure jnp + ``ppermute`` inside the caller's
``shard_map``, so ``jax.grad`` derives the backward ring automatically
(ppermute transposes to the reverse permutation).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e9


def _resolve_block_impl(impl: str, platform: Optional[str] = None) -> str:
    """'auto' -> the Pallas block kernel (ops/block_attention.py) on TPU,
    the jnp block on CPU meshes — same convention as
    resolve_attention_impl ('xla'/'fused' force)."""
    if impl == "auto":
        import os

        forced = os.environ.get("ACCO_RING_BLOCK_IMPL")
        if forced and forced != "auto":
            impl = forced  # validated below
        else:
            if platform is None:
                platform = jax.devices()[0].platform
            return "fused" if platform == "tpu" else "xla"
    if impl not in ("xla", "fused"):
        raise ValueError(f"ring block impl must be auto/xla/fused, got {impl!r}")
    return impl


def _merge(o, m, l, o_blk, m_blk, l_blk):
    """Online-softmax merge of an unnormalized block partial into the
    running (o, m, l) — THE numerically delicate rescale, shared by both
    ring layouts so they can never disagree."""
    m_new = jnp.maximum(m, m_blk)
    corr = jnp.exp(m - m_new)
    corr_blk = jnp.exp(m_blk - m_new)
    return (
        o * corr[..., None] + o_blk * corr_blk[..., None],
        m_new,
        l * corr + l_blk * corr_blk,
    )


def ring_attention(
    q: jax.Array,  # [B, H, Lc, D] — this device's query chunk
    k: jax.Array,  # [B, Hkv, Lc, D] — this device's key chunk
    v: jax.Array,  # [B, Hkv, Lc, D]
    axis_name: str,  # sequence mesh axis; must be called inside shard_map
    scale: Optional[float] = None,
    block_impl: str = "auto",
) -> jax.Array:
    """Causal attention where the sequence is sharded over ``axis_name``.

    Device ``i`` holds tokens ``[i*Lc, (i+1)*Lc)``. Returns this device's
    output chunk [B, H, Lc, D] in q.dtype. Padding masks are not supported
    on this path — it serves the const-len packed pretraining shape
    (`/root/reference/trainer_base.py:84-97` has no mask either).
    """
    ws = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    # GQA: the ring carries the *unrepeated* [B, Hkv, Lc, D] chunks —
    # repeating before the loop would multiply every ppermute hop's ICI
    # traffic by n_rep; heads are expanded per-block inside step().
    n_rep = q.shape[1] // k.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    block_impl = _resolve_block_impl(block_impl)

    B, H, Lc, D = q.shape
    qf = q.astype(jnp.float32) if block_impl == "xla" else q
    fwd_perm = [(i, (i + 1) % ws) for i in range(ws)]

    def block_update(o, m, l, k_c, v_c, kv_idx):
        if block_impl == "fused":
            from acco_tpu.ops.block_attention import block_attention_partial

            # three compiled bodies switched on the (traced) hop source:
            # past chunk = full block, self = causal triangle, future =
            # skip entirely (the jnp path pays a fully-masked block there)
            def full_case(o, m, l):
                return _merge(
                    o, m, l,
                    *block_attention_partial(q, k_c, v_c, scale=scale),
                )

            def diag_case(o, m, l):
                return _merge(
                    o, m, l,
                    *block_attention_partial(
                        q, k_c, v_c, diag=True, scale=scale
                    ),
                )

            branch = jnp.where(
                kv_idx < my_idx, 0, jnp.where(kv_idx == my_idx, 1, 2)
            )
            return lax.switch(
                branch,
                [full_case, diag_case, lambda o, m, l: (o, m, l)],
                o, m, l,
            )
        k_r = jnp.repeat(k_c, n_rep, axis=1) if n_rep > 1 else k_c
        v_r = jnp.repeat(v_c, n_rep, axis=1) if n_rep > 1 else v_c
        scores = (
            jnp.einsum("bhqd,bhkd->bhqk", qf, k_r.astype(jnp.float32)) * scale
        )
        # Block-causal mask: past chunks fully visible, the diagonal chunk
        # lower-triangular, future chunks fully masked.
        i_loc = jnp.arange(Lc)[:, None]
        j_loc = jnp.arange(Lc)[None, :]
        diag = jnp.where(j_loc <= i_loc, 0.0, _NEG_INF)
        block = jnp.where(
            kv_idx < my_idx, 0.0, jnp.where(kv_idx == my_idx, diag, _NEG_INF)
        )
        scores = scores + block

        m_new = jnp.maximum(m, scores.max(-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_r.astype(jnp.float32)
        )
        return o_new, m_new, l_new

    def step(carry, s):
        o, m, l, k_c, v_c = carry
        o, m, l = block_update(o, m, l, k_c, v_c, (my_idx - s) % ws)
        k_nxt = lax.ppermute(k_c, axis_name, fwd_perm)
        v_nxt = lax.ppermute(v_c, axis_name, fwd_perm)
        return (o, m, l, k_nxt, v_nxt), None

    # pcast: the accumulators must carry the shard_map varying-axis type
    # from the start — the Pallas block's outputs are varying over the
    # sequence axis, and lax.scan requires carry-in/out types to match.
    init = tuple(
        lax.pcast(x, (axis_name,), to="varying")
        for x in (
            jnp.zeros((B, H, Lc, D), jnp.float32),
            jnp.full((B, H, Lc), _NEG_INF, jnp.float32),
            jnp.zeros((B, H, Lc), jnp.float32),
        )
    ) + (k, v)
    # ws-1 permuting steps in the scan, the last delivered chunk consumed
    # outside it — ws blocks need only ws-1 ring hops, and a collective in
    # a uniform scan body can't be dead-code-eliminated by XLA.
    (o, m, l, k_last, v_last), _ = lax.scan(step, init, jnp.arange(ws - 1))
    o, m, l = block_update(o, m, l, k_last, v_last, (my_idx - (ws - 1)) % ws)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def windowed_ring_attention(
    q: jax.Array,  # [B, H, Lc, D] — this device's query chunk
    k: jax.Array,  # [B, Hkv, Lc, D]
    v: jax.Array,  # [B, Hkv, Lc, D]
    axis_name: str,
    window,  # int32 scalar (traced ok): 0 = global causal, w = sliding window
    q_positions: jax.Array,  # [Lc] absolute positions of this shard's tokens
    kv_positions_fn,  # shard_index -> [Lc] absolute positions of its tokens
    scale: Optional[float] = None,
    block_impl: str = "auto",
) -> jax.Array:
    """Ring attention with exact causal + sliding-window masking built from
    absolute token positions — GPT-Neo's alternating global/local layers
    under context parallelism (HF semantics: ``i`` attends ``j`` iff
    ``j <= i`` and, on local layers, ``j > i - window``).

    Layout-agnostic: the position arrays describe the shard layout, so
    contiguous (``src*Lc + arange``) and zig-zag (:func:`zigzag_positions`)
    both work — positions are pure functions of the (static) layout, so
    key positions per hop are *computed*, never communicated. Hops whose
    (q-chunk, kv-chunk) pair is fully masked (local layers: chunks beyond
    the window; any layer: fully-future chunks) skip their matmuls via
    ``lax.cond``; the K/V rotation still runs — the ring must stay uniform
    across devices.

    GPT-Neo's arch ceiling is 2048 tokens, so this path is a capability
    (the reference's flagship pretrain model on the long-context surface),
    not a perf frontier: the O(Lc^2) position-compare mask is one compare
    per score and vanishes next to the matmuls.
    """
    ws = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    n_rep = q.shape[1] // k.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    block_impl = _resolve_block_impl(block_impl)

    B, H, Lc, D = q.shape
    qf = q.astype(jnp.float32) if block_impl == "xla" else q
    qi = q_positions[:, None]  # [Lc, 1]
    fwd_perm = [(i, (i + 1) % ws) for i in range(ws)]

    def mask_for(src):  # [Lc, Lc] bool: may q-token i attend kv-token j?
        kj = kv_positions_fn(src)[None, :]
        return (kj <= qi) & ((window == 0) | (kj > qi - window))

    def block_update(o, m, l, k_c, v_c, src):
        mask = mask_for(src)

        def live(o, m, l):
            if block_impl == "fused":
                # the mask is regenerated IN-KERNEL from the position
                # vectors + traced window — [Lc, Lc] never touches HBM
                from acco_tpu.ops.block_attention import (
                    block_attention_partial,
                )

                return _merge(
                    o, m, l,
                    *block_attention_partial(
                        qf, k_c, v_c, scale=scale,
                        q_positions=q_positions,
                        kv_positions=kv_positions_fn(src),
                        window=window,
                    ),
                )
            k_r = jnp.repeat(k_c, n_rep, axis=1) if n_rep > 1 else k_c
            v_r = jnp.repeat(v_c, n_rep, axis=1) if n_rep > 1 else v_c
            scores = (
                jnp.einsum("bhqd,bhkd->bhqk", qf, k_r.astype(jnp.float32))
                * scale
            )
            scores = jnp.where(mask[None, None], scores, _NEG_INF)
            m_new = jnp.maximum(m, scores.max(-1))
            p = jnp.exp(scores - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_r.astype(jnp.float32)
            )
            return o_new, m_new, l_new

        return lax.cond(jnp.any(mask), live, lambda o, m, l: (o, m, l), o, m, l)

    def step(carry, s):
        o, m, l, k_c, v_c = carry
        o, m, l = block_update(o, m, l, k_c, v_c, (my_idx - s) % ws)
        k_nxt = lax.ppermute(k_c, axis_name, fwd_perm)
        v_nxt = lax.ppermute(v_c, axis_name, fwd_perm)
        return (o, m, l, k_nxt, v_nxt), None

    init = tuple(
        lax.pcast(x, (axis_name,), to="varying")
        for x in (
            jnp.zeros((B, H, Lc, D), jnp.float32),
            jnp.full((B, H, Lc), _NEG_INF, jnp.float32),
            jnp.zeros((B, H, Lc), jnp.float32),
        )
    ) + (k, v)
    (o, m, l, k_last, v_last), _ = lax.scan(step, init, jnp.arange(ws - 1))
    o, m, l = block_update(o, m, l, k_last, v_last, (my_idx - (ws - 1)) % ws)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def zigzag_positions(global_len: int, ws: int, shard_index) -> jax.Array:
    """Absolute positions [global_len/ws] of shard ``shard_index``'s tokens
    under zig-zag layout: half-chunks ``i`` and ``2ws-1-i`` of ``2ws``.

    The early/late pairing balances causal attention work: every shard's
    two halves together attend exactly ``2ws+1`` half-chunk blocks, so no
    device waits on a longer-tailed neighbor (the contiguous layout's
    device ``ws-1`` does ``ws`` blocks while device 0 does one — and the
    ring formulation makes everyone pay for the worst)."""
    lh = global_len // (2 * ws)
    early = shard_index * lh + jnp.arange(lh)
    late = (2 * ws - 1 - shard_index) * lh + jnp.arange(lh)
    return jnp.concatenate([early, late])


def zigzag_permutation(global_len: int, ws: int):
    """numpy permutation ``perm`` with ``x_zigzag = x[..., perm]``: global
    sequence -> concatenation of the ws shards' zig-zag layouts (so plain
    contiguous sharding over the axis lands half-chunks (i, 2ws-1-i) on
    shard i). Returns (perm, inverse_perm) as numpy int arrays."""
    import numpy as np

    if global_len % (2 * ws):
        raise ValueError(
            f"zig-zag layout needs global_len divisible by 2*ws "
            f"({2 * ws}); got {global_len} — a shorter permutation would "
            f"silently truncate every sequence"
        )
    lh = global_len // (2 * ws)
    order = []
    for i in range(ws):
        order.extend(range(i * lh, (i + 1) * lh))
        order.extend(range((2 * ws - 1 - i) * lh, (2 * ws - i) * lh))
    perm = np.asarray(order, dtype=np.int64)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.size)
    return perm, inv


def zigzag_ring_attention(
    q: jax.Array,  # [B, H, Lc, D] — zig-zag chunk: [early half; late half]
    k: jax.Array,  # [B, Hkv, Lc, D]
    v: jax.Array,  # [B, Hkv, Lc, D]
    axis_name: str,
    scale: Optional[float] = None,
    block_impl: str = "auto",
) -> jax.Array:
    """Causal ring attention over the zig-zag sequence layout.

    Device ``i``'s chunk is half-chunks ``(i, 2ws-1-i)`` (zigzag_positions).
    Per ring hop every device computes exactly TWO unmasked half-blocks
    (plus two diagonal triangles on the self hop) instead of one fully
    masked-out Lc x Lc block — ~2x less attention compute than
    :func:`ring_attention` at identical semantics, and the work is uniform
    across devices so no one gates the ring (striped/zig-zag balancing).

    Which (q-half, kv-half) pairs are live depends only on whether the
    hop wrapped around the ring, so the two computed blocks are selected
    with O(chunk) operand selects, never by masking O(chunk^2) scores:

    - self hop (s=0):     qa x ea (diag),  qb x lb (diag),  qb x ea (full)
    - no-wrap hop (j<=i): qa x ea (full),  qb x ea (full)
    - wrapped hop (j>i):  qb x ea (full),  qb x la (full)
    """
    ws = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    n_rep = q.shape[1] // k.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    block_impl = _resolve_block_impl(block_impl)

    B, H, Lc, D = q.shape
    lh = Lc // 2
    qf = q.astype(jnp.float32) if block_impl == "xla" else q
    qa, qb = qf[:, :, :lh, :], qf[:, :, lh:, :]
    i_loc = jnp.arange(lh)[:, None]
    j_loc = jnp.arange(lh)[None, :]
    diag_mask = jnp.where(j_loc <= i_loc, 0.0, _NEG_INF)
    fwd_perm = [(i, (i + 1) % ws) for i in range(ws)]

    def expand(x):
        return jnp.repeat(x, n_rep, axis=1) if n_rep > 1 else x

    def attend(q_half, k_half, v_half, bias):
        # bias is statically None (full block) or the causal triangle —
        # the kernel path maps it to its static diag flag
        if block_impl == "fused":
            from acco_tpu.ops.block_attention import block_attention_partial

            return block_attention_partial(
                q_half, k_half, v_half,
                diag=bias is not None, scale=scale,
            )
        scores = (
            jnp.einsum(
                "bhqd,bhkd->bhqk", q_half, expand(k_half).astype(jnp.float32)
            )
            * scale
        )
        if bias is not None:
            scores = scores + bias
        m_blk = scores.max(-1)
        p = jnp.exp(scores - m_blk[..., None])
        l_blk = p.sum(-1)
        o_blk = jnp.einsum(
            "bhqk,bhkd->bhqd", p, expand(v_half).astype(jnp.float32)
        )
        return o_blk, m_blk, l_blk

    def self_blocks(oa, ma, la, ob, mb, lb, k_c, v_c):
        ka, va = k_c[:, :, :lh, :], v_c[:, :, :lh, :]
        kb, vb = k_c[:, :, lh:, :], v_c[:, :, lh:, :]
        oa, ma, la = _merge(oa, ma, la, *attend(qa, ka, va, diag_mask))
        ob, mb, lb = _merge(ob, mb, lb, *attend(qb, kb, vb, diag_mask))
        ob, mb, lb = _merge(ob, mb, lb, *attend(qb, ka, va, None))
        return oa, ma, la, ob, mb, lb

    def hop_blocks(oa, ma, la, ob, mb, lb, k_c, v_c, wrapped):
        # no-wrap: (qa x ea, qb x ea); wrap: (qb x ea, qb x la).
        ea_k, ea_v = k_c[:, :, :lh, :], v_c[:, :, :lh, :]
        la_k, la_v = k_c[:, :, lh:, :], v_c[:, :, lh:, :]
        # Block 1: query half is qa (no-wrap) or qb (wrap), kv is ea.
        q1 = jnp.where(wrapped, qb, qa)
        o1, m1, l1 = attend(q1, ea_k, ea_v, None)
        # Its result merges into the a-accumulator (no-wrap) or b (wrap).
        oa2, ma2, la2 = _merge(oa, ma, la, o1, m1, l1)
        ob2, mb2, lb2 = _merge(ob, mb, lb, o1, m1, l1)
        oa = jnp.where(wrapped, oa, oa2)
        ma = jnp.where(wrapped, ma, ma2)
        la = jnp.where(wrapped, la, la2)
        # Block 2: qb x ea (no-wrap) or qb x la (wrap) — both into b. The
        # base is block 1's b-accumulator when block 1 went into b (wrap),
        # else the original b (block 1 went into a).
        k2 = jnp.where(wrapped, la_k, ea_k)
        v2 = jnp.where(wrapped, la_v, ea_v)
        o2, m2, l2 = attend(qb, k2, v2, None)
        ob3, mb3, lb3 = _merge(
            jnp.where(wrapped, ob2, ob),
            jnp.where(wrapped, mb2, mb),
            jnp.where(wrapped, lb2, lb),
            o2,
            m2,
            l2,
        )
        return oa, ma, la, ob3, mb3, lb3

    def step(carry, s):
        # The self block is consumed before the scan, so each iteration
        # permutes FIRST: after the hop, k_c holds device (i-s)'s chunk.
        oa, ma, la, ob, mb, lb, k_c, v_c = carry
        k_c = lax.ppermute(k_c, axis_name, fwd_perm)
        v_c = lax.ppermute(v_c, axis_name, fwd_perm)
        src = (my_idx - s) % ws  # kv source device of this hop
        wrapped = src > my_idx
        oa, ma, la, ob, mb, lb = hop_blocks(
            oa, ma, la, ob, mb, lb, k_c, v_c, wrapped
        )
        return (oa, ma, la, ob, mb, lb, k_c, v_c), None

    z_o = jnp.zeros((B, H, lh, D), jnp.float32)
    z_m = jnp.full((B, H, lh), _NEG_INF, jnp.float32)
    z_l = jnp.zeros((B, H, lh), jnp.float32)
    oa, ma, la, ob, mb, lb = self_blocks(z_o, z_m, z_l, z_o, z_m, z_l, k, v)
    carry = (oa, ma, la, ob, mb, lb, k, v)
    if ws > 1:
        # hops s=1..ws-2 in the scan; the last delivered chunk consumed
        # outside it (ws-1 hops total, like ring_attention).
        if ws > 2:
            carry, _ = lax.scan(step, carry, jnp.arange(1, ws - 1))
        oa, ma, la, ob, mb, lb, k_c, v_c = carry
        k_last = lax.ppermute(k_c, axis_name, fwd_perm)
        v_last = lax.ppermute(v_c, axis_name, fwd_perm)
        src = (my_idx - (ws - 1)) % ws
        oa, ma, la, ob, mb, lb = hop_blocks(
            oa, ma, la, ob, mb, lb, k_last, v_last, src > my_idx
        )
    o = jnp.concatenate(
        [
            oa / jnp.maximum(la, 1e-30)[..., None],
            ob / jnp.maximum(lb, 1e-30)[..., None],
        ],
        axis=2,
    )
    return o.astype(q.dtype)
