"""Pipeline parallelism: layer stages over a ``pp`` mesh axis.

Lifts the replicated-parameters ceiling on a second axis beyond tensor
parallelism (the reference replicates the full model per rank,
`/root/reference/trainer_decoupled.py:244-269`): the scanned layer stack
splits into ``pp`` contiguous stages, each held by one slice of the mesh,
and microbatch activations flow stage-to-stage over neighbor ICI links
with ``lax.ppermute``.

TPU-first shape of the design:

- **The parameter layout is TpLayout** (parallel/tp.py) with specs that
  split every stacked layer leaf on its layer-stack dim 0
  (``model.pp_param_specs``): per-stage flat vectors, ZeRO-1 sharding the
  stage's vector over dp, the replicated segment (embeddings / final norm
  / lm head) as the flat prefix — the whole flat-state machinery (specs,
  checkpoint, export, gather) is shared, not re-implemented.
- **The schedule is GPipe expressed as one ``lax.scan`` over ticks**
  (microbatch-count + pp - 1), SPMD-uniform: every stage runs the same
  compiled body each tick; stage 0 injects the next microbatch's
  embeddings, the last stage's finished microbatch folds into the loss
  (uniformly, via the vocab-parallel CE below — warmup/drain ticks mask
  to zero), and one ``ppermute`` per tick moves activations on.
  ``jax.grad`` of this loop IS the backward pipeline: the scan reverses
  and every ppermute transposes to the reverse hop — no hand-written
  backward schedule. Per-microbatch activation residuals are bounded by
  the model's own remat policy inside ``stage_blocks``.
- **The embedding/head are vocab-parallel over pp** and the loss is the
  Megatron-style vocab-parallel CE on the last stage's output, broadcast
  by one masked [b, L, D] psum per tick — SPMD-uniform (no collective
  ever sits inside a one-stage ``cond``), each stage does 1/pp of the
  head matmul, and nobody stores more than V/pp embedding rows.
- **Gradient correction is the tp recipe** (parallel/tp.py module
  docstring): the loss reaches every stage through forward pp-psums
  (the activation broadcast + the CE's lse/label psums), so under
  ``check_vma=False`` every gradient carries a uniform ×pp factor —
  cancelled by the ZeRO-1 count divisor — and the replicated segment
  (norm scales) needs one masked psum. ``zero1_update_shard``'s
  ``tp_axis``/``n_repl`` path does both, unchanged.

The pipeline microbatches are the round's ``n_grad_accumulation``
microbatch block: grad accumulation and pipelining are the same loop, so
``n_acc >= pp`` keeps the bubble fraction at ``(pp-1)/(n_acc+pp-1)``.

The pipeline composes with every other axis: tp inside each stage
(parallel/tp.ComposedLayout — the per-leaf gradient segments become two
boundary psums), sp inside each stage (ring attention over the
sequence-sharded chunks; the loss follows the CP partial-sum
convention), and all four at once — dp x pp x tp x sp is
gradient-exact vs plain dp (tests/test_pipeline_parallel.py).

On the schedule choice: this is GPipe, not 1F1B — but with the per-tick
``jax.checkpoint`` the scan's live state is one [b, L, D] carry per
tick, so the activation-memory argument for 1F1B (pp live microbatches
instead of n_acc) mostly evaporates: what GPipe+remat stores per tick
is what 1F1B stores per in-flight microbatch, at a fraction of the
scheduling complexity and with ``jax.grad`` deriving the backward
schedule for free. The bubble fraction is identical. A hand-scheduled
1F1B would save only the one extra stage-forward recompute per tick.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from acco_tpu.ops.losses import IGNORE_INDEX, causal_lm_loss


def make_pp_loss_fn(
    model,
    layout,  # TpLayout over model.pp_param_specs() (ComposedLayout: tp x pp)
    pp_axis: str,
    label_smoothing: float = 0.0,
    vocab_axes=None,  # axes the vocab dim shards over; default (pp_axis,);
    # tp x pp composition passes the ("pp", "tp") tuple — the embedding
    # lookup and the vocab-parallel CE run over the combined index
    # (lax.axis_index of a tuple is the flattened major-to-minor index,
    # matching ComposedLayout's sequential outer-then-inner vocab slices)
    seq_axis: str | None = None,  # pp x sp: the sequence dim is sharded
    # over this axis inside every stage (ring attention in stage_blocks);
    # labels arrive pre-shifted on the GLOBAL sequence (prep_cp_leaves)
    # and each microbatch's loss is the psum'd global token mean
    fused_loss=False,  # 'pallas': the VMEM-tiled vocab-parallel CE
    # kernel (ops/fused_ce.vocab_parallel_fused_ce_loss) instead of
    # materializing the [b, L, V/(pp·tp)] local logits each tick;
    # 'chunk'/True have no sharded form and fall back to materialized
    n_vocab_shards: int | None = None,  # pp·tp — the shared envelope
    # gate (losses.resolve_fused_loss) validates the PER-SHARD vocab
    # slice; defaults to the layout's shard count (= pp·tp)
) -> Callable:
    """Block loss under pipeline parallelism, as a function of this
    stage's local flat vector.

    ``loss_fn(flat_local, block) -> (loss_wsum, count)`` consumes the
    WHOLE microbatch block (the pipeline loop is the grad-accumulation
    loop): ``block`` carries input_ids/attention_mask/labels
    [M, b_local, L] plus ``valid`` [M]; returns the valid-weighted loss
    sum and the valid count, matching ``accumulate_grads``'s contract so
    the ZeRO-1 update path is shared with dp/tp.
    """

    # Megatron vocab padding: exclude padded rows from the softmax.
    from acco_tpu.ops.losses import real_vocab_of

    real_vocab = real_vocab_of(model)
    if vocab_axes is None:
        vocab_axes = pp_axis
    # the shared soft envelope gate (fail at build, not mid-trace),
    # validated against the per-shard vocab slice the kernel tiles
    import logging

    from acco_tpu.ops.losses import resolve_fused_loss

    use_pallas_ce = (
        resolve_fused_loss(
            fused_loss, model, real_vocab,
            warn=logging.getLogger("acco_tpu").warning,
            # the layout's shard count IS pp·tp — no guessing
            n_vocab_shards=n_vocab_shards or layout.tp,
        )
        == "pallas"
    )

    def loss_fn(flat_local: jax.Array, block: dict):
        params = layout.unravel_local(flat_local)
        pp = lax.axis_size(pp_axis)
        sidx = lax.axis_index(pp_axis)
        ids, labels = block["input_ids"], block["labels"]
        valid = block["valid"]
        M = ids.shape[0]
        head = model.lm_head(params)  # [D, V/pp] local slice

        def embed(ids_m):
            # model-owned: vocab-split wte lookup (+ learned positions for
            # GPT-Neo), SPMD-uniform, reconstructed by psum over the
            # vocab axes (pp, or (pp, tp) under composition)
            return model.pp_embed(params, ids_m, vocab_axes)

        # stage s -> s+1 chain (no wraparound: stage 0's input is injected)
        chain = [(i, i + 1) for i in range(pp - 1)]

        def tick_compute(h, loss_wsum, t):
            # Stage 0 injects microbatch t's embeddings (clamped index:
            # drain ticks re-embed the last microbatch, masked out below).
            m_in = jnp.clip(t, 0, M - 1)
            x0 = embed(ids[m_in]).astype(h.dtype)
            h_in = jnp.where(sidx == 0, x0, h)
            h_out = model.stage_blocks(
                params["layers"], h_in, stage_index=sidx, pp=pp
            )

            # Fold the last stage's finished microbatch (t-(pp-1)) into
            # the loss — UNIFORMLY: one masked psum broadcasts its output
            # ([b, L, D], cheap on ICI), then every stage computes its
            # V/pp slice of the head matmul and the vocab-parallel CE
            # (the pp analogue of the Megatron tp loss) — the head work
            # parallelizes over stages instead of gating every tick on
            # the last stage, and warmup/drain ticks mask to zero.
            m_out = t - (pp - 1)
            m_idx = jnp.clip(m_out, 0, M - 1)
            h_ce = lax.psum(
                jnp.where(sidx == pp - 1, h_out, jnp.zeros_like(h_out)),
                pp_axis,
            )
            hid = model.finalize(params, h_ce)
            if use_pallas_ce:
                # VMEM-tiled sharded CE: no [b, L, V/(pp·tp)] logits;
                # same CE semantics/conventions as the branches below
                from acco_tpu.ops.fused_ce import (
                    vocab_parallel_fused_ce_loss,
                )

                ce = lambda **kw: vocab_parallel_fused_ce_loss(
                    hid, head, labels[m_idx], vocab_axes,
                    label_smoothing, real_vocab=real_vocab, **kw,
                )
            else:
                local_logits = jnp.einsum(
                    "bld,dv->blv", hid, head,
                    preferred_element_type=jnp.float32,
                )
                ce = lambda **kw: causal_lm_loss(
                    local_logits, labels[m_idx], label_smoothing,
                    vocab_axis=vocab_axes, real_vocab=real_vocab, **kw,
                )
            if seq_axis is None:
                li = ce(shift=True)
            else:
                # sp: this shard's chunk of pre-shifted labels. The
                # CP-loss convention (common.make_flat_loss_fn): each
                # shard contributes its PARTIAL — local nll sum over the
                # psum'd global count (num_valid) — so the shard losses
                # SUM over sp to the microbatch's global token mean
                # (world_mean_loss re-sums them; a pre-psum'd mean here
                # would count sp x).
                cnt = (
                    (labels[m_idx] != IGNORE_INDEX).sum().astype(jnp.float32)
                )
                li = ce(shift=False, num_valid=lax.psum(cnt, seq_axis))
            live_w = jnp.where(m_out >= 0, valid[m_idx], 0.0)
            loss_wsum = loss_wsum + li * live_w
            return h_out, loss_wsum

        # GPipe activation checkpointing: without this the tick scan
        # stacks each tick's stage residuals AND the last stage's [B, L, V]
        # f32 logits over all M+pp-1 ticks — measured 45.7 GB/chip for the
        # 8B at {dp:4, pp:8} where the checkpointed loop fits. Saving only
        # the carry (one [b, L, D] activation per tick) and recomputing
        # the stage forward in the backward pass is the textbook pipeline
        # memory/flops trade. The ppermute stays OUTSIDE the checkpoint so
        # the backward doesn't re-run the hop collective.
        tick_ck = jax.checkpoint(tick_compute)

        def tick(carry, t):
            h, loss_wsum = carry
            h_out, loss_wsum = tick_ck(h, loss_wsum, t)
            h_next = lax.ppermute(h_out, pp_axis, chain)
            return (h_next, loss_wsum), None

        D = model.config.hidden_size
        h0 = jnp.zeros(ids.shape[1:] + (D,), model.param_dtype)
        (h, loss_wsum), _ = lax.scan(
            tick, (h0, jnp.zeros((), jnp.float32)), jnp.arange(M + pp - 1)
        )
        # loss_wsum is already replicated over pp: the vocab-parallel CE's
        # internal psums produce the full-vocab loss on every stage.
        return loss_wsum, valid.sum()

    return loss_fn


def accumulate_grads_pipelined(
    loss_fn: Callable,
    flat_params: jax.Array,
    block,
    grad_init: Optional[jax.Array] = None,
    count_init: Optional[jax.Array] = None,
):
    """Pipelined analogue of ``common.accumulate_grads``: one
    value-and-grad over the whole block (the pipeline scan inside
    ``loss_fn`` is the accumulation loop). Returns the same
    ``(grad_sum f32, count, loss_weighted_sum, terms_weighted_sum)``,
    honoring the ACCO half-round carry-ins; the terms are empty (a model
    whose objective has auxiliary terms is refused under pp)."""

    def wsum_loss(flat, batch):
        loss_wsum, _ = loss_fn(flat, batch)
        return loss_wsum

    batch = {
        "input_ids": block.input_ids,
        # carried for the batch-layout contract only: the pipelined loss
        # never reads it — pp mandates const_len_batch=True, stages run
        # mask-free (stage_blocks gets attention_mask=None), so the
        # banded/fused kernels' no-pad forms apply under pp too
        "attention_mask": block.attention_mask,
        "labels": block.labels,
        "valid": block.valid,
    }
    loss_wsum, g = jax.value_and_grad(wsum_loss)(flat_params, batch)
    count = block.valid.sum()
    grad_sum = g.astype(jnp.float32)
    if grad_init is not None:
        grad_sum = grad_sum + grad_init
    if count_init is not None:
        count = count + count_init
    return grad_sum, count, loss_wsum, {}
