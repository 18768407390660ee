"""Shared pieces of the three training modes.

``gradient_step`` in the reference (`/root/reference/trainer_decoupled.py:
18-39`) is one autocast fwd/bwd accumulating into the flat grad vector and
bumping a local count. Its TPU equivalent is :func:`accumulate_grads`: a
``lax.scan`` over the round's microbatches accumulating a float32 flat
gradient — shape-static, compiled once, and independent of any collective
so XLA can overlap it with in-flight communication.

Heterogeneous workers: the reference lets slow workers contribute fewer
micro-grads per round and fixes the average with an all-reduced count
(`trainer_decoupled.py:85-98`). Under SPMD every device must run the same
program, so variable *trip counts* become a per-microbatch validity mask:
masked microbatches still execute but contribute zero gradient and zero
count (SURVEY.md §7 'hard parts').
"""

from __future__ import annotations

import logging
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

log = logging.getLogger("acco_tpu")

from acco_tpu.ops.losses import IGNORE_INDEX


class MicrobatchBlock(NamedTuple):
    """One round's microbatches, stacked: leaves [n_acc, batch, seq]."""

    input_ids: jax.Array
    attention_mask: jax.Array
    labels: jax.Array
    # [n_acc] float32; 0.0 drops a microbatch's gradient AND count
    # (heterogeneous-worker support). All-ones for homogeneous rounds.
    valid: jax.Array


class HealthState(NamedTuple):
    """Round-carried training-health counters (the watchdog's on-device
    half — acco_tpu/resilience/watchdog.py is the host half).

    All scalars, replicated; every value is derived from psum'd
    quantities, so the replication is SPMD-exact. Shared by
    :class:`~acco_tpu.parallel.acco.AccoState` and
    :class:`~acco_tpu.parallel.ddp.DDPState` so the guarded-update
    mechanism cannot drift between the step classes.

    - ``skipped_rounds`` int32 — cumulative rounds whose optimizer
      commit was suppressed by the in-program anomaly guard (nonfinite
      or over-threshold gradients / nonfinite update). The device-side
      source of truth for ``summary["skipped_rounds"]``.
    - ``consec_skipped`` int32 — consecutive skipped rounds, reset by
      any healthy round; the host monitor escalates to auto-rollback
      when it crosses ``rollback_after_skipped``.
    - ``pending_ok`` float32 0/1 — health verdict of the gradients this
      round STAGED into ``pending_grads`` (from the round loss's
      finiteness, which is psum'd anyway). ACCO's even rounds read the
      staged grads back as their accumulation carry-in; a poisoned
      half-round must not contaminate the next half-round's fresh
      gradients, so the carry-in is zeroed when this is 0.
    """

    skipped_rounds: jax.Array
    consec_skipped: jax.Array
    pending_ok: jax.Array


def init_health() -> HealthState:
    """Fresh (all-healthy) health counters."""
    return HealthState(
        skipped_rounds=jnp.zeros((), jnp.int32),
        consec_skipped=jnp.zeros((), jnp.int32),
        pending_ok=jnp.ones((), jnp.float32),
    )


def health_specs() -> HealthState:
    """PartitionSpecs for the health leaves (replicated scalars)."""
    from jax.sharding import PartitionSpec as P

    return HealthState(P(), P(), P())


def abstract_health(mesh) -> HealthState:
    """Aval-only health leaves (ShapeDtypeStruct + replicated sharding) —
    for tools that hand-build abstract train states (overlap_hlo,
    hbm_check)."""
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)
        ),
        jax.eval_shape(init_health),
        health_specs(),
    )


def make_flat_loss_fn(
    model,
    unravel: Callable[[jax.Array], dict],
    n_params: int,
    label_smoothing: float = 0.0,
    seq_axis: Optional[str] = None,
    fused_loss: "bool | str" = False,  # False | 'auto' | 'chunk' | 'pallas'
    n_vocab_shards: int = 1,
    const_len: bool = False,
    with_terms: bool = False,
) -> Callable[[jax.Array, dict], jax.Array]:
    """Loss as a function of the (padded) flat parameter vector.

    ``with_terms``: the function returns ``(loss, terms)``, ``terms`` the
    dict of the objective's auxiliary scalars (ops.losses.model_ce; empty
    for a model whose objective is the cross-entropy alone): the form
    :func:`accumulate_grads` takes.

    ``fused_loss``: compute the lm-head matmul + cross-entropy without
    materializing the [B, L, V] float32 logits ('pallas' composes with
    CP and the vocab-parallel head; 'chunk' is dp-only — see the shared
    gate, ops.losses.resolve_fused_loss). ``'pallas'`` — the VMEM-tiled kernel
    (ops.fused_ce.fused_ce_loss: online softmax over vocab tiles, one
    fused backward); ``'chunk'`` or legacy ``True`` — the scan-chunked
    form (ops.losses.chunked_causal_lm_loss), the fallback where Pallas
    can't run. Requires the model to expose ``hidden``/``lm_head``
    (both families here do); anything else falls back to the
    materialized path.

    With ``seq_axis`` (context parallelism) the batch's sequence dim is
    sharded over that mesh axis: labels must arrive pre-shifted
    (ops.losses.shift_labels on the global array), the model must be a
    ring-attention model on the same axis, padding masks are unsupported
    (const-len packed data), and the mean's denominator is the psum'd
    global token count so the shard losses sum to the true loss.
    ``fused_loss='pallas'`` composes with CP — the shard's [B, Lc, D]
    hidden goes straight into the kernel with the pre-shifted local
    labels and the psum'd denominator, so the long-sequence regime that
    motivates a no-materialized-logits loss in the first place never
    builds its [B, Lc, V] logits (the convention make_pp_loss_fn
    already uses under pp x sp); 'chunk' has no CP form and the shared
    gate downgrades it to the materialized path.
    """
    # Vocab-parallel head under tensor parallelism: apply() returns LOCAL
    # [B, L, V/tp] logits and the CE runs sharded (psum'd lse/label logit)
    vp_axis = getattr(model, "tensor_axis", None)
    # Megatron vocab padding: exclude padded positions from the softmax
    from acco_tpu.ops.losses import real_vocab_of

    real_vocab = real_vocab_of(model)
    # fail soft at build time, not mid-trace: the shared gate downgrades
    # 'pallas' outside the kernel envelope and 'chunk' under Megatron
    # vocab padding (ops/losses.resolve_fused_loss — also the eval gate)
    from acco_tpu.ops.losses import resolve_fused_loss

    fused_loss = resolve_fused_loss(
        fused_loss, model, real_vocab, warn=log.warning,
        n_vocab_shards=n_vocab_shards if vp_axis is not None else 1,
        seq_sharded=seq_axis is not None,
    )
    # under tensor parallelism only the pallas kernel has a sharded
    # form (ops/fused_ce.vocab_parallel_fused_ce_loss); the gate already
    # returns False for anything else when n_vocab_shards > 1
    if vp_axis is not None and fused_loss != "pallas":
        fused_loss = False

    def loss_fn(flat_params: jax.Array, batch: dict) -> jax.Array:
        # its transpose (the leaves' gradients written back into the flat
        # vector) carries the same name in the backward pass
        with jax.named_scope("acco/flat_unpack"):
            params = unravel(flat_params[:n_params])
        # shared dispatch (ops.losses.model_ce — also both trainer
        # eval bodies), so train/eval numerics can never diverge
        from acco_tpu.ops.losses import model_ce

        if seq_axis is None:
            # const-len packed data (the pretrain default) carries an
            # all-ones mask by the batch-layout contract; telling the
            # model statically lets it skip the pad plumbing entirely —
            # Llama's fused kernel drops its pad operand, GPT-Neo's
            # window layers become eligible for the banded kernel.
            am = None if const_len else batch["attention_mask"]
            return model_ce(
                model, params, batch["input_ids"],
                am, batch["labels"],
                label_smoothing=label_smoothing, fused=fused_loss,
                vocab_axis=vp_axis, real_vocab=real_vocab,
                with_terms=with_terms,
            )
        # CP: pre-shifted local label chunk; this shard contributes its
        # PARTIAL — local nll sum over the psum'd global count — so the
        # shard losses sum over seq_axis to the true microbatch mean.
        targets = batch["labels"]
        local_valid = (targets != IGNORE_INDEX).sum().astype(jnp.float32)
        num_valid = jax.lax.psum(local_valid, seq_axis)
        return model_ce(
            model, params, batch["input_ids"], None, targets,
            label_smoothing=label_smoothing, fused=fused_loss,
            vocab_axis=vp_axis, real_vocab=real_vocab,
            num_valid=num_valid, shift=False, with_terms=with_terms,
        )

    return loss_fn


def accumulate_grads(
    loss_fn: Callable[[jax.Array, dict], tuple[jax.Array, dict]],
    flat_params: jax.Array,  # [padded] param dtype
    block: MicrobatchBlock,
    grad_init: Optional[jax.Array] = None,  # [padded] float32 carry-in
    count_init: Optional[jax.Array] = None,  # scalar float32 carry-in
) -> tuple[jax.Array, jax.Array, jax.Array, dict]:
    """Scan the block, returning (grad_sum f32, count, loss_weighted_sum,
    terms_weighted_sum).

    ``loss_fn`` returns ``(loss, terms)`` (make_flat_loss_fn's
    ``with_terms=True``). ``loss_weighted_sum`` is ``sum(loss_i * valid_i)``
    over this block's microbatches, ``terms_weighted_sum`` the same of each
    auxiliary term; callers divide by the *all-reduced* valid count
    (:func:`world_mean_loss`) so masked
    (heterogeneous-worker) microbatches never bias logged loss curves.
    ``grad_init``/``count_init`` express the reference's
    accumulate-on-top-of-previous-half-round behavior
    (`update_buffers_step` zeroes only every other round,
    trainer_decoupled.py:59-63).
    """
    grad0 = (
        grad_init
        if grad_init is not None
        else jnp.zeros(flat_params.shape, jnp.float32)
    )
    count0 = count_init if count_init is not None else jnp.zeros((), jnp.float32)

    value_and_grad = jax.value_and_grad(loss_fn, has_aux=True)

    def micro(carry, xs):
        grad_sum, count = carry
        batch = {
            "input_ids": xs.input_ids,
            "attention_mask": xs.attention_mask,
            "labels": xs.labels,
        }
        (loss, terms), g = value_and_grad(flat_params, batch)
        grad_sum = grad_sum + g.astype(jnp.float32) * xs.valid
        count = count + xs.valid
        return (grad_sum, count), (loss, terms)

    def weighted(per_microbatch, valid):
        return jax.tree.map(lambda t: (t * valid).sum(), per_microbatch)

    n_acc = block.valid.shape[0]
    with jax.named_scope("acco/accumulate"):
        if n_acc == 1:
            # The flagship pretrain config runs one microbatch per
            # half-round; a length-1 lax.scan still compiles to a while
            # loop wrapping the whole fwd/bwd (its cost in time is
            # unmeasured, but the while op walls the body off from the round-level
            # latency-hiding scheduler, which matters for the
            # ring-collective overlap). Inline it.
            (grad_sum, count), (loss, terms) = micro(
                (grad0, count0), jax.tree.map(lambda x: x[0], block)
            )
            return (
                grad_sum, count, loss * block.valid[0],
                weighted(terms, block.valid[0]),
            )

        (grad_sum, count), (losses, terms) = jax.lax.scan(
            micro, (grad0, count0), block
        )
        return (
            grad_sum, count, (losses * block.valid).sum(),
            weighted(terms, block.valid),
        )


def world_mean_loss(
    loss_weighted_sum: jax.Array,
    valid: jax.Array,
    axis_name: str,
    seq_axis: Optional[str] = None,
) -> jax.Array:
    """Valid-count-weighted mean loss across the whole mesh axis — devices
    with masked-out microbatches don't dilute the metric.

    Under context parallelism each device's loss is a *partial* (its
    sequence chunk's share): partials sum over ``seq_axis`` to the full
    microbatch loss, while the valid-count denominator sums over the data
    axis only (a microbatch is one unit however many shards computed it).
    """
    loss_axes = (axis_name,) + ((seq_axis,) if seq_axis else ())
    total_loss = jax.lax.psum(loss_weighted_sum, loss_axes)
    total_valid = jax.lax.psum(valid.sum(), axis_name)
    return total_loss / jnp.maximum(total_valid, 1.0)


def world_mean_terms(
    terms_weighted_sum: dict,
    valid: jax.Array,
    axis_name: str,
    seq_axis: Optional[str] = None,
) -> dict:
    """:func:`world_mean_loss` of each of the objective's auxiliary terms
    (accumulate_grads' fourth result); nothing for an empty dict."""
    return jax.tree.map(
        lambda t: world_mean_loss(t, valid, axis_name, seq_axis),
        terms_weighted_sum,
    )


def prep_cp_leaves(ids, am, labels, seq_axis, mesh, model):
    """Global-sequence preprocessing shared by every train step: under CP,
    next-token-align the labels on the GLOBAL sequence (shift_labels) and,
    for a zig-zag model, reorder the sequence so contiguous sharding lands
    half-chunks (i, 2ws-1-i) on shard i (ring_attention.zigzag_permutation
    — the layout zigzag_ring_attention expects). No-op outside CP."""
    from acco_tpu.ops.losses import shift_labels

    if seq_axis is None:
        return ids, am, labels
    labels = shift_labels(labels)
    if getattr(model, "zigzag", False):
        import numpy as np

        from acco_tpu.ops.ring_attention import zigzag_permutation

        perm, _ = zigzag_permutation(ids.shape[-1], mesh.shape[seq_axis])
        perm = jnp.asarray(np.asarray(perm), jnp.int32)
        ids = jnp.take(ids, perm, axis=-1)
        am = jnp.take(am, perm, axis=-1)
        labels = jnp.take(labels, perm, axis=-1)
    return ids, am, labels


def batch_specs(data_axis: str, seq_axis: Optional[str] = None):
    """The shared batch-layout contract of every train step: microbatch
    leaves [n_acc, global_batch, seq] sharded over the batch dim (and the
    seq dim under context parallelism), plus ``valid``
    [n_acc, data_world_size] (replicated over the seq axis)."""
    from jax.sharding import PartitionSpec as P

    row = P(None, data_axis, seq_axis)
    return (
        row,  # input_ids
        row,  # attention_mask
        row,  # labels
        P(None, data_axis),  # valid
    )


def make_valid(n_acc: int, world_size: int) -> jnp.ndarray:
    """All-microbatches-valid mask [n_acc, world_size]."""
    return jnp.ones((n_acc, world_size), jnp.float32)


def abstract_block(
    mesh, data_axis: str, n_acc: int, global_bs: int, seq: int,
    seq_axis: Optional[str] = None,
) -> dict:
    """Aval-only microbatch block (ShapeDtypeStruct + NamedSharding) per
    the batch-layout contract — what AOT warmup lowers the round programs
    against instead of real data. Shapes/dtypes MUST mirror the loader +
    ``put_block`` exactly (int32 leaves, float32 ``valid``): a mismatch
    doesn't error, it silently compiles a program the real call never
    requests."""
    from jax.sharding import NamedSharding

    specs = dict(zip(BATCH_KEYS, batch_specs(data_axis, seq_axis)))

    def aval(shape, dtype, key: str):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, specs[key])
        )

    row = (n_acc, global_bs, seq)
    return {
        "input_ids": aval(row, jnp.int32, "input_ids"),
        "attention_mask": aval(row, jnp.int32, "attention_mask"),
        "labels": aval(row, jnp.int32, "labels"),
        "valid": aval(
            (n_acc, mesh.shape[data_axis]), jnp.float32, "valid"
        ),
    }


# The batch-layout contract keys, in batch_specs order.
BATCH_KEYS = ("input_ids", "attention_mask", "labels", "valid")


# -- ahead-of-time compilation, shared by AccoTrainStep / DDPTrainStep ------
# (acco_tpu/compile): one implementation so a fix to the aval or warmup
# path can never drift between the step classes; each class contributes
# only its program dict (warmup_program_fns) and thin delegating methods.


def step_abstract_state(step, params_avals=None, *, seed: int = 0):
    """Aval-only train state for a step object: ``init_state`` traced
    through ``jax.eval_shape`` — no parameter or optimizer memory is
    allocated, but the side effects warmup needs (``geom``, ``unravel``,
    ``tp_layout``) are established exactly as the real init would, so
    the lowered programs are the ones the trainer will run."""
    if params_avals is None:
        params_avals = jax.eval_shape(
            lambda: step.model.init(jax.random.PRNGKey(seed))
        )
    avals = jax.eval_shape(step.init_state, params_avals)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        avals,
        step.state_shardings(),
    )


def step_warmup(
    step,
    n_acc: int,
    global_batch: int,
    seq: int,
    *,
    params_avals=None,
    seed: int = 0,
    include_seed: bool = True,
    runner=None,
):
    """Lower + compile a step's programs ahead of the first call,
    concurrently on background threads (XLA releases the GIL during
    compile) — see acco_tpu/compile/warmup.py for why the first real
    call is then served without blocking on XLA.

    With ``runner`` (a :class:`acco_tpu.compile.CompileWarmup`) the
    programs are submitted and the caller joins later (the trainer's
    overlapped path); without one, blocks and returns the
    :class:`WarmupReport` of per-program lower/compile timings."""
    from acco_tpu.compile import CompileWarmup
    from acco_tpu.parallel.mesh import DATA_AXIS

    state_avals = step.abstract_state(params_avals, seed=seed)
    batch_avals = abstract_block(
        step.mesh, DATA_AXIS, n_acc, global_batch, seq,
        seq_axis=step.seq_axis,
    )
    own_runner = runner is None
    if own_runner:
        runner = CompileWarmup()
    for name, fn in step.warmup_program_fns(
        include_seed=include_seed
    ).items():
        runner.submit(name, fn, state_avals, batch_avals)
    return runner.join() if own_runner else None


def step_program_callable(step, builders: dict, name: str, log=None):
    """Best available callable for a warmup program name: the installed
    AOT executable when the warmup produced one (dispatch then touches
    no compile path at all), else the memoized jit fn."""
    from acco_tpu.compile import aot_call_with_fallback

    jit_fn = builders[name]()
    compiled = step.compiled_programs.get(name)
    if compiled is None:
        return jit_fn
    return aot_call_with_fallback(compiled, jit_fn, name, log=log)


def shard_layout(
    mesh,
    model,
    seq_axis: Optional[str],
    data_axis: str,
    tensor_axis: Optional[str] = None,
    pipeline_axis: Optional[str] = None,
):
    """Back-compat re-export: the validation/geometry now lives in
    :func:`acco_tpu.sharding.layout.shard_layout` (one package owns the
    whole placement story)."""
    from acco_tpu.sharding.layout import shard_layout as _impl

    return _impl(
        mesh,
        model,
        seq_axis,
        data_axis,
        tensor_axis=tensor_axis,
        pipeline_axis=pipeline_axis,
    )


def flat_state_specs(shard_axes, tensor_axis: Optional[str]):
    """``(shard_spec, flat_spec)`` for the flat state leaves — a shim
    over the rule-table arithmetic in
    :func:`acco_tpu.sharding.tables.flat_state_specs`, kept for callers
    that want the raw spec pair without a table."""
    from acco_tpu.sharding.tables import flat_state_specs as _impl

    return _impl(shard_axes, tensor_axis)


def put_block(
    mesh, data_axis: str, block: dict, seq_axis: Optional[str] = None
) -> dict:
    """device_put a stacked host block onto the mesh per the batch-layout
    contract (single-process; the trainer handles the multi-process case)."""
    from jax.sharding import NamedSharding

    specs = dict(zip(BATCH_KEYS, batch_specs(data_axis, seq_axis)))
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k])) for k, v in block.items()
    }


def synthetic_block(
    mesh, data_axis: str, vocab_size: int, n_acc: int, global_bs: int, seq: int,
    seed: int = 0, seq_axis: Optional[str] = None,
) -> dict:
    """Random-token microbatch block laid out over the mesh — the shared
    input builder for the driver dry run (__graft_entry__.py) and the
    tensor-parallel tests."""
    import numpy as np

    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, vocab_size, (n_acc, global_bs, seq)), jnp.int32)
    return put_block(
        mesh,
        data_axis,
        {
            "input_ids": ids,
            "attention_mask": jnp.ones_like(ids),
            "labels": ids,
            "valid": make_valid(n_acc, mesh.shape[data_axis]),
        },
        seq_axis,
    )


def block_from_arrays(batches: dict, n_acc: int) -> MicrobatchBlock:
    """Build a MicrobatchBlock from stacked host arrays (adds all-valid
    mask when absent)."""
    valid = batches.get("valid")
    if valid is None:
        valid = jnp.ones((n_acc,), jnp.float32)
    return MicrobatchBlock(
        input_ids=batches["input_ids"],
        attention_mask=batches["attention_mask"],
        labels=batches["labels"],
        valid=jnp.asarray(valid, jnp.float32),
    )
