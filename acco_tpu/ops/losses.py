"""Causal LM loss with optional label smoothing.

Semantics parity:
- next-token shift + mean over non-ignored positions, as the reference's
  models compute internally (HF ``labels=input_ids`` path,
  `/root/reference/trainer_decoupled.py:28-34`);
- label smoothing matching HF's ``LabelSmoother`` (the only live class in
  the reference's vendored `utils/trainer_utils.py:862-902`):
  ``loss = (1 - eps) * nll + eps * mean_v(-log p_v)`` averaged over
  non-masked tokens, with ``ignore_index = -100``.

TPU notes: the softmax/log-sum-exp runs in float32 regardless of the
(bfloat16) activation dtype; everything is shape-static and fuses into the
logits matmul's epilogue under XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

IGNORE_INDEX = -100


def normalize_fused_loss(value) -> "bool | str":
    """Config-surface spellings of ``fused_loss`` to False | 'auto' |
    'chunk' | 'pallas'. Legacy booleans mean the scan-chunked form;
    'pallas' is the VMEM-tiled kernel (ops/fused_ce.py); 'auto' defers
    to the placement policy in :func:`resolve_fused_loss`."""
    if value in (False, None, 0, "0", "false", "False", ""):
        return False
    if value in (True, 1, "1", "true", "True", "chunk"):
        return "chunk"
    if value in ("pallas", "auto"):
        return value
    raise ValueError(
        f"fused_loss must be False/True/'auto'/'chunk'/'pallas', got {value!r}"
    )


def _auto_fused_policy(model, n_vocab_shards, seq_sharded, platform):
    """The ``fused_loss: 'auto'`` decision, mirroring
    ``use_pallas_attention: auto`` (ops/attention.resolve_attention_impl):
    'pallas' or False from platform and placement, never 'chunk'.

    UNMEASURED: the Pallas kernel has never run on the chip and no
    benchmark cell sets ``fused_loss`` to anything but ``auto`` (which
    resolves to False in all six), so every pick below is a guess from
    what the materialized path must write, not a result. ROADMAP S5 runs
    the kernel at the cells' widths; D2 then lets the code choose from
    shape and deletes what loses ('chunk' included: no cell has timed it).

    The rule, in order:
    - non-TPU platforms: False (the kernel is Mosaic-only; the
      interpreter is a test vehicle, not a performance path);
    - sharded vocab (tp / pp / pp·tp pipelined forms): 'pallas' — the
      materialized path writes and reads a [b, L, V/shards] f32 logits
      buffer per microbatch tick, and the 8B {dp:2, pp:8, tp:2}
      placement compiles to fit the chip with the kernel
      (tools/hbm_check.py); this is also where the kernel's envelope
      was AOT-fitted (tests/test_fused_ce.py canaries at 8B dims);
    - context parallelism: 'pallas' — the long-sequence regime is the
      no-materialized-logits loss's reason to exist;
    - single-chip / plain dp: 'pallas' only at V >= 100k (Llama-3-class
      vocabularies, where the [N, V] f32 logits are largest against
      the lm-head matmul); below that, False. The threshold is a guess.
    """
    if platform != "tpu":
        return False
    if n_vocab_shards > 1 or seq_sharded:
        return "pallas"
    cfg = model.config
    v = getattr(model, "padded_vocab", None) or cfg.vocab_size
    return "pallas" if v >= 100_000 else False


def resolve_fused_loss(fused_loss, model, real_vocab, warn=None,
                       n_vocab_shards: int = 1, seq_sharded: bool = False,
                       platform=None):
    """THE fused-loss capability gate, shared by the train paths
    (parallel/common.make_flat_loss_fn, parallel/pp.make_pp_loss_fn) and
    the eval path (trainer) so they can never diverge: an explicit
    'pallas' outside the kernel envelope (ops/fused_ce.
    supports_fused_ce) raises on the TPU platform — a kernel that was
    asked for and cannot run is an error there, not a slower program —
    and downgrades to 'chunk' elsewhere (CPU interpreter runs); 'chunk'
    with Megatron vocab padding (which it predates) goes to the
    materialized path. Requires the model to expose ``hidden``/``lm_head``. ``n_vocab_shards``: the
    vocab dim is sharded this many ways (tp, or pp·tp pipelined) — the
    envelope must hold for the PER-SHARD slice the kernel actually
    tiles, and the sharded fallback is always the materialized
    vocab-parallel CE (chunk has no sharded form). ``seq_sharded``: the
    sequence dim is sharded over a mesh axis (context parallelism) —
    the pallas kernel composes (pre-shifted labels + psum'd num_valid,
    the convention make_pp_loss_fn already uses for pp x sp), chunk does
    not and downgrades to the materialized path. ``'auto'`` resolves
    through :func:`_auto_fused_policy` (platform/placement-aware, like
    ``use_pallas_attention: auto``); a policy pick that then fails the
    envelope resolves to False silently — it was a default, not a user
    request. ``warn``: optional callable taking a message, called on
    each downgrade of an explicit request."""
    fused_loss = requested = normalize_fused_loss(fused_loss)
    if not fused_loss:
        return False
    if not (hasattr(model, "hidden") and hasattr(model, "lm_head")):
        if requested != "auto" and warn is not None:
            warn(
                f"fused_loss={requested!r}: model exposes no "
                "hidden/lm_head surface; using materialized logits"
            )
        return False
    if platform is None:
        platform = jax.devices()[0].platform
    if fused_loss == "auto":
        fused_loss = _auto_fused_policy(
            model, n_vocab_shards, seq_sharded, platform
        )
        if not fused_loss:
            return False
    if fused_loss == "pallas":
        # ONE envelope branch for both the explicit request and the
        # auto pick: a policy default that fails it resolves to False
        # silently (it was never asked for), a request downgrades
        # loudly.
        from acco_tpu.ops.fused_ce import supports_fused_ce

        cfg = model.config
        v = getattr(model, "padded_vocab", None) or cfg.vocab_size
        v_local = v // max(n_vocab_shards, 1)
        if not supports_fused_ce(8, cfg.hidden_size, v_local):
            if requested == "auto":
                return False
            if platform == "tpu":
                raise ValueError(
                    f"fused_loss='pallas': hidden {cfg.hidden_size} / "
                    f"per-shard vocab {v_local} is outside the kernel's "
                    "envelope (ops/fused_ce.supports_fused_ce); ask for "
                    "fused_loss='auto' or False instead"
                )
            if warn is not None:
                fallback = (
                    "'chunk'"
                    if n_vocab_shards == 1
                    and real_vocab is None
                    and not seq_sharded
                    else "the materialized "
                    + ("vocab-parallel " if n_vocab_shards > 1 else "")
                    + "CE"
                )
                warn(
                    f"fused_loss='pallas': hidden {cfg.hidden_size} / "
                    f"per-shard vocab {v_local} outside the kernel "
                    f"envelope; falling back to {fallback}"
                )
            fused_loss = "chunk"
    if fused_loss == "chunk" and (
        real_vocab is not None or n_vocab_shards > 1 or seq_sharded
    ):
        # never silently: the user asked for a memory-bounded loss and
        # the fallback re-materializes logits (a downgraded-pallas
        # request already got the envelope warning above)
        if warn is not None and requested == "chunk":
            warn(
                "fused_loss='chunk' has no "
                + (
                    "sharded"
                    if n_vocab_shards > 1
                    else "context-parallel"
                    if seq_sharded
                    else "Megatron-padded"
                )
                + " form; using the materialized "
                + ("vocab-parallel " if n_vocab_shards > 1 else "")
                + "CE"
            )
        return False
    return fused_loss


def real_vocab_of(model) -> int | None:
    """The UNPADDED vocab size when the model carries Megatron vocab
    padding (rows past it are excluded from the softmax), else None.
    The single source of this condition for every loss path (dp/tp/cp
    in parallel/common.py, pp in parallel/pp.py, eval in trainer.py)."""
    padded = getattr(model, "padded_vocab", None)
    if padded and padded != model.config.vocab_size:
        return model.config.vocab_size
    return None


def shift_labels(labels: jax.Array) -> jax.Array:
    """Pre-align labels to next-token targets: ``out[:, t] = labels[:,
    t+1]``, last column IGNORE_INDEX.

    Context parallelism needs this done on the *global* sequence before
    sharding — inside a sequence shard the next token of a chunk's last
    position lives on the neighbor device, so the shift cannot happen
    locally (use with ``causal_lm_loss(..., shift=False)``)."""
    return jnp.concatenate(
        [labels[..., 1:], jnp.full_like(labels[..., :1], IGNORE_INDEX)], axis=-1
    )


def _per_token_ce(
    logits: jax.Array,  # [..., V] any float dtype
    targets: jax.Array,  # [...] int32, IGNORE_INDEX = masked
    label_smoothing: float,
) -> tuple[jax.Array, jax.Array]:
    """Shared per-token CE body — THE semantics-parity contract (shift-
    free): f32 log-sum-exp, IGNORE_INDEX masking, HF LabelSmoother
    smoothing. Both the materialized and the chunked loss call this, so
    the documented chunked==materialized equivalence holds by
    construction. Returns ``(per_token_loss, valid_mask)`` float32."""
    logits = logits.astype(jnp.float32)
    mask = (targets != IGNORE_INDEX).astype(jnp.float32)
    safe_targets = jnp.where(targets == IGNORE_INDEX, 0, targets)
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(
        logits, safe_targets[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    per_tok = logz - true_logit
    if label_smoothing:
        # mean over vocab of -log p_v  ==  logz - mean(logits)
        smooth = logz - logits.mean(axis=-1)
        per_tok = (1.0 - label_smoothing) * per_tok + label_smoothing * smooth
    return per_tok, mask


def causal_lm_loss(
    logits: jax.Array,  # [B, L, V] any float dtype ([B, L, V/tp] w/ vocab_axis)
    labels: jax.Array,  # [B, L] int32, IGNORE_INDEX = masked
    label_smoothing: float = 0.0,
    shift: bool = True,
    num_valid=None,
    vocab_axis: str | None = None,
    real_vocab: int | None = None,
) -> jax.Array:
    """Mean (shifted) cross-entropy; scalar float32.

    ``shift=False`` treats ``labels`` as already next-token aligned
    (see shift_labels). ``num_valid`` overrides the mean's denominator —
    under sequence sharding it must be the *global* valid-token count
    (e.g. ``lax.psum`` of the local mask sum), so every shard normalizes
    identically and the shard losses sum to the true loss.
    ``vocab_axis``: the logits' vocab dim is sharded over that mesh axis
    (tensor parallelism) — delegates to the vocab-parallel CE so every
    call site dispatches through this one entry point.
    ``real_vocab``: the logits carry a tp-padded vocab dim (Megatron
    vocab padding, parallel/tp.pad_vocab); positions ≥ real_vocab are
    excluded from the softmax and the smoothing mean, so the loss is
    bit-equivalent to the unpadded model's."""
    if vocab_axis is not None:
        return vocab_parallel_causal_lm_loss(
            logits, labels, vocab_axis, label_smoothing,
            shift=shift, num_valid=num_valid, real_vocab=real_vocab,
        )
    if real_vocab is not None and real_vocab < logits.shape[-1]:
        logits = logits[..., :real_vocab]
    if shift:
        logits = logits[:, :-1, :]
        targets = labels[:, 1:]
    else:
        targets = labels
    per_tok, mask = _per_token_ce(logits, targets, label_smoothing)
    denom = jnp.maximum(mask.sum() if num_valid is None else num_valid, 1.0)
    return (per_tok * mask).sum() / denom


def vocab_parallel_causal_lm_loss(
    logits_local: jax.Array,  # [B, L, V/tp] this shard's vocab slice
    labels: jax.Array,  # [B, L] int32 GLOBAL ids, IGNORE_INDEX = masked
    vocab_axis: str,  # mesh axis the vocab dim is sharded over
    label_smoothing: float = 0.0,
    shift: bool = True,
    num_valid=None,
    real_vocab: int | None = None,
) -> jax.Array:
    """:func:`causal_lm_loss` over vocab-sharded logits, inside a
    ``shard_map`` carrying ``vocab_axis`` (Megatron vocab-parallel
    embedding/lm-head, parallel/tp.py). Semantics parity with
    ``_per_token_ce``: f32 log-sum-exp (stable max is psum'd with
    stop_gradient, the exp-sums and the in-range label logit are psum'd),
    IGNORE_INDEX masking, HF LabelSmoother smoothing. Every shard returns
    the same full-vocab loss value. ``real_vocab`` excludes tp-padding
    positions (global vocab index ≥ real_vocab) from the softmax and the
    smoothing mean — bit-equivalent to the unpadded model.
    """
    from jax import lax

    if shift:
        logits_local = logits_local[:, :-1, :]
        targets = labels[:, 1:]
    else:
        targets = labels
    l = logits_local.astype(jnp.float32)
    v_local = l.shape[-1]
    v0 = lax.axis_index(vocab_axis) * v_local
    vocab_total = v_local * lax.axis_size(vocab_axis)
    if real_vocab is not None and real_vocab < vocab_total:
        # per-shard count of real (non-padding) vocab positions
        n_real_local = jnp.clip(real_vocab - v0, 0, v_local)
        vmask = jnp.arange(v_local) < n_real_local
        # padded positions: excluded from max/sumexp/smoothing via -inf /
        # zero-masking (their rows are never labels, so the gather and
        # the label logit are unaffected)
        l = jnp.where(vmask, l, -jnp.inf)
        vocab_total = real_vocab
    mask = targets != IGNORE_INDEX
    safe = jnp.where(mask, targets, 0)
    # numerically-stabilizing max: value-only (softmax is shift-invariant,
    # so it carries no gradient). pmax has no autodiff rule even under
    # stop_gradient, so gather the per-shard maxes instead.
    gmax = jnp.max(
        lax.all_gather(jnp.max(lax.stop_gradient(l), axis=-1), vocab_axis),
        axis=0,
    )
    sumexp = lax.psum(jnp.exp(l - gmax[..., None]).sum(axis=-1), vocab_axis)
    logz = jnp.log(sumexp) + gmax
    loc = safe - v0
    in_range = (loc >= 0) & (loc < v_local)
    picked = jnp.take_along_axis(
        l, jnp.where(in_range, loc, 0)[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    true_logit = lax.psum(jnp.where(in_range, picked, 0.0), vocab_axis)
    per_tok = logz - true_logit
    if label_smoothing:
        finite = jnp.where(jnp.isfinite(l), l, 0.0)
        mean_logits = lax.psum(finite.sum(axis=-1), vocab_axis) / vocab_total
        per_tok = (1.0 - label_smoothing) * per_tok + label_smoothing * (
            logz - mean_logits
        )
    fmask = mask.astype(jnp.float32)
    denom = jnp.maximum(fmask.sum() if num_valid is None else num_valid, 1.0)
    return (per_tok * fmask).sum() / denom


def chunked_causal_lm_loss(
    hidden: jax.Array,  # [B, L, D] final hidden states (activation dtype)
    lm_head: jax.Array,  # [D, V] head matrix (wte.T when tied)
    labels: jax.Array,  # [B, L] int32, IGNORE_INDEX = masked
    label_smoothing: float = 0.0,
    n_chunks: int = 4,
) -> jax.Array:
    """``causal_lm_loss(hidden @ lm_head, labels)`` without ever
    materializing the [B, L, V] float32 logits.

    The logits tensor is the largest transient of the train step
    ([8, 1024, 50257] f32 = 1.6 GB at the flagship shape; [B, L, 128256]
    for Llama-3 vocab — unmaterializable at scale). Computing the lm-head
    matmul + log-sum-exp per *sequence chunk* inside a scan — with
    ``jax.checkpoint(nothing_saveable)`` so the backward pass recomputes
    each chunk's logits instead of keeping them — bounds live memory by
    one chunk's logits. Numerics match :func:`causal_lm_loss` (shifted
    targets, IGNORE_INDEX mask, f32 log-sum-exp, HF LabelSmoother
    smoothing; equivalence-tested value and grad).

    Its speed against the materialized path is unmeasured (no benchmark
    cell runs it; ROADMAP S5 / D2). The 'auto' policy (the shipped config
    default, resolve_fused_loss) never picks 'chunk'; it exists as the
    explicit-request fallback where Pallas can't run, for the
    memory-bound regime (long sequences / 128k-vocab models) where
    materializing the logits is not an option at all.

    Not used under context parallelism or any sharded/padded vocab (no
    num_valid/shift/vocab_axis plumbing — model_ce raises; the 'pallas'
    kernel covers those).
    """
    B, L, D = hidden.shape
    h_in = hidden[:, :-1, :]
    targets = labels[:, 1:]
    Lm1 = L - 1
    pad = (-Lm1) % n_chunks
    if pad:
        h_in = jnp.pad(h_in, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(
            targets, ((0, 0), (0, pad)), constant_values=IGNORE_INDEX
        )
    hc = h_in.reshape(B, n_chunks, -1, D).swapaxes(0, 1)  # [C, B, L/C, D]
    tc = targets.reshape(B, n_chunks, -1).swapaxes(0, 1)  # [C, B, L/C]

    @functools.partial(
        jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable
    )
    def chunk_terms(h_chunk, t_chunk):
        logits = jnp.einsum(
            "bld,dv->blv", h_chunk, lm_head, preferred_element_type=jnp.float32
        )
        per_tok, mask = _per_token_ce(logits, t_chunk, label_smoothing)
        return (per_tok * mask).sum(), mask.sum()

    def body(carry, xs):
        s, n = carry
        ds, dn = chunk_terms(*xs)
        return (s + ds, n + dn), None

    (total, valid), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (hc, tc)
    )
    return total / jnp.maximum(valid, 1.0)


def model_ce(
    model,
    params,
    ids,
    attention_mask,
    labels,
    *,
    label_smoothing: float,
    fused,  # resolve_fused_loss's verdict: False | 'chunk' | 'pallas'
    vocab_axis=None,
    real_vocab=None,
    num_valid=None,
    shift: bool = True,
    with_terms: bool = False,
):
    """THE fused-vs-materialized CE dispatch, shared by the train path
    (parallel/common.make_flat_loss_fn) and both trainer eval bodies so
    their numerics can never diverge. ``fused`` must already have passed
    :func:`resolve_fused_loss`; ``vocab_axis`` selects the sharded
    (tensor-parallel) forms.

    Returns the objective: the cross-entropy, plus the model's auxiliary
    terms where it has any (``model.has_aux_loss``: a model with experts adds
    its load-balancing and router z-losses, ``model.aux_loss``).
    ``with_terms=True`` returns ``(objective, terms)``, ``terms`` the dict of
    those auxiliary scalars, unweighted (empty for a model that has none):
    what the round programs carry to the logging boundary."""
    has_aux = getattr(model, "has_aux_loss", False)

    def forward(fn):
        """``(output, terms)`` of ``model.hidden`` or ``model.apply``."""
        if has_aux:
            return fn(params, ids, attention_mask, with_aux=True)
        return fn(params, ids, attention_mask), {}

    ce, terms = _model_ce(
        model, forward, params, labels, label_smoothing, fused,
        vocab_axis, real_vocab, num_valid, shift,
    )
    loss = ce + model.aux_loss(terms) if terms else ce
    return (loss, terms) if with_terms else loss


def _model_ce(model, forward, params, labels, label_smoothing, fused,
              vocab_axis, real_vocab, num_valid, shift):
    """``(cross-entropy, the model's auxiliary terms)``, by the resolved
    ``fused`` form."""
    if fused == "pallas":
        from acco_tpu.ops.fused_ce import (
            fused_ce_loss,
            vocab_parallel_fused_ce_loss,
        )

        h, terms = forward(model.hidden)
        with jax.named_scope("model/lm_head_ce"):
            head = model.lm_head(params)
            if vocab_axis is not None:
                return vocab_parallel_fused_ce_loss(
                    h, head, labels, vocab_axis, label_smoothing,
                    shift=shift, num_valid=num_valid, real_vocab=real_vocab,
                ), terms
            return fused_ce_loss(
                h, head, labels, label_smoothing,
                shift=shift, num_valid=num_valid, real_vocab=real_vocab,
            ), terms
    if fused == "chunk":
        # The chunk form predates sharding/CP and has no shift=False,
        # num_valid, or vocab_axis plumbing; resolve_fused_loss never
        # routes such a config here, so reaching this branch with any of
        # them set is caller misuse — fail at trace time rather than
        # silently drop the argument.
        if not (
            shift is True
            and num_valid is None
            and vocab_axis is None
            and real_vocab is None
        ):
            raise ValueError(
                "fused_loss='chunk' supports only shift=True, "
                "num_valid=None, vocab_axis=None, real_vocab=None (got "
                f"shift={shift!r}, "
                f"num_valid={'set' if num_valid is not None else None}, "
                f"vocab_axis={vocab_axis!r}, real_vocab={real_vocab!r}); "
                "use 'pallas' or the materialized path for "
                "sharded/CP/vocab-padded losses"
            )
        h, terms = forward(model.hidden)
        with jax.named_scope("model/lm_head_ce"):
            return chunked_causal_lm_loss(
                h, model.lm_head(params), labels, label_smoothing
            ), terms
    # the model's apply() names its own output projection model/lm_head_ce
    logits, terms = forward(model.apply)
    with jax.named_scope("model/lm_head_ce"):
        return causal_lm_loss(
            logits, labels, label_smoothing,
            shift=shift, num_valid=num_valid, vocab_axis=vocab_axis,
            real_vocab=real_vocab,
        ), terms


def token_nll(
    logits: jax.Array, labels: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Per-token shifted NLL and validity mask — the perplexity-eval
    building block (parity: `/root/reference/perplexity_eval.py:13-90`)."""
    logits = logits[:, :-1, :].astype(jnp.float32)
    targets = labels[:, 1:]
    mask = (targets != IGNORE_INDEX).astype(jnp.float32)
    safe_targets = jnp.where(targets == IGNORE_INDEX, 0, targets)
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(
        logits, safe_targets[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    return (logz - true_logit) * mask, mask
