"""ZeRO-1 optimizer-state sharding on the flat parameter vector.

The reference hand-rolls ZeRO-1 for its ACCO/DPU modes: the flat 1-D param
vector is split into ``world_size`` slices of ``ceil(P/ws)`` (ragged last
slice zero-padded), each rank owns an fp32 slice + its own AdamW, gradients
reach the owner via ``reduce_scatter`` and updated params return via
``all_gather`` (`/root/reference/trainer_decoupled.py:244-269,296-315,
67-126`).

TPU-native translation:
- the padded flat vector has global shape ``[ws * S]`` sharded
  ``PartitionSpec('dp')`` — each device's local view is its ``[S]`` slice;
- inside ``shard_map``, grads flow through ``lax.psum_scatter`` (tiled) and
  params return via ``lax.all_gather`` (tiled) — the same two collectives,
  emitted by XLA over ICI;
- the ragged tail is a compile-time constant ``pad_mask`` per shard rather
  than a different last-shard length, so every device runs the same
  program (SPMD requires uniform shapes; SURVEY.md §7 'hard parts').
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from acco_tpu.ops.adamw import AdamWState, adamw_shard_update, init_adamw_state


@dataclasses.dataclass(frozen=True)
class ShardGeometry:
    """Slice geometry parity: `/root/reference/trainer_decoupled.py:244-259`."""

    n_params: int
    world_size: int

    @property
    def shard_size(self) -> int:
        return -(-self.n_params // self.world_size)  # ceil

    @property
    def padded_size(self) -> int:
        return self.shard_size * self.world_size

    def pad_flat(self, flat: jax.Array) -> jax.Array:
        return jnp.pad(flat, (0, self.padded_size - self.n_params))

    def unpad_flat(self, flat_padded: jax.Array) -> jax.Array:
        return flat_padded[: self.n_params]

    def shard_pad_mask(self, shard_index: jax.Array) -> jax.Array:
        """[S] float32 mask of real (non-padding) positions for one shard;
        ``shard_index`` may be traced (lax.axis_index inside shard_map).

        Implemented as shard-relative comparisons (which shard holds the
        boundary, then an [S]-local arange) — absolute flat positions
        exceed int32 for billion-parameter vectors (Llama-3-8B), and jnp
        integer math is int32 without x64."""
        return _boundary_mask(shard_index, self.shard_size, self.n_params)


class UpdateHealth(NamedTuple):
    """On-device health verdict of one sharded optimizer update
    (``zero1_update_shard(..., with_health=True)``).

    - ``ok`` bool scalar, replicated — the update is safe to commit:
      the count-averaged global gradient and the updated parameter
      shard are both finite, and (when a cap is set) the global grad
      norm is under it. ``zero1_update_shard`` has already applied it to
      what it returns (an anomalous update is a bit-exact on-device no-op
      with no host involvement); the round programs gate their scalar
      counters on it.
    - ``grad_norm`` float32 scalar, replicated — global L2 norm of the
      count-averaged gradient (the host monitor's spike/drift signal,
      already fetched lazily with the round metrics).
    """

    ok: jax.Array
    grad_norm: jax.Array


class Zero1State(NamedTuple):
    """Sharded optimizer state. Leaves are global ``[padded_size]`` arrays
    sharded along ``dp`` (each device materializes only its [S] slice),
    plus a replicated cumulative-gradient counter for the LR schedule
    (the reference's per-grad ``scheduler._step_count`` bookkeeping,
    trainer_decoupled.py:102-104) and a replicated running count of
    *committed* micro-grads — the device-side source of truth for the
    host's ``count_grad_tot`` (the all-reduced count the reference
    accumulates at `trainer_decoupled.py:501-502`), exact under
    heterogeneous-worker microbatch masks."""

    opt: AdamWState
    sched_grads: jax.Array  # scalar int32, replicated
    grads_committed: jax.Array  # scalar float32, replicated


def init_zero1_state(flat_params_f32: jax.Array, geom: ShardGeometry) -> Zero1State:
    """Host-side init: fp32 master copy of the (padded) flat params."""
    padded = geom.pad_flat(flat_params_f32.astype(jnp.float32))
    return Zero1State(
        opt=init_adamw_state(padded),
        sched_grads=jnp.zeros((), jnp.int32),
        grads_committed=jnp.zeros((), jnp.float32),
    )


def _boundary_mask(shard_index, shard_size: int, boundary: int) -> jax.Array:
    """[shard_size] float32: 1.0 where this shard's flat position is below
    ``boundary``. Avoids absolute flat indices (int32 overflow at
    billion-param scale): shards strictly before the boundary shard are
    all-ones, after it all-zeros, and the boundary shard compares a local
    arange against the remainder — every quantity stays < shard_size."""
    q, r = divmod(int(boundary), int(shard_size))
    local = (jnp.arange(shard_size) < r).astype(jnp.float32)
    return jnp.where(
        shard_index < q,
        jnp.ones((shard_size,), jnp.float32),
        jnp.where(shard_index == q, local, jnp.zeros((shard_size,), jnp.float32)),
    )


def flat_shard_index(axis_name) -> jax.Array:
    """This device's shard index along one axis or an axis tuple, matching
    the major-to-minor order psum_scatter/all_gather(tiled) use."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    idx = jnp.zeros((), jnp.int32)
    for ax in axes:
        idx = idx * lax.axis_size(ax) + lax.axis_index(ax)
    return idx


def _own_shard(flat: jax.Array, shard_index, shard_size: int) -> jax.Array:
    """This device's ``[shard_size]`` slice of a local ``[n * shard_size]``
    vector, in the tiled order all_gather writes. Addressed in the vector's
    own 1-D layout (a ``[n, S]`` view would re-tile it); past int32 element
    offsets, by row."""
    n = flat.shape[0] // shard_size
    if n == 1:
        return flat
    if flat.shape[0] < 2**31:
        return lax.dynamic_slice(flat, (shard_index * shard_size,), (shard_size,))
    return lax.dynamic_index_in_dim(
        flat.reshape(n, shard_size), shard_index, keepdims=False
    )


def zero1_update_shard(
    flat_grads_local: jax.Array,  # [padded_size] per-device UNREDUCED grad sum
    opt_shard: AdamWState,  # local [S] view inside shard_map
    grad_divisor: jax.Array,  # traced scalar: total micro-grad count
    lr: jax.Array,
    geom: ShardGeometry,
    weight_decay: float,
    beta1: float,
    beta2: float,
    eps: float = 1e-8,
    axis_name="dp",
    out_dtype=jnp.bfloat16,
    comm_impl: str = "xla",
    tp_axis=None,
    n_repl: int = 0,
    n_repl_both: int = 0,
    inner_axis: str | None = None,
    with_health: bool = False,
    max_grad_norm: float = 0.0,
    commit=True,
    old_flat: jax.Array | None = None,
) -> tuple:
    """One sharded AdamW step, committed. MUST run inside shard_map over
    ``axis_name`` (a mesh axis or an axis tuple — with context parallelism
    the optimizer shards over (dp, sp) jointly, and the psum in the scatter
    is also what sums the sequence shards' partial gradients).

    reduce-scatter(SUM) -> average by grad count -> AdamW on the fp32 shard
    -> all-gather updated params: the exact collective sequence of
    `communication_step` (`/root/reference/trainer_decoupled.py:86-112`),
    with count-based averaging for heterogeneous workers (`:97-98`).

    ``comm_impl``: 'xla' = lax.psum_scatter/all_gather (on the target
    libtpu these lower to blocking all-reduces); 'ring' = async
    ppermute rings (ring_collectives.py) that the latency-hiding
    scheduler can overlap with the gradient branch — single mesh axis
    only, falls back to 'xla' for axis tuples (context parallelism).

    Tensor parallelism (``tp_axis`` set): this update runs *within* one
    tp group — the scatter/gather axes exclude ``tp_axis`` — and applies
    the measured check_vma=False gradient correction (parallel/tp.py):
    every gradient is divided by tp (folded into the divisor by the
    caller is NOT assumed; it happens here), and the replicated prefix
    (first ``n_repl`` flat positions) additionally psums over tp, making
    its update identical on every tp shard.

    ``commit`` (Python bool, or traced in a parity-generic round): whether
    the optimizer state takes this update — ACCO's speculative rounds pass
    False and keep the old state (the reference's snapshot/restore,
    `trainer_decoupled.py:79-84,113-126`); the returned flat vector is the
    updated one on every parity.

    Health guard (``with_health=True``, needs ``old_flat``: the local
    ``[padded_size]`` working vector the round started from): additionally
    returns an :class:`UpdateHealth`, and an anomalous update commits
    nothing — state AND flat vector are the old ones, bit-exactly. The
    verdict is the averaged gradient shard's sum of squares and the
    updated fp32 parameter shard's, combined in ONE [2]-element psum over
    the shard axes (plus the tp axis when set): no host sync.
    ``max_grad_norm > 0`` also flags finite-but-spiked gradients whose
    global L2 norm exceeds the cap (a static compile-time threshold; the
    adaptive spike/drift classification lives on the host,
    resilience/watchdog.py). The verdict reduces over the *updated*
    parameters, so nothing may be overwritten before a whole pass has
    finished. What that costs, per shard of S elements:

    - a committing round decides first and writes once: a read-only pass
      computes the update in registers and emits the two sums (16S bytes
      read); one write pass, the taken branch of a ``lax.cond`` on the
      verdict, then recomputes it and writes ``p, mu, nu`` and the
      ``out_dtype`` shard (16S read, 14S written); the other branch hands
      back the old state and this shard of the old vector. No tentative
      copy of the state exists.
    - a speculative round (static ``commit=False``) writes no state, so the
      tentative ``out_dtype`` shard and one select over it are the cheaper
      shape (16S + 2S, then 6S).

    Either way the all-gather carries a shard that is already the answer:
    the gathered slices of the old vector ARE the old vector, on ACCO's
    odd round too, where it is the speculative one and not ``cast(p)``.

    Returns ``(flat_params [padded_size] in out_dtype, opt shard)``, plus
    the :class:`UpdateHealth` when ``with_health``. The caller gates its
    own scalars (LR schedule, committed-grads counter) on ``commit & ok``.
    """
    if comm_impl not in ("xla", "ring"):
        raise ValueError(f"comm_impl must be 'xla' or 'ring', got {comm_impl!r}")
    if with_health and old_flat is None:
        raise ValueError("with_health=True needs old_flat: a skipped update returns it")
    use_ring = comm_impl == "ring" and isinstance(axis_name, str)
    if use_ring:
        from acco_tpu.parallel.ring_collectives import (
            ring_all_gather,
            ring_reduce_scatter,
        )
    # Device scopes (telemetry.trace.DEVICE_SCOPES): each phase of the
    # update carries a name in the compiled program, staging copies
    # included, so a profile can tell the wire from the vector passes.
    with jax.named_scope("acco/reduce_scatter"):
        if use_ring:
            grad_sum = ring_reduce_scatter(
                flat_grads_local.astype(jnp.float32), axis_name
            )
        else:
            grad_sum = lax.psum_scatter(
                flat_grads_local.astype(jnp.float32), axis_name, tiled=True
            )
    idx = flat_shard_index(axis_name)
    with jax.named_scope("acco/optimizer"):
        divisor = grad_divisor.astype(jnp.float32)
        if tp_axis is not None:
            tp = lax.axis_size(tp_axis)  # axis tuples: product (pp x tp)
            divisor = divisor * tp
        # replicated-prefix position ranges [lo, hi) of the flat vector and
        # the model axes each is replicated over.
        # Single model axis: one prefix [0:n_repl) psum'd over tp_axis.
        # Composed pp x tp (ComposedLayout): the prefix splits in two —
        # [0:n_repl_both) is replicated on BOTH axes (final norms, psum
        # over the full tuple), [n_repl_both:n_repl) is outer-split but
        # inner-replicated (per-stage norm scales, psum over inner only).
        repl = []
        if tp_axis is not None and n_repl > 0:
            if inner_axis is None or n_repl_both >= n_repl:
                repl = [(0, n_repl, tp_axis)]
            else:
                repl = [(0, n_repl_both, tp_axis), (n_repl_both, n_repl, inner_axis)]

    def repl_masks():
        """``repl``'s ranges as [S] bool masks of this dp(x sp) shard. Built
        where they are used: closed over by a ``cond`` branch, a mask would
        be an operand, i.e. a buffer."""
        def below(boundary):
            return _boundary_mask(idx, geom.shard_size, boundary).astype(bool)

        return [below(hi) & ~below(lo) if lo else below(hi) for lo, hi, _ in repl]

    with jax.named_scope("acco/optimizer"):
        synced = tuple(
            lax.psum(jnp.where(mask, grad_sum / divisor, 0.0), axes)
            for mask, (_, _, axes) in zip(repl_masks(), repl)
        )

    def averaged(grad_sum, synced):
        """The count-averaged gradient shard, replicated prefix synced:
        elementwise in its arguments, so each pass that needs it fuses it."""
        g = grad_sum / divisor
        for mask, s in reversed(list(zip(repl_masks(), synced))):
            g = jnp.where(mask, s, g)
        return g

    def update(grad_shard, opt):
        return adamw_shard_update(
            opt,
            grad_shard,
            lr=lr,
            weight_decay=weight_decay,
            beta1=beta1,
            beta2=beta2,
            eps=eps,
            pad_mask=geom.shard_pad_mask(idx),
        )

    def verdict(grad_shard, new_params) -> UpdateHealth:
        # The shards partition the flat vector, so psum'ing per-shard sums
        # of squares yields the global quantities. NaN/inf propagate through
        # square+sum+psum, so a single nonfinite element anywhere in the
        # global gradient or updated parameters makes its total nonfinite.
        # Pad positions are excluded with where() (a multiply would keep
        # NaN: x*0 is NaN for nonfinite x, and the ragged tail is the one
        # place a structural nonfinite is harmless). One [2] psum — under
        # tp each tp group's local vector is a disjoint piece of the model
        # EXCEPT the replicated prefix, whose squared contribution is
        # pre-divided by its replication factor (it appears on every shard
        # of the axes it was synced over above) so the psum counts every
        # element exactly once and grad_norm matches the single-device
        # value. The division keeps NaN/inf propagation intact
        # (nonfinite/k is nonfinite).
        real = geom.shard_pad_mask(idx) > 0
        grad_ss_v = jnp.square(jnp.where(real, grad_shard, 0.0))
        param_ss_v = jnp.square(jnp.where(real, new_params, 0.0))
        if repl:
            inv_repl = 1.0
            for mask, (_, _, axes) in reversed(list(zip(repl_masks(), repl))):
                inv_repl = jnp.where(
                    mask, 1.0 / jnp.float32(lax.axis_size(axes)), inv_repl
                )
            grad_ss_v = grad_ss_v * inv_repl
            param_ss_v = param_ss_v * inv_repl
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        if tp_axis is not None:
            axes = axes + (
                (tp_axis,) if isinstance(tp_axis, str) else tuple(tp_axis)
            )
        totals = lax.psum(
            jnp.stack([jnp.sum(grad_ss_v), jnp.sum(param_ss_v)]), axes
        )
        ok = jnp.isfinite(totals[0]) & jnp.isfinite(totals[1])
        if max_grad_norm and max_grad_norm > 0:
            ok = ok & (totals[0] <= jnp.float32(max_grad_norm) ** 2)
        return UpdateHealth(ok=ok, grad_norm=jnp.sqrt(totals[0]))

    def gather(shard):
        with jax.named_scope("acco/all_gather"):
            if use_ring:
                return ring_all_gather(shard, axis_name)
            return lax.all_gather(shard, axis_name, tiled=True)

    def select(pred, new, old):
        """Per-leaf commit select; a Python bool picks at trace time."""
        if isinstance(pred, bool):
            return new if pred else old
        return jax.tree.map(lambda n, o: jnp.where(pred, n, o), new, old)

    if not with_health:
        with jax.named_scope("acco/optimizer"):
            new_opt = update(averaged(grad_sum, synced), opt_shard)
        with jax.named_scope("acco/cast"):
            new_shard = new_opt.params.astype(out_dtype)
            # a parity-generic round's speculative/commit selects
            opt_out = select(commit, new_opt, opt_shard)
        return gather(new_shard), opt_out

    old_shard = _own_shard(old_flat, idx, geom.shard_size)
    if isinstance(commit, bool) and not commit:
        # static: a parity-specialized speculative round writes no state
        with jax.named_scope("acco/optimizer"):
            grad_shard = averaged(grad_sum, synced)
            new_params = update(grad_shard, opt_shard).params
        with jax.named_scope("acco/cast"):
            new_shard = new_params.astype(out_dtype)
        with jax.named_scope("acco/guard"):
            health = verdict(grad_shard, new_params)
            new_shard = jnp.where(health.ok, new_shard, old_shard)
        return gather(new_shard), opt_shard, health

    with jax.named_scope("acco/guard"):
        grad_shard = averaged(grad_sum, synced)
        health = verdict(grad_shard, update(grad_shard, opt_shard).params)

    # The write pass is the taken branch of a conditional on the scalar
    # verdict, not a select per leaf: compiled for the chip, the branch is
    # the unguarded update's own single fusion (no select, the old vector
    # not even read) and the other branch forwards its operands with no
    # copy, where XLA split the bf16 select off a where()-gated write. A
    # branch's operands are buffers: a gradient that arrives as a fused
    # expression (DDP's concatenation, the ring's two halves) is written
    # out once by the verdict pass.
    def write(grad_sum, synced, opt, old_shard):
        new = update(averaged(grad_sum, synced), opt)
        return select(commit, new, opt), new.params.astype(out_dtype)

    def keep(grad_sum, synced, opt, old_shard):
        return opt, old_shard

    with jax.named_scope("acco/optimizer"):
        opt_out, new_shard = lax.cond(
            health.ok, write, keep, grad_sum, synced, opt_shard, old_shard
        )
    return gather(new_shard), opt_out, health
