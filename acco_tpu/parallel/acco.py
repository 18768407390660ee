"""ACCO — "Accumulate while you Communicate" — as one compiled XLA round.

The reference implements ACCO with two CUDA streams, a host communication
thread, mp.Barrier handshakes, and an explicit speculative-rollback of the
optimizer state (`/root/reference/trainer_decoupled.py:129-168,431-598`).
On TPU none of that machinery exists or is needed: a *round* here is a
single jitted ``shard_map`` program with two data-independent branches —

- **communication branch** — operates on the gradients handed over at the
  end of the previous round (``pending_grads``): all-reduce the grad count
  (`communication_step` step 1, `trainer_decoupled.py:86`), reduce-scatter
  the flat gradient (`:88-93`), count-averaged sharded AdamW on the fp32
  shard (`:97-100`), all-gather the updated parameters (`:106-112`);
- **compute branch** — fwd/bwd over this round's microbatches at the
  *current* working parameters, accumulating into the flat grad vector
  (`gradient_step`, `:18-39`).

Neither branch reads the other's outputs, so the communication can in
principle run while the MXU computes — the overlap the reference gets
from its com_thread/com_stream. Whether it actually happens is a
compiler/scheduling property, read off the compiled schedule rather
than assumed: on the target libtpu the stock
``psum_scatter``/``all_gather`` lower to blocking all-reduces scheduled
after the compute (no overlap). ``comm_impl='ring'`` re-expresses both
collectives as bidirectional ppermute rings
(parallel/ring_collectives.py) that compile to async
collective-permute-start/done pairs, and with the layer scan unrolled
(``scan_unroll=True``) the latency-hiding scheduler provably places the
fwd/bwd compute inside the in-flight windows — see OVERLAP.md and
tools/overlap_hlo.py (28/28 windows carry compute on a v5e-8 AOT
compile). What the chip then exposes is a benchmark metric,
``exposed_collective_ms`` (``neo27b-l4-dp4``: 9.33 ms a round, beside
DDP's 14.45: ledger, PR 26). Host races are impossible by construction either way
(SURVEY.md §5 'race detection': no threads, one compiled program).

Round semantics preserved exactly (SURVEY.md §3.2):

- rounds alternate even/odd via ``round_idx`` (= ``count_after_init``);
- **even** rounds apply a *speculative* optimizer step: the comm branch
  produces estimated parameters θ̃ from the first half-round's gradients,
  but the optimizer state (fp32 shard + Adam moments + step) is **not
  committed** — in the reference this is the explicit snapshot/rollback
  dance (`trainer_decoupled.py:79-84,113-126`); functionally it is just
  selecting the old state;
- **odd** rounds commit the *real* update computed from both half-rounds'
  summed gradients (the accumulator is zeroed only after even rounds,
  ``update_buffers_step`` `:59-63`) and advance the LR schedule;
- gradient averaging divides by the all-reduced *micro-grad count*, not
  the world size (`:97-98`), which keeps heterogeneous (uneven-speed)
  workers correct; here slow workers mask microbatches out via
  ``MicrobatchBlock.valid`` instead of running fewer loop trips (SPMD
  programs must be shape-uniform).

DPU ("delayed parameter update", `train_dpu` `:605-730`) is the same round
with speculation disabled and the accumulator zeroed every round: each
update applies the previous round's gradients — one round stale.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from acco_tpu.ops.adamw import AdamWState
from acco_tpu.parallel.common import (
    HealthState,
    MicrobatchBlock,
    accumulate_grads,
    batch_specs,
    init_health,
    make_flat_loss_fn,
    make_valid,
    shard_layout,
    world_mean_loss,
    world_mean_terms,
)
from acco_tpu.parallel.flat_layout import FlatLayout
from acco_tpu.parallel.mesh import DATA_AXIS
from acco_tpu.parallel.zero1 import (
    ShardGeometry,
    Zero1State,
    init_zero1_state,
    zero1_update_shard,
)


class AccoState(NamedTuple):
    """Round-carried train state.

    Global shapes (local view in parentheses). ws = data-parallel group
    count, ns = total device/shard count (ws * sp under context
    parallelism, else ws), Pp = padded param count:
    - ``flat_params`` [Pp] replicated — working params; real θ after odd
      rounds, estimated θ̃ after even rounds.
    - ``pending_grads`` [ns*Pp] sharded over (dp[, sp]) ([Pp]) —
      gradients handed to this round's communication (the grad-carrying
      role of ``com_buffer``; under CP each sp shard holds its partial).
    - ``pending_count`` [ws] sharded over dp ([1]) — their counts
      (``count_grad_this_round``; replicated across sp).
    - ``zero1`` — fp32 param shard + Adam moments (sharded over dp[, sp])
      + LR counter.
    - ``round_idx`` scalar — ``count_after_init`` parity driver.

    Tensor parallelism (``tensor_axis`` set) prefixes every flat leaf's
    layout with a tp-major block per shard — ``flat_params`` becomes
    [tp*Pp] sharded over tp (each tp shard's local params per
    parallel/tp.TpLayout), ``pending_grads`` [tp*ns*Pp] and the opt
    leaves [tp*Pp], both sharded over (tp, dp[, sp]) — and ZeRO-1 runs
    within each tp group.

    There is deliberately NO separate gradient accumulator (the
    reference's ``params.grad`` flat view): the reference zeroes its
    accumulator only after even rounds (`update_buffers_step`,
    trainer_decoupled.py:59-63), so the accumulator a round starts from
    is *always* either zeros (odd rounds) or exactly the staged
    ``pending_grads`` (even rounds — the odd half's gradients, staged
    and carried). Each round program therefore derives its carry-in from
    ``pending_grads`` and the round parity instead of storing a second
    ns*Pp f32 buffer — saving its HBM footprint and a full-vector write
    per round.
    """

    flat_params: jax.Array
    pending_grads: jax.Array
    pending_count: jax.Array
    zero1: Zero1State
    round_idx: jax.Array
    # Training-health counters (common.HealthState, replicated scalars):
    # skip counts maintained by the in-program anomaly guard, plus the
    # staged-grads verdict even rounds consult before reading
    # pending_grads back as their accumulation carry-in.
    health: HealthState


def _state_template() -> "AccoState":
    """Structure-only AccoState (placeholder leaves) for matching the
    state rule table against every leaf path."""
    return AccoState(
        flat_params=0,
        pending_grads=0,
        pending_count=0,
        zero1=Zero1State(
            opt=AdamWState(params=0, mu=0, nu=0, count=0),
            sched_grads=0,
            grads_committed=0,
        ),
        round_idx=0,
        health=HealthState(
            skipped_rounds=0, consec_skipped=0, pending_ok=0
        ),
    )


class AccoRoundMetrics(NamedTuple):
    loss: jax.Array  # world-mean of this round's valid-microbatch losses
    lr: jax.Array
    round_grads: jax.Array  # all-reduced count consumed by this round's comm
    is_real_update: jax.Array  # bool: odd round committed the optimizer
    # global L2 norm of the count-averaged gradient this round's comm
    # consumed (0.0 when nan_guard=False compiles the signals out)
    grad_norm: jax.Array
    skipped: jax.Array  # bool: the guard suppressed this round's commit
    # world-means of the objective's auxiliary terms, by name
    # (ops.losses.model_ce); empty where the loss is the cross-entropy alone
    terms: dict = {}


class AccoTrainStep:
    """Builds the ACCO (or DPU) round program for one model + mesh.

    ``mode='acco'``: speculative even / real odd rounds.
    ``mode='dpu'``: every round is a real update on one-round-stale
    gradients (the sequential arrangement of the same kernels).
    """

    def __init__(
        self,
        model,
        mesh,
        schedule,
        *,
        weight_decay: float,
        beta1: float,
        beta2: float,
        eps: float = 1e-8,
        label_smoothing: float = 0.0,
        param_dtype=jnp.bfloat16,
        lr_grad_accounting: bool = False,
        mode: str = "acco",
        seq_axis: str | None = None,
        comm_impl: str = "xla",
        fused_loss: "bool | str" = False,  # False | 'auto' | 'chunk' | 'pallas'
        tensor_axis: str | None = None,
        pipeline_axis: str | None = None,
        const_len_batch: bool = False,  # all-ones masks by contract:
        # skip pad plumbing (enables the banded GPT-Neo kernel)
        nan_guard: bool = True,  # in-program anomaly guard: skip (don't
        # commit) rounds with nonfinite/spiked grads or nonfinite update
        guard_max_grad_norm: float = 0.0,  # >0: also skip rounds whose
        # global grad norm exceeds this (static threshold; 0 = off)
    ):
        if mode not in ("acco", "dpu"):
            raise ValueError(f"mode must be 'acco' or 'dpu', got {mode!r}")
        self.nan_guard = bool(nan_guard)
        self.guard_max_grad_norm = float(guard_max_grad_norm or 0.0)
        self.comm_impl = comm_impl
        self.fused_loss = fused_loss
        self.const_len_batch = const_len_batch
        self.model = model
        self.mesh = mesh
        self.schedule = schedule
        self.weight_decay = weight_decay
        self.beta1 = beta1
        self.beta2 = beta2
        self.eps = eps
        self.label_smoothing = label_smoothing
        self.param_dtype = param_dtype
        self.lr_grad_accounting = lr_grad_accounting
        self.mode = mode
        self.seq_axis = seq_axis
        self.shard_axes, self.world_size, self.num_shards = shard_layout(
            mesh, model, seq_axis, DATA_AXIS, tensor_axis=tensor_axis,
            pipeline_axis=pipeline_axis,
        )
        self.tensor_axis = tensor_axis
        self.pipeline_axis = pipeline_axis
        # The per-device parameter layout (local flat vector per tp shard
        # / pp stage / (stage, tp-shard) pair) and its gradient correction
        # are one mechanism — parallel/tp.py's TpLayout/ComposedLayout +
        # the uniform-factor recipe — keyed on the active model axis
        # (a (pp, tp) tuple under composition; lax.axis_size of a tuple
        # is the product, so the ZeRO-1 divisor handles it unchanged).
        if tensor_axis and pipeline_axis:
            self.model_axis = (pipeline_axis, tensor_axis)
            self.tp = mesh.shape[pipeline_axis] * mesh.shape[tensor_axis]
        else:
            self.model_axis = tensor_axis or pipeline_axis
            self.tp = mesh.shape[self.model_axis] if self.model_axis else 1
        self.tp_layout = None  # built in init_state when a model axis is set
        self.geom: ShardGeometry | None = None
        # the order of the flat vector's elements (parallel/flat_layout.py);
        # None under a model axis, where tp_layout owns a row-major order
        self.layout: FlatLayout | None = None
        self.unravel = None
        self._round: dict = {}
        self._seed = None
        # name -> jax.stages.Compiled, installed by the AOT warmup
        # (trainer.join_warmup); program_callable prefers these.
        self.compiled_programs: dict = {}

    # -- state --------------------------------------------------------------

    def init_state(self, params_pytree: dict) -> AccoState:
        from acco_tpu.parallel.mesh import sharded_zeros

        cast = jax.tree.map(
            lambda x: x.astype(self.param_dtype), params_pytree
        )
        specs = None
        if self.model_axis:
            from acco_tpu.parallel.tp import ComposedLayout, TpLayout

            if self.tensor_axis and self.pipeline_axis:
                self.tp_layout = ComposedLayout(
                    cast,
                    self.model.pp_param_specs(),
                    self.mesh.shape[self.pipeline_axis],
                    self.model.tp_param_specs(),
                    self.mesh.shape[self.tensor_axis],
                )
            else:
                split_specs = (
                    self.model.tp_param_specs()
                    if self.tensor_axis
                    else self.model.pp_param_specs()
                )
                self.tp_layout = TpLayout(cast, split_specs, self.tp)
            self.unravel = self.tp_layout.unravel_local
            self.geom = ShardGeometry(self.tp_layout.n_local, self.num_shards)
            Pp, ns = self.geom.padded_size, self.num_shards
            specs = self.state_specs()
            # [tp, Pp] rows = each tp shard's padded local flat vector,
            # placed shard-by-shard (no full-size device transient).
            flat_all, zero1 = self.tp_layout.init_sharded_state(
                self.geom, cast, self.mesh, specs.flat_params,
                specs.zero1.opt.params,
            )
        else:
            self.layout = FlatLayout(cast)
            self.unravel = self.layout.unravel
            flat = self.layout.ravel(cast)
            self.geom = ShardGeometry(self.layout.n_flat, self.num_shards)
            Pp, ns = self.geom.padded_size, self.num_shards
            specs = self.state_specs()
            flat_all = self.geom.pad_flat(flat)
            zero1 = init_zero1_state(flat.astype(jnp.float32), self.geom)
        state = AccoState(
            flat_params=flat_all,
            pending_grads=sharded_zeros(
                self.mesh, specs.pending_grads, (self.tp * ns * Pp,), jnp.float32
            ),
            pending_count=jnp.zeros((self.world_size,), jnp.float32),
            zero1=zero1,
            round_idx=jnp.zeros((), jnp.int32),
            health=init_health(),
        )
        return jax.device_put(state, self.state_shardings())

    def rule_table(self):
        """Sharding rule table for this step's state tree — the single
        source behind ``state_specs``, checkpoint restore shardings, and
        the ``rules`` lint gate (analysis/rules.py)."""
        from acco_tpu.sharding import train_state_table

        return train_state_table(self.mode, self.shard_axes, self.model_axis)

    def state_specs(self) -> AccoState:
        from acco_tpu.sharding import specs_for_tree

        return specs_for_tree(self.rule_table(), _state_template())

    def state_shardings(self) -> AccoState:
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.state_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )

    # -- ahead-of-time compilation (acco_tpu/compile) -----------------------
    # Shared machinery lives in parallel/common.py (step_abstract_state /
    # step_warmup / step_program_callable — one implementation for this
    # class and DDPTrainStep); this class contributes its program dict.

    def abstract_state(self, params_avals=None, *, seed: int = 0) -> AccoState:
        """Aval-only train state (see common.step_abstract_state)."""
        from acco_tpu.parallel.common import step_abstract_state

        return step_abstract_state(self, params_avals, seed=seed)

    def warmup_program_fns(self, *, include_seed: bool = True) -> dict:
        """The jit programs one training run of this step dispatches, by
        name — ACCO: seed + both parity-specialized rounds; DPU: seed +
        the single round. (Built on the caller thread: ``round_fn``
        memoizes into ``self._round``, which is not thread-safe.)"""
        programs = {}
        if include_seed:
            programs["seed"] = self.seed_fn()
        if self.mode == "acco":
            programs["round_even"] = self.round_fn(parity=True)
            programs["round_odd"] = self.round_fn(parity=False)
        else:
            programs["round"] = self.round_fn()
        return programs

    def warmup(
        self,
        n_acc: int,
        global_batch: int,
        seq: int,
        *,
        params_avals=None,
        seed: int = 0,
        include_seed: bool = True,
        runner=None,
    ):
        """AOT lower + compile this step's round programs ahead of the
        first call (see common.step_warmup)."""
        from acco_tpu.parallel.common import step_warmup

        return step_warmup(
            self, n_acc, global_batch, seq, params_avals=params_avals,
            seed=seed, include_seed=include_seed, runner=runner,
        )

    def program_callable(self, name: str, log=None):
        """Best available callable for ``seed`` / ``round_even`` /
        ``round_odd`` / ``round`` (see common.step_program_callable)."""
        from acco_tpu.parallel.common import step_program_callable

        return step_program_callable(
            self,
            {
                "seed": self.seed_fn,
                "round": self.round_fn,
                "round_even": partial(self.round_fn, parity=True),
                "round_odd": partial(self.round_fn, parity=False),
            },
            name,
            log=log,
        )

    def _loss_fn(self):
        return make_flat_loss_fn(
            self.model,
            self.unravel,
            self.geom.n_params,
            self.label_smoothing,
            seq_axis=self.seq_axis,
            fused_loss=self.fused_loss,
            n_vocab_shards=self.tp,
            const_len=self.const_len_batch,
            with_terms=True,
        )

    def _accumulate(self, flat_params, block, grad_init=None, count_init=None):
        """Grad accumulation over the microbatch block: the per-microbatch
        scan (common.accumulate_grads), or — under pipeline parallelism —
        the GPipe tick loop, where pipelining IS the accumulation loop
        (parallel/pp.py)."""
        if self.pipeline_axis:
            from acco_tpu.parallel.pp import (
                accumulate_grads_pipelined,
                make_pp_loss_fn,
            )

            return accumulate_grads_pipelined(
                make_pp_loss_fn(
                    self.model, self.tp_layout, self.pipeline_axis,
                    self.label_smoothing,
                    vocab_axes=self.model_axis,
                    seq_axis=self.seq_axis,
                    fused_loss=self.fused_loss,
                    n_vocab_shards=self.tp,
                ),
                flat_params,
                block,
                grad_init=grad_init,
                count_init=count_init,
            )
        return accumulate_grads(
            self._loss_fn(), flat_params, block,
            grad_init=grad_init, count_init=count_init,
        )

    def _prep_batches(self, batches: dict) -> tuple:
        """Batch dict -> positional leaves; under CP the labels are
        next-token aligned on the GLOBAL sequence before sharding (the
        chunk boundary's next token lives on the neighbor device), then
        optionally zig-zag reordered (common.prep_cp_leaves)."""
        from acco_tpu.parallel.common import prep_cp_leaves

        ids, am, labels = prep_cp_leaves(
            batches["input_ids"],
            batches["attention_mask"],
            batches["labels"],
            self.seq_axis,
            self.mesh,
            self.model,
        )
        return (ids, am, labels, batches["valid"])

    # -- seeding ------------------------------------------------------------

    def _staged_ok(self, grad_sum, loss):
        """Replication-exact verdict on the grads just staged into
        ``pending_grads`` (consumed as the next even round's
        accumulation carry-in): finite loss AND every rank's local grad
        sum finite. Loss alone is not enough — a backward-pass overflow
        can stage nonfinite grads under a finite forward loss, and the
        next even round would accumulate fresh gradients on top of
        them, one bad batch costing two skipped updates. The staged
        grads are rank-local until the update's psum_scatter, so
        exactness costs one extra SCALAR psum over the grad-reduction
        axes (+ the model axes: ``pending_ok`` is a replicated leaf,
        and under tp each shard stages a distinct piece of the model).
        ``g * 0`` maps nonfinite to NaN and finite to 0, so the sum
        probe cannot itself overflow. Must be called inside the
        shard_map body (axis names bound).
        """
        axes = (
            self.shard_axes
            if isinstance(self.shard_axes, tuple)
            else (self.shard_axes,)
        )
        if self.model_axis is not None:
            ma = self.model_axis
            axes = axes + (tuple(ma) if isinstance(ma, tuple) else (ma,))
        with jax.named_scope("acco/guard"):
            probe = jnp.sum(grad_sum * 0.0)
            local_bad = jnp.logical_not(jnp.isfinite(probe))
            bad = lax.psum(local_bad.astype(jnp.float32), axes)
            return (jnp.isfinite(loss) & (bad == 0)).astype(jnp.float32)

    def seed_fn(self):
        """Compute-only round that fills the pending buffers before round 0.

        Plays the role of the reference's bootstrap: with warmup it is the
        post-warmup grad round (`warmup_steps` tail,
        `trainer_decoupled.py:359-383`); without warmup, the dummy-grad
        init of `prepare_grads`/`prepare_buffer_com` (`:266-269,441`). In
        ACCO mode the accumulator is *not* zeroed (``count_after_init=-2``
        semantics), so these gradients also join round 1's real update —
        the seed is the first half of the first two-half-round update;
        that carry is implicit here: round 0 is even, and even ACCO
        rounds accumulate on top of the staged ``pending_grads``. In DPU
        mode rounds never read the staged grads as carry-in, so the seed
        grads are committed exactly once (by round 0), not double-weighted.
        """
        if self._seed is not None:
            return self._seed

        def body(state: AccoState, ids, am, labels, valid):
            block = MicrobatchBlock(ids, am, labels, valid[:, 0])
            grad_sum, count, loss_wsum, _ = self._accumulate(
                state.flat_params, block
            )
            loss = world_mean_loss(loss_wsum, block.valid, DATA_AXIS, self.seq_axis)
            health = state.health
            if self.nan_guard:
                # Verdict on the grads this seed stages: round 0 reads
                # them back as its accumulation carry-in.
                health = health._replace(
                    pending_ok=self._staged_ok(grad_sum, loss)
                )
            return state._replace(
                pending_grads=grad_sum,
                pending_count=count[None],
                health=health,
            ), loss

        sharded = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self.state_specs(),) + batch_specs(DATA_AXIS, self.seq_axis),
            out_specs=(self.state_specs(), P()),
            check_vma=False,
        )
        self._seed = jax.jit(
            lambda state, batches: sharded(state, *self._prep_batches(batches)),
            donate_argnums=0,
        )
        return self._seed

    # -- the round ----------------------------------------------------------

    def _body(self, state: AccoState, ids, am, labels, valid, parity=None):
        """``parity``: None = round parity traced from ``state.round_idx``
        (one program serves both rounds); True/False = this program is
        specialized to an even/odd round — the speculative-vs-commit
        ``where`` selects over the full flat vectors constant-fold away
        (the host knows the parity anyway, and the selects cost real HBM
        traffic every round)."""
        acco = self.mode == "acco"
        if not acco:
            is_even = False  # dpu: never speculative (static)
        elif parity is None:
            is_even = state.round_idx % 2 == 0  # traced
        else:
            is_even = bool(parity)  # static: selects below fold at trace
        speculative = is_even

        def sel(pred, a, b):
            """where() that short-circuits on static (Python bool) preds."""
            if isinstance(pred, bool):
                return a if pred else b
            return jnp.where(pred, a, b)

        # ---- communication branch: consume pending_grads ----
        raw_total = lax.psum(state.pending_count[0], DATA_AXIS)
        total = jnp.maximum(raw_total, 1.0)
        with jax.named_scope("acco/optimizer"):
            lr = self.schedule(state.zero1.sched_grads)
        # Speculative rollback, functionally: keep the old optimizer state
        # on even rounds (reference's snapshot/restore, :79-84,113-126).
        commit = (
            not speculative
            if isinstance(speculative, bool)
            else jnp.logical_not(speculative)
        )
        # In-program anomaly guard (nan_guard): an unhealthy update
        # (nonfinite or over-threshold grads, nonfinite new params) is a
        # bit-exact no-op — the working params stay put on EVERY parity (a
        # poisoned θ̃ would send the next half-round's compute off a
        # cliff before any host-side check could even see it — the
        # speculative half-step of the ISSUE's motivation), and the
        # optimizer commit additionally requires health.
        # zero1_update_shard applies both to what it returns (a committing
        # round pays one read-only pass over the shard for the verdict; a
        # speculative one a select over the bf16 shard); the scalars
        # below follow the same predicate. nan_guard=False compiles the
        # guard out entirely.
        upd = zero1_update_shard(
            state.pending_grads,
            state.zero1.opt,
            total,
            lr,
            self.geom,
            self.weight_decay,
            self.beta1,
            self.beta2,
            self.eps,
            self.shard_axes,
            self.param_dtype,
            comm_impl=self.comm_impl,
            tp_axis=self.model_axis,
            n_repl=self.tp_layout.n_repl if self.tp_layout else 0,
            n_repl_both=getattr(self.tp_layout, "n_repl_both", 0),
            inner_axis=(
                self.tensor_axis
                if (self.tensor_axis and self.pipeline_axis)
                else None
            ),
            with_health=self.nan_guard,
            max_grad_norm=self.guard_max_grad_norm,
            commit=commit,
            old_flat=state.flat_params,
        )
        if self.nan_guard:
            new_flat, opt_out, uh = upd
            ok, grad_norm = uh.ok, uh.grad_norm
            if isinstance(commit, bool):
                commit_ok = ok if commit else False
            else:
                commit_ok = jnp.logical_and(commit, ok)
        else:
            new_flat, opt_out = upd
            ok, grad_norm = None, jnp.float32(0.0)
            commit_ok = commit
        # the selects below are the guard's where ok is data, and the
        # speculative/commit selects of a parity-generic program otherwise
        def select_scope():
            if ok is not None:
                return jax.named_scope("acco/guard")
            return jax.named_scope("acco/cast")

        with select_scope():
            sched_inc = (
                total.astype(jnp.int32) if self.lr_grad_accounting else 1
            )
            sched_out = state.zero1.sched_grads + sel(commit_ok, sched_inc, 0)

        # ---- compute branch: grads at the current working params ----
        # Carry-in (the reference's zero-only-after-even-rounds
        # accumulator, `update_buffers_step` :59-63): even ACCO rounds
        # accumulate on top of the staged odd-half gradients — which are
        # exactly ``pending_grads``, read-only in both branches — odd and
        # DPU rounds start from zero. No separate accumulator buffer.
        # Guarded carry-in: pending_ok is last round's verdict on the
        # grads it staged — a poisoned half-round (NaN loss => NaN
        # grad_sum) must not be accumulated ON TOP OF by this round's
        # fresh gradients, or one bad batch would cost two updates.
        pok = (state.health.pending_ok > 0) if self.nan_guard else None
        if not acco or (isinstance(is_even, bool) and not is_even):
            grad0 = count0 = None
        elif isinstance(is_even, bool) and pok is None:  # static even
            grad0, count0 = state.pending_grads, state.pending_count[0]
        else:  # traced parity and/or guarded carry-in
            carry = is_even if pok is None else (
                pok if isinstance(is_even, bool) else jnp.logical_and(is_even, pok)
            )
            with select_scope():
                grad0 = jnp.where(
                    carry,
                    state.pending_grads,
                    jnp.zeros_like(state.pending_grads),
                )
                count0 = jnp.where(carry, state.pending_count[0], 0.0)
        block = MicrobatchBlock(ids, am, labels, valid[:, 0])
        grad_sum, count, loss_wsum, terms_wsum = self._accumulate(
            state.flat_params, block, grad_init=grad0, count_init=count0
        )

        # ---- barrier / buffer swap (update_buffers_step, :43-63) ----
        loss_out = world_mean_loss(
            loss_wsum, block.valid, DATA_AXIS, self.seq_axis
        )
        if ok is not None:
            skipped = jnp.logical_not(ok)
            health_out = HealthState(
                skipped_rounds=state.health.skipped_rounds
                + skipped.astype(jnp.int32),
                consec_skipped=jnp.where(
                    skipped, state.health.consec_skipped + 1, 0
                ),
                # verdict on the grads THIS round stages (consumed next
                # round as the accumulation carry-in)
                pending_ok=self._staged_ok(grad_sum, loss_out),
            )
        else:
            skipped = jnp.bool_(False)
            health_out = state.health
        new_state = AccoState(
            flat_params=new_flat,
            pending_grads=grad_sum,
            pending_count=count[None],
            zero1=Zero1State(
                opt=opt_out,
                sched_grads=sched_out,
                # Real updates commit the all-reduced count — the device-
                # side count_grad_tot (`trainer_decoupled.py:501-502`).
                # Guarded: a skipped round makes no progress.
                grads_committed=state.zero1.grads_committed
                + sel(commit_ok, raw_total, jnp.zeros_like(raw_total)),
            ),
            round_idx=state.round_idx + 1,
            health=health_out,
        )
        metrics = AccoRoundMetrics(
            loss=loss_out,
            lr=lr,
            round_grads=raw_total,
            is_real_update=jnp.bool_(commit_ok),
            grad_norm=grad_norm,
            skipped=skipped,
            terms=world_mean_terms(
                terms_wsum, block.valid, DATA_AXIS, self.seq_axis
            ),
        )
        return new_state, metrics

    def round_fn(self, parity=None):
        """The jitted round: ``(state, batches) -> (state, metrics)``.

        Batch leaves as in :meth:`DDPTrainStep.step_fn`: global
        [n_acc, global_batch, seq] + ``valid`` [n_acc, world_size].

        ``parity``: None compiles one generic program whose round parity
        is traced from ``state.round_idx``. True (even/speculative) or
        False (odd/commit) compiles a parity-specialized program — the
        rollback/zeroing selects over the full flat vectors fold away
        (the host loop alternates the two; every benchmark cell runs this
        pair, so what it wins over the generic program is unmeasured). The
        caller owns keeping the call parity consistent with
        ``state.round_idx``; in DPU mode all three are the same program.
        """
        key = None if self.mode == "dpu" else parity
        if key in self._round:
            return self._round[key]
        body = partial(self._body, parity=key)
        sharded = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self.state_specs(),) + batch_specs(DATA_AXIS, self.seq_axis),
            out_specs=(
                self.state_specs(),
                AccoRoundMetrics(P(), P(), P(), P(), P(), P(), P()),
            ),
            check_vma=False,
        )
        self._round[key] = jax.jit(
            lambda state, batches: sharded(state, *self._prep_batches(batches)),
            donate_argnums=0,
        )
        return self._round[key]

    def make_valid(self, n_acc: int) -> jnp.ndarray:
        return make_valid(n_acc, self.world_size)
