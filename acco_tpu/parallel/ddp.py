"""The synchronous baseline: DDP + ZeRO-1 sharded AdamW, one compiled step.

Capability parity with the reference's ``train_ddp`` mode
(`DistributedDataParallel` + ``ZeroRedundancyOptimizer(AdamW)``,
`/root/reference/trainer_decoupled.py:226-241,732-833`): every step
accumulates ``n_grad_accumulation`` micro-gradients, averages across the
world, applies the sharded AdamW, and advances the LR schedule by the total
gradient count (``world_size * n_acc``, `:762-763`).

TPU-native shape: one ``shard_map`` program over the ``dp`` mesh axis —
fwd/bwd scan, ``psum_scatter`` of the flat grad, AdamW on the fp32 shard,
``all_gather`` of updated params. XLA schedules the collectives; there is
no host-side optimizer loop.
"""

from __future__ import annotations

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
from acco_tpu.parallel.zero1 import ShardGeometry, Zero1State, init_zero1_state, zero1_update_shard


class DDPState(NamedTuple):
    flat_params: jax.Array  # [padded] param_dtype, replicated
    zero1: Zero1State  # opt leaves sharded along dp; sched replicated
    # Training-health counters (common.HealthState): skip counts from
    # the in-program anomaly guard. pending_ok is carried for state-
    # layout parity with AccoState but DDP consumes its gradients in the
    # same program that computes them, so it is never read back.
    health: HealthState


class StepMetrics(NamedTuple):
    loss: jax.Array  # valid-count-weighted world-mean over the step's microbatches
    lr: jax.Array
    grads_this_step: jax.Array  # total micro-grad count (all-reduced)
    # global L2 norm of the count-averaged gradient this step applied
    # (0.0 when nan_guard=False compiles the signals out)
    grad_norm: jax.Array
    skipped: jax.Array  # bool: the guard suppressed this step's commit
    # world-means of the objective's auxiliary terms, by name
    # (ops.losses.model_ce); empty where the loss is the cross-entropy alone
    terms: dict = {}


class DDPTrainStep:
    """Builds init-state and the jitted step for one model + mesh."""

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
        seq_axis: str | None = None,
        comm_impl: str = "xla",
        fused_loss: "bool | str" = False,  # False | 'auto' | 'chunk' | 'pallas'
        tensor_axis: str | None = None,
        pipeline_axis: str | None = None,
        const_len_batch: bool = False,  # all-ones masks by contract:
        # skip pad plumbing (enables the banded GPT-Neo kernel)
        nan_guard: bool = True,  # in-program anomaly guard: skip (don't
        # commit) steps with nonfinite/spiked grads or nonfinite update
        guard_max_grad_norm: float = 0.0,  # >0: also skip steps whose
        # global grad norm exceeds this (static threshold; 0 = off)
    ):
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
        # False = reference-faithful (lr advances 1 per update; see
        # acco_tpu/ops/schedules.py on the reference's _step_count no-op).
        self.lr_grad_accounting = lr_grad_accounting
        self.seq_axis = seq_axis
        self.shard_axes, self.world_size, self.num_shards = shard_layout(
            mesh, model, seq_axis, DATA_AXIS, tensor_axis=tensor_axis,
            pipeline_axis=pipeline_axis,
        )
        self.tensor_axis = tensor_axis
        self.pipeline_axis = pipeline_axis
        # tp shard / pp stage / (stage, tp-shard) pair: one local-flat-
        # vector layout mechanism (parallel/tp.py TpLayout/ComposedLayout;
        # parallel/pp.py module docstring). Composed: model_axis is the
        # (pp, tp) tuple — lax.axis_size of a tuple is the product.
        if tensor_axis and pipeline_axis:
            self.model_axis = (pipeline_axis, tensor_axis)
            self.tp = mesh.shape[pipeline_axis] * mesh.shape[tensor_axis]
        else:
            self.model_axis = tensor_axis or pipeline_axis
            self.tp = mesh.shape[self.model_axis] if self.model_axis else 1
        self.tp_layout = None
        self.geom: ShardGeometry | None = None
        # the order of the flat vector's elements (parallel/flat_layout.py);
        # None under a model axis, where tp_layout owns a row-major order
        self.layout: FlatLayout | None = None
        self.unravel = None
        self._step = None
        # name -> jax.stages.Compiled, installed by the AOT warmup
        # (trainer.join_warmup); program_callable prefers these.
        self.compiled_programs: dict = {}

    # -- state --------------------------------------------------------------

    def init_state(self, params_pytree: dict) -> DDPState:
        cast = jax.tree.map(
            lambda x: x.astype(self.param_dtype), params_pytree
        )
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
            specs = self.state_specs()
            flat_all, zero1 = self.tp_layout.init_sharded_state(
                self.geom, cast, self.mesh, specs.flat_params,
                specs.zero1.opt.params,
            )
        else:
            self.layout = FlatLayout(cast)
            self.unravel = self.layout.unravel
            flat = self.layout.ravel(cast)
            self.geom = ShardGeometry(self.layout.n_flat, self.num_shards)
            flat_all = self.geom.pad_flat(flat)
            zero1 = init_zero1_state(flat.astype(jnp.float32), self.geom)
        state = DDPState(
            flat_params=flat_all, zero1=zero1, health=init_health()
        )
        return jax.device_put(state, self.state_shardings())

    def state_shardings(self) -> DDPState:
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.state_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )

    def rule_table(self):
        """Sharding rule table for this step's state tree — the single
        source behind ``state_specs``, checkpoint restore shardings, and
        the ``rules`` lint gate (analysis/rules.py)."""
        from acco_tpu.sharding import train_state_table

        return train_state_table("ddp", self.shard_axes, self.model_axis)

    def state_specs(self) -> DDPState:
        from acco_tpu.sharding import specs_for_tree

        template = DDPState(
            flat_params=0,
            zero1=Zero1State(
                opt=AdamWState(params=0, mu=0, nu=0, count=0),
                sched_grads=0,
                grads_committed=0,
            ),
            health=HealthState(
                skipped_rounds=0, consec_skipped=0, pending_ok=0
            ),
        )
        return specs_for_tree(self.rule_table(), template)

    # -- ahead-of-time compilation (acco_tpu/compile) -----------------------
    # Shared machinery in parallel/common.py (one implementation for this
    # class and AccoTrainStep); DDP contributes its single program.

    def abstract_state(self, params_avals=None, *, seed: int = 0) -> DDPState:
        """Aval-only train state (see common.step_abstract_state)."""
        from acco_tpu.parallel.common import step_abstract_state

        return step_abstract_state(self, params_avals, seed=seed)

    def warmup_program_fns(self, *, include_seed: bool = True) -> dict:
        """DDP dispatches a single program (``include_seed`` accepted for
        interface parity with :meth:`AccoTrainStep.warmup_program_fns`)."""
        return {"step": self.step_fn()}

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
        """AOT lower + compile the DDP step ahead of the first call (see
        common.step_warmup)."""
        from acco_tpu.parallel.common import step_warmup

        return step_warmup(
            self, n_acc, global_batch, seq, params_avals=params_avals,
            seed=seed, include_seed=include_seed, runner=runner,
        )

    def program_callable(self, name: str, log=None):
        """Best available callable for ``step`` (see
        common.step_program_callable)."""
        from acco_tpu.parallel.common import step_program_callable

        return step_program_callable(
            self, {"step": self.step_fn}, name, log=log
        )

    # -- step ---------------------------------------------------------------

    def _body(self, state: DDPState, ids, am, labels, valid):
        block = MicrobatchBlock(ids, am, labels, valid[:, 0])
        if self.pipeline_axis:
            from acco_tpu.parallel.pp import (
                accumulate_grads_pipelined,
                make_pp_loss_fn,
            )

            grad_sum, count, loss_wsum, terms_wsum = accumulate_grads_pipelined(
                make_pp_loss_fn(
                    self.model, self.tp_layout, self.pipeline_axis,
                    self.label_smoothing,
                    vocab_axes=self.model_axis,
                    seq_axis=self.seq_axis,
                    fused_loss=self.fused_loss,
                    n_vocab_shards=self.tp,
                ),
                state.flat_params,
                block,
            )
        else:
            loss_fn = make_flat_loss_fn(
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
            grad_sum, count, loss_wsum, terms_wsum = accumulate_grads(
                loss_fn, state.flat_params, block
            )
        raw_total = lax.psum(count, DATA_AXIS)
        total = jnp.maximum(raw_total, 1.0)
        sched_inc = (
            total.astype(jnp.int32) if self.lr_grad_accounting else jnp.int32(1)
        )
        with jax.named_scope("acco/optimizer"):
            lr = self.schedule(state.zero1.sched_grads)
        upd = zero1_update_shard(
            grad_sum,
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
            old_flat=state.flat_params,
        )
        loss_out = world_mean_loss(
            loss_wsum, block.valid, DATA_AXIS, self.seq_axis
        )
        if self.nan_guard:
            # In-program anomaly guard: an unhealthy update (nonfinite
            # or over-threshold grads, nonfinite new params) commits
            # NOTHING — params, opt moments, Adam step count
            # (zero1_update_shard returns the old ones), the LR schedule
            # and the committed-grads counter (here) are all the old
            # values, bit-exactly, selected on-device with no host sync.
            new_flat, new_opt, uh = upd
            ok, grad_norm = uh.ok, uh.grad_norm
            skipped = jnp.logical_not(ok)
            with jax.named_scope("acco/guard"):
                sched_inc = jnp.where(ok, sched_inc, 0)
                committed_inc = jnp.where(ok, raw_total, 0.0)
            health_out = HealthState(
                skipped_rounds=state.health.skipped_rounds
                + skipped.astype(jnp.int32),
                consec_skipped=jnp.where(
                    skipped, state.health.consec_skipped + 1, 0
                ),
                pending_ok=jnp.isfinite(loss_out).astype(jnp.float32),
            )
        else:
            new_flat, new_opt = upd
            grad_norm = jnp.float32(0.0)
            skipped = jnp.bool_(False)
            committed_inc = raw_total
            health_out = state.health
        new_state = DDPState(
            flat_params=new_flat,
            zero1=Zero1State(
                opt=new_opt,
                sched_grads=state.zero1.sched_grads + sched_inc,
                grads_committed=state.zero1.grads_committed + committed_inc,
            ),
            health=health_out,
        )
        metrics = StepMetrics(
            loss=loss_out,
            lr=lr,
            grads_this_step=raw_total,
            grad_norm=grad_norm,
            skipped=skipped,
            terms=world_mean_terms(
                terms_wsum, block.valid, DATA_AXIS, self.seq_axis
            ),
        )
        return new_state, metrics

    def step_fn(self):
        """The jitted step: ``(state, batches) -> (state, metrics)``.

        ``batches`` leaves: input_ids/attention_mask/labels with *global*
        shape [n_acc, global_batch, seq] (sharded over dp on the batch
        dim) and ``valid`` [n_acc, world_size] (1.0 = microbatch counts).
        """
        if self._step is not None:
            return self._step
        sharded_body = jax.shard_map(
            self._body,
            mesh=self.mesh,
            in_specs=(self.state_specs(),) + batch_specs(DATA_AXIS, self.seq_axis),
            out_specs=(self.state_specs(), StepMetrics(P(), P(), P(), P(), P(), P())),
            check_vma=False,
        )

        from functools import partial

        # donate the input state: without this every step keeps the old
        # fp32 optimizer state alive next to the new one — 2x the state
        # HBM (enough to OOM a 350M model on one v5e chip).
        @partial(jax.jit, donate_argnums=0)
        def step(state: DDPState, batches: dict):
            from acco_tpu.parallel.common import prep_cp_leaves

            ids, am, labels = prep_cp_leaves(
                batches["input_ids"],
                batches["attention_mask"],
                batches["labels"],
                self.seq_axis,
                self.mesh,
                self.model,
            )
            return sharded_body(state, ids, am, labels, batches["valid"])

        self._step = step
        return step

    def make_valid(self, n_acc: int) -> jnp.ndarray:
        return make_valid(n_acc, self.world_size)
