"""The trainer / public API layer: ``DecoupledTrainer``.

Surface parity with the reference's ``DecoupledTrainer`` —
``DecoupledTrainer(model, tokenizer, train_dataset, eval_dataset, args,
log).train()`` dispatching on ``args.method_name`` ∈ {``acco``, ``ddp``,
``dpu``} (`/root/reference/trainer_decoupled.py:170-223,418-429` and
`trainer_base.py:19-129`) — with the mechanism redesigned for TPU:

- the three training modes are single compiled ``shard_map`` programs
  (`acco_tpu/parallel/{acco,ddp}.py`); there are no host threads, CUDA
  streams, or barriers to manage (`trainer_decoupled.py:444-475` has no
  equivalent here by design — SURVEY.md §5 'race detection');
- the host loop only feeds stacked microbatch blocks and reads metrics
  *lazily* (device->host sync happens at logging boundaries, not every
  round, so dispatch runs ahead of the device);
- checkpointing is Orbax save **and resume** of the full sharded train
  state — an explicit improvement over the reference's save-only
  ``state_dict`` drops (`trainer_decoupled.py:559-574`);
- data: rank sharding by *process* (`trainer_base.py:193-200` sharded by
  GPU rank; here one process feeds all its local devices and the batch is
  laid out over the global mesh).

Observability parity: the per-N-grads progress line, TensorBoard scalar
names (``loss_t/step/samples``, ``eval_loss_*``), and the ``results.csv``
ledger row at the end of training (`/root/reference/utils/logs_utils.py`).
"""

from __future__ import annotations

import json
import logging
import os
import time
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from acco_tpu.data.loader import ShardedBatchIterator, shard_dataset
from acco_tpu.data.prefetch import AsyncPrefetcher, PrefetchingBlockSource
from acco_tpu.data.tokenize import make_map_fn_const_len, make_map_fn_truncate
from acco_tpu.ops.schedules import get_schedule
from acco_tpu.parallel.acco import AccoTrainStep
from acco_tpu.parallel.common import BATCH_KEYS, batch_specs
from acco_tpu.parallel.ddp import DDPTrainStep
from acco_tpu.parallel.flat_layout import (
    LAYOUT_META_KEY,
    flat_layout_tag,
    restore_flat_state,
)
from acco_tpu.parallel.mesh import (
    DATA_AXIS,
    SEQ_AXIS,
    initialize_distributed,
    make_mesh,
)
from acco_tpu.resilience import (
    CheckpointManager,
    FaultInjector,
    ShutdownHandler,
    TrainingHealthMonitor,
)
from acco_tpu.telemetry import (
    DECLARED_DEVICE_SCOPES,
    INSIDE_TRAINER_INIT,
    Tracer,
    metrics,
    scope_table,
    setup_phases,
)
from acco_tpu.utils import logs as logs_utils
from acco_tpu.utils.checkpoint import latest_checkpoint

_module_log = logging.getLogger(__name__)


class _WarmupHandle:
    """Background AOT-warmup bookkeeping: the runner, the step object its
    programs belong to, and the const-len verdict they were lowered
    under (a later downgrade means the programs are stale — see
    ``DecoupledTrainer.__init__``)."""

    def __init__(self, runner, step, const_len: bool) -> None:
        self.runner = runner
        self.step = step
        self.const_len = const_len
        self.logged = False


def _arg(args: Any, name: str, default: Any = None) -> Any:
    """Fetch ``args.name`` tolerating dicts, ConfigNodes, and None values."""
    if isinstance(args, dict):
        value = args.get(name, default)
    else:
        value = getattr(args, name, default)
    return default if value is None else value


_SETUP_LABELS = {
    "build_model": "model", "load_data": "data", "trainer_init": "trainer",
    "summary_writer": "writer", "state_init": "state",
    "warmup_join": "warmup join", "scope_table": "scope table",
}


def _setup_line(total_s: float, events: list) -> str:
    """The run's one set-up line: seconds from the tracer's zero to the
    first dispatch, by phase (``telemetry.setup_phases``), with what the
    warmup's join learned (``compile/warmup_join``'s args) and what the
    cache dir held at the launch (``setup/config``'s, from ``main.run``)::

        set-up 84.1 s: config 0.3, imports 0.1, model 0.4, data 1.2, trainer
        9.8 (tokenize 6.1, writer 2.2), state 7.2, warmup join 58.3 [3 hits
        0 misses, cache 201->188/192 MiB], seed 2.9

    ``writer 2.2 [heavy_modules torch, tensorflow]`` where the process had
    loaded one of ``utils.logs.HEAVY_MODULES`` by the end of that span.
    """
    phases = setup_phases(events)
    args = {
        e["name"]: e.get("args") or {} for e in events
        if e.get("name") in (
            "setup/config", "setup/summary_writer", "compile/warmup_join"
        )
    }
    joined = args.get("compile/warmup_join", {})
    heavy = args.get("setup/summary_writer", {}).get("heavy_modules")
    at_launch = args.get("setup/config", {}).get("cache_dir_bytes")
    parts = []
    for phase, seconds in phases.items():
        if phase in INSIDE_TRAINER_INIT:
            continue
        part = f"{_SETUP_LABELS.get(phase, phase)} {seconds:.1f}"
        if phase == "trainer_init":
            said = {"summary_writer": f" [heavy_modules {', '.join(heavy)}]"} if heavy else {}
            inside = [
                f"{_SETUP_LABELS.get(p, p)} {phases[p]:.1f}{said.get(p, '')}"
                for p in ("tokenize", "summary_writer") if p in phases
            ]
            part += f" ({', '.join(inside)})" if inside else ""
        if phase == "warmup_join" and "hits" in joined:
            part += f" [{joined['hits']} hits {joined['misses']} misses"
            if "cache_dir_bytes" in joined:
                part += ", cache "
                if at_launch is not None:
                    part += f"{at_launch / 2**20:.0f}->"
                part += f"{joined['cache_dir_bytes'] / 2**20:.0f}"
                if "cache_max_bytes" in joined:
                    part += f"/{joined['cache_max_bytes'] / 2**20:.0f}"
                part += " MiB"
            part += "]"
        parts.append(part)
    return f"set-up {total_s:.1f} s: " + ", ".join(parts)


class DecoupledTrainer:
    """Train a causal LM with ACCO, DPU, or synchronous DDP on a TPU mesh.

    Parameters mirror the reference constructor
    (`/root/reference/main.py:54-64`): ``model`` is an
    ``acco_tpu.models`` model (init/apply), ``tokenizer`` any callable
    tokenizer with ``eos_token_id``/``pad_token_id`` (HF or the byte
    fallback), datasets are HF datasets with a ``text`` column (or already
    tokenized with ``input_ids``), ``args`` the composed ``cfg.train``
    node. Extra keyword-only knobs take the place of reference globals:
    ``seed`` (model init), ``run_dir`` (Hydra's chdir'ed run dir),
    ``mesh`` / ``dist_info`` (injection points for tests).
    """

    def __init__(
        self,
        model,
        tokenizer,
        train_dataset,
        eval_dataset,
        args,
        log=None,
        *,
        seed: int = 0,
        run_dir: str = ".",
        mesh=None,
        dist_info: Optional[dict] = None,
        initial_params: Optional[dict] = None,
        shutdown_handler: Optional[ShutdownHandler] = None,
        tracer: Optional[Tracer] = None,
    ) -> None:
        self._t_construct = time.time()
        # Telemetry (acco_tpu/telemetry): the span tracer + the global
        # closed-world metrics registry. Host clocks only — enabled or
        # disabled, telemetry adds ZERO host-device syncs (the module
        # never imports jax; the host-lint sync gate holds it to that).
        # main.run hands over the tracer it made at its entry, so set-up
        # and the round loop share one clock from the process's start; a
        # trainer built in code starts the clock here. Rank and the
        # config's telemetry block are known further down (_construct),
        # where the tracer is told.
        self.tracer = tracer if tracer is not None else Tracer()
        from acco_tpu.compile import trace_compiles

        # every backend compile of the process, warmup thread or lazy on
        # the main thread, becomes a compile/backend span
        trace_compiles(self.tracer)
        with self.tracer.span("setup/trainer_init", cat="setup") as init:
            self._construct(
                model, tokenizer, train_dataset, eval_dataset, args, log,
                seed=seed, run_dir=run_dir, mesh=mesh, dist_info=dist_info,
                initial_params=initial_params,
                shutdown_handler=shutdown_handler,
            )
            init.update(method=self.method, world_size=self.world_size)

    def _construct(
        self,
        model,
        tokenizer,
        train_dataset,
        eval_dataset,
        args,
        log,
        *,
        seed: int,
        run_dir: str,
        mesh,
        dist_info: Optional[dict],
        initial_params: Optional[dict],
        shutdown_handler: Optional[ShutdownHandler],
    ) -> None:
        self.model = model
        # Pretrained start (the reference's finetune mode, main.py:33-35):
        # when given, these weights replace the random init in train().
        self.initial_params = initial_params
        self.tokenizer = tokenizer
        self.args = args
        self.log = log or _module_log
        self.seed = int(seed)
        self.run_dir = run_dir

        self.dist = dist_info or initialize_distributed(self.log)
        self.mesh = mesh if mesh is not None else make_mesh(_arg(args, "mesh_shape"))
        # world_size = data-parallel group count (the reference's "workers").
        # An 'sp' mesh axis > 1 enables context parallelism: the sequence is
        # sharded over it (ring attention) and ZeRO-1 shards over dp x sp.
        self.world_size = self.mesh.shape[DATA_AXIS]
        self.seq_axis = (
            SEQ_AXIS
            if SEQ_AXIS in self.mesh.shape and self.mesh.shape[SEQ_AXIS] > 1
            else None
        )
        # A 'tp' mesh axis > 1 enables tensor parallelism (parallel/tp.py):
        # model layer matrices shard over it, ZeRO-1 shards each tp shard's
        # local flat vector over dp (x sp).
        from acco_tpu.parallel.mesh import PIPELINE_AXIS, TENSOR_AXIS

        self.tensor_axis = (
            TENSOR_AXIS
            if TENSOR_AXIS in self.mesh.shape and self.mesh.shape[TENSOR_AXIS] > 1
            else None
        )
        # A 'pp' mesh axis > 1 enables pipeline parallelism (parallel/pp.py):
        # the layer stack splits into contiguous stages over it, the
        # round's n_grad_accumulation microbatches flow the GPipe loop.
        self.pipeline_axis = (
            PIPELINE_AXIS
            if PIPELINE_AXIS in self.mesh.shape
            and self.mesh.shape[PIPELINE_AXIS] > 1
            else None
        )
        if (
            self.pipeline_axis
            and int(_arg(args, "n_grad_accumulation", 1))
            < self.mesh.shape[PIPELINE_AXIS]
        ):
            self.log.warning(
                "n_grad_accumulation (%d) < pp (%d): the pipeline bubble "
                "dominates — use n_acc >= pp microbatches per round",
                int(_arg(args, "n_grad_accumulation", 1)),
                self.mesh.shape[PIPELINE_AXIS],
            )
        self.rank = self.dist["rank"]
        self.id_run = logs_utils.create_id_run()

        self.method = str(_arg(args, "method_name", "acco"))
        # Rank 0 writes the trace, like the reference's rank gating; another
        # rank, or telemetry.enabled=false, drops what the tracer has held
        # since the launch. The annotation it is handed puts every span on
        # the profiler's host plane too while train.profile_steps captures
        # a trace: host spans and device ops on one clock.
        tel = _arg(args, "telemetry", None) or {}
        _tel = tel.get if hasattr(tel, "get") else (
            lambda k, d=None: getattr(tel, k, d)
        )
        self.telemetry_enabled = bool(_tel("enabled", True))
        self.tracer.configure(
            enabled=self.telemetry_enabled and self.rank == 0,
            process_name=f"acco-{self.method}",
            max_events=int(_tel("max_trace_events", 200_000)),
            annotate=jax.profiler.TraceAnnotation,
        )
        if self.method not in ("acco", "ddp", "dpu"):
            raise ValueError(
                f"method_name must be one of acco/ddp/dpu, got {self.method!r}"
            )
        # run_baseline_ddp gates the DDP machinery in the reference
        # (`trainer_decoupled.py:210-211`): train_ddp without it crashes,
        # and with it the decoupled buffers are never built. Here the step
        # is derived from method_name alone, so the flag is validated
        # rather than silently ignored.
        baseline_flag = _arg(args, "run_baseline_ddp")
        if baseline_flag is not None and bool(baseline_flag) != (
            self.method == "ddp"
        ):
            raise ValueError(
                f"run_baseline_ddp={bool(baseline_flag)} contradicts "
                f"method_name={self.method!r}: the flag must be True exactly "
                "for the ddp baseline (reference trainer_decoupled.py:210)"
            )
        # const-len packed batches carry all-ones masks by contract —
        # the static flag lets train/eval programs drop pad plumbing.
        # eval_const_len is the EVAL dataset's own verdict (decided per
        # dataset in _check_const_len): a short-row eval set costs eval
        # its mask drop, never training its mask-free programs.
        self.const_len_batch = bool(_arg(args, "const_len_batch", True))
        self.eval_const_len = self.const_len_batch
        # Async input pipeline (data/prefetch.py): collate + sharded
        # device transfer for round N+1 run while round N executes.
        # prefetch=False is the synchronous debugging opt-out.
        self.prefetch = bool(_arg(args, "prefetch", True))
        self.prefetch_depth = int(_arg(args, "prefetch_depth", 2))
        self.batch_size = int(_arg(args, "batch_size", 8))
        self.n_acc = int(_arg(args, "n_grad_accumulation", 1))
        self.max_length = int(_arg(args, "max_length", 1024))
        self.nb_grad_tot = int(_arg(args, "nb_steps_tot", 1000))
        self.use_mixed_precision = bool(_arg(args, "use_mixed_precision", True))
        self.param_dtype = jnp.bfloat16 if self.use_mixed_precision else jnp.float32
        self.label_smoothing = float(_arg(args, "label_smoothing_factor", 0.0))
        self.delta_step_for_log = int(_arg(args, "delta_step_for_log", 10))

        self.schedule = get_schedule(
            str(_arg(args, "scheduler_name", "cosine")),
            float(_arg(args, "learning_rate", 6e-4)),
            int(_arg(args, "warmup", 0)),
            self.nb_grad_tot,
        )

        # Training-health watchdog (ISSUE 7): the in-program anomaly
        # guard lives inside the compiled round programs
        # (parallel/{acco,ddp}.py — nonfinite/spiked grads or a
        # nonfinite update make the round a bit-exact on-device no-op);
        # the host monitor classifies spikes vs drift from rolling
        # statistics at the logging boundary and escalates persistent
        # anomalies into an auto-rollback (_rollback).
        self.nan_guard = bool(_arg(args, "nan_guard", True))
        self.guard_max_grad_norm = float(
            _arg(args, "guard_max_grad_norm", 0.0) or 0.0
        )
        self.rollback_enabled = bool(_arg(args, "rollback", True))
        self.rollback_after_skipped = max(
            1, int(_arg(args, "rollback_after_skipped", 8))
        )
        self.rollback_max = int(_arg(args, "rollback_max", 2))
        if self.rollback_enabled and not self.nan_guard:
            # rollback triggers on the guard's consecutive-skip counter;
            # without the guard nothing ever increments it.
            self.log.warning(
                "rollback=True has no trigger with nan_guard=False; "
                "auto-rollback is effectively disabled"
            )
        # Config-driven fault injection (resilience/faults.py): parsed
        # here — with the pure-config validation below — so a malformed
        # chaos spec fails before hours of tokenization, and a drill
        # that would silently inject nothing cannot start.
        self.fault_injector = FaultInjector.from_config(
            _arg(args, "fault_injection"), log=self.log
        )
        self._rollbacks = 0
        self._health_monitor: Optional[TrainingHealthMonitor] = None
        self._last_consec_skipped = 0

        # Pure-config validation BEFORE the data section: tokenizing a full
        # corpus and then failing on a config error wastes hours.
        comm_impl = str(_arg(args, "comm_impl", "auto"))
        if comm_impl not in ("auto", "ring", "xla"):
            raise ValueError(
                f"comm_impl must be auto/ring/xla, got {comm_impl!r}"
            )
        # Resolve ONCE here; _make_step consumes self.comm_impl verbatim
        # (keeps the warning and the behavior from drifting apart).
        if comm_impl == "ring" and self.seq_axis is not None:
            # zero1_update_shard quietly needs the stock path for axis
            # tuples; an explicit 'ring' request under CP must not be
            # silently downgraded.
            self.log.warning(
                "comm_impl='ring' is unsupported with context parallelism "
                "(the ZeRO-1 shard spans the (dp, sp) axis tuple and "
                "ppermute rings run over a single axis); falling back to "
                "the XLA collectives"
            )
            comm_impl = "xla"
        elif comm_impl == "auto":
            # ring = async ppermute hops the TPU scheduler can overlap
            # with compute (ring_collectives.py); single-axis multi-chip
            # layouts only. Elsewhere (CPU tests, CP axis tuples,
            # single chip) stock XLA collectives are the right call.
            comm_impl = (
                "ring"
                if (
                    jax.devices()[0].platform == "tpu"
                    and self.seq_axis is None
                    and self.world_size > 1
                )
                else "xla"
            )
        self.comm_impl = comm_impl
        from acco_tpu.ops.losses import normalize_fused_loss

        self.fused_loss = normalize_fused_loss(_arg(args, "fused_loss", False))
        if self.fused_loss == "chunk" and self.seq_axis is not None:
            # Same convention as the ring-under-CP fallback above: an
            # explicitly requested option that the CP path cannot honor
            # must warn, not silently downgrade (the user likely set it
            # because the logits don't fit). 'pallas' DOES compose with
            # CP — both the flat dp x sp path (common.make_flat_loss_fn)
            # and the pipelined pp x sp path (pp.make_pp_loss_fn) carry
            # the pre-shifted labels + psum'd num_valid convention —
            # only 'chunk' has no CP form.
            self.log.warning(
                "fused_loss='chunk' has no context-parallel form; "
                "falling back to materialized logits — "
                "fused_loss='pallas' composes with CP if the logits "
                "stream matters"
            )
        if self.fused_loss == "chunk" and self.tensor_axis is not None:
            self.log.warning(
                "fused_loss='chunk' has no vocab-parallel form; using the "
                "materialized vocab-parallel CE (its [B, L, V/tp] local "
                "logits already bound memory) — fused_loss='pallas' has "
                "a sharded kernel if the logits stream matters"
            )
        if self.seq_axis and self.max_length % self.mesh.shape[self.seq_axis]:
            raise ValueError(
                f"max_length {self.max_length} must divide evenly over the "
                f"sp axis ({self.mesh.shape[self.seq_axis]} shards)"
            )
        if (
            self.seq_axis
            and getattr(model, "zigzag", False)
            and self.max_length % (2 * self.mesh.shape[self.seq_axis])
        ):
            raise ValueError(
                f"zig-zag context parallelism shards the sequence into "
                f"2*sp half-chunks: max_length {self.max_length} must be "
                f"divisible by {2 * self.mesh.shape[self.seq_axis]} "
                f"(build the model with zigzag=False to use contiguous "
                f"sharding instead)"
            )
        if self.pipeline_axis and not self.const_len_batch:
            # Same contract as CP below: the pipeline loss path does not
            # propagate per-token attention masks (activations travel the
            # stage chain without their masks), so padded batches would
            # silently attend pad tokens. Refuse instead.
            raise ValueError(
                "pipeline parallelism (pp > 1) requires const_len_batch="
                "True: the pipelined loss path has no per-token attention "
                "mask; pack the data const-length"
            )
        if self.seq_axis and not self.const_len_batch:
            # The CP loss path computes attention over full-length packed
            # chunks and does not propagate per-token attention masks
            # (common.py make_flat_loss_fn); padded finetune batches would
            # silently make pad tokens attendable. Refuse instead. (A
            # dataset-level check after tokenization catches data that
            # bypasses this flag, e.g. pre-tokenized variable-length rows.)
            raise ValueError(
                "context parallelism (sp > 1) requires const_len_batch=True: "
                "the sequence-sharded attention path has no per-token "
                "attention mask, so padded (truncation-mode) batches are "
                "not supported"
            )

        # Compile-once subsystem (acco_tpu/compile). Persistent cache
        # first: every compile below this line — warmup or lazy — lands
        # in (or is served from) the cache, so a preemption-resume or
        # repeat launch of the same config compiles nothing. The config
        # files name outputs/compile_cache (resolved against the
        # checkout; $JAX_COMPILATION_CACHE_DIR wins over it). Without
        # the key — a trainer built in code — the cache configuration
        # is left as the caller set it.
        from acco_tpu.compile import setup_compilation_cache

        self.compile_cache_dir = setup_compilation_cache(
            _arg(args, "compile_cache_dir", ""), log=self.log
        )
        self.compile_report = None
        self._warmup = None
        try:
            self.warmup_compile = bool(_arg(args, "warmup_compile", True))
            if self.warmup_compile:
                # Parallel AOT warmup, started BEFORE the data section: the
                # seed/round programs lower + compile on background threads
                # (XLA releases the GIL) while the host tokenizes the corpus
                # and builds the loaders below — the compile minutes hide
                # under work the startup path pays anyway, instead of
                # serializing at first dispatch inside the timed loop.
                self._warmup = self._start_warmup()

            # Data: process-rank shard -> tokenize -> static-shape loaders.
            with self.tracer.span("setup/tokenize", cat="setup") as tokenized:
                n_proc, proc = jax.process_count(), jax.process_index()
                self.local_devices = self.world_size // n_proc
                self.train_dataset = self._tokenized(
                    shard_dataset(train_dataset, n_proc, proc) if n_proc > 1 else train_dataset
                )
                self.eval_dataset = (
                    self._tokenized(
                        shard_dataset(eval_dataset, n_proc, proc) if n_proc > 1 else eval_dataset
                    )
                    if eval_dataset is not None
                    else None
                )
                if self.const_len_batch or self.seq_axis:
                    # Catch data that bypasses the const_len_batch flag (e.g.
                    # pre-tokenized variable-length rows the loader would pad):
                    # collectively agreed so one process's bad shard fails every
                    # process together instead of deadlocking the others at the
                    # next collective. Not just CP: const_len_batch=True makes
                    # every train/eval program statically DROP its all-ones
                    # masks, so a padded row would become silently-attendable
                    # padding on any mesh.
                    self._check_const_len()
                self.train_loader = ShardedBatchIterator(
                    self.train_dataset,
                    batch_size=self.batch_size * self.local_devices,
                    max_length=self.max_length,
                    pad_token_id=int(getattr(tokenizer, "pad_token_id", 0) or 0),
                    shuffle=True,
                    seed=self.seed,
                )
                self.eval_loader = (
                    ShardedBatchIterator(
                        self.eval_dataset,
                        batch_size=self.batch_size * self.local_devices,
                        max_length=self.max_length,
                        pad_token_id=int(getattr(tokenizer, "pad_token_id", 0) or 0),
                        shuffle=False,
                        drop_last=False,
                    )
                    if self.eval_dataset is not None and len(self.eval_dataset) > 0
                    else None
                )
                tokenized["rows"] = len(self.train_dataset)

            # Observability (rank 0 writes, like the reference's rank gating).
            self.trace_path = os.path.join(
                self.run_dir, f"trace_{self.id_run}.json"
            )
            run_name = str(_arg(args, "run_name", self.method))
            # a span of its own inside setup/trainer_init: the making of the
            # run's event writer (utils/logs.EventWriter imports TensorBoard's
            # protos and never torch, whose import is 25-69 s of a launch); its
            # args say which writer the run got and which heavy packages the
            # process has loaded all the same (a Hugging Face tokenizer's torch)
            with self.tracer.span("setup/summary_writer", cat="setup") as made:
                self.writer = (
                    logs_utils.make_summary_writer(
                        os.path.join(self.run_dir, "tensorboard", run_name, self.id_run)
                    )
                    if self.rank == 0
                    else logs_utils.NoOpWriter()
                )
                made["writer"] = (
                    "events" if isinstance(self.writer, logs_utils.EventWriter)
                    else "noop"
                )
                made["heavy_modules"] = logs_utils.heavy_modules()
            self.ckpt_dir = os.path.join(self.run_dir, "checkpoints", run_name)
            self.checkpoint_every_s = float(_arg(args, "checkpoint_every_s", 1800))
            # Resilience (acco_tpu/resilience): overlapped async checkpointing
            # (the save blocks only for the device->host snapshot; commit +
            # retention run under the next rounds), startup GC of step dirs a
            # killed saver left uncommitted, and preemption-safe shutdown.
            self.ckpt_manager = CheckpointManager(
                self.ckpt_dir,
                async_save=bool(_arg(args, "ckpt_async", True)),
                keep_last=int(_arg(args, "ckpt_keep_last", 0)),
                keep_every_s=float(_arg(args, "ckpt_keep_every_s", 0.0)),
                rank=self.rank,
                log=self.log,
                tracer=self.tracer,
            )
            # Injected handler (tests: deterministic preemption); otherwise a
            # real SIGTERM/SIGINT latch, installed for the duration of train().
            self._shutdown = shutdown_handler
            self._handle_signals = bool(_arg(args, "handle_signals", True))
            # Multi-process: signal delivery is per-process, so the stop
            # decision is allgathered — at this round cadence, not every
            # round (a per-round host collective would serialize the async
            # dispatch pipeline the whole trainer is built around).
            self._preempt_sync_rounds = max(
                1, int(_arg(args, "preempt_sync_rounds", 8))
            )

            self._batch_shardings = {
                name: NamedSharding(self.mesh, spec)
                for name, spec in zip(BATCH_KEYS, batch_specs(DATA_AXIS, self.seq_axis))
            }
            self._eval_fn = None

            # The const-len verdict _check_const_len just decided is a
            # compile-relevant input (it statically drops the programs' pad
            # plumbing): if it downgraded after the optimistic warmup above
            # started, those programs are NOT the ones train() will run —
            # discard and restart with the real flag. The stale compiles
            # finish in the background; their only effect is unused
            # persistent-cache entries.
            if (
                self._warmup is not None
                and self._warmup.const_len != self.const_len_batch
            ):
                self.log.info(
                    "const-len verdict changed during data setup; restarting "
                    "compile warmup with const_len_batch=%s",
                    self.const_len_batch,
                )
                self._warmup.runner.close(wait=False)
                self._warmup = self._start_warmup()
            # Eval program warmup waits until here on purpose: it depends on
            # eval_const_len, decided by the data section above.
            if self._warmup is not None:
                self._submit_eval_warmup()
        except BaseException:
            # A failed constructor must not leave warmup threads queueing
            # new compiles (close cancels the unstarted ones; in-flight
            # XLA compiles are uncancellable and finish in the background).
            if self._warmup is not None:
                self._warmup.runner.close(wait=False)
            raise

    # -- data ---------------------------------------------------------------

    def _check_const_len(self) -> None:
        """Whenever masks are statically dropped (const_len_batch=True —
        the default — or context parallelism, whose sequence-sharded
        attention has no per-token mask), every row must be at least
        max_length: a row the loader would pad becomes
        silently-attendable padding. Multi-process: the verdict is
        allgathered so all processes raise together (a lone raise would
        strand the rest at a collective)."""

        def ok(dataset) -> bool:
            if dataset is None or len(dataset) == 0:
                # vacuously fine (e.g. a rank-sharded eval set with fewer
                # rows than processes leaves some shards empty)
                return True
            # Longer rows are truncated by the loader (no padding, CP-safe);
            # only shorter rows would be padded.
            if hasattr(dataset, "min_row_len"):
                # FlatTokenDataset: O(1)-ish vectorized min over the row
                # offsets — never iterate an OpenWebText-scale corpus in
                # Python at startup.
                return dataset.min_row_len() >= self.max_length
            try:
                # HF/Arrow datasets: vectorized list-length min — this
                # check now runs on EVERY const-len run (not just CP),
                # so an offline-pretokenized corpus must not be decoded
                # row by row in Python before step 0.
                import pyarrow.compute as pc

                col = dataset.data.column("input_ids")
                return (
                    int(pc.min(pc.list_value_length(col)).as_py())
                    >= self.max_length
                )
            except Exception:
                return all(
                    len(row["input_ids"]) >= self.max_length
                    for row in dataset
                )

        # PER-DATASET verdicts: ANDing train and eval
        # let a short-row eval set silently cost training its mask-free
        # programs and the banded GPT-Neo kernel. Both verdicts are
        # allgathered together so every process flips the same flags.
        local_verdict = np.asarray(
            [ok(self.train_dataset), ok(self.eval_dataset)], np.int32
        )
        world_verdict = local_verdict
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            world_verdict = np.min(
                multihost_utils.process_allgather(local_verdict), axis=0
            )
        train_ok, eval_ok = bool(world_verdict[0]), bool(world_verdict[1])
        if train_ok and eval_ok:
            return

        def detail(which: str) -> str:
            return (
                f"some process's {which} dataset has rows with input_ids "
                f"shorter than max_length ({self.max_length}), which the "
                "loader would pad — and the padding would be silently "
                "attendable because const-len programs drop their "
                "(assumed all-ones) masks"
            )

        failed = "train" if not train_ok else "eval"
        if self.seq_axis or self.pipeline_axis:
            # CP has no per-token mask at all; pp mandates const-len.
            # No mask-honoring program exists on these meshes: error
            # (for eval too — the CP/pp eval bodies share the maskless
            # attention path).
            raise ValueError(
                ("context parallelism requires"
                 if self.seq_axis
                 else "pipeline parallelism requires")
                + f" const-length rows: {detail(failed)}. Pack the data "
                "const-length (offline packing or the default "
                "tokenize path)"
            )
        # Dense meshes have mask-honoring programs — use them rather
        # than attend padding. Decided per dataset: a short-row eval
        # set downgrades eval only (every process reached the same
        # allgathered verdicts, so the flips are SPMD-uniform).
        if not train_ok:
            self.log.warning(
                "const_len_batch=True but %s; downgrading to "
                "const_len_batch=False so the real padding masks are "
                "honored (pad plumbing stays in the compiled programs)",
                detail("train"),
            )
            self.const_len_batch = False
        if not eval_ok and train_ok:
            self.log.warning(
                "const_len_batch=True but %s; eval runs with its padding "
                "masks honored while training keeps its mask-free "
                "const-len programs (pack the eval set const-length to "
                "drop eval's pad plumbing too)",
                detail("eval"),
            )
        # Strictly per dataset: eval's verdict stands alone — a short-row
        # TRAIN set must not cost a const-len-clean eval set its
        # mask-free program either (the mirror of the asymmetry above).
        self.eval_const_len = eval_ok

    def _tokenized(self, dataset):
        """Tokenize a 'text'-column dataset with the mode the config picks:
        const-len packing for pretraining, truncation for finetuning
        (`/root/reference/trainer_base.py:77-125`). Pass-through when the
        dataset already carries input_ids (offline pre-tokenization,
        `dl_dataset.py` parity)."""
        if dataset is None:
            return None
        cols = getattr(dataset, "column_names", None)
        if cols is not None and "input_ids" in cols:
            return self._maybe_flatten(dataset)
        if cols is None:  # plain list of dicts (tests) or already flat
            first = dataset[0] if len(dataset) else {}
            if "input_ids" in first:
                return self._maybe_flatten(dataset)
            raise ValueError("list datasets must already contain input_ids")
        if self.const_len_batch:
            packed = self._native_pack(dataset)
            if packed is not None:
                return packed
            fn = make_map_fn_const_len(self.tokenizer, self.max_length)
        else:
            fn = make_map_fn_truncate(self.tokenizer, self.max_length)
        return self._maybe_flatten(dataset.map(fn, batched=True, remove_columns=cols))

    def _native_pack(self, dataset):
        """const-len packing through the C++ kernel: tokenize once, EOS-join
        pack over the whole corpus (the map path packs per map-chunk and
        drops a remainder per chunk; this path drops one remainder total).
        Returns None to fall back to the dataset.map path."""
        if not bool(_arg(self.args, "native_data", True)):
            return None
        try:
            from acco_tpu.native import FlatTokenDataset

            # Tokenize in bounded chunks: one call over the whole corpus
            # materializes all text plus all encodings in host RAM at once;
            # chunking keeps peak memory at
            # O(chunk + flat tokens) while from_rows still packs globally.
            chunk = 4096
            enc: list = []
            for lo in range(0, len(dataset), chunk):
                # Slice the dataset, not a materialized column: HF datasets
                # load each slice from arrow, so peak RAM stays
                # O(chunk texts + flat tokens).
                rows = dataset[lo : lo + chunk]["text"]
                enc.extend(
                    self.tokenizer(list(rows), truncation=False)["input_ids"]
                )
            docs = FlatTokenDataset.from_rows(enc)
            packed = docs.pack_const_len(
                self.max_length, int(self.tokenizer.eos_token_id)
            )
            offsets = (
                np.arange(packed.shape[0] + 1, dtype=np.int64) * self.max_length
            )
            return FlatTokenDataset(packed.ravel(), offsets)
        except Exception as exc:
            self.log.warning("native packing unavailable (%s)", exc)
            return None

    def _maybe_flatten(self, dataset):
        """Convert to the flat-buffer layout the native C++ collate kernels
        operate on (acco_tpu/native). One pass at startup; per-round batch
        assembly then never enters the Python interpreter. Opt out with
        native_data=False; any failure falls back to the row-dict path."""
        if not bool(_arg(self.args, "native_data", True)):
            return dataset
        try:
            from acco_tpu.native import FlatTokenDataset

            return FlatTokenDataset.from_dataset(dataset)
        except Exception as exc:
            self.log.warning("native data path unavailable (%s)", exc)
            return dataset

    def _put_block(self, stacked: dict) -> dict:
        """Host microbatch block [n_acc, local_batch, L] -> global device
        arrays laid out over the mesh (single-process: device_put; multi-
        process: assemble from per-process shards)."""
        stacked = dict(stacked)
        stacked["valid"] = self._valid_block()
        out = {}
        for key, arr in stacked.items():
            sharding = self._batch_shardings[key]
            if jax.process_count() == 1:
                out[key] = jax.device_put(arr, sharding)
            else:
                out[key] = jax.make_array_from_process_local_data(sharding, arr)
        return out

    def _valid_block(self) -> np.ndarray:
        """Per-round microbatch validity [n_acc, local_dp_devices].

        All-ones normally; ``microbatch_mask`` (a [n_acc][world_size] 0/1
        nested list) emulates heterogeneous / slow workers — the
        reference's uneven per-worker accumulation counts
        (`/root/reference/trainer_decoupled.py:37,85-98`): masked
        microbatches still execute (SPMD shape uniformity) but contribute
        zero gradient and zero count, and the count-weighted averaging
        keeps the update unbiased.
        """
        mask = _arg(self.args, "microbatch_mask")
        if mask is None:
            return np.ones((self.n_acc, self.local_devices), np.float32)
        mask = np.asarray(mask, np.float32)
        if mask.shape != (self.n_acc, self.world_size):
            raise ValueError(
                f"microbatch_mask must be [n_grad_accumulation={self.n_acc}]"
                f"[world_size={self.world_size}], got {mask.shape}"
            )
        if mask.sum() == 0:
            raise ValueError("microbatch_mask masks out every microbatch")
        # slice this process's dp columns (single-process: all of them)
        start = jax.process_index() * self.local_devices
        return np.ascontiguousarray(mask[:, start : start + self.local_devices])

    # -- compile warmup (acco_tpu/compile) ----------------------------------

    def _start_warmup(self) -> Optional[_WarmupHandle]:
        """Kick off background AOT lower+compile of every program this
        run will dispatch, from abstract avals only (no state allocation
        — ``AccoTrainStep.abstract_state`` traces ``init_state`` through
        ``jax.eval_shape``). A failure here never fails training: the
        programs just compile lazily at first call, as before."""
        with self.tracer.span("setup/start_warmup", cat="setup") as started:
            handle = self._submit_warmup()
            if handle is not None:
                started["programs"] = handle.runner.submitted
        return handle

    def _submit_warmup(self) -> Optional[_WarmupHandle]:
        from acco_tpu.compile import CompileWarmup

        try:
            step = self._make_step(self.method)
            params_avals = (
                jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
                    self.initial_params,
                )
                if self.initial_params is not None
                else None
            )
            runner = CompileWarmup(log=self.log, tracer=self.tracer)
            step.warmup(
                self.n_acc,
                self.batch_size * self.world_size,
                self.max_length,
                params_avals=params_avals,
                seed=self.seed,
                # Seed only when this run will actually dispatch it: a
                # resumed run restores its buffers and never seeds, and
                # an ACCO run with warmup rounds seeds through a separate
                # DPU-mode step object (_train), not this program.
                include_seed=(
                    self.method in ("acco", "dpu")
                    and not _arg(self.args, "resume_from")
                    and not (
                        self.method == "acco"
                        and int(_arg(self.args, "n_warmup_steps", 0)) > 0
                    )
                ),
                runner=runner,
            )
            self.step_obj = step
            return _WarmupHandle(runner, step, self.const_len_batch)
        except Exception as exc:
            self.log.warning(
                "compile warmup unavailable (%s); programs will compile "
                "lazily at first call",
                exc,
            )
            return None

    def _submit_eval_warmup(self) -> None:
        """Add the eval program to the in-flight warmup (when this run
        will eval at all). Built here — after the data section — because
        the eval program's shape depends on the eval dataset's own
        const-len verdict."""
        # Mirror the train loop's gate exactly (eval AND a nonzero
        # eval_step AND an eval loader): a program the loop can never
        # dispatch must not be compiled.
        do_eval = (
            bool(_arg(self.args, "eval", False))
            and int(_arg(self.args, "eval_step", 0)) != 0
            and self.eval_loader is not None
        )
        if not do_eval or self._warmup is None:
            return
        with self.tracer.span("setup/start_warmup", cat="setup", programs=1):
            try:
                eval_fn = self._build_eval_fn()
                step = self._warmup.step
                # the flat-param placement comes from the step's sharding
                # rule table (acco_tpu/sharding) — same source as state_specs
                flat_aval = jax.ShapeDtypeStruct(
                    (step.tp * step.geom.padded_size,),
                    self.param_dtype,
                    sharding=NamedSharding(
                        self.mesh, step.rule_table().match("flat_params")
                    ),
                )
                row = NamedSharding(self.mesh, P(DATA_AXIS, self.seq_axis))
                batch_aval = jax.ShapeDtypeStruct(
                    (self.batch_size * self.world_size, self.max_length),
                    jnp.int32,
                    sharding=row,
                )
                self._warmup.runner.submit(
                    "eval", eval_fn, flat_aval, batch_aval, batch_aval, batch_aval
                )
                self._eval_fn = eval_fn
            except Exception as exc:
                self.log.warning("eval compile warmup skipped (%s)", exc)

    def join_warmup(self, timeout: Optional[float] = None):
        """Block until the background compile warmup finishes (no-op when
        none is running), log the per-program lower/compile timings and
        the persistent-cache hit/miss counters once, and return the
        :class:`acco_tpu.compile.WarmupReport` (also kept as
        ``self.compile_report``). Called by ``train()`` right before the
        first dispatch; tests and tools may call it directly."""
        if self._warmup is None:
            return self.compile_report
        report = self._warmup.runner.join(timeout=timeout)
        self.compile_report = report
        # Install/log only from a COMPLETE join: a timed-out join returns
        # a snapshot (programs still compiling in the background), and a
        # later join() must still get to install their executables.
        if report.complete and not self._warmup.logged:
            self._warmup.logged = True
            # Install the AOT executables: real dispatches then run them
            # DIRECTLY instead of re-entering jit's compile path (jax
            # keeps AOT and jit caches separate, so a jit call after
            # warmup would re-deserialize from the persistent cache —
            # wasted work; the AOT call touches no cache at dispatch
            # time).
            step = self._warmup.step
            for name, rec in report.programs.items():
                if not rec.ok or rec.compiled is None:
                    continue
                if name == "eval":
                    if self._eval_fn is not None:
                        from acco_tpu.compile import aot_call_with_fallback

                        self._eval_fn = aot_call_with_fallback(
                            rec.compiled, self._eval_fn, "eval", log=self.log
                        )
                else:
                    step.compiled_programs[name] = rec.compiled
            for line in report.log_lines():
                self.log.info("%s", line)
            failed = [n for n, r in report.programs.items() if not r.ok]
            if failed:
                self.log.warning(
                    "compile warmup failed for %s; those programs will "
                    "compile lazily at first call",
                    failed,
                )
        return report

    # -- train --------------------------------------------------------------

    def _make_step(self, mode: str):
        opt_kw = dict(
            weight_decay=float(_arg(self.args, "weight_decay", 0.0)),
            beta1=float(_arg(self.args, "adam_beta1", 0.9)),
            beta2=float(_arg(self.args, "adam_beta2", 0.999)),
            label_smoothing=self.label_smoothing,
            param_dtype=self.param_dtype,
            lr_grad_accounting=bool(_arg(self.args, "lr_grad_accounting", False)),
            seq_axis=self.seq_axis,
            comm_impl=self.comm_impl,
            fused_loss=self.fused_loss,
            tensor_axis=self.tensor_axis,
            pipeline_axis=self.pipeline_axis,
            # const-len packed data carries all-ones masks by contract;
            # telling the step statically skips the kernels' pad
            # plumbing (and enables GPT-Neo's banded window kernel)
            const_len_batch=self.const_len_batch,
            # in-program anomaly guard (the watchdog's on-device half);
            # compile-relevant: nan_guard=False compiles the health
            # signals and guard selects out entirely
            nan_guard=self.nan_guard,
            guard_max_grad_norm=self.guard_max_grad_norm,
        )
        if mode == "ddp":
            return DDPTrainStep(self.model, self.mesh, self.schedule, **opt_kw)
        return AccoTrainStep(self.model, self.mesh, self.schedule, mode=mode, **opt_kw)

    def train(self) -> dict:
        """Run the configured method to ``nb_steps_tot`` total gradients.

        Dispatch parity: `/root/reference/trainer_decoupled.py:418-429`.
        Returns a summary dict (final loss, counts, wall time) and appends
        the results.csv ledger row.
        """
        self._block_source = None
        from acco_tpu.compile import trace_compiles

        trace_compiles(self.tracer)  # again: a second train() of one trainer
        own_handler = False
        if self._shutdown is None and self._handle_signals:
            # auto-created per train() call and discarded after: a latch
            # consumed by this run must not instantly stop a later one
            self._shutdown = ShutdownHandler(log=self.log)
            own_handler = True
        # handle_signals=False keeps an injected handler a pure
        # request()-driven latch too: an embedding app that owns its
        # signal sequencing must not have its handlers displaced.
        installed = (
            self._shutdown.install()
            if self._shutdown is not None and self._handle_signals
            else False
        )
        try:
            return self._train()
        finally:
            # The prefetch worker must never outlive the trainer (or
            # deadlock blocked on its full queue): close on every exit
            # path, error paths included.
            if self._block_source is not None:
                self._block_source.close()
                self._block_source = None
            # Drain the in-flight async checkpoint on every exit path
            # (error paths included — close logs instead of raising so
            # the original exception is never masked); the happy path
            # already waited and surfaced errors inside _train.
            self.ckpt_manager.close()
            # Release the warmup pool's threads on error exits too (the
            # happy path joined before the first dispatch; in-flight
            # compiles finish in the background and only warm the cache).
            if self._warmup is not None:
                self._warmup.runner.close(wait=False)
            # the trace is written: later compiles of the process (an
            # evaluation, another trainer's) are not this run's
            trace_compiles(None)
            if installed:
                self._shutdown.uninstall()
            if own_handler:
                self._shutdown = None

    def _train(self) -> dict:
        t_beg = time.time()
        # Telemetry for this run: the span tracer (rank-0, Perfetto
        # trace.json at the end).
        tracer = self.tracer
        # Reuse the warmup's step object: its memoized round programs are
        # the ones the background threads compiled.
        step = (
            self._warmup.step
            if self._warmup is not None
            else self._make_step(self.method)
        )
        self.step_obj = step
        with tracer.span("setup/state_init", cat="setup"):
            if self.initial_params is not None:
                params = self.initial_params
            elif self.tensor_axis is not None or self.pipeline_axis is not None:
                # tp/pp exist for models whose full parameters exceed one
                # chip's HBM — initialize on the host CPU backend, where
                # init_state's per-shard staging (TpLayout.init_sharded_state)
                # picks them up without any full-size device transient.
                # local_devices: in a multi-process world jax.devices()[0]
                # belongs to process 0 — every process must init on its OWN
                # host device or the implicit transfer deadlocks.
                with jax.default_device(jax.local_devices(backend="cpu")[0]):
                    params = self.model.init(jax.random.PRNGKey(self.seed))
            else:
                params = self.model.init(jax.random.PRNGKey(self.seed))
            state = step.init_state(params)
            # the state holds its own copies: the leaves would stay on the
            # device for the whole run (2 B a parameter: 1.2 GiB of a 626M model)
            del params
            # a static fact of the layout, so one number a run: the share of the
            # flat vector that unpack takes out as a bitcast (0 under tp / pp,
            # whose layouts are row-major)
            flat_bitcast_share = step.layout.bitcast_share if step.layout else 0.0
            metrics.emit("train_flat_bitcast_share", flat_bitcast_share)
            self.log.info(
                "flat vector: layout %s, %d elements, flat_bitcast_share=%.6f",
                flat_layout_tag(step), step.geom.n_params, flat_bitcast_share,
            )

        # Join the background AOT warmup (started at construction and
        # overlapped with tokenize / loader setup / state init above):
        # past this line every program this run dispatches holds its
        # compiled executable, installed for direct AOT dispatch.
        with tracer.span("compile/warmup_join", cat="compile") as joined:
            report = self.join_warmup()
            if report is not None:
                joined.update(
                    hits=report.cache.get("hits", 0),
                    misses=report.cache.get("misses", 0),
                )
            # where the evictions happen: a miss in a cache that stands
            # at its cap may be another launch's eviction (ROADMAP S7)
            from acco_tpu.compile import cache_dir_usage

            cache_bytes, cache_cap = cache_dir_usage()
            if cache_bytes is not None:
                metrics.emit("compile_cache_dir_bytes", cache_bytes)
                joined["cache_dir_bytes"] = cache_bytes
            if cache_cap is not None:
                metrics.emit("compile_cache_max_bytes", cache_cap)
                joined["cache_max_bytes"] = cache_cap

        # Resume (framework improvement over the reference's save-only).
        meta = {"count_grad_tot": 0, "rounds_done": 0, "elapsed_s": 0.0}
        resume_from = _arg(self.args, "resume_from")
        if resume_from:
            with tracer.span("setup/restore", cat="setup") as restored:
                path = (
                    resume_from
                    if os.path.basename(resume_from).startswith("step_")
                    else latest_checkpoint(resume_from, log=self.log)
                )
                if path is None:
                    raise FileNotFoundError(f"No checkpoint under {resume_from!r}")
                if os.path.basename(resume_from).startswith("step_"):
                    from acco_tpu.utils.checkpoint import validate_checkpoint

                    reason = validate_checkpoint(path)
                    if reason is not None:
                        raise ValueError(
                            f"explicitly requested checkpoint {path!r} is not "
                            f"restorable ({reason}); point resume_from at the "
                            "checkpoint ROOT to fall back to the newest "
                            "complete step instead"
                        )
                state, meta = restore_flat_state(path, state, step, log=self.log)
                self.log.info(
                    "Resumed from %s at %d grads", path, meta["count_grad_tot"]
                )
                restored["path"] = path
        count_grad_tot = float(meta["count_grad_tot"])
        rounds_done = int(meta["rounds_done"])
        if "loader" in meta:
            # Exact data-iterator resume (SURVEY §5): the checkpoint carries
            # (epoch, batch_pos); the shuffle order is a pure function of
            # seed+epoch, so the resumed run consumes exactly the batch
            # sequence an uninterrupted run would have. The state is valid
            # on every rank: ranks hold different shards but share the
            # seed ladder and consume in lockstep.
            self.train_loader.set_state(meta["loader"])
        elif resume_from:
            # Legacy checkpoints (no loader state): fast-forward the epoch
            # seed so the run doesn't replay epoch-0 order; position within
            # the epoch is approximated to the boundary.
            self.train_loader.epoch = (rounds_done * self.n_acc) // max(
                len(self.train_loader), 1
            )

        # setup/seed: the block source (its worker starts collating), the
        # seed program or the DPU warm-up rounds, and the two device_gets
        # that WAIT for them: the run's first device work ends at those,
        # not at the dispatch.
        with tracer.span("setup/seed", cat="setup", rounds=0) as seeded:
            # Input pipeline: a PrefetchingBlockSource collates + transfers
            # round N+1's block on a worker thread while round N's compiled
            # program executes (prefetch=False runs the same interface
            # synchronously). Created AFTER the resume restore above so the
            # worker starts from the restored position.
            source = PrefetchingBlockSource(
                self.train_loader,
                self.n_acc,
                self._put_block,
                depth=self.prefetch_depth,
                prefetch=self.prefetch,
            )
            self._block_source = source
            # Valid micro-grads contributed per half-round: the microbatch_mask
            # sum under heterogeneous workers, ws*n_acc otherwise. This host
            # mirror of the device-side count drives the termination check
            # without a per-round device sync; the authoritative count is the
            # state's grads_committed counter, reconciled at every logging /
            # eval boundary (bookkeeping that hardcodes ws*n_acc inflates
            # progress under a mask).
            mask = _arg(self.args, "microbatch_mask")
            grads_per_round = (
                float(np.asarray(mask, np.float32).sum())
                if mask is not None
                else float(self.world_size * self.n_acc)
            )

            if self.method in ("acco", "dpu") and rounds_done == 0:
                # ACCO warmup parity (`trainer_decoupled.py:436-438,318-383`):
                # n_warmup_steps sequential real-update rounds — i.e. DPU rounds
                # — before the decoupled regime takes over.
                n_warmup = int(_arg(self.args, "n_warmup_steps", 0))
                if self.method == "acco" and n_warmup > 0:
                    warm = self._make_step("dpu")
                    # the warm step reuses the main step's resolved layout —
                    # including tp_layout, whose n_repl drives the replicated-
                    # prefix gradient psum under tensor parallelism
                    warm.geom, warm.unravel = step.geom, step.unravel
                    warm.layout = step.layout
                    warm.tp_layout = step.tp_layout
                    state, _ = warm.seed_fn()(state, source.next_block())
                    warm_round = warm.round_fn()
                    for _ in range(n_warmup):
                        state, _ = warm_round(state, source.next_block())
                        count_grad_tot += grads_per_round
                    seeded["rounds"] = n_warmup
                    # Hand over mid-stream: round 0 (even) consumes the staged
                    # pending grads speculatively AND — because even ACCO
                    # rounds read ``pending_grads`` as their accumulator
                    # carry-in — folds them into round 1's *real* update too:
                    # the reference's count_after_init=-2 post-warmup carry
                    # (`trainer_decoupled.py:359-383,441`), without which the
                    # last warmup round's gradients would be dropped.
                    state = state._replace(round_idx=jnp.zeros((), jnp.int32))
                else:
                    state, _ = step.program_callable("seed", log=self.log)(
                        state, source.next_block()
                    )
            elif self.method in ("acco", "dpu"):
                pass  # resumed: buffers restored, no seed
            # Dispatch through program_callable: the AOT executables the
            # warmup installed run directly (no jit-path cache interaction
            # per dispatch); without a warmup these are the plain jit fns.
            if self.method == "acco":
                # Parity-specialized round programs: the host knows the round
                # parity, so the speculative-rollback/zeroing selects over the
                # full flat vectors constant-fold out of each program.
                round_programs = ("round_even", "round_odd")
            elif self.method == "dpu":
                round_programs = ("round",)
            else:
                round_programs = ("step",)
            round_fns = {
                name: step.program_callable(name, log=self.log)
                for name in round_programs
            }

            # Count bookkeeping: DDP/DPU commit one round's valid grads per
            # round; ACCO commits two half-rounds every odd round
            # (`trainer_decoupled.py:501-502,763`). ACCO round parity is
            # tracked host-side from the state's round_idx (one device sync
            # here, none per round; warmup resets it, resume restores it).
            round_idx_host = (
                int(jax.device_get(state.round_idx))
                if self.method in ("acco", "dpu")
                else 0
            )
            first_metrics = last_metrics = None
            # Host half of the watchdog, fresh per train(): fed at the
            # logging boundary (piggybacking the existing device fetch), it
            # classifies spikes vs drift and escalates K consecutive guard-
            # skipped rounds into the auto-rollback below.
            self._health_monitor = TrainingHealthMonitor(
                escalate_after=self.rollback_after_skipped, log=self.log
            )
            if self.nan_guard:
                # A resumed state carries its lifetime skip counter; without
                # this anchor the monitor's first boundary would read the
                # whole history as "new skips this run" and misclassify a
                # healthy resume as anomalous (same re-anchor _rollback does
                # after its restore).
                self._health_monitor.last_skipped_rounds = int(
                    jax.device_get(state.health.skipped_rounds)
                )
        self._rollbacks = 0
        self._last_consec_skipped = 0
        injector = self.fault_injector
        nb_com = 0
        log_epoch = 0
        t_last_epoch = time.time()
        t_last_ckpt = time.time()
        eval_mark = count_grad_tot
        final_loss = float("nan")
        eval_every = int(_arg(self.args, "eval_step", 0))
        do_eval = bool(_arg(self.args, "eval", False)) and self.eval_loader is not None
        do_save = bool(_arg(self.args, "save", False))

        # Profiling hooks (SURVEY §5; reference has only wall-clock
        # timers): train.profile_steps=N captures a jax.profiler trace of
        # N steady-state rounds under <run_dir>/profile, starting after
        # every round program has compiled (ACCO runs TWO
        # parity-specialized programs, so its first two rounds are
        # compile rounds) — inspect with TensorBoard or xprof to see the
        # async collectives of the comm branch overlapping the fwd/bwd
        # (tools/overlap_hlo.py is the structural version of this check).
        profile_steps = int(_arg(self.args, "profile_steps", 0))
        profile_after = 2 if self.method == "acco" else 1
        profile_dir = os.path.join(self.run_dir, "profile")
        profiling = False
        profiled_rounds = None  # [first, last] round inside the capture
        profiled_programs: list[str] = []  # the program of each such round
        scope_table_path = None
        if profile_steps:
            with tracer.span("setup/scope_table", cat="setup"):
                scope_table_path = self._write_scope_table(step, round_programs)
        t_last_round = time.time()
        round_wall_ms: list[float] = []
        rounds_this_run = 0  # run-local: resume restores rounds_done > 0
        interrupted = False
        last_round_end_us = None  # tracer-clock end of the previous round
        # Construction to first dispatch: tokenisation, state init, the
        # compile warmup's join, the resume restore.
        setup_s = time.time() - self._t_construct
        # The same by phase, from the tracer's set-up spans (empty where
        # telemetry is off): the summary's, and ONE line in every run's
        # log, since the untraced runs are where a median setup_s comes
        # from and their output is all there is of them.
        setup_events = tracer.events()
        setup_phases_s = setup_phases(setup_events)
        if setup_phases_s and self.rank == 0:
            self.log.info(
                "%s", _setup_line(tracer.now_us() / 1e6, setup_events)
            )

        while True:
            if count_grad_tot >= self.nb_grad_tot:
                # The host-side count is optimistic: it assumes every
                # dispatched round committed. Guard-skipped rounds are
                # reconciled away at logging boundaries, but skips
                # between the LAST boundary and the target would
                # otherwise end the run short — reconcile once against
                # the device counter before declaring done (a single
                # blocking fetch at the exit crossing, not per round).
                if self.nan_guard and rounds_this_run > 0:
                    committed = float(
                        jax.device_get(state.zero1.grads_committed)  # lint: host-sync-ok
                    )
                    if committed >= self.nb_grad_tot:
                        break
                    self.log.info(
                        "exit check: %d grads committed < %d target "
                        "(guard-skipped rounds since the last logging "
                        "boundary) — continuing",
                        int(committed), int(self.nb_grad_tot),
                    )
                    count_grad_tot = committed
                else:
                    break
            if (
                profile_steps
                and rounds_this_run == profile_after
                and self.rank == 0
                and not profiling
            ):
                ts_profile = tracer.now_us()
                jax.block_until_ready(state)  # compile round fully done
                jax.profiler.start_trace(profile_dir)
                # after the fact and not a span: an annotation would put
                # a new name on the capture's host plane
                tracer.complete_event(
                    "train/profile_start", (tracer.now_us() - ts_profile) / 1e3,
                    cat="train", ts_us=ts_profile,
                )
                profiling = True
                profiled_rounds = [rounds_done + 1, rounds_done + 1]
            program_name = round_programs[round_idx_host % len(round_programs)]
            fn = round_fns[program_name]
            # The loop's host time is tiled by spans: loader/next_block,
            # train/dispatch and, at a boundary, train/log_boundary_sync
            # then train/log_boundary_host — so every idle gap of the
            # device in a captured profile lies under a name.
            ts_round = tracer.now_us()
            with tracer.span("loader/next_block", cat="train"):
                block = source.next_block()
            with tracer.span("train/dispatch", cat="train"):
                if injector is not None and injector.pending:
                    # Chaos drill (fault_injection: in the config): poison
                    # the inputs/carried state between dispatches — the
                    # compiled programs are untouched, so the guard sees
                    # exactly what a real anomaly would produce.
                    state, block = injector.apply(
                        rounds_this_run, state, block
                    )
                state, last_metrics = fn(state, block)
            if first_metrics is None:
                first_metrics = last_metrics
            rounds_done += 1
            rounds_this_run += 1
            nb_com += 1
            # Wall time between dispatches: converges to the true round
            # time in steady state (the dispatch queue backpressures) with
            # no per-round device sync — the role of the reference's
            # per-grad timing lists (`utils/logs_utils.py:248-259`).
            now = time.time()
            wall_ms = (now - t_last_round) * 1e3
            round_wall_ms.append(wall_ms)
            t_last_round = now
            metrics.emit("train_rounds_total", 1)
            if tracer.enabled:
                end_us = tracer.now_us()
                # the round span tiles the tracer clock edge-to-edge
                # (previous round end -> this dispatch end), so boundary
                # work recorded in between nests inside it
                start_us = (
                    last_round_end_us
                    if last_round_end_us is not None
                    else ts_round
                )
                tracer.complete_event(
                    "train/round", (end_us - start_us) / 1e3,
                    cat="train", ts_us=start_us,
                    args={"round": rounds_done},
                )
                last_round_end_us = end_us
            if profiling:
                profiled_rounds[1] = rounds_done
                profiled_programs.append(program_name)
            if profiling and rounds_this_run >= profile_after + profile_steps:
                self._stop_profile(state)
                profiling = False
                self.log.info("profiler trace written to %s", profile_dir)
            if self.method in ("ddp", "dpu"):
                count_grad_tot += grads_per_round
            else:  # acco: real updates land on odd round_idx
                if round_idx_host % 2 == 1:
                    count_grad_tot += 2 * grads_per_round
                round_idx_host += 1

            # Lazy metric materialization at the logging cadence only.
            nb_grad_local = rounds_done * self.n_acc
            if nb_grad_local // self.delta_step_for_log > log_epoch:
                # Reconcile against the device-side committed-grad counter
                # (exact under heterogeneous masks) — one lazy read at the
                # logging cadence; dispatch stays async between boundaries.
                # The watchdog's health counters, the loss and the grad
                # norm ride the SAME fetch: one fence, no other blocking
                # device read at the boundary, and everything the boundary
                # learns is on its span.
                with tracer.span(
                    "train/log_boundary_sync", cat="train", round=rounds_done
                ) as fence:
                    t_sync = time.perf_counter()
                    (
                        committed, health_host, loss_host, grad_norm_host,
                        terms_host,
                    ) = jax.device_get(  # lint: host-sync-ok
                        (
                            state.zero1.grads_committed,
                            state.health,
                            last_metrics.loss,
                            last_metrics.grad_norm,
                            last_metrics.terms,
                        )
                    )
                    sync_ms = (time.perf_counter() - t_sync) * 1e3
                    final_loss = float(loss_host)
                    grad_norm = float(grad_norm_host)
                    # the objective's auxiliary terms, by the model's names
                    # (none for a model whose loss is the cross-entropy)
                    loss_terms = {k: float(v) for k, v in terms_host.items()}
                    fence.update(
                        loss=final_loss,
                        grad_norm=grad_norm,
                        committed=float(committed),
                        skipped_rounds=int(health_host.skipped_rounds),
                        **loss_terms,
                    )
                # From the fence's return to the point where the loop goes
                # back for the next block: the host work of the boundary.
                with tracer.span("train/log_boundary_host", cat="train"):
                    metrics.emit("train_log_sync_ms", sync_ms)
                    count_grad_tot = float(committed)
                    metrics.emit("train_loss", final_loss)
                    metrics.emit("train_grads_committed", float(committed))
                    for name, value in loss_terms.items():
                        metrics.emit("train_" + name, value)
                    log_epoch, t_last_epoch = logs_utils.print_training_evolution(
                        self.log,
                        nb_grad_local,
                        nb_com,
                        self.delta_step_for_log,
                        self.rank,
                        t_beg,
                        t_last_epoch,
                        final_loss,
                        log_epoch,
                    )
                    logs_utils.log_to_tensorboard(
                        self.writer,
                        nb_step=int(count_grad_tot),
                        nb_samples=int(count_grad_tot) * self.batch_size,
                        rank=self.rank,
                        loss=final_loss,
                        eval_loss=None,
                        t0=t_beg,
                        delta_step_for_log=1,
                        epoch=-1,
                    )
                    if self.nan_guard:
                        self._last_consec_skipped = int(health_host.consec_skipped)
                        metrics.emit("train_grad_norm", grad_norm)
                        verdict = self._health_monitor.observe(
                            grad_norm=grad_norm,
                            loss=final_loss,
                            skipped_rounds=int(health_host.skipped_rounds),
                            consec_skipped=int(health_host.consec_skipped),
                        )
                        logs_utils.log_health_to_tensorboard(
                            self.writer,
                            nb_step=int(count_grad_tot),
                            grad_norm=grad_norm,
                            skipped_rounds=int(health_host.skipped_rounds),
                            consec_skipped=int(health_host.consec_skipped),
                            rollbacks=self._rollbacks,
                        )
                        if verdict.escalate:
                            if not self.rollback_enabled:
                                # Abort rather than continue: every round is
                                # guard-skipped, and each boundary reconciles
                                # count_grad_tot back to the frozen device
                                # counter — the loop's exit condition can
                                # never be met, so "keep going" means
                                # spinning on no-op rounds forever.
                                raise RuntimeError(
                                    f"watchdog: "
                                    f"{int(health_host.consec_skipped)} "
                                    "consecutive anomalous rounds and "
                                    "rollback=False — aborting (the guard "
                                    "froze params/optimizer at the last "
                                    "healthy commit; checkpoints on disk "
                                    "are unchanged)"
                                )
                            else:
                                state, source, rb_meta = self._rollback(
                                    state, source
                                )
                                count_grad_tot = float(rb_meta["count_grad_tot"])
                                rounds_done = int(rb_meta["rounds_done"])
                                eval_mark = count_grad_tot
                                if self.method in ("acco", "dpu"):
                                    round_idx_host = int(
                                        jax.device_get(state.round_idx)  # lint: host-sync-ok
                                    )
                                # re-anchor the log cadence to the restored
                                # round count — otherwise health checks pause
                                # until the run re-passes the old boundary
                                log_epoch = (
                                    rounds_done * self.n_acc
                                ) // self.delta_step_for_log
                                continue

            # Eval cadence is grad-count based, independent of log cadence
            # (reference: every eval_step grads, trainer_decoupled.py:525-531).
            if do_eval and eval_every and count_grad_tot - eval_mark >= eval_every:
                eval_mark = count_grad_tot
                t_ev = time.perf_counter()
                with tracer.span("train/eval", cat="train"):
                    eval_loss = self.evaluate(state.flat_params)
                metrics.emit(
                    "train_eval_ms", (time.perf_counter() - t_ev) * 1e3
                )
                final_loss = float(last_metrics.loss)
                self.log.info(
                    "eval loss %.4f at %d grads", eval_loss, int(count_grad_tot)
                )
                logs_utils.log_to_tensorboard(
                    self.writer,
                    nb_step=int(count_grad_tot),
                    nb_samples=int(count_grad_tot) * self.batch_size,
                    rank=self.rank,
                    loss=final_loss,
                    eval_loss=eval_loss,
                    t0=t_beg,
                    delta_step_for_log=1,
                    epoch=-1,
                )

            # All processes enter _save: the Orbax save of a multi-host
            # sharded array is a collective (every process writes its
            # addressable shards); only the side files are rank-0-gated.
            # The *decision* must also be collective — per-process wall
            # clocks disagree, and one process entering the save while
            # another dispatches the next round would deadlock both.
            if do_save and self._ckpt_due(time.time() - t_last_ckpt):
                t_last_ckpt = time.time()
                if self._last_consec_skipped > 0:
                    # Health gate: the state is mid-anomaly. The host
                    # cannot tell a transient skip (state held bit-exact
                    # and healthy) from fresh persistent corruption
                    # (e.g. a poisoned master shard — the state itself
                    # is bad even though frozen), and saving the latter
                    # would put a poisoned checkpoint on disk as the
                    # NEWEST one: the restore chain prefers it, and
                    # retention GC may delete the good one behind it —
                    # exactly the state the escalation path needs. Skip
                    # this period; a healthy boundary resumes saving.
                    # (The verdict is the latest boundary's — replicated
                    # device scalars, so every process gates together.)
                    self.log.warning(
                        "periodic checkpoint skipped: state is anomalous "
                        "(%d consecutive guard-skipped rounds)",
                        self._last_consec_skipped,
                    )
                else:
                    # export_npz=False: the portable params.npz needs a
                    # full dense float32 gather on the train loop (host
                    # traffic ~4 bytes/param — GBs for the large
                    # configs), which would dominate the round-boundary
                    # stall the async save just removed. Periodic
                    # checkpoints carry the Orbax state only; the
                    # final/preemption save below writes the npz.
                    self._save(state, count_grad_tot, rounds_done, t_beg,
                               export_npz=False)

            # Preemption-safe shutdown (resilience/preemption.py): a
            # SIGTERM/SIGINT latched since the last boundary stops the
            # loop HERE — between rounds, never mid-dispatch — and falls
            # through to the normal end-of-train path: final checkpoint,
            # prefetcher close, async-save drain, results row. The
            # preemption becomes a resumable event instead of a corpse.
            if self._preempted(rounds_this_run):
                interrupted = True
                self.log.warning(
                    "shutdown requested: stopping at round boundary "
                    "(%d grads done) and checkpointing%s",
                    int(count_grad_tot),
                    "" if do_save else " — save=False, so NOT saving",
                )
                break

        if profiling:  # nb_grad_tot reached before profile_steps rounds
            self._stop_profile(state)
        health_final = (
            jax.device_get(state.health) if self.nan_guard else None
        )
        if last_metrics is not None:
            final_loss = float(last_metrics.loss)
            # Authoritative final count from the device-side counter.
            count_grad_tot = float(jax.device_get(state.zero1.grads_committed))
        total_time = time.time() - t_beg
        if do_save:
            if (
                health_final is not None
                and int(health_final.consec_skipped) > 0
                and latest_checkpoint(self.ckpt_dir) is not None
            ):
                # Same health gate as the periodic save: a run ending
                # mid-anomaly may hold fresh persistent corruption the
                # host cannot distinguish from a transient skip, and a
                # final save would supersede the newest complete
                # checkpoint as the restore chain's first choice
                # (retention GC may then delete it) — trading bounded
                # work loss (one periodic-save interval) for guaranteed
                # recoverability. Only when such a
                # checkpoint EXISTS, though — with nothing on disk (a
                # preemption before the first periodic save), skipping
                # the only save this run would ever write loses all
                # progress, and the guarded state is safe to keep: the
                # guard held params/opt bit-exact at the last healthy
                # commit, and a poisoned pending carry is fenced by
                # pending_ok on resume.
                self.log.warning(
                    "final checkpoint skipped: state is anomalous "
                    "(%d consecutive guard-skipped rounds); the newest "
                    "complete checkpoint is preserved for recovery",
                    int(health_final.consec_skipped),
                )
            else:
                if health_final is not None and int(health_final.consec_skipped) > 0:
                    self.log.warning(
                        "final checkpoint saved DESPITE %d consecutive "
                        "guard-skipped rounds: nothing is on disk yet, "
                        "and skipping the only save would lose all "
                        "progress (guard-refused anomalies leave "
                        "params/optimizer at their last healthy commit)",
                        int(health_final.consec_skipped),
                    )
                self._save(state, count_grad_tot, rounds_done, t_beg)
        # Drain the in-flight async commit before declaring the run over
        # (and surface its failure HERE, on the train loop): on a
        # preemption this is the "checkpoint is durable before we die"
        # guarantee; on a normal finish it keeps the old synchronous
        # contract that train() returning means the state is on disk.
        self.ckpt_manager.wait()
        # Health columns join the existing metrics/CSV path: monitor
        # counters + the device-side skip totals.
        health_row = (
            self._health_monitor.summary()
            if self._health_monitor is not None
            else {}
        )
        if health_final is not None:
            health_row["skipped_rounds"] = int(health_final.skipped_rounds)
        health_row["rollbacks"] = self._rollbacks
        if self.rank == 0:
            self._write_results(final_loss, total_time, extra=health_row)
            # Lists pair 1:1 per round executed IN THIS RUN (a resumed
            # run's earlier rounds have no wall times here).
            logs_utils.save_grad_acc(
                self.id_run,
                self.run_dir,
                self.rank,
                list_grad_acc=[self.n_acc] * len(round_wall_ms),
                list_grad_times=[round(t, 2) for t in round_wall_ms],
            )
        if tracer.enabled:
            try:
                tracer.write(
                    self.trace_path,
                    other_data={
                        "method": self.method,
                        "world_size": self.world_size,
                        "id_run": self.id_run,
                        # where a reader finds the device's side of the
                        # same clock: the jax.profiler capture (None
                        # without train.profile_steps), the rounds inside
                        # it, and the scope names its ops carry
                        "profile_dir": (
                            profile_dir if profiled_rounds else None
                        ),
                        "profiled_rounds": profiled_rounds,
                        "device_scopes": list(DECLARED_DEVICE_SCOPES),
                        # the capture names an op by its instruction, not
                        # by its scope: {program: {instruction: scope}},
                        # and the program each captured round ran
                        "scope_table": scope_table_path,
                        "profiled_programs": profiled_programs,
                    },
                )
                self.log.info("telemetry trace -> %s", self.trace_path)
            except OSError as exc:
                self.log.warning("trace write failed: %s", exc)
        self.writer.flush()
        self.final_state = state
        self.step_obj = step
        return {
            # loss of this run's first round (kept as a device scalar
            # until here: no sync is added to the loop) and of its last
            "first_loss": (
                float(first_metrics.loss)
                if first_metrics is not None
                else float("nan")
            ),
            "final_loss": final_loss,
            "count_grad_tot": int(count_grad_tot),
            "rounds": rounds_done,
            "setup_s": setup_s,
            # {phase: seconds} over the tracer's set-up spans, main.run's
            # included when it made the tracer (telemetry.SETUP_SPANS;
            # start_warmup, tokenize and summary_writer lie inside
            # trainer_init)
            "setup": setup_phases_s,
            "total_time_s": total_time,
            "method": self.method,
            # True = stopped by a shutdown request (preemption/SIGTERM)
            # before nb_steps_tot; the final checkpoint above makes it
            # resumable via train.resume_from.
            "interrupted": interrupted,
            # Watchdog counters: rounds the in-program guard turned into
            # bit-exact no-ops, and auto-rollbacks performed.
            "skipped_rounds": (
                int(health_final.skipped_rounds)
                if health_final is not None
                else 0
            ),
            "rollbacks": self._rollbacks,
            "flat_bitcast_share": flat_bitcast_share,
        }

    def _stop_profile(self, state) -> None:
        """End the capture once the traced rounds have finished; what that
        cost the loop is a ``train/profile_stop`` event written after the
        fact (an annotation would put a new name on the capture's host
        plane, under which the device's idle time would then be filed)."""
        ts = self.tracer.now_us()
        jax.block_until_ready(state)
        jax.profiler.stop_trace()
        self.tracer.complete_event(
            "train/profile_stop", (self.tracer.now_us() - ts) / 1e3,
            cat="train", ts_us=ts,
        )

    # -- eval ---------------------------------------------------------------

    def _build_eval_fn(self):
        """Build the compiled eval program for the active mesh (dense /
        CP / tp / pp bodies share the label-alignment and masked-mean
        conventions of the train paths). Extracted from ``evaluate()``
        so the AOT warmup (``_submit_eval_warmup``) can compile it at
        construction, overlapped with startup, instead of at the first
        eval boundary inside the timed loop."""
        model, n_params = self.model, self.step_obj.geom.n_params
        unravel = self.step_obj.unravel
        tp_axis = self.tensor_axis
        pp_axis = self.pipeline_axis
        # model_axis: tp, pp, or the (pp, tp) tuple under composition
        model_axis = self.step_obj.model_axis
        flat_spec = P(model_axis) if model_axis else P()

        def wrap_cp_prep(sharded_body, seq_axis_):
            """jit wrapper shared by the CP and pp x sp eval paths:
            next-token-align the labels on the GLOBAL sequence (and
            zig-zag reorder) before the shard_map — one copy, so the
            two paths can never drift."""

            @jax.jit
            def eval_fn(flat, ids, am, labels):
                if seq_axis_ is not None:
                    from acco_tpu.parallel.common import prep_cp_leaves

                    ids, am, labels = prep_cp_leaves(
                        ids, am, labels, seq_axis_, self.mesh, model
                    )
                return sharded_body(flat, ids, am, labels)

            return eval_fn
        from acco_tpu.ops.losses import real_vocab_of

        real_vocab = real_vocab_of(model)

        if pp_axis is not None:
            # pp eval: each stage holds only its layers, so the model
            # runs through the same pipeline loop as training. The
            # eval batch is split into M microbatches (the largest
            # divisor of the local batch <= pp) so the pipeline
            # fills instead of paying the full (pp-1)/pp bubble per
            # batch at M=1. Setting each microbatch's ``valid``
            # weight to its token count turns the loss fn's
            # valid-weighted mean sum directly into the nll sum, so
            # the global token-weighted mean stays exact under any
            # label mask. Composes with sp (chunks + pre-shifted
            # labels, the CP eval convention) — the pipelined loss
            # fn already returns per-shard partials under seq_axis.
            from acco_tpu.ops.losses import IGNORE_INDEX
            from acco_tpu.parallel.pp import make_pp_loss_fn

            seq_axis = self.seq_axis
            pp_size = self.mesh.shape[pp_axis]
            loss_fn = make_pp_loss_fn(
                model, self.step_obj.tp_layout, pp_axis,
                self.label_smoothing, vocab_axes=model_axis,
                seq_axis=seq_axis, fused_loss=self.fused_loss,
                n_vocab_shards=self.step_obj.tp,
            )

            def body(flat, ids, am, labels):
                B, L = ids.shape
                M = max(
                    d for d in range(1, B + 1)
                    if B % d == 0 and d <= pp_size
                )
                ids_r = ids.reshape(M, B // M, L)
                labels_r = labels.reshape(M, B // M, L)
                if seq_axis is None:
                    # shift=True inside the loss: first label column
                    # of each row never scores
                    counts = (
                        (labels_r[:, :, 1:] != IGNORE_INDEX)
                        .sum((1, 2)).astype(jnp.float32)
                    )  # [M] token counts
                    weights = counts
                    axes = (DATA_AXIS,)
                else:
                    # sp: pre-shifted label chunks; the loss divides
                    # each microbatch by its sp-global count, so
                    # weight by that to recover the local nll sum
                    counts = (
                        (labels_r != IGNORE_INDEX)
                        .sum((1, 2)).astype(jnp.float32)
                    )
                    weights = jax.lax.psum(counts, seq_axis)
                    axes = (DATA_AXIS, seq_axis)
                block = {
                    "input_ids": ids_r,
                    "attention_mask": am.reshape(M, B // M, L),
                    "labels": labels_r,
                    "valid": weights,
                }
                # valid = per-microbatch token counts => wsum is the
                # (local) nll sum, no per-microbatch mean re-weighting
                wsum, _ = loss_fn(flat, block)
                return jax.lax.psum(wsum, axes) / jnp.maximum(
                    jax.lax.psum(counts.sum(), axes), 1.0
                )

            row = P(DATA_AXIS, seq_axis)
            sharded_eval = jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(flat_spec, row, row, row),
                out_specs=P(),
                check_vma=False,
            )

            eval_fn = wrap_cp_prep(sharded_eval, seq_axis)

        elif self.seq_axis is None and tp_axis is None:
            # fused_loss applies to eval too: the [B, L, V] f32
            # logits the flag exists to avoid would otherwise
            # reappear at the first eval boundary and OOM the run.
            # the shared gate (also the train path's): a run that
            # trained on the fallback must not die at its first
            # eval boundary
            from acco_tpu.ops.losses import resolve_fused_loss

            fused = resolve_fused_loss(
                self.fused_loss, model, real_vocab
            )

            @partial(
                jax.jit,
                in_shardings=(
                    NamedSharding(self.mesh, P()),
                    NamedSharding(self.mesh, P(DATA_AXIS, None)),
                    NamedSharding(self.mesh, P(DATA_AXIS, None)),
                    NamedSharding(self.mesh, P(DATA_AXIS, None)),
                ),
                out_shardings=NamedSharding(self.mesh, P()),
            )
            def eval_fn(flat, ids, am, labels):
                from acco_tpu.ops.losses import model_ce

                if self.eval_const_len:
                    am = None  # all-ones by contract: skip pad plumbing
                return model_ce(
                    model, unravel(flat[:n_params]), ids, am, labels,
                    label_smoothing=self.label_smoothing, fused=fused,
                    real_vocab=real_vocab,
                )

        elif self.seq_axis is not None:
            # CP eval (tp-composable): ring model must run inside
            # shard_map; labels are next-token aligned on the global
            # sequence first. The global valid-token-weighted mean
            # (psum'd nll sum over psum'd token count) matches the
            # non-CP eval path exactly, so eval losses are comparable
            # across mesh shapes. Under tp the flat vector is the
            # shard's local params and the model psums internally.
            from acco_tpu.ops.losses import (
                IGNORE_INDEX,
                resolve_fused_loss,
            )

            seq_axis, smoothing = self.seq_axis, self.label_smoothing
            # same gate as the CP train path: under fused_loss the
            # long-sequence eval must not re-materialize the
            # [B, Lc, V] logits the flag exists to avoid
            cp_fused = resolve_fused_loss(
                self.fused_loss, model, real_vocab,
                n_vocab_shards=(
                    getattr(self.step_obj, "tp", 1)
                    if tp_axis is not None
                    else 1
                ),
                seq_sharded=True,
            )

            def body(flat, ids, am, labels):
                from acco_tpu.ops.losses import model_ce

                nll_sum = model_ce(
                    model, unravel(flat[:n_params]), ids, None, labels,
                    label_smoothing=smoothing, fused=cp_fused,
                    vocab_axis=tp_axis, real_vocab=real_vocab,
                    num_valid=jnp.float32(1.0),  # => masked nll SUM
                    shift=False,
                )
                count = (labels != IGNORE_INDEX).sum().astype(jnp.float32)
                axes = (DATA_AXIS, seq_axis)
                return jax.lax.psum(nll_sum, axes) / jnp.maximum(
                    jax.lax.psum(count, axes), 1.0
                )

            row = P(DATA_AXIS, self.seq_axis)
            sharded = jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(flat_spec, row, row, row),
                out_specs=P(),
                check_vma=False,
            )

            eval_fn = wrap_cp_prep(sharded, seq_axis)

        else:
            # tp without CP: the tensor-parallel model must run inside
            # shard_map (its per-sublayer psums need the tp axis), so
            # the jit path's global masked mean becomes an explicit
            # psum'd nll-sum over psum'd token count across dp — the
            # same value the jit path computes.
            from acco_tpu.ops.losses import (
                IGNORE_INDEX,
                resolve_fused_loss,
            )

            smoothing = self.label_smoothing
            tp_fused = resolve_fused_loss(
                self.fused_loss, model, real_vocab,
                n_vocab_shards=self.step_obj.tp,
            )

            def body(flat, ids, am, labels):
                from acco_tpu.ops.losses import model_ce

                if self.eval_const_len:
                    am = None  # all-ones by contract: skip pad plumbing
                nll_sum = model_ce(
                    model, unravel(flat[:n_params]), ids, am, labels,
                    label_smoothing=smoothing, fused=tp_fused,
                    vocab_axis=tp_axis, real_vocab=real_vocab,
                    num_valid=jnp.float32(1.0),  # => masked nll SUM
                )
                count = (
                    (labels[:, 1:] != IGNORE_INDEX).sum().astype(jnp.float32)
                )
                return jax.lax.psum(nll_sum, DATA_AXIS) / jnp.maximum(
                    jax.lax.psum(count, DATA_AXIS), 1.0
                )

            row = P(DATA_AXIS, None)
            eval_fn = jax.jit(
                jax.shard_map(
                    body,
                    mesh=self.mesh,
                    in_specs=(flat_spec, row, row, row),
                    out_specs=P(),
                    check_vma=False,
                )
            )

        return eval_fn

    def evaluate(self, flat_params) -> float:
        """Mean eval loss over the local eval shard (parity: ``eval_loop``,
        `/root/reference/trainer_decoupled.py:399-415`)."""
        if self.eval_loader is None:
            return float("nan")
        if self._eval_fn is None:
            self._eval_fn = self._build_eval_fn()
        losses = []
        full = self.batch_size * self.local_devices
        # eval_fn is a cross-process collective: every process must call it
        # the same number of times, so agree on min(full batches) first.
        n_batches = len(self.eval_dataset) // full
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            n_batches = int(
                np.min(multihost_utils.process_allgather(np.asarray(n_batches)))
            )
        row_sharding = NamedSharding(self.mesh, P(DATA_AXIS, self.seq_axis))

        def device_batches():
            batch_iter = iter(self.eval_loader)
            for _ in range(n_batches):
                batch = next(batch_iter)
                yield [
                    jax.device_put(batch[k], row_sharding)
                    if jax.process_count() == 1
                    else jax.make_array_from_process_local_data(
                        row_sharding, batch[k]
                    )
                    for k in ("input_ids", "attention_mask", "labels")
                ]

        # The eval input pipeline prefetches like the train loop: the
        # per-batch float() sync below gives the worker a whole program's
        # wall time to collate + transfer the next batch.
        arrs_iter = (
            AsyncPrefetcher(device_batches(), depth=self.prefetch_depth)
            if self.prefetch
            else device_batches()
        )
        try:
            for arrs in arrs_iter:
                # Materialize per batch (the reference's eval_loop
                # accumulates .item() the same way): keeps at most one eval
                # program in flight — enqueueing hundreds of
                # collective-bearing programs starves device threads past
                # the CPU backend's 40 s rendezvous termination on
                # oversubscribed hosts (8 virtual devices on one core),
                # and eval is not the hot path.
                losses.append(float(self._eval_fn(flat_params, *arrs)))
        finally:
            if isinstance(arrs_iter, AsyncPrefetcher):
                arrs_iter.close()
        return float(np.mean(losses)) if losses else float("nan")

    def _ckpt_due(self, elapsed: float) -> bool:
        """Collectively-agreed time-based checkpoint trigger: process 0's
        clock decides, everyone follows."""
        due = elapsed > self.checkpoint_every_s
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            due = bool(multihost_utils.broadcast_one_to_all(np.asarray(due)))
        return due

    def _preempted(self, rounds_this_run: int) -> bool:
        """Collectively-agreed shutdown decision. Single-process: the
        local latch decides immediately. Multi-process: signals land on
        different processes at different times (or on only one), so the
        flags are OR-reduced across processes — but only every
        ``preempt_sync_rounds`` rounds, because the allgather is a host
        sync and a per-round one would serialize the async dispatch
        pipeline. Worst case adds a few rounds of latency to the grace
        window; every process then agrees to stop at the SAME boundary
        (a lone stopper would strand the rest at the next collective)."""
        if self._shutdown is None:
            return False
        local = self._shutdown.should_stop()
        if jax.process_count() == 1:
            return local
        if rounds_this_run % self._preempt_sync_rounds != 0:
            return False
        from jax.experimental import multihost_utils

        return bool(
            np.max(
                multihost_utils.process_allgather(
                    np.asarray(int(local), np.int32)
                )
            )
        )

    # -- watchdog escalation ------------------------------------------------

    def _rollback(self, state, source):
        """Auto-rollback: restore the newest complete checkpoint and
        fence the poisoned data window.

        Persistent numerical corruption (a poisoned optimizer shard, a
        bad batch that slipped a guard threshold, bit-flipped state)
        makes the in-program guard skip every round: params frozen,
        progress zero, and no host-side retry can fix state that is
        already wrong. The recovery that works — and the one every
        production stack converges on — is rollback-and-fence:

        - restore through PR 2's ``latest_checkpoint`` fallback chain
          (the newest COMPLETE step wins; torn/corrupt dirs are skipped
          with reasons);
        - fence the data window: the loader resumes from the position of
          the last CONSUMED block (the prefetcher's exact-resume
          contract), NOT the checkpoint's recorded position — every
          batch between the checkpoint and the anomaly is skipped
          deterministically, so the same poisoned batch is never
          replayed into the same state (it would diverge identically);
        - bounded: more than ``rollback_max`` rollbacks means the
          anomaly is not data-positional — raise rather than loop.

        Returns ``(restored_state, new_block_source, ckpt_meta)``; the
        caller re-anchors its host-side counters from the meta.
        """
        self._rollbacks += 1
        if self._rollbacks > self.rollback_max:
            raise RuntimeError(
                f"watchdog: {self._rollbacks - 1} auto-rollbacks already "
                f"performed (rollback_max={self.rollback_max}) and training "
                "is anomalous again — the corruption is not recoverable by "
                "rewinding state past the bad data window; inspect the "
                "checkpoints and data shard"
            )
        path = latest_checkpoint(self.ckpt_dir, log=self.log)
        if path is None:
            raise RuntimeError(
                f"watchdog: {self.rollback_after_skipped} consecutive "
                "anomalous rounds and no complete checkpoint under "
                f"{self.ckpt_dir!r} to roll back to — the guard has been "
                "holding params at their last healthy values, but recovery "
                "needs save=True (or rollback=False to disable escalation)"
            )
        # The fence position BEFORE closing the source: the last
        # consumed block's exact-resume position.
        fence = dict(source.iter_state())
        source.close()
        self._block_source = None
        # Drain the in-flight async commit first: the finalize thread
        # may still be writing the very step dir we are about to
        # restore, and Orbax save/restore of one tree must not overlap.
        self.ckpt_manager.wait()
        state, meta = restore_flat_state(path, state, self.step_obj, log=self.log)
        self.train_loader.set_state(fence)
        new_source = PrefetchingBlockSource(
            self.train_loader,
            self.n_acc,
            self._put_block,
            depth=self.prefetch_depth,
            prefetch=self.prefetch,
        )
        self._block_source = new_source
        self._health_monitor.note_rollback()
        # Re-anchor the monitor's skip baseline to the restored counter
        # (it rewound with the state).
        self._health_monitor.last_skipped_rounds = int(
            jax.device_get(state.health.skipped_rounds)
        )
        self._last_consec_skipped = 0
        self.log.warning(
            "watchdog: rolled back to %s (%d grads); data window fenced "
            "to epoch=%s batch_pos=%s — the poisoned batches will not be "
            "replayed",
            path,
            int(meta["count_grad_tot"]),
            fence.get("epoch"),
            fence.get("batch_pos"),
        )
        return state, new_source, meta

    # -- persistence --------------------------------------------------------

    def _save(
        self,
        state,
        count_grad_tot: float,
        rounds_done: int,
        t_beg: float,
        export_npz: bool = True,
    ):
        count_grad_tot = int(count_grad_tot)
        meta = {
            "count_grad_tot": count_grad_tot,
            "rounds_done": rounds_done,
            "elapsed_s": time.time() - t_beg,
            "method": self.method,
            "id_run": self.id_run,
            # the order of the state's flat vectors (parallel/flat_layout.py)
            LAYOUT_META_KEY: flat_layout_tag(self.step_obj),
            # exact data-iterator position (identical on every rank:
            # shards differ, the seed ladder and consumption don't).
            # Through the block source: the position of the last
            # CONSUMED block — blocks the prefetch worker has staged
            # but the round loop has not consumed are excluded, so a
            # mid-stream checkpoint replays them identically.
            "loader": (
                self._block_source.iter_state()
                if getattr(self, "_block_source", None) is not None
                else self.train_loader.iter_state()
            ),
        }
        # The npz export must read its params BEFORE the next round runs:
        # the round programs donate their input state, so a background
        # device_get on the live leaves would race the donation. One
        # synchronous device->host gather here (same cost Orbax itself
        # pays for its snapshot); the actual npz write — the disk part —
        # happens on the finalize thread, before meta.json commits it.
        # Periodic saves pass export_npz=False and skip the gather
        # entirely (see the call site) — it is the one remaining
        # size-proportional synchronous cost.
        flat_host = (
            self._export_flat_host(state)
            if self.rank == 0 and export_npz
            else None
        )

        def extra_files(path: str) -> None:
            if flat_host is not None:
                np.savez(os.path.join(path, "params.npz"), flat_params=flat_host)

        path = self.ckpt_manager.save(
            count_grad_tot,
            state,
            meta,
            extra_files=extra_files if self.rank == 0 else None,
        )
        if self.rank == 0:
            self.log.info(
                "checkpoint -> %s%s",
                path,
                " (committing async)" if self.ckpt_manager.in_flight else "",
            )

    def _write_scope_table(self, step, programs) -> Optional[str]:
        """``<run_dir>/device_scopes.json``: for each of the round
        ``programs`` that is installed, ``{instruction name: device
        scope}`` and the fusions that mix scopes, from the compiled
        program's own text (telemetry.trace.scope_table). Written when a
        profile will be captured: the TPU's profile names an op by its
        instruction, and this is what says which scope (DEVICE_SCOPES)
        the instruction lies in. None where no AOT program is installed
        (no warmup), or on ranks that capture nothing."""
        if self.rank != 0:
            return None
        tables = {
            name: scope_table(step.compiled_programs[name].as_text())
            for name in programs
            if name in step.compiled_programs
        }
        if not tables:
            return None
        path = os.path.join(self.run_dir, "device_scopes.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(tables, f)
        return path

    def _export_flat_host(self, state) -> Optional[np.ndarray]:
        """Dense float32 param vector on host for the portable params.npz
        artifact (the role of the reference's state_dict drop,
        `trainer_decoupled.py:559-574`): mesh-agnostic, loadable by
        perplexity_eval.py without the train-state template. float32:
        numpy's npz format cannot round-trip bfloat16. None when the
        export is impossible (multi-host tensor parallelism)."""
        layout = getattr(self.step_obj, "tp_layout", None)
        if layout is None:
            # flat_params is replicated; rank 0 holds the full vector. The
            # artifact stays in ravel_pytree's order, whatever the state's.
            flat = np.asarray(
                jax.device_get(state.flat_params)[: self.step_obj.geom.n_params]
            )
            return np.asarray(
                self.step_obj.layout.to_row_major(flat), dtype=np.float32
            )
        if jax.process_count() == 1:
            # tp: flat_params is the tp-major stack of per-shard local
            # vectors; reassemble the dense pytree and re-ravel it so
            # the artifact stays mesh-agnostic. Entirely on host —
            # the dense model may not fit one chip's HBM (that is
            # what tp is for), so no device may see a full copy.
            stacked = np.asarray(
                jax.device_get(state.flat_params), dtype=np.float32
            ).reshape(layout.tp, self.step_obj.geom.padded_size)
            gathered = layout.gather_params(stacked)
            if hasattr(self.model, "unpad_vocab"):
                gathered = self.model.unpad_vocab(gathered)
            from acco_tpu.parallel.tp import host_ravel

            return host_ravel(gathered, dtype=np.float32)
        # multi-host tp: rank 0 cannot address remote tp shards;
        # the Orbax state holds everything — skip the npz.
        self.log.warning(
            "params.npz export skipped (tensor parallelism over "
            "multiple hosts); restore through the Orbax state"
        )
        return None

    def _write_results(
        self, final_loss: float, total_time: float, extra: Optional[dict] = None
    ) -> None:
        if hasattr(self.args, "to_container"):
            args_dict = self.args.to_container()
        elif isinstance(self.args, dict):
            args_dict = dict(self.args)
        else:  # attribute-style args (SimpleNamespace etc.), like _arg
            args_dict = dict(vars(self.args))
        row = logs_utils.create_dict_result(
            args_dict,
            self.world_size,
            self.dist.get("n_nodes", 1),
            jax.devices()[0].platform,
            total_time,
            self.id_run,
            final_loss,
        )
        if extra:
            # health/watchdog columns (save_result merges schemas, so
            # rows without them coexist)
            row.update(extra)
        logs_utils.save_result(os.path.join(self.run_dir, "results.csv"), row)
