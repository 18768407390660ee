"""Benchmark harness: flagship pretrain workload throughput + MFU.

Measures tokens/sec/chip and MFU for the ACCO round program on Llama-125M
at the reference pretrain shape (seq 1024, per-chip batch 8 —
`config/train/acco.yaml`, BASELINE.md), and the synchronous DDP baseline
on the same shapes. The headline reference claim is qualitative —
"matches or exceeds standard DDP performance"
(`/root/reference/README.md:44`) — so ``vs_baseline`` reports the
measured ACCO/DDP wall-clock ratio (>= 1.0 means the claim holds here).

One process measures, and it is the one that holds the chip. A machine
with no TPU is a failed run: the script exits non-zero and prints no
metric line (a CPU timing is not a device metric). Every record names
the device it ran on (``platform``, ``device_kind``, ``n_chips``).

Prints exactly one JSON line on stdout, e.g.::

  {"metric": "...tokens_per_sec_per_chip...", "value": N,
   "unit": "tokens/s/chip", "vs_baseline": <acco/ddp ratio>,
   "mfu": M, ...}
"""

from __future__ import annotations

import json
import os
import sys
import time


def _time_steps(step_fns, state, batches, warmup=4, iters=10):
    """Time steps cycling through ``step_fns`` (ACCO: the even/odd
    parity-specialized round programs, in order; DDP: one fn).

    ``batches``: a device block dict, or a zero-arg callable producing a
    fresh block per round — the loader-fed mode, where the measured time
    includes the host input pipeline (collate + device_put) so it proves
    the input path hides under the round."""
    import jax

    if not isinstance(step_fns, (list, tuple)):
        step_fns = [step_fns]
    next_block = batches if callable(batches) else (lambda: batches)
    i = 0
    for _ in range(warmup):
        state, m = step_fns[i % len(step_fns)](state, next_block())
        i += 1
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, m = step_fns[i % len(step_fns)](state, next_block())
        i += 1
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / iters, state


def _time_rounds_synced(step_fns, state, batches, warmup=2, iters=8):
    """Median per-round wall time with a device sync after every round.

    The flat ``_time_steps`` loop lets async dispatch pipeline the host
    path in BOTH feed modes (the consumer runs rounds ahead of the
    device), so it cannot see an input stall at all. This variant
    measures what the trainer pays at every sync boundary (logging /
    eval / checkpoint reads): after the sync, the synchronous feed must
    run collate + transfer before the next round can dispatch, while the
    prefetcher already has the block staged. Median, not mean: robust to
    load bursts on shared hosts."""
    import statistics

    import jax

    if not isinstance(step_fns, (list, tuple)):
        step_fns = [step_fns]
    next_block = batches if callable(batches) else (lambda: batches)
    i = 0
    for _ in range(warmup):
        state, _ = step_fns[i % len(step_fns)](state, next_block())
        i += 1
    jax.block_until_ready(state)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        state, _ = step_fns[i % len(step_fns)](state, next_block())
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t0)
        i += 1
    return statistics.median(times), state


def _time_ckpt_stall(step_fns, state, batches, saves=4):
    """Median host-blocking checkpoint stall at a round boundary, sync vs
    async (the resilience subsystem's shipped path) — the role
    ``loader_step_ms`` plays for the input pipeline, for the save path.

    Each sample runs one round, syncs the device (the trainer's boundary
    condition: the state the save reads is final), then times ONLY the
    save call: the synchronous path pays serialize + file writes + commit
    there, the async path pays just Orbax's device->host snapshot and
    commits under the following rounds. The async commit is drained
    *untimed* between samples, mirroring the production cadence where
    the commit always finishes long before the next save is due.
    Returns ``(sync_ms, async_ms, state)``.
    """
    import shutil
    import statistics
    import tempfile

    import jax

    from acco_tpu.resilience import CheckpointManager

    if not isinstance(step_fns, (list, tuple)):
        step_fns = [step_fns]
    next_block = batches if callable(batches) else (lambda: batches)
    out = {}
    for mode, async_save in (("sync", False), ("async", True)):
        root = tempfile.mkdtemp(prefix=f"acco-bench-ckpt-{mode}-")
        # keep_last=0: retention disabled, so the sync window times
        # exactly what the old inline save_checkpoint path paid
        # (serialize + write + commit) — an rmtree of the previous
        # checkpoint inside the timed sync window would inflate the
        # sync-vs-async gap. Old dirs are dropped untimed below instead.
        mgr = CheckpointManager(root, async_save=async_save, keep_last=0)
        times = []
        try:
            i = 0
            for s in range(saves):
                state, _ = step_fns[i % len(step_fns)](state, next_block())
                i += 1
                jax.block_until_ready(state)
                t0 = time.perf_counter()
                path = mgr.save(s, state, {"bench_ckpt_mode": mode})
                times.append((time.perf_counter() - t0) * 1e3)
                mgr.wait()  # drain the commit outside the timed window
                # bound disk use for real-size states, also untimed
                shutil.rmtree(path, ignore_errors=True)
        finally:
            mgr.close()
            shutil.rmtree(root, ignore_errors=True)
        out[mode] = statistics.median(times)
    return out["sync"], out["async"], state


def _estimates_fields() -> dict:
    """dp=8 fields from ESTIMATES.json (written by tools/step_estimate.py),
    empty when the estimate has not been generated."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ESTIMATES.json")
    try:
        with open(path) as f:
            rows = json.load(f)["rows"]
        row = next(r for r in rows if r["devices"] == 8)
    except (OSError, ValueError, KeyError, StopIteration):
        return {}
    return {
        "est_dp8_acco_step_ms": round(row["acco_est_ms"], 1),
        "est_dp8_ddp_step_ms": round(row["ddp_est_ms"], 1),
        "est_dp8_ddp_over_acco": round(row["ddp_over_acco_step"], 4),
        "est_dp8_acco_pct_comm_hidden": round(
            row["acco_pct_comm_hidden"], 1
        ),
    }


def _make_loader_feed(
    mesh, vocab_size, n_acc, global_bs, seq, prefetch_depth=0,
):
    """Block source backed by the production input pipeline: a pre-packed
    const-len FlatTokenDataset streamed through ShardedBatchIterator
    (native C++ collate when built) and device_put per round — what the
    trainer does, minus multi-process sharding. Returns ``(next_block,
    close)``; with ``prefetch_depth > 0`` blocks come through the async
    PrefetchingBlockSource (the trainer's shipped path), otherwise
    synchronously (the prefetch=False opt-out)."""
    import numpy as np

    from acco_tpu.data.loader import ShardedBatchIterator
    from acco_tpu.data.prefetch import PrefetchingBlockSource
    from acco_tpu.native import FlatTokenDataset
    from acco_tpu.parallel.common import make_valid, put_block
    from acco_tpu.parallel.mesh import DATA_AXIS

    rng = np.random.default_rng(0)
    n_rows = max(4 * n_acc * global_bs, 64)  # a few rounds before wrapping
    flat = rng.integers(0, vocab_size, size=n_rows * seq, dtype=np.int32)
    offsets = np.arange(0, (n_rows + 1) * seq, seq, dtype=np.int64)
    loader = ShardedBatchIterator(
        FlatTokenDataset(flat, offsets),
        batch_size=global_bs,
        max_length=seq,
        pad_token_id=0,
    )
    valid = make_valid(n_acc, mesh.shape[DATA_AXIS])

    def put(stacked):
        stacked["valid"] = valid
        return put_block(mesh, DATA_AXIS, stacked)

    source = PrefetchingBlockSource(
        loader, n_acc, put,
        depth=max(prefetch_depth, 1), prefetch=prefetch_depth > 0,
    )
    return source.next_block, source.close


def main() -> None:
    import dataclasses

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(
            f"bench.py measures on a TPU; JAX found {device.platform!r} "
            f"({device.device_kind}). No metric is printed for a CPU run."
        )

    import jax.numpy as jnp

    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.ops.attention import resolve_attention_impl
    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.acco import AccoTrainStep
    from acco_tpu.parallel.common import synthetic_block
    from acco_tpu.parallel.ddp import DDPTrainStep
    from acco_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from acco_tpu.utils import logs as logs_utils
    from acco_tpu.utils.flops import llama_train_flops_per_token, mfu

    n_chips = jax.device_count()
    device_kind = device.device_kind
    platform = device.platform
    mesh = make_mesh({DATA_AXIS: n_chips})

    seq = int(os.environ.get("ACCO_BENCH_SEQ", 1024))
    per_chip_bs = int(os.environ.get("ACCO_BENCH_BS", 8))
    n_acc = int(os.environ.get("ACCO_BENCH_NACC", 1))
    iters = int(os.environ.get("ACCO_BENCH_ITERS", 10))
    global_bs = per_chip_bs * n_chips
    tokens_per_round = n_acc * global_bs * seq

    model_family = os.environ.get("ACCO_BENCH_MODEL", "llama")
    if model_family not in ("llama", "llama350m", "gptneo"):
        raise ValueError(
            f"ACCO_BENCH_MODEL must be llama/llama350m/gptneo, got {model_family!r}"
        )
    if model_family == "gptneo":
        from acco_tpu.models.gpt_neo import GPTNeoConfig

        cfg = GPTNeoConfig.from_json(
            os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "config", "model", "gpt-neo-125M.json",
            )
        )
        if seq > cfg.max_position_embeddings:
            # ACCO_BENCH_SEQ=2048 — the architecture's real ceiling
            # (the reference json pins 1024): the regime where the
            # einsum plan + banded local layers is the shipped program
            cfg = dataclasses.replace(cfg, max_position_embeddings=seq)
    elif model_family == "llama350m":
        cfg = LlamaConfig.from_json(
            os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "config", "model", "llama-350M.json",
            )
        )
        if seq > cfg.max_position_embeddings:
            cfg = dataclasses.replace(cfg, max_position_embeddings=seq)
    else:
        cfg = LlamaConfig(max_position_embeddings=max(seq, 1024))
    # Remat policy: full no-remat OOMs a v5e at seq 1024 x bs 8 (the 12
    # layers' [B,H,L,L] float32 attention scores alone are ~9.6 GB); the
    # 'dots' policy keeps the matmul outputs and recomputes scores +
    # elementwise — measured fastest here (SURVEY.md §'HBM bandwidth').
    from acco_tpu.ops.attention import normalize_remat

    remat_env = os.environ.get("ACCO_BENCH_REMAT", "dots").lower()
    remat = normalize_remat(remat_env)  # the one shared spelling map
    attn = os.environ.get("ACCO_BENCH_ATTN", "auto")
    comm = os.environ.get("ACCO_BENCH_COMM", "xla")
    unroll_env = os.environ.get("ACCO_BENCH_UNROLL", "0")
    unroll = True if unroll_env in ("1", "true", "True") else 1
    if model_family == "gptneo":
        from acco_tpu.models.gpt_neo import GPTNeoModel

        # attention passes through so a forced ACCO_BENCH_ATTN=flash fails
        # loudly (GPT-Neo is xla-only by design) instead of being ignored.
        model = GPTNeoModel(
            cfg, param_dtype=jnp.bfloat16, remat=remat, attention=attn,
            scan_unroll=unroll,
        )
    else:
        model = LlamaModel(
            cfg, param_dtype=jnp.bfloat16, remat=remat, attention=attn,
            scan_unroll=unroll,
        )
    params = model.init(jax.random.PRNGKey(0))
    sched = get_schedule("cosine", 6e-4, 1000, 50000)
    # synthetic data is const-len packed (all-ones masks): the static
    # flag lets the kernels drop their pad plumbing, and GPT-Neo's
    # window layers take the banded kernel — matching a real pretrain
    opt_kw = dict(
        weight_decay=0.1, beta1=0.9, beta2=0.95, const_len_batch=True
    )

    from acco_tpu.ops.losses import normalize_fused_loss

    fused = normalize_fused_loss(os.environ.get("ACCO_BENCH_FUSED", "0"))
    opt_kw["fused_loss"] = fused
    variant = f"_fusedce_{fused}" if fused else ""
    batches = synthetic_block(mesh, DATA_AXIS, model.config.vocab_size, n_acc, global_bs, seq)

    loader_dt = loader_sync_dt = acco_synced_dt = None
    ckpt_sync_ms = ckpt_async_ms = None
    compile_cold_ms = compile_warm_ms = None
    compile_cold_cache_hits = compile_cache_hits = None
    if os.environ.get("ACCO_BENCH_COMPILE", "1") != "0":
        # Compile-once measurement (acco_tpu/compile): AOT-compile the
        # ACCO round programs (seed + even/odd parity rounds) twice
        # through the persistent cache, which lives where
        # setup_compilation_cache's rule puts it. The first pass is the
        # full XLA compile wherever the cache holds no entry yet
        # (compile_cold_cache_hits says how many it held); the second
        # runs on a FRESH step object (fresh jit wrappers, so no
        # in-memory cache can serve it) and is what a repeat launch /
        # preemption-resume of the same config pays: a disk
        # deserialization.
        from acco_tpu.compile import setup_compilation_cache

        setup_compilation_cache()

        def compile_pass():
            step = AccoTrainStep(
                model, mesh, sched, mode="acco", comm_impl=comm, **opt_kw
            )
            report = step.warmup(n_acc, global_bs, seq)
            bad = [r.error for r in report.programs.values() if not r.ok]
            if bad:
                raise RuntimeError("; ".join(bad))
            ms = sum(rec.compile_ms for rec in report.programs.values())
            return round(ms, 2), report.cache["hits"]

        try:
            compile_cold_ms, compile_cold_cache_hits = compile_pass()
            compile_warm_ms, compile_cache_hits = compile_pass()
        except Exception as exc:
            print(f"# compile cold/warm measurement failed: {exc}", file=sys.stderr)
    guard_overhead_pct = skipped_rounds = chaos_skipped = None
    chaos = os.environ.get("ACCO_BENCH_CHAOS") or None
    acco = AccoTrainStep(model, mesh, sched, mode="acco", comm_impl=comm, **opt_kw)
    acco_state = acco.init_state(params)
    acco_state, _ = acco.seed_fn()(acco_state, batches)
    # Alternate the parity-specialized round programs the way the
    # trainer does (round_idx starts even after the seed).
    round_fns = [acco.round_fn(parity=True), acco.round_fn(parity=False)]
    acco_dt, acco_state = _time_steps(
        round_fns, acco_state, batches, iters=iters
    )
    # Robustness overhead: the in-program health guard (ISSUE 7) is
    # ON by default in the step classes, so acco_dt above already
    # pays it; a second step object with nan_guard=False runs the
    # signal-free programs. Samples are INTERLEAVED (one guarded
    # round, one unguarded, per iteration) with per-round device
    # syncs and compared as medians: on shared/virtual-CPU hosts the
    # round time drifts by far more than the guard's cost, and two
    # sequential passes would measure that drift, not the guard.
    # Best-effort: the extra state is co-resident, so an OOM here
    # must not cost the headline record.
    if os.environ.get("ACCO_BENCH_GUARD", "1") != "0":
        try:
            import statistics

            noguard = AccoTrainStep(
                model, mesh, sched, mode="acco", comm_impl=comm,
                nan_guard=False, **opt_kw
            )
            ng_state = noguard.init_state(params)
            ng_state, _ = noguard.seed_fn()(ng_state, batches)
            ng_fns = [
                noguard.round_fn(parity=True),
                noguard.round_fn(parity=False),
            ]
            # round_fn's contract: call parity must track
            # state.round_idx. acco_state is mid-sequence after
            # _time_steps, ng_state is fresh — each side continues
            # from ITS OWN parity.
            g_r = int(jax.device_get(acco_state.round_idx))
            n_r = int(jax.device_get(ng_state.round_idx))
            times_g, times_n = [], []
            for _ in range(2):  # warmup (compiles the ng programs)
                acco_state, _ = round_fns[g_r % 2](acco_state, batches)
                ng_state, _ = ng_fns[n_r % 2](ng_state, batches)
                g_r += 1
                n_r += 1
            jax.block_until_ready((acco_state, ng_state))
            for _ in range(2 * iters):
                t0 = time.perf_counter()
                acco_state, _ = round_fns[g_r % 2](acco_state, batches)
                jax.block_until_ready(acco_state)
                times_g.append(time.perf_counter() - t0)
                g_r += 1
                t0 = time.perf_counter()
                ng_state, _ = ng_fns[n_r % 2](ng_state, batches)
                jax.block_until_ready(ng_state)
                times_n.append(time.perf_counter() - t0)
                n_r += 1
            del ng_state
            noguard_dt = statistics.median(times_n)
            guard_overhead_pct = round(
                (statistics.median(times_g) - noguard_dt)
                / noguard_dt * 100.0,
                2,
            )
        except Exception as exc:
            print(f"# guard overhead measurement failed: {exc}", file=sys.stderr)
    # Chaos drill (ACCO_BENCH_CHAOS="nan_grads@1", comma-separable):
    # run a few extra rounds with the fault injector poisoning the
    # chosen round, then read the state's skip counter — proves the
    # guard skips (and ONLY skips) under injected anomalies, on the
    # exact programs the timing sections ran.
    if chaos:
        try:
            import jax as _jax

            from acco_tpu.resilience.faults import FaultInjector

            injector = FaultInjector.from_config(
                [s.strip() for s in chaos.split(",") if s.strip()]
            )
            before = int(_jax.device_get(acco_state.health.skipped_rounds))
            # Continue the state's own parity sequence (round_fn
            # contract) — the drill must run the trajectory a
            # trainer would, not a parity-flipped one.
            base = int(_jax.device_get(acco_state.round_idx))
            n_chaos = max(s.round for s in injector.specs) + 3
            for r in range(n_chaos):
                block = batches
                acco_state, block = injector.apply(r, acco_state, block)
                acco_state, _ = round_fns[(base + r) % 2](acco_state, block)
            chaos_skipped = (
                int(_jax.device_get(acco_state.health.skipped_rounds))
                - before
            )
        except Exception as exc:
            print(f"# chaos drill failed: {exc}", file=sys.stderr)
    if getattr(acco_state, "health", None) is not None:
        import jax as _jax

        skipped_rounds = int(
            _jax.device_get(acco_state.health.skipped_rounds)
        )
    data_mode = os.environ.get("ACCO_BENCH_DATA", "loader")
    if data_mode != "synthetic":
        # Loader-fed passes: same programs, but every round's block
        # comes through the real input pipeline (FlatTokenDataset ->
        # native collate -> stack -> device_put), once synchronous
        # (prefetch=False) and once through the async prefetcher (the
        # trainer's shipped path). Timed per-round-synced (see
        # _time_rounds_synced) against a synced synthetic baseline:
        # loader_vs_synthetic ~1.0 = the host path hides under the
        # round; the sync/prefetch pair is the measured overlap win
        # (round-2 VERDICT weak #6).
        depth = int(os.environ.get("ACCO_BENCH_PREFETCH_DEPTH", 2))
        acco_synced_dt, acco_state = _time_rounds_synced(
            round_fns, acco_state, batches, iters=iters
        )
        next_sync, close_sync = _make_loader_feed(
            mesh, model.config.vocab_size, n_acc, global_bs, seq,
            prefetch_depth=0,
        )
        loader_sync_dt, acco_state = _time_rounds_synced(
            round_fns, acco_state, next_sync, iters=iters
        )
        close_sync()
        next_pre, close_pre = _make_loader_feed(
            mesh, model.config.vocab_size, n_acc, global_bs, seq,
            prefetch_depth=depth,
        )
        loader_dt, acco_state = _time_rounds_synced(
            round_fns, acco_state, next_pre, iters=iters
        )
        close_pre()
    if os.environ.get("ACCO_BENCH_CKPT", "1") != "0":
        # Checkpoint stall at the round boundary, sync vs async (the
        # resilience subsystem's overlapped save): until this slot
        # existed the trainer's save_checkpoint stall was invisible —
        # the last synchronous host stall in the round loop, and the
        # one the async path removes. Best-effort: a full disk or a
        # broken orbax must not cost the headline throughput record.
        try:
            ckpt_sync_ms, ckpt_async_ms, acco_state = _time_ckpt_stall(
                round_fns, acco_state, batches
            )
        except Exception as exc:
            print(f"# ckpt stall measurement failed: {exc}", file=sys.stderr)
    del acco_state  # free ~2.8 GB of round state before the DDP phase

    ddp = DDPTrainStep(model, mesh, sched, comm_impl=comm, **opt_kw)
    ddp_state = ddp.init_state(params)
    ddp_dt, _ = _time_steps(ddp.step_fn(), ddp_state, batches, iters=iters)

    acco_tps_chip = tokens_per_round / acco_dt / n_chips
    ddp_tps_chip = tokens_per_round / ddp_dt / n_chips
    if model_family == "gptneo":
        from acco_tpu.utils.flops import gpt_neo_train_flops_per_token

        flops_tok = gpt_neo_train_flops_per_token(cfg, seq)
    else:
        flops_tok = llama_train_flops_per_token(cfg, seq)
    acco_mfu = mfu(acco_tps_chip, flops_tok, device_kind)
    ddp_mfu = mfu(ddp_tps_chip, flops_tok, device_kind)

    # Per-phase keys go THROUGH the closed-world telemetry registry
    # (acco_tpu/telemetry/metrics.py): the record reads them back with
    # REGISTRY.scalar, so a phase key the registry does not declare can
    # never reach BENCH_*.json — the same one-surface rule the trainer's
    # results.csv columns follow.
    from acco_tpu.telemetry import metrics

    if ckpt_sync_ms is not None:
        metrics.emit("ckpt_sync_stall_ms", ckpt_sync_ms)
    if ckpt_async_ms is not None:
        metrics.emit("ckpt_async_stall_ms", ckpt_async_ms)
    if guard_overhead_pct is not None:
        metrics.emit("guard_overhead_pct", guard_overhead_pct)
    _reg = metrics.REGISTRY.scalar

    record = {
        "metric": (
            "acco_tokens_per_sec_per_chip_"
            + {
                "gptneo": "gptneo125m",
                "llama350m": "llama350m",
                "llama": "llama125m",
            }[model_family]
            + f"_seq{seq}{variant}"
        ),
        "value": round(acco_tps_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(acco_tps_chip / ddp_tps_chip, 4),
        "mfu": round(acco_mfu, 4),
        "ddp_tokens_per_sec_per_chip": round(ddp_tps_chip, 1),
        "ddp_mfu": round(ddp_mfu, 4),
        "acco_step_ms": round(acco_dt * 1e3, 2),
        "ddp_step_ms": round(ddp_dt * 1e3, 2),
        # loader-fed passes (host pipeline included), per-round-synced
        # against the synced synthetic baseline; ~1.0 ratio = input path
        # fully hidden under the round. loader_* is the shipped
        # (prefetched) path; loader_sync_* the prefetch=False opt-out —
        # prefetched ratio >= sync ratio is the overlap win, measured.
        "acco_synced_step_ms": (
            round(acco_synced_dt * 1e3, 2)
            if acco_synced_dt is not None
            else None
        ),
        "loader_step_ms": (
            round(loader_dt * 1e3, 2) if loader_dt is not None else None
        ),
        "loader_vs_synthetic": (
            round(acco_synced_dt / loader_dt, 4)
            if loader_dt is not None and acco_synced_dt is not None
            else None
        ),
        "loader_sync_step_ms": (
            round(loader_sync_dt * 1e3, 2)
            if loader_sync_dt is not None
            else None
        ),
        "loader_sync_vs_synthetic": (
            round(acco_synced_dt / loader_sync_dt, 4)
            if loader_sync_dt is not None and acco_synced_dt is not None
            else None
        ),
        # The stall/overhead keys below read BACK from the telemetry
        # registry (emitted above) — one declared surface.
        # host-blocking checkpoint stall at a round boundary (medians,
        # device synced first): sync = the old save_checkpoint path
        # (serialize + write + commit on the critical path), async = the
        # shipped resilience path (device->host snapshot only; the
        # commit overlaps the following rounds). async < sync is the
        # measured win of overlapped checkpointing.
        "ckpt_sync_stall_ms": (
            round(_reg("ckpt_sync_stall_ms"), 2)
            if _reg("ckpt_sync_stall_ms") is not None
            else None
        ),
        "ckpt_async_stall_ms": (
            round(_reg("ckpt_async_stall_ms"), 2)
            if _reg("ckpt_async_stall_ms") is not None
            else None
        ),
        # Compile-once (acco_tpu/compile): summed XLA-compile ms for the
        # ACCO round programs on the first pass (cold where
        # compile_cold_cache_hits is 0) vs re-compiled through the
        # now-populated cache (warm — a disk deserialization, what a
        # repeat launch or preemption-resume of the same config pays).
        # compile_cache_hits counts the warm pass's programs served from
        # the cache.
        "compile_cold_ms": compile_cold_ms,
        "compile_cold_cache_hits": compile_cold_cache_hits,
        "compile_warm_ms": compile_warm_ms,
        "compile_cache_hits": compile_cache_hits,
        # Training-health watchdog (acco_tpu/resilience): per-round cost
        # of the in-program anomaly guard (guarded vs nan_guard=False
        # INTERLEAVED per-round-synced medians — the guard ships ON, so
        # acco_step_ms already includes it), the guard's skip counter
        # over every round this worker ran (0 on clean runs; chaos
        # injections land here), and the ACCO_BENCH_CHAOS drill's
        # counted skips.
        "guard_overhead_pct": _reg("guard_overhead_pct"),
        "skipped_rounds": skipped_rounds,
        "chaos": chaos,
        "chaos_skipped_rounds": chaos_skipped,
        # AOT scheduled-HLO multi-chip estimate (tools/step_estimate.py /
        # ESTIMATES.md): the closest honest approximation of the
        # reference's multi-worker wall-clock claim one chip allows.
        **_estimates_fields(),
        "n_chips": n_chips,
        "device_kind": device_kind,
        "platform": platform,
        "seq": seq,
        "per_chip_batch": per_chip_bs,
        # variant provenance: rows differing only in these knobs must
        # be tellable apart in the ledger.
        # attn records the RESOLVED impl — 'auto' resolves differently
        # per shape/platform and across code revisions, so the raw env
        # value cannot tell rows apart.
        "attn": resolve_attention_impl(
            attn, seq, platform=platform, remat=remat,
            head_dim=cfg.hidden_size // cfg.num_heads,
        ),
        "remat": str(remat_env),
        "fused_loss": str(fused),
    }
    print(json.dumps(record))
    print(
        f"# chips={n_chips} ({device_kind}) acco={acco_tps_chip:.1f} tok/s/chip "
        f"(mfu={acco_mfu:.3f}) ddp={ddp_tps_chip:.1f} tok/s/chip "
        f"step_acco={acco_dt * 1e3:.1f}ms step_ddp={ddp_dt * 1e3:.1f}ms",
        file=sys.stderr,
    )

    # ACCO-vs-DDP wall-clock ledger row, the role of the reference's
    # results.csv run ledger (`/root/reference/utils/logs_utils.py:128-138`).
    try:
        logs_utils.save_result(
            os.path.join(os.path.dirname(os.path.abspath(__file__)), "results.csv"),
            {
                "0_id_run": logs_utils.create_id_run(),
                "bench": record["metric"],
                "device": device_kind,
                "N_workers": n_chips,
                "acco_tokens_per_sec_per_chip": record["value"],
                "ddp_tokens_per_sec_per_chip": record["ddp_tokens_per_sec_per_chip"],
                "acco_over_ddp": record["vs_baseline"],
                "acco_mfu": record["mfu"],
                "acco_step_ms": record["acco_step_ms"],
                "ddp_step_ms": record["ddp_step_ms"],
                "loader_step_ms": record["loader_step_ms"],
                "loader_vs_synthetic": record["loader_vs_synthetic"],
                "loader_sync_step_ms": record["loader_sync_step_ms"],
                "loader_sync_vs_synthetic": record["loader_sync_vs_synthetic"],
                "ckpt_sync_stall_ms": record["ckpt_sync_stall_ms"],
                "ckpt_async_stall_ms": record["ckpt_async_stall_ms"],
                "compile_cold_ms": record["compile_cold_ms"],
                "compile_warm_ms": record["compile_warm_ms"],
                "compile_cache_hits": record["compile_cache_hits"],
                "guard_overhead_pct": record["guard_overhead_pct"],
                "skipped_rounds": record["skipped_rounds"],
                "seq": seq,
                "per_chip_batch": per_chip_bs,
                "attn": record["attn"],
                "remat": record["remat"],
                "fused_loss": record["fused_loss"],
            },
        )
    except Exception as exc:  # ledger is best-effort; the JSON line is the API
        print(f"# results.csv write failed: {exc}", file=sys.stderr)


if __name__ == "__main__":
    main()
