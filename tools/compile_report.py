#!/usr/bin/env python
"""Per-program compile report for a train config (CPU-runnable).

Shows what the compile-once subsystem (acco_tpu/compile) does for the
programs a given config would dispatch: each program's lower + compile
wall ms on a first pass (cold wherever the cache reports a miss), the
same through the cache that pass filled (a disk deserialization — what
a repeat launch or preemption-resume pays), and the hit/miss counters. No dataset, tokenizer, or training state is
touched — programs are lowered from abstract avals only, so the report
runs in seconds on a laptop CPU for any config whose model fits in host
memory.

Usage (same override surface as main.py)::

    python tools/compile_report.py train=acco model=tiny
    python tools/compile_report.py train=ddp model=gptneo \
        train.batch_size=4 train.max_length=512

The cache is the one a run of that config uses: $JAX_COMPILATION_CACHE_DIR
if it is set, else <checkout>/outputs/compile_cache. So the first pass
also says what a relaunch of the config would hit today; empty that
directory (or point the variable at an empty one) for a cold reading.
"""

from __future__ import annotations

import argparse
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    parser.add_argument(
        "overrides",
        nargs="*",
        help="main.py-style config overrides (train=acco model=tiny ...)",
    )
    parser.add_argument(
        "--skip-warm",
        action="store_true",
        help="first pass only (e.g. to fill the cache before a launch)",
    )
    args = parser.parse_args(argv)

    # CPU-runnable by construction: give the report a multi-device mesh
    # even on a laptop, like tests/conftest.py does.
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from acco_tpu.compile import (
        CacheStatsWindow,
        cache_stats,
        setup_compilation_cache,
    )
    from acco_tpu.configuration import compose_config

    cfg = compose_config(os.path.join(REPO_ROOT, "config"), args.overrides)

    cache_dir = setup_compilation_cache()

    import jax.numpy as jnp

    from acco_tpu.models.registry import build_model
    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.acco import AccoTrainStep
    from acco_tpu.parallel.ddp import DDPTrainStep
    from acco_tpu.parallel.mesh import DATA_AXIS, make_mesh

    train = cfg.train
    use_mp = bool(train.get("use_mixed_precision", True))
    mesh_shape = train.get("mesh_shape") or {DATA_AXIS: jax.device_count()}
    sharded = {
        axis: size
        for axis, size in dict(mesh_shape).items()
        if axis != DATA_AXIS and int(size or 1) > 1
    }
    if sharded:
        # A tp/pp/sp config's programs need the trainer's full model
        # wiring (sequence_axis / tensor_axis / vocab padding); reporting
        # the dp-only lowering here would describe programs the real run
        # never compiles — a false cache verdict. Refuse rather than lie.
        print(
            f"config shards over {sharded} — this report only covers "
            "data-parallel meshes; run the config itself and read the "
            "trainer's 'compile[...]' log lines for the real per-program "
            "timings",
            file=sys.stderr,
        )
        return 2
    mesh = make_mesh(mesh_shape)
    model = build_model(
        cfg.model,
        repo_root=REPO_ROOT,
        param_dtype=jnp.bfloat16 if use_mp else jnp.float32,
        remat=train.get("remat", False),
        attention=train.get("use_pallas_attention", "auto"),
        scan_unroll=train.get("scan_unroll", 1),
    )
    method = str(train.get("method_name", "acco"))
    # comm_impl participates in the round programs' HLO (and so their
    # cache keys — tests/test_compile_cache.py asserts it): resolve
    # 'auto' the way the trainer does for a dp-only mesh, and honor an
    # explicit value, or the report describes programs the real run
    # never compiles.
    comm_impl = str(train.get("comm_impl", "auto"))
    if comm_impl == "auto":
        comm_impl = (
            "ring"
            if jax.devices()[0].platform == "tpu" and jax.device_count() > 1
            else "xla"
        )
    opt_kw = dict(
        weight_decay=float(train.get("weight_decay", 0.0)),
        beta1=float(train.get("adam_beta1", 0.9)),
        beta2=float(train.get("adam_beta2", 0.999)),
        label_smoothing=float(train.get("label_smoothing_factor", 0.0)),
        lr_grad_accounting=bool(train.get("lr_grad_accounting", False)),
        param_dtype=jnp.bfloat16 if use_mp else jnp.float32,
        const_len_batch=bool(train.get("const_len_batch", True)),
        comm_impl=comm_impl,
        fused_loss=train.get("fused_loss", False),
    )
    schedule = get_schedule(
        str(train.get("scheduler_name", "cosine")),
        float(train.get("learning_rate", 6e-4)),
        int(train.get("warmup", 0)),
        int(train.get("nb_steps_tot", 1000)),
    )
    n_acc = int(train.get("n_grad_accumulation", 1))
    seq = int(train.get("max_length", 1024))
    global_bs = int(train.get("batch_size", 8)) * mesh.shape[DATA_AXIS]

    def make_step():
        if method == "ddp":
            return DDPTrainStep(model, mesh, schedule, **opt_kw)
        return AccoTrainStep(model, mesh, schedule, mode=method, **opt_kw)

    def one_pass(label: str):
        window = CacheStatsWindow()
        report = make_step().warmup(n_acc, global_bs, seq)
        delta = window.delta()
        print(f"\n== {label} ==")
        for name, rec in sorted(report.programs.items()):
            if rec.ok:
                print(
                    f"  {name:<12} lower {rec.lower_ms:8.1f} ms   "
                    f"compile {rec.compile_ms:8.1f} ms"
                )
            else:
                print(f"  {name:<12} FAILED: {rec.error}")
        print(
            f"  cache: {delta['hits']} hit(s), {delta['misses']} miss(es)"
            + (
                f", {delta['time_saved_s']:.1f} s compile time saved"
                if delta["time_saved_s"]
                else ""
            )
        )
        return report, delta

    print(
        f"config: method={method} mesh={dict(mesh.shape)} "
        f"n_acc={n_acc} global_batch={global_bs} seq={seq}"
    )
    print(f"cache dir: {cache_dir}")
    cold, _ = one_pass("first pass (compiles what the cache does not hold)")
    if not args.skip_warm:
        warm, wdelta = one_pass("warm (what a relaunch/resume pays)")
        cold_ms = sum(r.compile_ms or 0.0 for r in cold.programs.values())
        warm_ms = sum(r.compile_ms or 0.0 for r in warm.programs.values())
        if warm_ms > 0:
            print(
                f"\ncompile-once win: first pass {cold_ms:.0f} ms -> warm "
                f"{warm_ms:.0f} ms ({cold_ms / warm_ms:.1f}x), "
                f"{wdelta['hits']} program(s) served from the cache"
            )
    print(f"\ntotals this process: {cache_stats()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
