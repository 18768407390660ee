#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the trainer still starts on the chip.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # one four-chip host, dp=4 paths only

It drives the system's main path through the entry point users call
(``main.py``'s ``run``: config composition, ``DecoupledTrainer``, prefetch,
AOT warmup, health guard, telemetry, async checkpoint) on GPT-Neo-125M at
its published width and full depth, seq 1024, per-chip batch 8, with
weights made from the config's seed, and checks what comes out. The last
line of standard output is ``{"ok": true, "device": {...}}`` only if every
phase passed; any failure ends the run non-zero with no such line. No JAX
accelerator, an interpret-mode switch in the environment, or a directory
that holds this file and nothing else of the repo are failures.

One process per chip. This parent never imports JAX. It starts one child
per job (``python chip_smoke.py --job NAME``), strictly one after another,
and waits for each: a chip belongs to one process at a time, and the
warm-relaunch check is only a check if the second run is a fresh process
that finds the compile cache by its path alone.

Jobs, one chip: the kernels against their einsum reference at the model's
attention shape (the banded kernel at the 2.7B cells' shape too, at the steps
``ops.banded_attention.banded_block_sizes`` gives it), and the stock flash
kernel at OLMoE's, at the tiles
``ops.attention.flash_block_sizes`` gives it; ``train=acco``, ``dpu``,
``ddp``; the same ACCO job with
``train.use_pallas_attention=false`` (plain XLA einsum attention, the test
oracle) to compare losses with; the ACCO job again, which must compile
nothing. Jobs, ``--chips 4``: DDP at dp=4 against DDP on one of the four
devices at the same global batch; ACCO with the manual ring against ACCO
with stock collectives; DPU.

``--rehearse`` runs the same jobs and checks at a tiny size on whatever
devices JAX finds (the CPU, with the kernels in interpret mode). It exists
to find wrong paths and arguments before chip time is spent; it never
prints the success line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "outputs", "chip_smoke")
INTERPRET_SWITCHES = ("ACCO_FUSED_ATTN_INTERPRET", "ACCO_FUSED_CE_INTERPRET")
BUDGET_S = 1150.0  # the driver allows 1200 s, compilation included
U_BF16 = 2.0**-8  # bfloat16 unit roundoff (8 significand bits)

# Tolerances, all derived from U_BF16 and printed where they are applied.
# Kernel vs einsum on one tensor: both round the probabilities and the
# output to bf16 at different points, a few roundoffs per element.
KERNEL_TOL = 8 * U_BF16
# Losses of two runs that differ only in arithmetic order (kernel vs
# einsum attention, ring vs stock collectives, dp=4 vs one device). A
# loss is a mean over the batch's tokens (8 x 1023 at the real size), so
# roundoffs of size u in the activations average down to about
# u / sqrt(8184) = u / 90. The first round sees identical parameters and
# only the forward differs: u / 16 is allowed. By the last round the
# difference has gone through every update in between (Adam's normalised
# step lets a roundoff-sized change of a gradient move a parameter by up
# to the learning rate): u / 4. Measured on the v5e, kernels vs einsum:
# 1.0e-05 and 5.8e-05.
FIRST_LOSS_RTOL = U_BF16 / 16
LAST_LOSS_RTOL = U_BF16 / 4

MOSAIC_TARGET = "tpu_custom_call"
ATTENTION_KERNELS = (
    "acco_fused_attn_fwd",
    "acco_fused_attn_bwd",
    "acco_banded_attn_fwd",
    "acco_banded_attn_dq",
    "acco_banded_attn_dkv",
)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(message, flush=True)


# -- sizes --------------------------------------------------------------------


def real_size(chips: int) -> dict:
    return {
        "model": ["model=gptneo", "model.tokenizer=byte"],
        "seq": 1024,
        "batch": 8,
        "rounds": 16 if chips == 1 else 8,
        "kernel_attention": "auto",
        "attention_shape": (8, 12, 1024, 64),
        "window": 256,
        # the banded kernel also at the 2.7B cells' widths: the steps
        # ops.banded_attention.banded_block_sizes chooses differ there
        "banded_shapes": [(8, 12, 1024, 64), (2, 20, 2048, 128)],
        # the stock flash kernel at the tiles ops.attention.flash_block_sizes
        # chooses, at the shape olmoe-l1-acco-1chip gives it
        "flash_shape": (1, 16, 4096, 128),
    }


def rehearsal_size(chips: int) -> dict:
    """Two GPT-Neo layers (one global, one window) at the smallest shape
    the kernels take, for the CPU interpreter."""
    path = os.path.join(WORK, "rehearsal_model.json")
    os.makedirs(WORK, exist_ok=True)
    with open(path, "w") as f:
        json.dump(
            {
                "model_type": "gpt_neo",
                "vocab_size": 257,
                "hidden_size": 128,
                "num_layers": 2,
                "num_heads": 2,
                "max_position_embeddings": 128,
                "window_size": 64,
                "attention_layers": ["global", "local"],
                "tie_word_embeddings": True,
                "bos_token_id": 256,
                "eos_token_id": 256,
            },
            f,
        )
    return {
        "model": ["model=tiny_neo", f"model.config_path={path}"],
        "seq": 128,
        "batch": 2,
        "rounds": 8,
        # 'auto' is the einsum path off the TPU: ask for the kernel
        "kernel_attention": "fused",
        "attention_shape": (2, 2, 128, 64),
        "window": 64,
        "banded_shapes": [(2, 2, 128, 64)],
        "flash_shape": None,  # the stock kernel has no interpreter switch
    }


def run_dir_of(job: str) -> str:
    return os.path.join(WORK, "runs", job)


def train_overrides(size: dict, chips: int, job: str, mode: str, extra=()) -> list:
    """The ``main.py`` command line of one training job. Everything not
    named here is the config's default (prefetch, AOT warmup, health
    guard, telemetry, save with the async checkpoint)."""
    return [
        f"train={mode}",
        "data=synthetic",
        *size["model"],
        f"train.max_length={size['seq']}",
        f"train.batch_size={size['batch']}",
        # one round commits one gradient per chip and accumulation step
        f"train.nb_steps_tot={size['rounds'] * chips}",
        # Schedule override: the config warms up over 1000 steps, so a
        # run of a few rounds would train at a learning rate near zero
        # and its loss could not be told from noise.
        "train.warmup=0",
        f"+hydra.run.dir={run_dir_of(job)}",
        *extra,
    ]


# -- children: each runs in its own process and may import JAX ----------------


def device_gate(chips: int, rehearse: bool) -> dict:
    """First thing a child does: see what JAX found."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if not rehearse:
        check(
            device["platform"] == "tpu",
            f"JAX found no TPU: {device} (chip_smoke.py does not run on a CPU)",
        )
    check(
        device["count"] == chips,
        f"expected {chips} device(s), JAX reports {device['count']}"
        + ("" if chips == 4 else "; the four-chip path is --chips 4"),
    )
    return device


def peak_device_bytes() -> dict:
    """The largest peaks over the local devices, as ``memory_stats()``
    reports them: bytes in use (arrays the program holds) and bytes
    reserved (on the TPU, where the executables' scratch memory shows).
    None where the backend reports none."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {
        key: max((s[key] for s in stats if key in s), default=None)
        for key in ("peak_bytes_in_use", "peak_bytes_reserved")
    }


def mosaic_kernels(hlo_text: str) -> list:
    """Names of this repo's Pallas kernels that the executable holds as
    Mosaic custom calls."""
    found = set()
    for line in hlo_text.splitlines():
        if MOSAIC_TARGET in line:
            found.update(n for n in ATTENTION_KERNELS if n in line)
    return sorted(found)


def kernels_job(size: dict, rehearse: bool) -> dict:
    """The attention kernels against the einsum oracle, forward and
    backward, at the model's own attention shape: the first check of their
    numbers that is not the interpreter's (tests/ compares the same pairs
    in interpret mode on the CPU)."""
    import jax
    import jax.numpy as jnp

    from acco_tpu.ops.attention import (
        attention_mask_bias,
        dot_product_attention,
        flash_dot_product_attention,
    )
    from acco_tpu.ops.banded_attention import banded_dot_product_attention
    from acco_tpu.ops.fused_attention import fused_dot_product_attention

    W = size["window"]

    def oracle(L, window):
        bias = attention_mask_bias(L, window)
        return lambda q, k, v: dot_product_attention(q, k, v, bias, scale=1.0)

    # name -> (shape, kernel, the window of its einsum oracle)
    pairs = {
        "fused (global)": (
            size["attention_shape"],
            lambda q, k, v: fused_dot_product_attention(q, k, v, window=0, scale=1.0),
            0,
        ),
    }
    for shape in size["banded_shapes"]:
        pairs[f"banded (window {W}, steps from the shape) at L={shape[2]} D={shape[3]}"] = (
            shape,
            lambda q, k, v: banded_dot_product_attention(q, k, v, window=W, scale=1.0),
            W,
        )
    if size["flash_shape"] is not None:
        pairs["flash (stock kernel, tiles from the shape)"] = (
            size["flash_shape"],
            lambda q, k, v: flash_dot_product_attention(q, k, v, scale=1.0),
            0,
        )

    def fwd_bwd(fn, q, k, v, g):
        def run(q, k, v, g):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(g)

        return jax.jit(run)(q, k, v, g)

    report = {}
    for name, (shape, kernel, window) in pairs.items():
        qkvg = [
            (0.5 * jax.random.normal(key, shape)).astype(jnp.bfloat16)
            for key in jax.random.split(jax.random.PRNGKey(0), 4)
        ]
        got, want = fwd_bwd(kernel, *qkvg), fwd_bwd(oracle(shape[2], window), *qkvg)
        errs = {}
        for label, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            check(bool(jnp.isfinite(a).all()), f"{name} {label}: not finite")
            scale = max(1.0, float(jnp.abs(b).max()))
            errs[label] = float(jnp.abs(a - b).max()) / scale
        report[name] = errs
        say(
            f"kernel {name} vs einsum at (B,H,L,D)={shape} bf16: max error "
            + ", ".join(f"{k} {e:.2e}" for k, e in errs.items())
            + f" of the tensor's scale (tolerance 8 x 2^-8 = {KERNEL_TOL:.2e})"
        )
        worst = max(errs.values())
        check(worst <= KERNEL_TOL, f"{name} disagrees with the einsum oracle: {errs}")
    return {"kernel_errors": report}


def build_one_device_trainer(argv: list):
    """DDP's comparison side on a four-chip host: ``main.py`` always meshes
    every device, so the script builds the trainer itself on one of them
    (the same steps as ``main.run``, plus the mesh)."""
    import logging

    import jax
    import jax.numpy as jnp

    from acco_tpu.configuration import compose_config
    from acco_tpu.data.datasets import load_text_dataset
    from acco_tpu.data.tokenizer import load_tokenizer
    from acco_tpu.models.registry import build_model
    from acco_tpu.parallel.mesh import make_mesh
    from acco_tpu.trainer import DecoupledTrainer

    logging.basicConfig(level=logging.INFO)
    log = logging.getLogger("acco_tpu")
    cfg = compose_config(os.path.join(ROOT, "config"), argv)
    run_dir = cfg.select("hydra.run.dir")
    os.makedirs(run_dir, exist_ok=True)
    model = build_model(
        cfg.model,
        repo_root=ROOT,
        param_dtype=jnp.bfloat16,
        remat=cfg.train.get("remat", False),
        attention=cfg.train.get("use_pallas_attention", "auto"),
        scan_unroll=cfg.train.get("scan_unroll", 1),
    )
    train_ds, eval_ds = load_text_dataset(cfg.data, log)
    return DecoupledTrainer(
        model,
        load_tokenizer(cfg.model.get("tokenizer"), log),
        train_ds,
        eval_ds,
        cfg.train,
        log,
        seed=int(cfg.select("seed", 12345)),
        run_dir=run_dir,
        mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]),
    )


def train_job(
    argv: list,
    *,
    rehearse: bool,
    min_rounds: int,
    expect_kernels,
    one_device: bool = False,
    expect_shards: int = 0,
) -> dict:
    """One training run through the trainer's own loop, then every check
    that needs nothing but this run. ``expect_kernels``: True, the round
    program must hold the attention kernels as Mosaic custom calls; False,
    it must hold none (the einsum side of the comparison)."""
    import jax

    import main as main_module
    from acco_tpu.native import native_available
    from acco_tpu.ops.attention import resolve_attention_impl
    from acco_tpu.ops.losses import real_vocab_of, resolve_fused_loss
    from acco_tpu.utils.checkpoint import latest_checkpoint

    say("command line: python main.py " + " ".join(argv))
    if one_device:
        trainer = build_one_device_trainer(argv)
        summary = trainer.train()
    else:
        trainer, summary = main_module.run(argv)

    report = trainer.compile_report
    check(report is not None and report.complete, "the compile warmup did not run to an end")
    for line in report.log_lines():
        say(line)
    failed = {n: r.error for n, r in report.programs.items() if r.error}
    check(not failed, f"warmup compile failed (the trainer would only retry lazily): {failed}")

    model, step = trainer.model, trainer.step_obj
    attention = resolve_attention_impl(
        model.attention, trainer.max_length, remat=model.remat,
        head_dim=model.config.head_dim,
    )
    fused_loss = resolve_fused_loss(trainer.fused_loss, model, real_vocab_of(model))
    round_name = {"acco": "round_even", "dpu": "round", "ddp": "step"}[trainer.method]
    compiled = step.compiled_programs.get(round_name)
    check(compiled is not None, f"no AOT executable was installed for {round_name!r}")
    hlo = compiled.as_text()
    kernels = mosaic_kernels(hlo)
    say(
        f"implementations: attention={attention} (requested "
        f"{model.attention!r}), const_len_batch={trainer.const_len_batch}, "
        f"fused_loss={fused_loss} (requested {trainer.fused_loss!r}), "
        f"comm_impl={trainer.comm_impl}; Mosaic custom calls in {round_name}: "
        f"{kernels or 'none'}"
    )
    if expect_kernels and not rehearse:
        check(attention == "fused", f"attention resolved to {attention!r} on a TPU, not the kernel")
        missing = sorted(set(ATTENTION_KERNELS) - set(kernels))
        check(
            not missing,
            f"the compiled {round_name} program holds no Mosaic custom call for {missing}: "
            "the einsum path was chosen in their place",
        )
    if expect_kernels is False:
        check(attention == "xla" and not kernels, "the einsum side of the comparison ran a kernel")

    first, last = summary["first_loss"], summary["final_loss"]
    check(math.isfinite(first) and math.isfinite(last), f"loss not finite: {first} -> {last}")
    check(summary["skipped_rounds"] == 0, f"{summary['skipped_rounds']} round(s) skipped by the guard")
    check(summary["rounds"] >= min_rounds, f"only {summary['rounds']} rounds ran")
    check(last < first, f"loss did not fall: {first} -> {last}")

    ckpt = latest_checkpoint(trainer.ckpt_dir)  # the newest step that validates
    check(ckpt is not None, f"no complete checkpoint under {trainer.ckpt_dir}")
    check(os.path.isfile(os.path.join(ckpt, "meta.json")), f"{ckpt}: meta.json not committed")
    ckpt_bytes = sum(
        os.path.getsize(os.path.join(d, f)) for d, _, fs in os.walk(ckpt) for f in fs
    )

    # what the parent compares across jobs
    result = {
        "rounds": summary["rounds"],
        "first_loss": first,
        "last_loss": last,
        "setup_s": summary["setup_s"],
        "compile_ms": {n: round(r.compile_ms) for n, r in report.programs.items()},
        "cache_hits": report.cache["hits"],
        "cache_misses": report.cache["misses"],
        "cache_dir": report.cache_dir,
        "checkpoint_bytes": ckpt_bytes,
    }

    if expect_shards:
        from acco_tpu.analysis.census import check_census
        from acco_tpu.analysis.programs import ring_comm_bytes

        vector = trainer.final_state.zero1.opt.params
        shards = vector.addressable_shards
        devices = sorted(s.device.id for s in shards)
        spans = sorted((s.index[0].start or 0, s.index[0].stop) for s in shards)
        say(
            f"ZeRO-1 optimizer vector: {vector.shape[0]} elements, "
            f"{len(shards)} addressable shards on devices {devices}, spans {spans}"
        )
        check(
            len(shards) == expect_shards and len(set(devices)) == expect_shards,
            f"expected {expect_shards} shards on {expect_shards} distinct devices",
        )
        check(
            len(set(spans)) == expect_shards
            and all(b - a == vector.shape[0] // expect_shards for a, b in spans),
            f"the shards do not tile the vector: {spans}",
        )
        padded = step.geom.padded_size
        # bf16 parameters travel as 2 bytes on the TPU; the CPU backend
        # of a rehearsal widens them to 4 (analysis/programs.py)
        param_bytes = 4 if jax.default_backend() == "cpu" else 2
        census = check_census(
            hlo,
            ring_comm_bytes(padded, step.num_shards, param_bytes),
            # bookkeeping psums are a few elements, a gradient-path hop
            # an eighth of the vector (a rehearsal's vector is small)
            small_elems=min(1_000_000, padded // 64),
        )
        say(f"collective census of {round_name}: {census.summary()}; kinds {census.kinds}")
        check(census.large_ops > 0, "a dp=4 round with no gradient-path collective")
        # The manual ring moves exactly the model's bytes. What the
        # compiler makes of the stock reduce-scatter + all-gather is its
        # own choice (for v5e:2x2 it emits two all-reduces, twice the
        # bytes), so that census is printed and not held to the model.
        if trainer.comm_impl == "ring":
            check(census.ok, f"ring census off the comm model: {census.errors}")

    peaks = peak_device_bytes()
    say(
        f"{trainer.method}: {summary['rounds']} rounds, loss {first:.4f} -> {last:.4f}, "
        f"skipped {summary['skipped_rounds']}, set-up {summary['setup_s']:.1f} s, "
        f"train() {summary['total_time_s']:.1f} s, cache {result['cache_hits']} hit(s) / "
        f"{result['cache_misses']} miss(es) in {result['cache_dir']}, peak device memory "
        f"{peaks['peak_bytes_in_use']} bytes in use / {peaks['peak_bytes_reserved']} "
        f"reserved, checkpoint {ckpt_bytes} bytes committed, collate "
        f"{'native (built by g++)' if native_available() else 'numpy'}"
        f" (g++ on the machine: {shutil.which('g++') is not None})"
    )
    # the run's checkpoint has served its purpose: keep the disk small
    shutil.rmtree(os.path.join(trainer.run_dir, "checkpoints"), ignore_errors=True)
    return result


def jobs_for(chips: int, size: dict) -> dict:
    """Job name -> (function, keyword arguments), in the order they run."""

    def train(job, mode, extra=(), attention=size["kernel_attention"], **kw):
        kw.setdefault("expect_kernels", True)
        extra = (f"train.use_pallas_attention={attention}", *extra)
        return (
            train_job,
            dict(
                argv=train_overrides(size, chips, job, mode, extra),
                min_rounds=size["rounds"],
                **kw,
            ),
        )

    if chips == 1:
        return {
            "kernels": (kernels_job, dict(size=size)),
            "acco": train("acco", "acco"),
            "dpu": train("dpu", "dpu"),
            "ddp": train("ddp", "ddp"),
            # Without the kernels the [B, H, L, L] scores of twelve
            # layers do not fit the chip's 16 GB at this batch (the chip's
            # compiler says so, no chip needed): the einsum side recomputes
            # them in the backward pass, which changes no value it computes.
            "acco_einsum": train(
                "acco_einsum", "acco", ("train.remat=dots",),
                attention="false", expect_kernels=False,
            ),
            "acco_warm": train("acco_warm", "acco"),
        }
    return {
        "ddp": train("ddp", "ddp", expect_shards=chips),
        # the same global batch, rounds and schedule as the dp=4 side: four
        # accumulation steps on the one device stand for the four chips
        "ddp_one_device": train(
            "ddp_one_device", "ddp", (f"train.n_grad_accumulation={chips}",),
            one_device=True,
        ),
        "acco_ring": train("acco_ring", "acco", ("train.comm_impl=ring",), expect_shards=chips),
        "acco_xla": train("acco_xla", "acco", ("train.comm_impl=xla",), expect_shards=chips),
        "dpu": train("dpu", "dpu", expect_shards=chips),
    }


def child(args) -> int:
    size = (rehearsal_size if args.rehearse else real_size)(args.chips)
    device = device_gate(args.chips, args.rehearse)
    fn, kwargs = jobs_for(args.chips, size)[args.job]
    # a run dir of an earlier call would only confuse the checkpoint check
    shutil.rmtree(run_dir_of(args.job), ignore_errors=True)
    result = fn(rehearse=args.rehearse, **kwargs)
    result["device"] = device
    with open(args.out, "w") as f:
        json.dump(result, f)
    return 0


# -- parent: never imports JAX ------------------------------------------------


def run_child(job: str, args, deadline: float) -> dict:
    out = os.path.join(WORK, "results", f"{job}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    if os.path.exists(out):
        os.remove(out)
    cmd = [
        sys.executable, os.path.abspath(__file__), "--job", job, "--out", out,
        "--chips", str(args.chips),
    ] + (["--rehearse"] if args.rehearse else [])
    left = deadline - time.time()
    check(left > 30, f"no time left for job {job!r} (budget {BUDGET_S:.0f} s)")
    say(f"--- job {job} (fresh process, {left:.0f} s left) ---")
    env = dict(os.environ)
    if args.rehearse:
        env.setdefault("ACCO_FUSED_ATTN_INTERPRET", "1")  # kernels off the TPU
    t0 = time.time()
    try:
        rc = subprocess.run(cmd, cwd=ROOT, timeout=left, env=env).returncode
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"job {job!r} was killed at the time limit") from None
    check(rc == 0, f"job {job!r} exited with code {rc}")
    check(os.path.exists(out), f"job {job!r} wrote no result")
    with open(out) as f:
        result = json.load(f)
    result["process_s"] = time.time() - t0
    say(f"--- job {job} passed in {result['process_s']:.0f} s ---")
    return result


def agree(what: str, a: dict, b: dict) -> None:
    """First and last loss of two runs that differ only in the order of
    their arithmetic."""
    for key, rtol in (("first_loss", FIRST_LOSS_RTOL), ("last_loss", LAST_LOSS_RTOL)):
        x, y = a[key], b[key]
        diff = abs(x - y) / max(abs(x), abs(y))
        say(
            f"{what}: {key} {x:.5f} vs {y:.5f}, relative difference {diff:.2e} "
            f"(tolerance 2^-8 / {U_BF16 / rtol:.0f} = {rtol:.2e})"
        )
        check(diff <= rtol, f"{what}: {key} differs by {diff:.2e}")


def parent(args) -> int:
    for name in ("main.py", "acco_tpu", "config"):
        check(
            os.path.exists(os.path.join(ROOT, name)),
            f"{name} is not beside chip_smoke.py: the script checks the repo, not itself",
        )
    if not args.rehearse:
        set_switches = [s for s in INTERPRET_SWITCHES if os.environ.get(s)]
        check(not set_switches, f"interpret-mode switch set in the environment: {set_switches}")
    deadline = time.time() + BUDGET_S
    size = (rehearsal_size if args.rehearse else real_size)(args.chips)
    results = {}
    for job in jobs_for(args.chips, size):
        results[job] = run_child(job, args, deadline)

    if args.chips == 1:
        agree("ACCO, kernels vs einsum attention", results["acco"], results["acco_einsum"])
        cold, warm = results["acco"], results["acco_warm"]
        say(
            f"warm relaunch: set-up {cold['setup_s']:.1f} s with {cold['cache_misses']} "
            f"miss(es) -> {warm['setup_s']:.1f} s with {warm['cache_misses']} miss(es), "
            f"{warm['cache_hits']} hit(s); compile ms {cold['compile_ms']} -> {warm['compile_ms']}"
        )
        check(
            warm["cache_misses"] == 0 and warm["cache_hits"] >= len(warm["compile_ms"]),
            f"the relaunch compiled again: {warm['cache_misses']} miss(es) in {warm['cache_dir']}",
        )
    else:
        agree("DDP, dp=4 vs one device at the same global batch",
              results["ddp"], results["ddp_one_device"])
        agree("ACCO dp=4, manual ring vs stock collectives",
              results["acco_ring"], results["acco_xla"])
    device = next(iter(results.values()))["device"]
    if args.rehearse:
        say(f"rehearsal passed on {device}: not a chip run, no result line")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on any device; never prints the success line")
    ap.add_argument("--job", help=argparse.SUPPRESS)  # set by the parent only
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        return child(args) if args.job else parent(args)
    except SmokeFailure as exc:
        say(f"chip_smoke FAILED: {exc}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
