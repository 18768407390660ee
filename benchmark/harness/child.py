"""One schedule of a cell, in a process of its own: the only code of the
benchmark that imports JAX and the program.

It drives the entry point users call, ``main.run(argv)``, with a command line
built from the cell's file, lets the trainer's own loop run, stops it the way a
preemption would, and then reads what the program left behind: its
``trace_<id>.json``, its metrics registry (sampled meanwhile), its
``WarmupReport``, the profiler's trace. No private timing loop, no call into a
step function.
"""

from __future__ import annotations

import bisect
import glob
import json
import math
import os
import shutil
import time
from dataclasses import dataclass

from benchmark.harness import window as win
from benchmark.harness.manifest import WORK, BenchFailure, Manifest, ManifestError, load_peaks
from benchmark.harness.sampler import RegistrySampler, SamplerLog, SamplerPolicy

OUT_OF_REACH = 1_000_000_000  # train.nb_steps_tot: the sampler ends the run
# the trainer starts its profile after 2 rounds (ACCO) or 1 (DDP, DPU)
PROFILE_AFTER = 2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(message: str) -> None:
    print(message, flush=True)


def load_rehearsal() -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "rehearsal.json")) as f:
        return json.load(f)


def rehearsal_cell(cell: dict, config: dict) -> tuple[dict, dict]:
    """The cell and configuration at the rehearsal's tiny size: same
    schedules, same code paths, nothing that means anything as a number."""
    tiny = load_rehearsal()
    os.makedirs(WORK, exist_ok=True)
    model_path = os.path.join(WORK, "rehearsal_model.json")
    with open(model_path, "w") as f:
        json.dump(tiny["model"], f)
    cell = {
        **cell,
        **{k: tiny[k] for k in ("seq_len", "batch_per_chip", "overrides", "warmup_rounds",
                                "ref_round", "trace_rounds")},
    }
    config = {**config, "model_path": model_path, "model": tiny["model"]}
    return cell, config


def device_gate(chips: int, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearse:
        return device
    if device["platform"] != "tpu":
        raise BenchFailure(f"JAX found no TPU: {device}. The benchmark does not run on a CPU")
    if device["count"] != chips:
        raise BenchFailure(f"the cell needs {chips} chip(s), JAX reports {device['count']}")
    return device


def peak_device_bytes() -> dict:
    """Largest peaks over the local devices, as ``memory_stats()`` has them;
    None where the backend reports none."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = {
        key: max((s[key] for s in stats if key in s), default=None)
        for key in ("peak_bytes_in_use", "peak_bytes_reserved")
    }
    # in use counts the arrays the program holds, reserved shows the
    # executables' scratch (PR 21). Each is a floor of what the chip held, so
    # the result line's memory_peak_bytes is the larger; the per-layer
    # metrics report the two under their own names
    seen = [v for v in peaks.values() if v is not None]
    peaks["peak_bytes"] = max(seen) if seen else None
    return peaks


def build_argv(cell: dict, config: dict, schedule: dict, seed: int, run_dir: str,
               trace: bool) -> list[str]:
    """The ``main.py`` command line of one schedule. What is not named here
    or in the cell's file is the config's default."""
    argv = [
        *schedule["overrides"],
        "data=synthetic",
        f"model.config_path={config['model_path']}",
        "model.tokenizer=byte",
        f"seed={seed}",
        f"data.synthetic_seed={seed}",
        f"train.max_length={cell['seq_len']}",
        f"train.batch_size={cell['batch_per_chip']}",
        "train.save=false",
        "train.eval=false",
        f"train.nb_steps_tot={OUT_OF_REACH}",
        *cell["overrides"],
        f"+hydra.run.dir={run_dir}",
    ]
    if trace:
        argv.append(f"train.profile_steps={cell['trace_rounds']}")
    return argv


def warmup_report_dict(report) -> dict | None:
    if report is None:
        return None
    return {
        "complete": bool(report.complete),
        "ok": bool(report.ok),
        "cache": dict(report.cache),
        "cache_dir": report.cache_dir,
        "programs": {
            name: {"lower_ms": rec.lower_ms, "compile_ms": rec.compile_ms, "error": rec.error}
            for name, rec in report.programs.items()
        },
    }


def census_of(trainer, schedule: dict) -> dict | None:
    """The program's own census of its compiled round: of the program the
    schedule's entry in the cell's file names under ``round_program``, where
    it names one and the state is sharded (dp > 1)."""
    import jax

    from acco_tpu.analysis.census import check_census
    from acco_tpu.analysis.programs import ring_comm_bytes

    step = trainer.step_obj
    if getattr(step, "num_shards", 1) <= 1:
        return None
    name = schedule.get("round_program")
    compiled = step.compiled_programs.get(name) if name else None
    if compiled is None:
        return None
    padded = step.geom.padded_size
    # bf16 parameters travel as 2 bytes on the TPU; the CPU backend widens them
    param_bytes = 4 if jax.default_backend() == "cpu" else 2
    census = check_census(
        compiled.as_text(),
        ring_comm_bytes(padded, step.num_shards, param_bytes),
        small_elems=min(1_000_000, padded // 64),
    )
    say(f"collective census of the compiled round: {census.summary()}; kinds {census.kinds}")
    return {
        "measured_bytes": census.measured_bytes,
        "expected_bytes": census.expected_bytes,
        "large_ops": census.large_ops,
        "kinds": census.kinds,
    }


def read_device_trace(run_dir: str, rounds: int, rehearse: bool):
    from benchmark.harness import xplane

    paths = sorted(glob.glob(os.path.join(run_dir, "profile", "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise BenchFailure(f"the trainer wrote no profiler trace under {run_dir}/profile")
    regexes = {}
    if rehearse:
        tiny = load_rehearsal()
        regexes = dict(plane_regex=tiny["device_plane_regex"], line_regex=tiny["ops_line_regex"])
    trace = xplane.DeviceTrace.from_ops(xplane.read_ops(paths[-1], **regexes), rounds)
    if trace.devices == 0 or trace.busy_s() <= 0:
        raise BenchFailure(f"{paths[-1]}: no operation ran on a device in the traced rounds")
    return trace


def fence_losses(samples: list, fences: list, offset: float) -> tuple[dict, dict]:
    """Match each boundary the sampler saw to the fence that ended last
    before it: ``({round: loss}, {round: sample})``."""
    losses, by_round = {}, {}
    ends = [f.end_us for f in fences]
    for s in samples:
        # 1 ms of slack between the two clocks
        i = bisect.bisect_right(ends, (s.t_epoch - offset) * 1e6 + 1000.0)
        if i:
            losses[fences[i - 1].round] = s.loss
            by_round[fences[i - 1].round] = s
    return losses, by_round


@dataclass
class Observed:
    """What the benchmark's own threads and listeners saw while ``main.run``
    ran, and where the trainer's clock sits on the epoch."""

    trainer: object
    summary: dict
    sampler: SamplerLog
    compile_times: list  # epoch of every backend-compile event of the process
    offset: float  # epoch seconds at the tracer's zero
    memory: dict
    counters: dict


def drive(argv: list[str], policy: SamplerPolicy) -> Observed:
    """Run the trainer's own loop through ``main.run`` under the sampler."""
    import jax

    import main as main_module
    from acco_tpu.telemetry import REGISTRY

    compile_times: list[float] = []

    def on_duration(event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            compile_times.append(time.time())

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    sampler = RegistrySampler(REGISTRY, policy)
    sampler.start()
    try:
        trainer, summary = main_module.run(argv)
    finally:
        sampler.halt()
        sampler.join(timeout=5)
    return Observed(
        trainer=trainer,
        summary=summary,
        sampler=sampler.log,
        compile_times=compile_times,
        # the tracer's clock is microseconds since its construction
        offset=time.time() - trainer.tracer.now_us() / 1e6,
        memory=peak_device_bytes(),
        counters={k: v for k, v in REGISTRY.snapshot().items() if not isinstance(v, dict)},
    )


def judge(seen: Observed, window, losses: dict, sample_at: dict, ref_loss, ref_round: int) -> list:
    """Everything that makes a run incorrect, reference check apart."""
    summary, problems = seen.summary, []
    if not summary.get("interrupted"):
        problems.append("the run ended by itself, not by the benchmark's stop")
    if summary["skipped_rounds"] or summary["rollbacks"]:
        problems.append(
            f"{summary['skipped_rounds']} round(s) skipped by the guard, "
            f"{summary['rollbacks']} rollback(s)"
        )
    bad = [r for r, v in losses.items() if not math.isfinite(v)]
    if bad or not math.isfinite(summary["first_loss"]):
        problems.append(f"loss not finite at round(s) {bad or 'first'}")
    if ref_loss is not None and not ref_loss < summary["first_loss"]:
        problems.append(
            f"loss at round {ref_round} ({ref_loss:.4f}) is not below the first "
            f"round's ({summary['first_loss']:.4f})"
        )
    t_first = seen.offset + window.first.end_us / 1e6
    t_last = seen.offset + window.last.end_us / 1e6
    in_window = [t for t in seen.compile_times if t_first <= t <= t_last]
    if in_window:
        problems.append(f"{len(in_window)} compilation(s) inside the window")
    first_s, last_s = sample_at.get(window.first.round), sample_at.get(window.last.round)
    if first_s is None or last_s is None:
        problems.append("the sampler did not see the window's first or last boundary")
    elif (first_s.cache_requests, first_s.cache_hits) != (last_s.cache_requests, last_s.cache_hits):
        problems.append(
            f"compile-cache counters moved inside the window: requests "
            f"{first_s.cache_requests:.0f} -> {last_s.cache_requests:.0f}"
        )
    report = seen.trainer.compile_report
    if report is None or not report.complete or not report.ok:
        problems.append("the AOT warmup did not run to an end without error")
    elif not getattr(seen.trainer.step_obj, "compiled_programs", {}):
        problems.append("no AOT program was installed")
    return problems


def layer_metrics(manifest: Manifest, cell: dict, schedule: dict, ctx: dict) -> dict:
    """Every per-layer metric of the cell that this child is the one to read:
    a metric is reported only where, and from the schedule in which, the
    end-to-end metric it moves is. A reader that finds nothing returns None and
    the metric is left out."""
    out = {}
    for spec in manifest.layer_metrics(cell["name"]):
        moved = cell["end_to_end"].get(spec["moves"])
        if moved is None or moved["schedule"] not in ("*", schedule["name"]):
            continue
        value = manifest.reducer(spec["reducer"])(ctx, spec.get("args", {}))
        if value is not None:
            out[spec["name"]] = float(value)
    return out


def run(args) -> int:
    t_launch = args.t0 if args.t0 else time.time()
    manifest = Manifest()
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    if args.rehearse:
        cell, config = rehearsal_cell(cell, config)
    schedule = cell["schedules"][args.schedule]
    trace_on = bool(args.trace)

    device = device_gate(cell["chips"], args.rehearse)
    try:
        peaks = load_peaks(device["kind"])
    except ManifestError:
        if not args.rehearse:
            raise
        peaks = None  # a rehearsal has no peaks and reports no share of them

    run_dir = os.path.join(WORK, "runs", cell["name"], schedule["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    argv = build_argv(cell, config, schedule, args.seed, run_dir, trace_on)
    say("command line: python main.py " + " ".join(argv))
    # in a traced run the window opens only after the traced rounds
    warmup_rounds = cell["warmup_rounds"]
    if trace_on:
        warmup_rounds = max(warmup_rounds, PROFILE_AFTER + cell["trace_rounds"])
    seen = drive(
        argv,
        # every schedule of a cell measures for the whole --seconds
        SamplerPolicy(warmup_rounds, args.seconds, cell["ref_round"]),
    )
    trainer, summary, memory = seen.trainer, seen.summary, seen.memory

    with open(trainer.trace_path, encoding="utf-8") as f:
        trace = json.load(f)
    t_stop = seen.sampler.t_stop
    window = win.measure_window(
        trace, warmup_rounds, None if t_stop is None else (t_stop - seen.offset) * 1e6
    )
    all_fences = win.fences(trace)
    losses, sample_at = fence_losses(seen.sampler.boundaries, all_fences, seen.offset)
    quantities = {
        "tokens_per_s_per_chip": win.tokens_per_s_per_chip(
            window, cell["batch_per_chip"], cell["seq_len"]
        ),
        "setup_s": seen.offset + window.first.end_us / 1e6 - t_launch,
        "window_s": window.seconds,
        "rounds": window.rounds,
        "first_loss": summary["first_loss"],
    }
    problems = []
    try:
        quantities["loss_at_ref_round"] = win.loss_at_ref_round(
            losses, cell["ref_round"], every=all_fences[1].round - all_fences[0].round
        )
    except win.WindowError as exc:
        problems.append(str(exc))
    ref_loss = quantities.get("loss_at_ref_round")
    problems += judge(seen, window, losses, sample_at, ref_loss, cell["ref_round"])
    if trainer.compile_report is not None:
        for line in trainer.compile_report.log_lines():
            say(line)

    result = {"device": device, "schedule": schedule["name"]}
    if trace_on:
        device_trace = read_device_trace(run_dir, cell["trace_rounds"], args.rehearse)
        ctx = {
            "trace": trace,
            "window": window,
            "counters": seen.counters,
            "warmup_report": warmup_report_dict(trainer.compile_report),
            "memory": memory,
            "quantities": quantities,
            "cell": cell,
            "config": config,
            "peaks": peaks,
            "say": say,
            "device_trace": device_trace,
            "census": census_of(trainer, schedule),
        }
        result.update(
            busy_s=device_trace.busy_s(),
            window_s=device_trace.window_s(),
            breakdown=device_trace.breakdown(),
            layer_metrics=layer_metrics(manifest, cell, schedule, ctx),
        )

    say(
        f"{schedule['name']}: window of {window.rounds} rounds in {window.seconds:.3f} s between "
        f"{window.n_fences} fences (rounds {window.first.round}..{window.last.round}), "
        f"{quantities['tokens_per_s_per_chip']:.1f} tokens/s/chip, first loss "
        f"{summary['first_loss']:.4f}, loss at round {cell['ref_round']} {ref_loss}, set-up "
        f"{quantities['setup_s']:.2f} s (trainer's own: {summary['setup_s']:.2f} s), "
        f"{len(seen.compile_times)} compile event(s) before the window's end, peak device memory "
        f"{memory['peak_bytes_in_use']} bytes in use / {memory['peak_bytes_reserved']} reserved"
    )

    if schedule.get("reference_check"):
        from benchmark.harness import refcheck

        # the trainer's state goes first: the float32 reference needs the room
        trainer.final_state = None
        ref = refcheck.check(trainer, config, cell["seq_len"], args.seed, say=say)
        result["reference"] = ref
        if not ref["ok"]:
            problems.append("the program disagrees with the float32 reference")

    for p in problems:
        say(f"NOT CORRECT: {p}")
    result.update(
        correct=not problems,
        problems=problems,
        attempted=window.rounds,
        failed=int(summary["skipped_rounds"]),
        quantities=quantities,
        memory_peak_bytes=memory["peak_bytes"],
        process_s=time.time() - t_launch,
    )
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f)
    return 0


def main(args) -> int:
    try:
        return run(args)
    except (BenchFailure, ManifestError, win.WindowError) as exc:
        say(f"benchmark FAILED: {exc}")
        return 1

