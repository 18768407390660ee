"""Summarize a telemetry ``trace_*.json`` into one terminal report.

Reads the Chrome/Perfetto trace a run wrote (``acco_tpu/telemetry``),
validates it, and prints three tables:

1. **top spans** — per span name: count, total/mean/median/max wall, so
   "where did the host's time go" has an answer without opening a viewer;
2. **logging boundaries** — what each ``train/log_boundary_sync`` fence
   learned (round, loss, grad norm, committed grads, skipped rounds) and
   how long the fence and the host work after it took;
3. **set-up** — the main thread's set-up spans from the launch to the first
   round (what the run's ``set-up`` log line holds), beneath them each warmed
   program's ``compile/lower`` and ``compile/compile`` on its warmup thread
   (how long it waited for a worker, hit or miss) and the main thread's
   ``compile/backend`` events: what compiled lazily on the critical path.

Where the DEVICE's time went, and which host span each of its idle gaps
lies under, is read from the ``jax.profiler`` capture the trace names
under ``otherData.profile_dir`` (``train.profile_steps``), by the
benchmark's reducers (``benchmark/reducers/``).

Pure host-side: no jax import (the telemetry package is jax-free by
contract), safe on any machine.

Usage::

    python tools/trace_report.py                      # newest outputs/**/trace_*.json
    python tools/trace_report.py outputs/run/trace_x.json
    python tools/trace_report.py --top 20 path.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from acco_tpu.telemetry import (  # noqa: E402
    INSIDE_TRAINER_INIT,
    setup_phases,
    validate_trace,
)

def newest_trace(root: str = REPO) -> str | None:
    paths = glob.glob(os.path.join(root, "outputs", "**", "trace_*.json"),
                      recursive=True)
    paths = [p for p in paths if not p.endswith(".tmp")]
    return max(paths, key=os.path.getmtime) if paths else None


def _fmt_ms(v) -> str:
    return "-" if v is None else f"{v:,.1f}"


def span_table(events: list[dict], top: int) -> list[str]:
    by_name: dict[str, list[float]] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        by_name.setdefault(ev.get("name", "?"), []).append(
            ev.get("dur", 0.0) / 1e3
        )
    rows = sorted(
        by_name.items(), key=lambda kv: -sum(kv[1])
    )[:top]
    lines = [
        "top spans (by total wall):",
        "  {:<28} {:>6} {:>12} {:>9} {:>9} {:>9}".format(
            "span", "count", "total ms", "mean", "median", "max"
        ),
    ]
    for name, durs in rows:
        lines.append(
            "  {:<28} {:>6} {:>12} {:>9} {:>9} {:>9}".format(
                name[:28], len(durs), _fmt_ms(sum(durs)),
                _fmt_ms(sum(durs) / len(durs)), _fmt_ms(median(durs)),
                _fmt_ms(max(durs)),
            )
        )
    if not rows:
        lines.append("  (no complete events)")
    return lines


def boundary_table(events: list[dict], last: int = 8) -> list[str]:
    """The last ``last`` logging boundaries: what the fence learned and
    what the fence and the host work after it cost."""
    fences = sorted(
        (e for e in events
         if e.get("ph") == "X" and e.get("name") == "train/log_boundary_sync"),
        key=lambda e: e["ts"],
    )
    hosts = sorted(
        (e for e in events
         if e.get("ph") == "X" and e.get("name") == "train/log_boundary_host"),
        key=lambda e: e["ts"],
    )
    if not fences:
        return ["logging boundaries: (none in this trace)"]
    lines = [
        f"logging boundaries (last {min(last, len(fences))} of {len(fences)}):",
        "  {:>7} {:>10} {:>10} {:>10} {:>8} {:>9} {:>9}".format(
            "round", "loss", "grad norm", "committed", "skipped",
            "fence ms", "host ms"
        ),
    ]
    for fence in fences[-last:]:
        args = fence.get("args") or {}
        end = fence["ts"] + fence.get("dur", 0.0)
        # the boundary's host span begins where its fence ended
        host = next((h for h in hosts if 0 <= h["ts"] - end < 1000.0), None)

        def num(key, fmt):
            return format(args[key], fmt) if key in args else "-"

        lines.append(
            "  {:>7} {:>10} {:>10} {:>10} {:>8} {:>9} {:>9}".format(
                num("round", "d"), num("loss", ".4f"), num("grad_norm", ".3f"),
                num("committed", ".0f"), num("skipped_rounds", "d"),
                _fmt_ms(fence.get("dur", 0.0) / 1e3),
                _fmt_ms(host["dur"] / 1e3 if host else None),
            )
        )
    return lines


def setup_table(events: list[dict]) -> list[str]:
    """Set-up by phase, then the warmup threads' programs and the lazy
    compiles, all before the first ``train/dispatch``."""
    phases = setup_phases(events)
    if not phases:
        return ["set-up: (no setup/* span in this trace)"]
    spans = [e for e in events if e.get("ph") == "X"]
    first_dispatch = min(
        (e["ts"] for e in spans if e["name"] == "train/dispatch"),
        default=float("inf"),
    )
    lines = ["set-up (main thread, s):"]
    for phase, seconds in phases.items():
        indent = "  " if phase in INSIDE_TRAINER_INIT else ""
        lines.append("  {}{:<18} {:>10.3f}".format(indent, phase, seconds))
    for name, said in (("setup/summary_writer", "the writer"),
                       ("compile/warmup_join", "the join learned")):
        span = next((e for e in spans if e["name"] == name), None)
        if span and span.get("args"):
            lines.append(f"  {said}: " + ", ".join(
                f"{k}={v}" for k, v in span["args"].items()
            ))
    programs: dict[str, dict] = {}
    for e in spans:
        if e["name"] in ("compile/lower", "compile/compile"):
            programs.setdefault(e["args"]["program"], {})[e["name"]] = e
    if programs:
        lines.append("  warmup threads (s):")
        lines.append("    {:<14} {:>8} {:>8} {:>8}  {}".format(
            "program", "waited", "lower", "compile", "cache"))
        for name, got in programs.items():
            lower, comp = got.get("compile/lower"), got.get("compile/compile")
            first = lower or comp
            args = (comp or {}).get("args", {})
            lines.append("    {:<14} {:>8.3f} {:>8} {:>8}  {}".format(
                name, (first["ts"] - first["args"]["submitted_us"]) / 1e6,
                "-" if lower is None else f"{lower['dur'] / 1e6:.3f}",
                "-" if comp is None else f"{comp['dur'] / 1e6:.3f}",
                "hit" if args.get("hits") and not args.get("misses")
                else "miss" if args.get("misses") else "-",
            ))
    # the main thread's backend compiles: no warmed program owns them, the
    # critical path compiled (or deserialised) them lazily
    main = next((e["tid"] for e in spans if e["name"] in
                 ("setup/trainer_init", "setup/state_init")), None)
    bare = [
        e for e in spans
        if e["name"] == "compile/backend" and e["tid"] == main
        and e["ts"] < first_dispatch
    ]
    if bare:
        longest = max(bare, key=lambda e: e["dur"])
        lines.append(
            "  lazy compiles before the first dispatch: {} event(s), {:.3f} s "
            "(longest {:.3f} s at {:.3f} s)".format(
                len(bare), sum(e["dur"] for e in bare) / 1e6,
                longest["dur"] / 1e6, longest["ts"] / 1e6,
            )
        )
    return lines


def report(path: str, top: int = 12) -> list[str]:
    with open(path, encoding="utf-8") as f:
        trace = json.load(f)
    problems = validate_trace(trace)
    events = trace.get("traceEvents", [])
    other = trace.get("otherData") or {}
    lines = [
        f"== trace report: {path} ==",
        "process={} events={} dropped={} valid={}".format(
            other.get("process", "?"), len(events),
            other.get("dropped_events", 0),
            "yes" if not problems else f"NO ({len(problems)} problems)",
        ),
    ]
    for p in problems[:5]:
        lines.append(f"  ! {p}")
    lines.append("")
    lines += span_table(events, top)
    lines.append("")
    lines += boundary_table(events)
    lines.append("")
    lines += setup_table(events)
    if other.get("profile_dir"):
        lines.append("")
        lines.append(
            "device profile of rounds {}: {}".format(
                other.get("profiled_rounds"), other["profile_dir"]
            )
        )
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "trace", nargs="?",
        help="trace json (default: newest outputs/**/trace_*.json)",
    )
    ap.add_argument("--top", type=int, default=12,
                    help="span rows to show (default 12)")
    args = ap.parse_args(argv)
    path = args.trace or newest_trace()
    if path is None or not os.path.exists(path):
        print("no trace found (run a training session first, or pass a path)")
        return 1
    print("\n".join(report(path, top=args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
