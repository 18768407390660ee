#!/usr/bin/env python3
"""The benchmark's command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown`` when
traced): with ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics. A machine with no TPU, or with fewer chips than the cell
asks for, or a directory that holds the benchmark and nothing else of the repo,
ends the run non-zero with no such line.

One process per chip. This parent never imports JAX: it starts one child per
schedule of the cell (``--child``), strictly one after another, and waits for
each. The children drive ``main.run`` (``harness/child.py``).

``--rehearse`` runs the same path at a tiny size on whatever devices JAX finds
(the CPU), to find wrong paths and arguments before chip time is spent. It
never prints the result line.

This file names no cell, configuration or metric: those are the files that
``BENCHMARK.json`` lists (``harness/manifest.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.manifest import (  # noqa: E402
    WORK,
    BenchFailure,
    Manifest,
    ManifestError,
)

CHILD_LIMIT_S = 1100.0  # the driver allows a compiling run 1200 s
PROGRAM_FILES = ("main.py", "acco_tpu", "config")


def say(message: str) -> None:
    print(message, flush=True)


def run_child(args, index: int, schedule: dict, deadline: float) -> dict:
    out = os.path.join(WORK, "results", f"{args.workload}.{schedule['name']}.json")
    if os.path.exists(out):
        os.remove(out)
    t0 = time.time()
    cmd = [
        sys.executable, os.path.abspath(__file__), "--child", str(index),
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", str(args.trace),
        "--t0", repr(t0), "--out", out,
    ] + (["--rehearse"] if args.rehearse else [])
    left = deadline - t0
    if left < 30:
        raise BenchFailure(f"no time left for schedule {schedule['name']!r}")
    say(f"--- schedule {schedule['name']} (fresh process) ---")
    try:
        rc = subprocess.run(cmd, cwd=ROOT, timeout=left).returncode
    except subprocess.TimeoutExpired:
        # subprocess.run has killed and reaped the child
        raise BenchFailure(f"schedule {schedule['name']!r} was killed at the time limit") from None
    if rc != 0 or not os.path.exists(out):
        raise BenchFailure(f"schedule {schedule['name']!r} exited with code {rc} and no result")
    with open(out) as f:
        return json.load(f)


def combine(manifest: Manifest, cell: dict, children: dict, trace: bool) -> dict:
    """The result line from the children's results."""
    first = next(iter(children.values()))
    device = dict(first["device"])
    peaks = [c["memory_peak_bytes"] for c in children.values() if c["memory_peak_bytes"] is not None]
    device["memory_peak_bytes"] = max(peaks) if peaks else None

    def quantity(spec: dict):
        if spec["schedule"] == "*":
            return sum(c["quantities"][spec["quantity"]] for c in children.values())
        return children[spec["schedule"]]["quantities"].get(spec["quantity"])

    metrics = {}
    if not trace:
        for m in manifest.end_to_end(cell["name"]):
            spec = cell["end_to_end"].get(m["name"])
            value = quantity(spec) if spec else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        device["busy_s"] = sum(c["busy_s"] for c in children.values())
        device["window_s"] = sum(c["window_s"] for c in children.values())
        for m in manifest.layer_metrics(cell["name"]):
            values = [c["layer_metrics"][m["name"]] for c in children.values()
                      if m["name"] in c.get("layer_metrics", {})]
            if values:  # a metric that moves a summed metric is summed over the children too
                metrics[m["name"]] = {"value": sum(values), "unit": m["unit"]}
    line = {
        "correct": all(c["correct"] for c in children.values()),
        "attempted": sum(c["attempted"] for c in children.values()),
        "failed": sum(c["failed"] for c in children.values()),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        line["breakdown"] = first["breakdown"]
    return line


def parent(args) -> int:
    missing = [n for n in PROGRAM_FILES if not os.path.exists(os.path.join(ROOT, n))]
    if missing:
        raise BenchFailure(
            f"{missing} not beside benchmark/: the benchmark measures the repo, not itself"
        )
    manifest = Manifest()
    cell = manifest.cell(args.workload)
    manifest.config(cell["config"])  # a cell whose configuration is missing fails here
    # the program's list of scope names; telemetry imports no JAX, so the chip stays the children's
    from acco_tpu.telemetry.trace import DECLARED_DEVICE_SCOPES

    breaches = manifest.breaches(DECLARED_DEVICE_SCOPES)
    if breaches:  # here, and not as a line that lacks a metric its cell lists
        raise ManifestError("what a cell reports must follow from what its configuration runs "
                            "(harness/manifest.py):\n" + "\n".join(f"  {b}" for b in breaches))
    deadline = time.time() + CHILD_LIMIT_S
    children = {}
    for index, schedule in enumerate(cell["schedules"]):
        children[schedule["name"]] = run_child(args, index, schedule, deadline)
    line = combine(manifest, cell, children, bool(args.trace))
    if args.rehearse:
        say(f"rehearsal passed on {line['device']}: not a chip run, no result line")
        say("what the line would hold: " + json.dumps(line["metrics"]))
        return 0
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on any device; never prints the result line")
    ap.add_argument("--child", type=int, help=argparse.SUPPRESS)  # set by the parent only
    ap.add_argument("--t0", type=float, help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        if args.child is not None:
            from benchmark.harness import child

            args.schedule = args.child
            return child.main(args)
        return parent(args)
    except (ManifestError, BenchFailure) as exc:
        say(f"benchmark FAILED: {exc}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
