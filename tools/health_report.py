"""Summarize training-health / robustness counters across runs.

The watchdog writes its counters into two existing ledgers — the
per-run ``results.csv`` row (``skipped_rounds`` / ``rollbacks`` /
``grad_norm_spikes`` / ``grad_norm_drifts``) and ``bench.py``'s JSON
record (``guard_overhead_pct`` / ``skipped_rounds`` / ``chaos``). This
tool reads both back and prints one robustness table, so BENCH_* rounds
can track guard overhead and skip/rollback behavior the same way they
track tokens/sec — no JAX import, safe on any machine.

Usage::

    python tools/health_report.py                    # ./results.csv + BENCH_*.json
    python tools/health_report.py --results path.csv BENCH_r05.json ...
"""

from __future__ import annotations

import argparse
import csv
import glob
import json
import os
import sys

HEALTH_COLUMNS = (
    "skipped_rounds",
    "rollbacks",
    "grad_norm_spikes",
    "grad_norm_drifts",
)
BENCH_FIELDS = (
    "guard_overhead_pct",
    "skipped_rounds",
    "chaos",
)
# serve_load records (tools/load_harness.py) carry the serving
# robustness counters instead of the training ones
SERVE_BENCH_FIELDS = (
    "requests",
    "p50_ttft_ms",
    "p99_ttft_ms",
    "tokens_per_s",
    "shed_rate",
    "cancelled",
    "server_500",
    "leaked_pages",
    "drain_ms",
    "chaos",
)


def _fmt(value) -> str:
    return "-" if value in (None, "", "None") else str(value)


def report_results_csv(path: str) -> list[str]:
    if not os.path.exists(path):
        return [f"results ledger: {path} (absent)"]
    with open(path, newline="") as f:
        rows = list(csv.DictReader(f))
    health_rows = [
        r for r in rows if any(r.get(c) not in (None, "") for c in HEALTH_COLUMNS)
    ]
    lines = [
        f"results ledger: {path} — {len(rows)} rows, "
        f"{len(health_rows)} with health columns"
    ]
    if not health_rows:
        lines.append(
            "  (no health columns yet: rows predate the watchdog, or "
            "every run was pre-guard)"
        )
        return lines
    lines.append(
        "  {:<24} {:>7} {:>9} {:>6} {:>6}  {}".format(
            "id_run", "skipped", "rollback", "spike", "drift",
            "method/bench"
        )
    )
    for r in health_rows:
        lines.append(
            "  {:<24} {:>7} {:>9} {:>6} {:>6}  {}".format(
                _fmt(r.get("0_id_run"))[:24],
                _fmt(r.get("skipped_rounds")),
                _fmt(r.get("rollbacks")),
                _fmt(r.get("grad_norm_spikes")),
                _fmt(r.get("grad_norm_drifts")),
                _fmt(r.get("method_name") or r.get("bench")),
            )
        )
    return lines


def _record_from_text(text: str):
    """First line that parses as a dict carrying a bench metric."""
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            cand = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(cand, dict) and "metric" in cand:
            return cand
    return None


def report_bench_json(path: str) -> list[str]:
    try:
        with open(path) as f:
            text = f.read()
    except OSError as exc:
        return [f"{path}: unreadable ({exc})"]
    rec = None
    try:
        # BENCH_r*.json: a driver wrapper object whose "tail" string
        # holds the harness stdout (the JSON record line among it).
        whole = json.loads(text)
        if isinstance(whole, dict):
            if "metric" in whole:
                rec = whole
            elif isinstance(whole.get("tail"), str):
                rec = _record_from_text(whole["tail"])
    except json.JSONDecodeError:
        pass
    if rec is None:
        # raw harness output: the record is its own line
        rec = _record_from_text(text)
    if rec is None:
        return [f"{path}: no bench record found"]
    if rec.get("metric") == "serve_load":
        fields = ", ".join(
            f"{k}={_fmt(rec.get(k))}" for k in SERVE_BENCH_FIELDS
        )
        return [f"{os.path.basename(path)}: serve_load — {fields}"]
    fields = ", ".join(f"{k}={_fmt(rec.get(k))}" for k in BENCH_FIELDS)
    step = rec.get("acco_step_ms")
    return [
        f"{os.path.basename(path)}: {rec.get('metric')} "
        f"(step={_fmt(step)} ms) — {fields}"
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "bench_json", nargs="*",
        help="bench JSON files (default: ./BENCH_*.json)",
    )
    ap.add_argument("--results", default="results.csv")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench_paths = args.bench_json or sorted(
        glob.glob(os.path.join(root, "BENCH_*.json"))
    )
    results = (
        args.results
        if os.path.isabs(args.results) or os.path.exists(args.results)
        else os.path.join(root, args.results)
    )
    lines = ["== training-health report =="]
    lines += report_results_csv(results)
    lines.append("")
    lines.append(f"bench records ({len(bench_paths)}):")
    for path in bench_paths:
        lines += ["  " + l for l in report_bench_json(path)]
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
