"""Summarize training-health / robustness counters across runs.

The watchdog writes its counters into a run's ``results.csv`` row
(``skipped_rounds`` / ``rollbacks`` / ``grad_norm_spikes`` /
``grad_norm_drifts``; the trainer writes the file into its run
directory), and ``tools/load_harness.py`` writes a ``serve_load`` record
with the serving counters. This tool reads both back and prints one
robustness table — no JAX import, safe on any machine.

Usage::

    python tools/health_report.py --results <run_dir>/results.csv
    python tools/health_report.py <serve_load record>.json ...
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
            "method"
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
                _fmt(r.get("method_name")),
            )
        )
    return lines


def _record_from_text(text: str):
    """First line that parses as a dict carrying a ``metric`` key."""
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
        whole = json.loads(text)  # the record file load_harness writes
        if isinstance(whole, dict) and "metric" in whole:
            rec = whole
    except json.JSONDecodeError:
        pass
    if rec is None:
        # raw harness output: the record is its own line
        rec = _record_from_text(text)
    if rec is None or rec.get("metric") != "serve_load":
        return [f"{path}: no serve_load record found"]
    fields = ", ".join(
        f"{k}={_fmt(rec.get(k))}" for k in SERVE_BENCH_FIELDS
    )
    return [f"{os.path.basename(path)}: serve_load — {fields}"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "bench_json", nargs="*",
        help="serve_load record files (default: ./BENCH_*.json, where "
             "tools/load_harness.py writes by default)",
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
    lines.append(f"serve_load records ({len(bench_paths)}):")
    for path in bench_paths:
        lines += ["  " + l for l in report_bench_json(path)]
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
