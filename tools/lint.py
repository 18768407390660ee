"""Graph lint: one command that proves the repo's structural invariants.

``python tools/lint.py --ci`` is the gate a PR must pass. It runs, in
order of increasing cost (everything on the CPU backend, no chips):

1. **host lint** — AST checks over ``acco_tpu/`` and ``tools/`` (host
   syncs in loops, jits missing donation where round state / KV pools
   flow through, threads without a join path, unused imports) and the
   unused-import check over ``tests/``;
2. **ruff** — if a ``ruff`` binary exists on PATH, run it with the
   repo's ``pyproject.toml`` config (skipped with a note otherwise —
   the AST unused-import check above is the enforceable baseline);
3. **slow-marker audit** — any test whose recorded duration exceeds the
   threshold must carry ``@pytest.mark.slow`` (evidence comes from
   ``outputs/test_durations.json``, written by ``tests/conftest.py``;
   missing file = pass-with-note);
4. **metrics-gate** — AST walk over the production sources resolving
   every literal-named telemetry call (``metrics.emit``/``emit_many``,
   tracer ``span``/``complete_event``/``instant``) against the
   closed-world declarations in ``acco_tpu/telemetry`` — the static
   mirror of the registry's runtime ``UndeclaredMetricError``;
5. **graph gates** — every program a production run dispatches (ACCO
   even+odd, DPU, DDP, eval, serve prefill buckets + decode),
   AOT-lowered from avals on a tiny-but-real model, each checked for
   honored donation, collective census vs the analytic comm model, and
   the bf16/fp32 dtype policy over its state pytree;
6. **rules gate** — sharding-rule coverage (analysis/rules.py): every
   leaf of every program's state tree must match exactly one rule of
   its sharding rule table (acco_tpu/sharding) — unmatched or
   ambiguously-matched leaves fail, making the rule tables and the
   dtype policy's closed-world walk mutually validating.

Exit status is nonzero iff any gate fails.

``python tools/lint.py --overlap`` is the slow lane: AOT-compiles the
production ACCO round on the TPU toolchain (libtpu, no chips; minutes
per dp size) and runs the async-overlap verdict at dp=8/16/32. The
dp=32 failure is the RECORDED baseline (this libtpu's device-count async
gate refuses to form pairs there — ROADMAP "dp≥32 overlap wall"): the
lane exits 0 when dp=8/16 pass and dp=32 fails *as expected*, and
prints loudly if dp=32 ever starts passing so the baseline can be
retired. The overlap analyzer itself is regression-tested in tier-1
against canned scheduled-HLO fixtures (the CPU backend never emits
async pairs, so overlap can't gate the CPU compiles above).
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import time
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# dp sizes the overlap lane proves, and the recorded expected failures
# (dp=32: 0 async pairs / 65 blocking on this libtpu — ROADMAP item 1).
OVERLAP_DP_SIZES = (8, 16, 32)
OVERLAP_EXPECTED_FAIL = {32}


@dataclass
class Gate:
    name: str
    ok: bool
    detail: list[str] = field(default_factory=list)
    note: str | None = None   # non-fatal context (skips, baselines)


def _print_gate(g: Gate) -> None:
    mark = "ok " if g.ok else "FAIL"
    head = f"[{mark}] {g.name}"
    if g.note:
        head += f" — {g.note}"
    print(head)
    for line in g.detail:
        print(f"       {line}")


def _import_cpu_jax():
    """The gates analyse programs built on 8 virtual CPU devices: both
    variables must be in the environment before jax is imported."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    return jax


# -- 1. host lint ------------------------------------------------------------


def gate_host_lint() -> Gate:
    from acco_tpu.analysis.host_lint import lint_paths

    findings = lint_paths(
        [os.path.join(REPO, "acco_tpu"), os.path.join(REPO, "tools")]
    )
    # Test code legitimately syncs in loops (asserting per-step values is
    # the point) and jits undonated throwaway state; only the import
    # hygiene rule applies there. tests/fixtures holds the gate suite's
    # seeded violations — dirty on purpose, excluded from the walk.
    from acco_tpu.analysis.host_lint import DEFAULT_EXCLUDE_DIRS

    findings += lint_paths(
        [os.path.join(REPO, "tests")], rules={"unused-import"},
        exclude_dirs=DEFAULT_EXCLUDE_DIRS + ("fixtures",),
    )
    return Gate(
        name="host-lint",
        ok=not findings,
        detail=[str(f) for f in findings],
        note=f"{len(findings)} findings" if findings else "clean",
    )


def gate_ruff() -> Gate:
    exe = shutil.which("ruff")
    if exe is None:
        return Gate(
            name="ruff", ok=True,
            note="no ruff binary on PATH — skipped (AST unused-import "
            "check is the enforced baseline)",
        )
    proc = subprocess.run(
        [exe, "check", "."], cwd=REPO, capture_output=True, text=True
    )
    out = (proc.stdout + proc.stderr).strip().splitlines()
    return Gate(
        name="ruff", ok=proc.returncode == 0, detail=out[:40],
        note=None if proc.returncode == 0 else f"exit {proc.returncode}",
    )


def gate_slow_markers() -> Gate:
    from acco_tpu.analysis.slow_markers import audit_recorded

    rep = audit_recorded(os.path.join(REPO, "outputs", "test_durations.json"))
    return Gate(
        name="slow-markers", ok=rep.ok, detail=rep.violations,
        note=rep.summary(),
    )


def gate_metrics() -> Gate:
    """Every literal-named telemetry call site across the production
    sources must name a declared metric (telemetry/metrics.py DECLARED)
    or span (telemetry/trace.py SPAN_NAMES), and every declared metric
    must have a call site that emits it."""
    from acco_tpu.analysis.metrics_gate import check_repo

    rep = check_repo(REPO)
    return Gate(
        name="metrics-gate", ok=rep.ok,
        detail=[str(f) for f in rep.findings],
        note=rep.summary(),
    )


# -- 4. graph gates ----------------------------------------------------------


def _build_programs(serve_buckets=None):
    """Lower the tiny program registry once; shared by gate_programs
    and gate_rules so --ci never compiles the registry twice."""
    _import_cpu_jax()
    from acco_tpu.analysis.programs import build_all_tiny

    t0 = time.time()
    programs = build_all_tiny(serve_buckets=serve_buckets)
    print(
        f"# lowered {len(programs)} programs from avals "
        f"in {time.time() - t0:.1f}s"
    )
    return programs


def gate_programs(serve_buckets=None, programs=None) -> list[Gate]:
    from acco_tpu.analysis.census import check_census
    from acco_tpu.analysis.donation import check_donation
    from acco_tpu.analysis.dtypes import check_dtype_policy

    gates: list[Gate] = []
    if programs is None:
        programs = _build_programs(serve_buckets=serve_buckets)
    for p in programs:
        hlo = p.hlo()
        don = check_donation(p.lowered, p.compiled(), hlo)
        cen = check_census(
            hlo, p.expect_comm_bytes, p.expect_comm_ops,
            small_elems=p.small_elems,
        )
        dt = check_dtype_policy(p.state_tree, p.dtype_rules)
        ok = don.ok and cen.ok and dt.ok
        detail = [
            f"donation: {don.summary()}",
            f"census:   {cen.summary()}",
            f"dtypes:   {dt.summary()}",
        ]
        if not don.ok:
            detail += [f"  {f.path}: {f.status}" for f in don.dropped]
        if not dt.ok:
            detail += [f"  {v.message}" for v in dt.violations]
        gates.append(Gate(name=f"program:{p.name}", ok=ok, detail=detail))
    return gates


def gate_rules(programs) -> Gate:
    """Sharding-rule coverage over every dispatched program's state tree:
    each leaf must match exactly one rule of the program's table
    (analysis/rules.py) — the placement analogue of the dtype gate."""
    from acco_tpu.analysis.rules import check_rule_coverage

    detail, ok, checked = [], True, 0
    for p in programs:
        rep = check_rule_coverage(p.state_tree, p.rule_table)
        checked += rep.checked
        if not rep.ok:
            ok = False
            detail.append(f"{p.name}: {rep.summary()}")
            detail += [f"  {v.message}" for v in rep.violations[:6]]
    return Gate(
        name="rules",
        ok=ok,
        detail=detail,
        note=(
            f"{checked} state leaves across {len(programs)} programs, "
            "each matched exactly one rule"
            if ok
            else f"{len(detail)} program(s) with coverage violations"
        ),
    )


# -- overlap slow lane -------------------------------------------------------


def run_overlap(dp_sizes, seq: int, bs: int, layers: int) -> int:
    """AOT-compile the real ACCO round per dp size on the TPU toolchain
    and apply the overlap verdict to both parities. Exit 0 iff every
    non-baseline size passes and every recorded-baseline size fails as
    expected."""
    from acco_tpu.analysis.overlap import check_overlap

    # imports jax + forces CPU platform internally; the TPU *topology*
    # compile needs no devices
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from overlap_hlo import build_round

    failures = 0
    for dp in dp_sizes:
        expected_fail = dp in OVERLAP_EXPECTED_FAIL
        print(f"== overlap dp={dp} (compiling both parities; slow)")
        t0 = time.time()
        try:
            step, state, batches = build_round(dp, seq, bs, layers)
            ok_both = True
            for parity, tag in ((True, "even"), (False, "odd")):
                compiled = (
                    step.round_fn(parity=parity).lower(state, batches).compile()
                )
                rep = check_overlap(compiled.as_text())
                print(f"   {tag}: {rep.summary()}")
                ok_both = ok_both and rep.ok
        except Exception as exc:  # a compile error must fail the gate, not the lane
            msg = str(exc).split("\n", 1)[0]
            print(f"   compile error: {type(exc).__name__}: {msg[:200]}")
            ok_both = False
        dt = time.time() - t0
        if ok_both and expected_fail:
            print(
                f"   dp={dp}: PASSES but is recorded as a known-broken "
                "baseline — ROADMAP item 1 appears FIXED; update "
                "OVERLAP_EXPECTED_FAIL in tools/lint.py and the "
                f"OVERLAP.md table ({dt:.0f}s)"
            )
        elif ok_both:
            print(f"   dp={dp}: OVERLAPPED ({dt:.0f}s)")
        elif expected_fail:
            print(
                f"   dp={dp}: NOT PROVEN — expected failure (recorded "
                f"baseline, ROADMAP item 1) ({dt:.0f}s)"
            )
        else:
            print(f"   dp={dp}: NOT PROVEN — gate FAILURE ({dt:.0f}s)")
            failures += 1
    return 1 if failures else 0


def run_ci(serve_buckets=None) -> int:
    gates = [
        gate_host_lint(), gate_ruff(), gate_slow_markers(), gate_metrics(),
    ]
    programs = _build_programs(serve_buckets=serve_buckets)
    gates += gate_programs(programs=programs)
    gates.append(gate_rules(programs))
    print()
    for g in gates:
        _print_gate(g)
    bad = [g for g in gates if not g.ok]
    print(
        f"\n{len(gates) - len(bad)}/{len(gates)} gates passed"
        + (f" — {len(bad)} FAILED" if bad else "")
    )
    return 1 if bad else 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--ci", action="store_true",
        help="run every fast gate; nonzero exit on any failure",
    )
    ap.add_argument(
        "--overlap", action="store_true",
        help="slow lane: TPU-AOT overlap verdict at dp=8/16/32 "
        "(dp=32 expected-fail baseline)",
    )
    ap.add_argument(
        "--dp", type=int, nargs="*", default=None,
        help="override the overlap lane's dp sizes",
    )
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--bs", type=int, default=8)
    ap.add_argument("--layers", type=int, default=4)
    args = ap.parse_args()
    if not (args.ci or args.overlap):
        ap.error("pick a lane: --ci (fast gates) and/or --overlap (slow)")
    rc = 0
    if args.ci:
        rc |= run_ci()
    if args.overlap:
        rc |= run_overlap(
            tuple(args.dp) if args.dp else OVERLAP_DP_SIZES,
            args.seq, args.bs, args.layers,
        )
    sys.exit(rc)


if __name__ == "__main__":
    main()
