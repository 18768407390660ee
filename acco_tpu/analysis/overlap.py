"""Overlap gate: gradient-path collectives must be async and covered.

The paper's central structural claim (PAPER.md; reference
``trainer_decoupled.py``'s two CUDA streams) maps on TPU to: every
all-gather / reduce-scatter / collective-permute of the round's
communication branch compiles to an async ``-start``/``-done`` pair, and
the scheduler places real compute (fusions / dots of the gradient
branch) inside the in-flight window. This module turns
``tools/overlap_hlo.py``'s one-off check into a reusable per-program
verdict the lint gates call on any scheduled HLO text.

The verdict (unchanged from overlap_hlo, which now delegates here):

- zero *large* blocking collectives (scalar/tiny psums — the grad-count,
  health, loss reductions — can't meaningfully overlap anything and are
  exempt below ``small_elems``);
- at least one async pair; and
- ≥ 1/4 of the async windows contain compute (ring hops form a serial
  chain, so windows past the available compute run back-to-back — full
  coverage is not achievable nor required).

Known baseline: at dp=32 this libtpu's device-count async gate refuses
to form pairs from XLA's own collectives (every one compiles blocking;
``tests/test_ring_canary.py`` watches for the day that changes), so the
dp=32 gate is recorded as an EXPECTED failure under ``comm_impl='xla'``;
``tools/lint.py --overlap`` encodes that.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from acco_tpu.analysis.hlo import ScheduleReport, analyze_entry

# Collectives at or below this element count are scalar bookkeeping
# (grad-count psum, health [2] psum, loss means) — exempt from the
# blocking check. Chosen well below any gradient-path payload: the
# smallest real payload is one ring chunk, Pp/(2·ns) elements, which is
# > 1e6 for every production model; the tiny-CPU gate programs override.
DEFAULT_SMALL_ELEMS = 1_000_000


@dataclass
class OverlapReport:
    """One program's overlap verdict + the evidence behind it."""

    ok: bool
    async_pairs: int
    covered_windows: int        # windows with compute scheduled inside
    blocking_large: int
    blocking_small: int
    total_scheduled_ops: int
    windows: list[dict] = field(default_factory=list)

    def summary(self) -> str:
        return (
            f"{self.async_pairs} async pairs "
            f"({self.covered_windows} with compute in-window), "
            f"{self.blocking_large} blocking large / "
            f"{self.blocking_small} small collectives -> "
            f"{'OVERLAPPED' if self.ok else 'NOT PROVEN'}"
        )


def check_overlap(
    hlo: str, small_elems: int = DEFAULT_SMALL_ELEMS
) -> OverlapReport:
    """Run the overlap verdict on one compiled program's HLO text."""
    report = analyze_entry(hlo)
    return verdict_from_schedule(report, small_elems)


def verdict_from_schedule(
    report: ScheduleReport, small_elems: int = DEFAULT_SMALL_ELEMS
) -> OverlapReport:
    blocking_large = report.blocking(small_elems)
    blocking_all = [c for c in report.collectives if not c.asynchronous]
    covered = sum(
        1 for w in report.windows if w["compute_ops_in_window"] > 0
    )
    pairs = len(report.windows)
    ok = bool(
        not blocking_large
        and pairs
        and covered * 4 >= pairs
    )
    return OverlapReport(
        ok=ok,
        async_pairs=pairs,
        covered_windows=covered,
        blocking_large=len(blocking_large),
        blocking_small=len(blocking_all) - len(blocking_large),
        total_scheduled_ops=report.total_scheduled_ops,
        windows=report.windows,
    )


def analyze_schedule(hlo: str) -> dict:
    """Back-compat shape of ``tools/overlap_hlo.analyze_schedule`` —
    the dict the OVERLAP.md writer renders. New code should call
    :func:`check_overlap` and read the typed report."""
    rep = check_overlap(hlo)
    return {
        "async_pairs": rep.windows,
        "blocking_collectives": rep.blocking_large,
        "blocking_small_collectives": rep.blocking_small,
        "total_scheduled_ops": rep.total_scheduled_ops,
    }
