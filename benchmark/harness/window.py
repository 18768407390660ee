"""The measured window, from the program's own ``trace_<id>.json``.

The trainer fences the device once per logging cadence: the ``device_get`` it
wraps in the span ``train/log_boundary_sync`` returns only when the round just
dispatched has finished. Between two such fences the host clock measures
device-inclusive time; a single ``train/round`` duration does not (it is a gap
between asynchronous dispatches). So the window runs from the END of the first
fence at or after the cell's ``warmup_rounds`` to the END of the last fence
before the stop, and its work is the rounds dispatched between those two.

JAX-free, pure functions over the Chrome-trace dict: the tests run them on a
small recorded trace.
"""

from __future__ import annotations

import bisect
import statistics
from dataclasses import dataclass

FENCE_SPAN = "train/log_boundary_sync"
ROUND_SPAN = "train/round"


class WindowError(Exception):
    """The trace does not hold a measurable window."""


@dataclass(frozen=True)
class Fence:
    """One logging-boundary device fence: the last round dispatched before
    it, and its end on the tracer's clock (microseconds)."""

    round: int
    start_us: float
    end_us: float


@dataclass(frozen=True)
class Window:
    first: Fence
    last: Fence
    n_fences: int  # fences from first to last, both included

    @property
    def rounds(self) -> int:
        return self.last.round - self.first.round

    @property
    def seconds(self) -> float:
        return (self.last.end_us - self.first.end_us) / 1e6


def _complete(events: list, name: str) -> list:
    return sorted(
        (e for e in events if e.get("ph") == "X" and e.get("name") == name),
        key=lambda e: e["ts"],
    )


def fences(trace: dict) -> list[Fence]:
    """Every fence of the run, in order, with the number of the last round
    dispatched before it (the ``round`` argument of the ``train/round`` span
    that ended last before the fence began)."""
    events = trace["traceEvents"]
    rounds = _complete(events, ROUND_SPAN)
    # dispatch ends are monotone: the loop records one train/round per round
    ends = [e["ts"] + e["dur"] for e in rounds]
    numbers = [int(e["args"]["round"]) for e in rounds]
    out = []
    for e in _complete(events, FENCE_SPAN):
        # ts and dur are each rounded to 0.1 us by the tracer
        i = bisect.bisect_right(ends, e["ts"] + 0.25)
        if i == 0:
            continue  # a fence before any round: nothing to count from
        out.append(Fence(numbers[i - 1], e["ts"], e["ts"] + e["dur"]))
    return out


def measure_window(
    trace: dict, warmup_rounds: int, stop_us: float | None = None
) -> Window:
    """The window of a run: first fence at or after ``warmup_rounds`` to the
    last fence that ended before ``stop_us`` (the whole run if None)."""
    all_fences = fences(trace)
    usable = [
        f
        for f in all_fences
        if f.round >= warmup_rounds and (stop_us is None or f.end_us <= stop_us)
    ]
    if len(usable) < 2:
        raise WindowError(
            f"{len(usable)} fence(s) at or after round {warmup_rounds} "
            f"(of {len(all_fences)} in the run): no window to measure"
        )
    window = Window(usable[0], usable[-1], len(usable))
    if window.rounds <= 0 or window.seconds <= 0:
        raise WindowError(f"empty window: {window}")
    return window


def span_durations_ms(trace: dict, name: str, window: Window) -> list[float]:
    """Durations (ms) of the spans ``name`` that began inside the window."""
    lo, hi = window.first.end_us, window.last.end_us
    return [
        e["dur"] / 1e3
        for e in _complete(trace["traceEvents"], name)
        if lo <= e["ts"] < hi
    ]


STATS = {
    "mean": statistics.fmean,
    "median": statistics.median,
    "max": max,
    "sum": sum,
}


def tokens_per_s_per_chip(window: Window, batch_per_chip: int, seq_len: int) -> float:
    """Const-length packed blocks hold no padding, so a round is exactly
    ``batch x seq`` tokens on every chip."""
    return window.rounds * batch_per_chip * seq_len / window.seconds


def loss_at_ref_round(
    fence_losses: dict[int, float], ref_round: int, every: int, n: int = 4
) -> float:
    """Mean of the boundary losses at the ``n`` fences ending at
    ``ref_round`` (fences come every ``every`` rounds). All must be there: a
    run that stopped before ``ref_round`` has no such number."""
    wanted = [ref_round - k * every for k in range(n - 1, -1, -1)]
    missing = [r for r in wanted if r not in fence_losses]
    if missing:
        raise WindowError(
            f"no boundary loss at round(s) {missing} (have {sorted(fence_losses)})"
        )
    return statistics.fmean(fence_losses[r] for r in wanted)
