"""The window arithmetic, on a hand-built trace with known answers and on a
small trace the trainer recorded."""

import json
import os

import pytest

from _paths import FIXTURES

from benchmark.harness import window as win


def hand_built(rounds=45, every=10, round_us=1000.0, fence_us=200.0):
    """Rounds of 1 ms dispatched back to back from t=0, a 0.2 ms fence after
    every tenth; each fence delays what follows it."""
    events, t = [], 0.0
    for r in range(1, rounds + 1):
        events.append({"ph": "X", "name": "loader/next_block", "ts": t, "dur": 100.0})
        events.append({"ph": "X", "name": "train/dispatch", "ts": t + 100.0, "dur": 300.0})
        events.append(
            {"ph": "X", "name": "train/round", "ts": t, "dur": round_us, "args": {"round": r}}
        )
        t += round_us
        if r % every == 0:
            events.append({"ph": "X", "name": "train/log_boundary_sync", "ts": t, "dur": fence_us})
            t += fence_us
    return {"traceEvents": events}


def test_fences_carry_the_round_dispatched_before_them():
    fences = win.fences(hand_built())
    assert [f.round for f in fences] == [10, 20, 30, 40]
    assert fences[0].end_us == pytest.approx(10 * 1000.0 + 200.0)


@pytest.mark.parametrize(
    "warmup,stop_us,first,last,seconds",
    [
        # fence ends: 10200, 20400, 30600, 40800
        (0, None, 10, 40, 0.0306),
        (10, None, 10, 40, 0.0306),
        (11, None, 20, 40, 0.0204),  # a fence before the warm-up is not a start
        (10, 30600.0, 10, 30, 0.0204),  # the last fence that ENDED before the stop
        (10, 30599.0, 10, 20, 0.0102),
    ],
)
def test_window_between_fences(warmup, stop_us, first, last, seconds):
    w = win.measure_window(hand_built(), warmup, stop_us)
    assert (w.first.round, w.last.round) == (first, last)
    assert w.rounds == last - first
    assert w.seconds == pytest.approx(seconds)
    # 8 sequences of 1024 tokens a round and chip
    assert win.tokens_per_s_per_chip(w, 8, 1024) == pytest.approx(
        (last - first) * 8192 / seconds
    )


@pytest.mark.parametrize("warmup,stop_us", [(40, None), (41, None), (10, 20399.0)])
def test_no_window_without_two_fences(warmup, stop_us):
    with pytest.raises(win.WindowError):
        win.measure_window(hand_built(), warmup, stop_us)


def test_span_statistics_count_only_spans_inside_the_window():
    trace = hand_built()
    w = win.measure_window(trace, 10, None)
    dispatch = win.span_durations_ms(trace, "train/dispatch", w)
    assert len(dispatch) == w.rounds == 30
    assert win.STATS["median"](dispatch) == pytest.approx(0.3)
    syncs = win.span_durations_ms(trace, "train/log_boundary_sync", w)
    assert len(syncs) == 3  # the opening fence began before the window did
    assert win.span_durations_ms(trace, "ckpt/snapshot", w) == []


def test_loss_at_ref_round_needs_all_four_boundaries():
    losses = {10: 9.0, 20: 8.0, 30: 7.0, 40: 6.0, 50: 5.0}
    assert win.loss_at_ref_round(losses, 40, 10) == pytest.approx(7.5)
    assert win.loss_at_ref_round(losses, 50, 10) == pytest.approx(6.5)
    with pytest.raises(win.WindowError):
        win.loss_at_ref_round(losses, 60, 10)
    with pytest.raises(win.WindowError):
        win.loss_at_ref_round(losses, 30, 10)  # would need round 0


def test_recorded_trace():
    """A trace the trainer wrote (CPU rehearsal, DDP, 45 rounds): the format
    the arithmetic is written against, with the answers read off the file."""
    with open(os.path.join(FIXTURES, "trace_recorded.json")) as f:
        trace = json.load(f)
    fences = win.fences(trace)
    assert [f.round for f in fences] == [10, 20, 30, 40]
    w = win.measure_window(trace, 10)
    assert (w.rounds, w.n_fences) == (30, 4)
    assert w.seconds == pytest.approx((11996171.8 - 11167389.1) / 1e6)
    assert len(win.span_durations_ms(trace, "loader/next_block", w)) == 30
