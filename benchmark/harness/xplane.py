"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to device numbers.

The profiler writes one plane per device; on its ops line every executed HLO
instruction is an event with a start and a duration in nanoseconds. Container
instructions (``while``, ``conditional``, ``call``) span the events of their
bodies, so the line is a forest, not a flat list. Everything below first
flattens it: each instant belongs to the DEEPEST event that covers it, which
gives self times that add up to the busy time and never count a loop body
twice.

Busy is the union of the events' intervals; the traced span runs from a
device's first op to its last (the trainer fences the device before
``start_trace`` and before ``stop_trace``, so the profiler's own start-up is
outside it); idle is the span minus busy.

Collectives: an asynchronous one is a ``*-start`` event and the ``*-done``
event with the same suffix; its interval runs from the start's begin to the
done's end. A synchronous one is its own event. The exposed part of a
collective interval is the part during which the deepest running event is a
collective itself or nothing at all: no compute op was running on that device.

The pure functions take plain tuples, so the tests run them on hand-built
events with known answers; only ``read_ops`` touches the profiler's reader.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

TPU_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"


@dataclass(frozen=True)
class Op:
    start: float  # ns
    end: float  # ns
    name: str  # the HLO instruction's name: 'fusion.12', 'acco_fused_attn_fwd.3'
    text: str  # the event as the trace gives it, with its string stats


_INSTRUCTION = re.compile(r"^%(?P<name>[^\s=]+) = ")


def instruction_name(event_name: str) -> str:
    """The TPU's ops line names an event by the instruction's whole HLO text
    (``%fusion.12 = bf16[8,128]{1,0} fusion(...), kind=kLoop``); other
    backends by the bare name. Either way, the bare name."""
    m = _INSTRUCTION.match(event_name)
    return m["name"] if m else event_name


@dataclass(frozen=True)
class Segment:
    """A stretch of time owned by one op: no deeper op covers it."""

    start: float
    end: float
    op: Op

    @property
    def dur(self) -> float:
        return self.end - self.start


def read_ops(path: str, plane_regex: str = TPU_PLANE, line_regex: str = OPS_LINE) -> dict:
    """``{plane name: [Op, ...]}`` for every plane and line that match."""
    from jax.profiler import ProfileData

    plane_re, line_re = re.compile(plane_regex), re.compile(line_regex)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane_re.search(plane.name):
            continue
        ops = out.setdefault(plane.name, [])
        for line in plane.lines:
            if not line_re.search(line.name):
                continue
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                strings = [str(v) for _, v in ev.stats if isinstance(v, str)]
                ops.append(
                    Op(
                        float(ev.start_ns),
                        float(ev.start_ns + ev.duration_ns),
                        instruction_name(ev.name),
                        " ".join([ev.name, *strings]),
                    )
                )
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(op: Op, limit: int = 96) -> str:
    """A name a reader can place: the instruction's name and, where the trace
    gives the HLO text, the type of its result without layouts
    (``select_add_fusion bf16[8,1023,50257]`` is the cross-entropy's backward
    pass; ``fusion.167`` alone is nothing)."""
    head = f"%{op.name} = "
    if not op.text.startswith(head):
        return op.name
    rest = op.text[len(head):]
    if rest.startswith("("):  # a tuple type: up to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result = rest[: i + 1]
    else:
        result = rest.split(" ", 1)[0]
    return f"{op.name} {_LAYOUT.sub('', result)}"[:limit]


def flatten(ops: list[Op]) -> list[Segment]:
    """Cut a forest of nested ops into segments owned by the deepest op."""
    segments: list[Segment] = []
    stack: list[Op] = []
    cursor = 0.0

    def emit(until: float) -> None:
        nonlocal cursor
        if stack and until > cursor:
            segments.append(Segment(cursor, until, stack[-1]))
        cursor = max(cursor, until)

    # at equal starts the longer op is the container
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1].end <= op.start:
            emit(stack[-1].end)
            stack.pop()
        emit(op.start)
        cursor = max(cursor, op.start)
        stack.append(op)
    while stack:
        emit(stack[-1].end)
        stack.pop()
    return segments


def span_ns(ops: list[Op]) -> float:
    return max(o.end for o in ops) - min(o.start for o in ops) if ops else 0.0


def busy_ns(segments: list[Segment]) -> float:
    return sum(s.dur for s in segments)


def self_time_ns(segments: list[Segment], regex: str, field: str = "text") -> float:
    """Self time of the ops whose ``field`` ('name' or 'text') matches."""
    pattern = re.compile(regex)
    return sum(s.dur for s in segments if pattern.search(getattr(s.op, field)))


def self_times(segments: list[Segment], into: dict | None = None, key=lambda op: op.name) -> dict:
    """Self time in ns by ``key`` of the op (its name), added into ``into``
    where given."""
    totals = {} if into is None else into
    for s in segments:
        k = key(s.op)
        totals[k] = totals.get(k, 0.0) + s.dur
    return totals


def top_ops(segments: list[Segment], n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` op names with most self time, in ns."""
    return sorted(self_times(segments).items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(segments: list[Segment], n: int = 5) -> list[tuple[float, float]]:
    """The ``n`` longest gaps between segments: ``(start, duration)`` in ns."""
    gaps = []
    ordered = sorted(segments, key=lambda s: s.start)
    for a, b in zip(ordered, ordered[1:]):
        if b.start > a.end:
            gaps.append((a.end, b.start - a.end))
    return sorted(gaps, key=lambda g: -g[1])[:n]


_ASYNC = re.compile(r"^(?P<kind>.+)-(?P<phase>start|done)(?P<suffix>(\.\d+)*)$")
_OPERAND_START = re.compile(r"%(?P<name>[^\s=(),]+-start[.\d]*)\)")


def collective_intervals(ops: list[Op], regex: str) -> list[tuple[float, float]]:
    """Merged intervals during which a collective was in flight: start's
    begin to done's end for asynchronous pairs, the op itself otherwise. A
    done is paired with the start its HLO text names as operand where the
    trace gives the text, else with the oldest pending start of its kind."""
    pattern = re.compile(regex)
    pending: list[tuple[str, Op]] = []  # (kind, start op), oldest first; few are ever in flight
    raw: list[tuple[float, float]] = []
    for op in sorted((o for o in ops if pattern.search(o.name)), key=lambda o: o.start):
        m = _ASYNC.match(op.name)
        if m is None:
            raw.append((op.start, op.end))
        elif m["phase"] == "start":
            pending.append((m["kind"], op))
        else:
            named = _OPERAND_START.search(op.text)
            match = next(
                (p for p in pending if named and p[1].name == named["name"]),
                next((p for p in pending if p[0] == m["kind"]), None),
            )
            if match is None:  # a done whose start fell outside the trace
                raw.append((op.start, op.end))
            else:
                pending.remove(match)
                raw.append((match[1].start, op.end))
    raw.extend((o.start, o.end) for _, o in pending)  # starts whose done fell outside
    return merge(raw)


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def overlap_ns(intervals: list[tuple[float, float]], segments: list[Segment]) -> float:
    """Total time the (merged, sorted) intervals share with the segments."""
    total, i = 0.0, 0
    for s in sorted(segments, key=lambda s: s.start):
        while i < len(intervals) and intervals[i][1] <= s.start:
            i += 1
        j = i
        while j < len(intervals) and intervals[j][0] < s.end:
            total += min(s.end, intervals[j][1]) - max(s.start, intervals[j][0])
            j += 1
    return total


def collective_times_ns(ops: list[Op], segments: list[Segment], regex: str) -> tuple[float, float]:
    """``(in flight, exposed)`` on one device: total length of the collective
    intervals, and the part of it with no compute segment under it."""
    pattern = re.compile(regex)
    intervals = collective_intervals(ops, regex)
    in_flight = sum(b - a for a, b in intervals)
    compute = [s for s in segments if not pattern.search(s.op.name)]
    return in_flight, in_flight - overlap_ns(intervals, compute)


@dataclass
class DeviceTrace:
    """One traced run, flattened once, for the reducers to read."""

    ops: dict  # plane -> [Op]
    segments: dict  # plane -> [Segment]
    rounds: int  # rounds the trainer traced

    @classmethod
    def from_ops(cls, ops: dict, rounds: int) -> "DeviceTrace":
        ops = {p: o for p, o in ops.items() if o}
        return cls(ops, {p: flatten(o) for p, o in ops.items()}, rounds)

    @property
    def devices(self) -> int:
        return len(self.ops)

    def mean_over_devices(self, fn) -> float:
        return sum(fn(p) for p in self.ops) / self.devices

    def busy_s(self) -> float:
        return self.mean_over_devices(lambda p: busy_ns(self.segments[p])) / 1e9

    def window_s(self) -> float:
        return self.mean_over_devices(lambda p: span_ns(self.ops[p])) / 1e9

    def op_ms_per_round(self, regex: str, field: str = "text") -> float:
        ns = self.mean_over_devices(lambda p: self_time_ns(self.segments[p], regex, field))
        return ns / 1e6 / self.rounds

    def collective_ms_per_round(self, regex: str) -> tuple[float, float] | None:
        per_device = [
            collective_times_ns(self.ops[p], self.segments[p], regex) for p in self.ops
        ]
        if not any(total for total, _ in per_device):
            return None
        scale = 1e6 * self.rounds * self.devices
        return (
            sum(t for t, _ in per_device) / scale,
            sum(e for _, e in per_device) / scale,
        )

    def breakdown(self, n_ops: int = 10, n_gaps: int = 5) -> dict:
        """Top ops by self time summed over devices, and the longest idle
        gaps of any device; seconds. Gaps carry no host label yet: the
        trainer's spans are on perf_counter, not on the profiler's clock."""
        totals: dict[str, float] = {}
        gaps = []
        for segments in self.segments.values():
            self_times(segments, into=totals, key=op_label)
            gaps.extend(ns for _, ns in idle_gaps(segments, n_gaps))
        ops = sorted(totals.items(), key=lambda kv: -kv[1])[:n_ops]
        return {
            "device_ops": [[name, ns / 1e9 / self.devices] for name, ns in ops],
            "idle_gaps": [
                ["unattributed", ns / 1e9] for ns in sorted(gaps, reverse=True)[:n_gaps]
            ],
        }
