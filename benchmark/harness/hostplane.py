"""The program's host spans, read from the profiler's own trace.

While ``jax.profiler`` captures, every span the trainer records with
``Tracer.span`` is also a ``TraceAnnotation``: an event on the ``/host:CPU``
plane of the same ``*.xplane.pb`` that holds the device's ops, under the
span's own name and on the same clock. So a gap between two device ops can be
laid against what the host was doing in it, with no offset between two clocks
to estimate.

The spans of interest are those of the trainer's loop: the line (one per
thread) that holds ``train/dispatch``. Spans may nest; each instant belongs to
the innermost span that covers it, as each instant of a device's ops line
belongs to the deepest op (``xplane.flatten``, reused here).

The pure functions take plain tuples, so the tests run them on hand-built
spans with known answers; only ``read_host_spans`` touches the profiler's
reader. A program that annotates nothing (every commit before PR 23) leaves no
such event: the reader returns an empty list and raises nothing.
"""

from __future__ import annotations

import glob
import os
import re

from benchmark.harness.xplane import Op, Segment, flatten

HOST_PLANE = r"^/host:CPU$"
LOOP_SPAN = "train/dispatch"  # the thread that holds it is the trainer's loop
NONE = "none"  # the owner of an instant no span covers
# A gap shorter than this lies between two ops of one program and is no
# host's doing, whatever span the host is in meanwhile.
MIN_HOST_GAP_NS = 100_000.0


def profile_path(program_trace: dict) -> str | None:
    """The ``*.xplane.pb`` of the capture that the program's own trace names
    (``otherData.profile_dir``, written by the trainer since PR 23): the
    newest one, as ``child.read_device_trace`` takes it. None where the trace
    names no capture or the directory holds none."""
    profile_dir = (program_trace.get("otherData") or {}).get("profile_dir")
    if not profile_dir:
        return None
    paths = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def read_host_spans(
    path: str, names, loop_span: str = LOOP_SPAN, plane_regex: str = HOST_PLANE
) -> list[Op]:
    """The events named in ``names`` on the host line that holds
    ``loop_span``, as ``Op(start, end, name, name)`` in ns; ``[]`` where no
    line holds one."""
    from jax.profiler import ProfileData

    names = set(names)
    plane_re = re.compile(plane_regex)
    for plane in ProfileData.from_file(path).planes:
        if not plane_re.search(plane.name):
            continue
        for line in plane.lines:
            spans = [
                Op(float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name, ev.name)
                for ev in line.events
                if ev.name in names and ev.duration_ns > 0
            ]
            if any(s.name == loop_span for s in spans):
                return spans
    return []


def device_gaps(segments: list[Segment]) -> list[tuple[float, float]]:
    """Every gap between the segments of one device: ``(start, end)`` in ns.
    Their lengths add up to the traced span minus the busy time."""
    gaps = []
    ordered = sorted(segments, key=lambda s: s.start)
    for a, b in zip(ordered, ordered[1:]):
        if b.start > a.end:
            gaps.append((a.end, b.start))
    return gaps


def covering(gap: tuple[float, float], spans: list[Op]) -> list[str]:
    """Names of the spans that share time with the gap, in order of start."""
    lo, hi = gap
    return [s.name for s in sorted(spans, key=lambda s: s.start) if s.start < hi and s.end > lo]


def attribute(
    gaps: list[tuple[float, float]], spans: list[Op], min_gap_ns: float = MIN_HOST_GAP_NS
) -> dict[str, float]:
    """ns of gap time by the innermost span that covers each instant,
    ``NONE`` where no span does and for every gap shorter than
    ``min_gap_ns``. The values add up to the gaps' total length."""
    pieces = flatten(spans)  # disjoint, sorted by start, owned by the innermost span
    out: dict[str, float] = {}
    i = 0
    for lo, hi in sorted(gaps):
        length = hi - lo
        if length < min_gap_ns:
            out[NONE] = out.get(NONE, 0.0) + length
            continue
        while i < len(pieces) and pieces[i].end <= lo:
            i += 1
        covered, j = 0.0, i
        while j < len(pieces) and pieces[j].start < hi:
            share = min(hi, pieces[j].end) - max(lo, pieces[j].start)
            name = pieces[j].op.name
            out[name] = out.get(name, 0.0) + share
            covered += share
            j += 1
        out[NONE] = out.get(NONE, 0.0) + length - covered
    return out
