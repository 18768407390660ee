"""Device idle time under one of the program's host spans, per traced round,
averaged over chips. args: ``span``: a span name (``train/dispatch``), or
``none`` for idle time that no span covers.

Every gap between two device segments (all devices, all gaps) is laid against
the trainer loop's spans on the profile's host plane (``harness/hostplane.py``):
each instant of a gap belongs to the innermost span that covers it, else to
``none``; a gap under 100 us lies between two ops of one program and goes to
``none`` whatever the host does meanwhile. Over the span names and ``none`` the
values add up to ``device_idle_pct`` x traced span / rounds.

The profile is found through the program's own trace (``otherData.profile_dir``,
which the trainer writes since PR 23). A program that wrote none, or put no
span on the host plane, gives nothing to read: None.
"""

from benchmark.harness import hostplane

_SEEN: dict = {}  # profile path -> {span name: ms per round}; five metrics share one read


def _span_names() -> list[str]:
    from acco_tpu.telemetry import SPAN_NAMES  # the program's closed list; JAX-free

    return sorted(SPAN_NAMES)


def _by_span(ctx: dict) -> dict | None:
    trace = ctx.get("device_trace")
    path = hostplane.profile_path(ctx["trace"])
    if trace is None or path is None:
        return None
    if path in _SEEN:
        return _SEEN[path]
    spans = hostplane.read_host_spans(path, _span_names())
    if not spans:
        _SEEN[path] = None
        return None
    totals: dict = {}
    longest = []
    origin = min(op.start for ops in trace.ops.values() for op in ops)
    for plane, segments in trace.segments.items():
        gaps = hostplane.device_gaps(segments)
        for name, ns in hostplane.attribute(gaps, spans).items():
            totals[name] = totals.get(name, 0.0) + ns
        longest.extend((hi - lo, lo, hi, plane) for lo, hi in gaps)
    scale = 1e6 * trace.devices * trace.rounds
    by_span = {name: ns / scale for name, ns in totals.items()}
    ctx["say"](
        "device idle by host span, ms per traced round: "
        + ", ".join(f"{name} {ms:.4f}" for name, ms in sorted(by_span.items(), key=lambda kv: -kv[1]))
    )
    for length, lo, hi, plane in sorted(longest, reverse=True)[:5]:
        names = hostplane.covering((lo, hi), spans) or [hostplane.NONE]
        ctx["say"](
            f"idle gap of {length / 1e6:.3f} ms at {(lo - origin) / 1e6:.3f} ms into the traced "
            f"span on {plane}: under {', '.join(names)}"
        )
    _SEEN[path] = by_span
    return by_span


def reduce(ctx: dict, args: dict):
    by_span = _by_span(ctx)
    if by_span is None:
        return None
    return by_span.get(args["span"], 0.0)
