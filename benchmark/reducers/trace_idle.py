"""Busy and idle of the device over the traced rounds. args: ``report``:
``idle_pct`` (1 - busy / traced span) or ``busy_ms_per_round``."""


def reduce(ctx: dict, args: dict):
    trace = ctx.get("device_trace")
    if trace is None:
        return None
    busy, span = trace.busy_s(), trace.window_s()
    if args["report"] == "idle_pct":
        return 100.0 * (1.0 - busy / span)
    return busy * 1e3 / trace.rounds
