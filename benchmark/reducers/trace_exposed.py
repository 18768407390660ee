"""Collective time per traced round, averaged over chips. args:
``collective_regex`` (on op names); ``report``: ``total`` (start to done) or
``exposed`` (the part with no compute op running on that device)."""


def reduce(ctx: dict, args: dict):
    trace = ctx.get("device_trace")
    if trace is None:
        return None
    times = trace.collective_ms_per_round(args["collective_regex"])
    if times is None:
        return None
    return times[0] if args["report"] == "total" else times[1]
