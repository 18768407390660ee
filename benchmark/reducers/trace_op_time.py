"""Device self time of the ops that match a regex, per traced round, averaged
over chips. args: ``regex``; ``field``: ``text`` (the op's name and its string
stats, where a Pallas kernel's own name shows; default) or ``name``."""


def reduce(ctx: dict, args: dict):
    trace = ctx.get("device_trace")
    if trace is None:
        return None
    ms = trace.op_ms_per_round(args["regex"], args.get("field", "text"))
    return ms if ms > 0 else None
