"""Bytes the compiled round program's large collectives move, per chip and
round: the program's own census of its executable's HLO
(``acco_tpu/analysis/census.py``). args: ``scale``."""


def reduce(ctx: dict, args: dict):
    census = ctx.get("census")
    if not census or not census.get("measured_bytes"):
        return None
    return census["measured_bytes"] * args.get("scale", 1.0)
