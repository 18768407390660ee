"""A peak of ``device.memory_stats()`` on the fullest chip, read when the
trainer returned. args: ``key``, ``scale``."""


def reduce(ctx: dict, args: dict):
    value = ctx["memory"].get(args["key"])
    return None if value is None else value * args.get("scale", 1.0)
