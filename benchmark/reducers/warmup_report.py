"""A field of the program's ``WarmupReport`` (compile/warmup.py). args:
``field``: ``lower_ms`` or ``compile_ms`` (summed over the programs), or
``cache_hits`` / ``cache_misses`` (the report's own sum); ``scale``."""


def reduce(ctx: dict, args: dict):
    report = ctx.get("warmup_report")
    if not report:
        return None
    field = args["field"]
    if field in ("lower_ms", "compile_ms"):
        values = [p[field] for p in report["programs"].values() if p.get(field) is not None]
        if not values:
            return None
        value = sum(values)
    else:
        value = report["cache"].get(field.removeprefix("cache_"))
        if value is None:
            return None
    return value * args.get("scale", 1.0)
