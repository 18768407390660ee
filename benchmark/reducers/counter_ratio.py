"""One of the program's counters or gauges over another, as the registry stood
when the trainer returned (``ctx["counters"]``). args: ``numerator``,
``denominator`` (names in ``acco_tpu/telemetry/metrics.py``); ``scale`` (default
1); ``when_unset`` (default: nothing to read): what the ratio reads where the
program declares both names and never set one of them (a gauge reads None until
then), e.g. 0 for the fill of a cache that has no cap; ``mean_over_children``
(default false): divide by the number of the cell's schedules, so that the sum
``run.py`` takes over a cell's children is their mean: a share does not add up
over processes.

A program that does not declare either name (every commit before the one that
added it) gives nothing to read: None. So does a denominator of 0.
"""


def reduce(ctx: dict, args: dict):
    counters = ctx.get("counters") or {}
    if args["numerator"] not in counters or args["denominator"] not in counters:
        return None
    top, bottom = counters[args["numerator"]], counters[args["denominator"]]
    if top is None or bottom is None:
        value = args.get("when_unset")
    elif not bottom:
        value = None
    else:
        value = top / bottom * args.get("scale", 1.0)
    if value is not None and args.get("mean_over_children"):
        value /= len(ctx["cell"]["schedules"])
    return value
