"""A statistic of one of the program's host spans over the window.
args: ``span`` (name in the program's trace), ``stat`` (mean, median, max, sum)."""

from benchmark.harness.window import STATS, span_durations_ms


def reduce(ctx: dict, args: dict):
    durations = span_durations_ms(ctx["trace"], args["span"], ctx["window"])
    return STATS[args["stat"]](durations) if durations else None
