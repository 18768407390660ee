"""Time under one of the program's spans before the window's first fence, in
seconds, on the threads whose name matches. args: ``span`` (name in the
program's trace); ``thread`` (regex on the thread's name: ``^MainThread$``,
``^acco-compile``); ``how``: ``sum`` of the durations, or ``union``, the length
of the union of the intervals: the wall time during which SOME such thread was
inside the span, where the sum counts a second that three threads share three
times.

Only events that END at or before the fence count. A program that recorded no
``setup/*`` span (every commit before the one that added the set-up family)
gives nothing to read: None; one that did and never entered ``span`` reads 0.
"""

import re


def union_us(intervals: list) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def reduce(ctx: dict, args: dict):
    events = ctx["trace"]["traceEvents"]
    fence_us = ctx["window"].first.end_us
    if not any(e.get("ph") == "X" and e["name"].startswith("setup/") for e in events):
        return None
    wanted = re.compile(args["thread"])
    tids = {
        e["tid"] for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
        and wanted.search(e["args"]["name"])
    }
    intervals = [
        (e["ts"], e["ts"] + e.get("dur", 0.0)) for e in events
        if e.get("ph") == "X" and e["name"] == args["span"] and e["tid"] in tids
        and e["ts"] + e.get("dur", 0.0) <= fence_us + 0.25
    ]
    if args["how"] == "union":
        return union_us(intervals) / 1e6
    return sum(end - start for start, end in intervals) / 1e6
