"""Device self time of the ops that lie in given device scopes of the program
(``acco_tpu/telemetry/trace.py`` ``DEVICE_SCOPES``), per traced round, averaged
over chips. args: ``scopes``: the innermost scopes whose ops count, ``""`` for
ops in no scope; ``except_ops``: a regex on op names that are left out whatever
their scope (the collectives: their time is ``collective_ms``'s).

The TPU's profile names an op by its HLO instruction and does not carry the
instruction's ``op_name`` (my chip run, PR 23: the ops line's events have
``device_offset_ps``, ``device_duration_ps`` and nothing that names a scope). So
the trainer writes, beside the capture, ``{program: {"scopes": {instruction:
scope}, "inferred": [instructions], "mixed": {fusion: [scopes]}}}`` from each
compiled round program's own text (``acco_tpu/telemetry/scopes.py``: an
instruction the compiler left without metadata goes by what reads its result),
and says which program each captured round ran (``otherData.scope_table``,
``profiled_programs``). This reducer joins on that: an op belongs to the module run that covers it on the
``XLA Modules`` line, the k-th module run is the k-th captured round, and the
round's program gives the instruction's scope. A run of one program alone
(DDP) needs no module line.

Every segment of the device's busy time has exactly one owner: a collective
(by ``except_ops``) or one scope or ``""``. So over disjoint ``scopes`` the
metrics of one ``except_ops`` and the collectives' own self time add up to
``round_device_ms``.

A program that wrote no table (every commit before PR 23) gives nothing to
read: None.
"""

import json
import os
import re

from benchmark.harness import hostplane, xplane

MODULES_LINE = r"^XLA Modules$"
_SEEN: dict = {}  # (profile path, except_ops) -> {owner: ms per round}


def module_programs(modules: list, programs: list) -> dict:
    """``{module event name: program}``: the k-th module run of a device, by
    start, is the k-th captured round."""
    ordered = sorted(modules, key=lambda m: m.start)
    return {m.name: program for m, program in zip(ordered, programs)}


def program_of(segment, modules: list, by_module: dict, only: str | None) -> str | None:
    """The program that ran the segment's instruction."""
    if only is not None:
        return only
    for m in modules:  # a dozen runs a capture
        if m.start <= segment.start < m.end:
            return by_module.get(m.name)
    return None


def _by_owner(ctx: dict, except_ops: str) -> dict | None:
    trace = ctx.get("device_trace")
    other = ctx["trace"].get("otherData") or {}
    table_path, programs = other.get("scope_table"), other.get("profiled_programs")
    if trace is None or not table_path or not programs or not os.path.exists(table_path):
        return None
    key = (table_path, except_ops)
    if key in _SEEN:
        return _SEEN[key]
    with open(table_path, encoding="utf-8") as f:
        tables = json.load(f)
    only = programs[0] if len(set(programs)) == 1 else None
    modules: dict = {}
    if only is None:
        path = hostplane.profile_path(ctx["trace"])
        if path:
            modules = xplane.read_ops(path, line_regex=MODULES_LINE)
        if not any(modules.values()):
            _SEEN[key] = None  # several programs and no line that says which ran when
            return None
    skip = re.compile(except_ops)
    for table in tables.values():
        table["inferred"] = set(table.get("inferred", ()))
    totals: dict = {}
    labels: dict = {}
    inferred_ns = 0.0
    for plane, segments in trace.segments.items():
        runs = modules.get(plane, [])
        by_module = module_programs(runs, programs)
        for s in segments:
            if skip.search(s.op.name):
                owner = "collective"
            else:
                table = tables.get(program_of(s, runs, by_module, only), {})
                owner = table.get("scopes", {}).get(s.op.name, "")
                shown = owner or "no scope"
                if s.op.name in table.get("inferred", ()):
                    inferred_ns += s.dur
                    shown = "~" + shown  # no op_name of its own: by what reads its result
                # XLA fuses across scopes: the fusion goes by its own op_name
                mixed = table.get("mixed", {}).get(s.op.name)
                label = (xplane.op_label(s.op) + (f"  [fuses {', '.join(mixed)}]" if mixed else ""), shown)
                labels[label] = labels.get(label, 0.0) + s.dur
            totals[owner] = totals.get(owner, 0.0) + s.dur
    scale = 1e6 * trace.devices * trace.rounds
    by_owner = {owner: ns / scale for owner, ns in totals.items()}
    ctx["say"](
        "device self time by scope, ms per traced round: "
        + ", ".join(f"{o or 'no scope'} {ms:.3f}" for o, ms in sorted(by_owner.items(), key=lambda kv: -kv[1]))
        + f"; of it {inferred_ns / scale:.3f} in instructions with no op_name of their own (~)"
    )
    for (label, shown), ns in sorted(labels.items(), key=lambda kv: -kv[1])[:32]:
        ctx["say"](f"  {ns / scale:8.3f} ms  {shown:21s} {label}")
    _SEEN[key] = by_owner
    return by_owner


def reduce(ctx: dict, args: dict):
    by_owner = _by_owner(ctx, args["except_ops"])
    if by_owner is None:
        return None
    return sum(by_owner.get(scope, 0.0) for scope in args["scopes"])
