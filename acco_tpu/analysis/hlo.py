"""Shared HLO-text parsing: the one place this repo walks compiled programs.

Every structural claim the paper leans on — async overlap, buffer
donation, bytes-on-wire, dtype placement — is checked against either the
optimized *scheduled* HLO text (``compiled.as_text()``) or the
executable's module header. This module is the single implementation
``tools/overlap_hlo.py`` and the ``acco_tpu.analysis`` gate suite share.

Scheduled-HLO conventions this parser relies on (stable across the
jaxlib CPU and libtpu backends in this image):

- instruction defs print as ``%name = <result-type> opcode(operands)``,
  where the result type is a (possibly nested) tuple or ``dtype[dims]``
  with an optional layout brace group — :func:`parse_op` consumes it
  structurally rather than by regex;
- operands inside the opcode's paren group are bare ``%names``;
- buffer donation lands in the module header as
  ``input_output_alias={ {out}: (param, {}, may-alias), ... }``;
- async collectives appear as ``<kind>-start`` / ``<kind>-done`` pairs
  in the scheduled entry; whatever the scheduler placed between them
  runs while the collective is on the wire.

Pure stdlib — no jax import — so host-side lints can use it from any
process without touching a backend.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8,
}

# numpy dtype name -> HLO dtype token (for matching avals to entry params)
NUMPY_TO_HLO = {
    "bool": "pred", "int8": "s8", "uint8": "u8",
    "int16": "s16", "uint16": "u16", "float16": "f16", "bfloat16": "bf16",
    "int32": "s32", "uint32": "u32", "float32": "f32",
    "int64": "s64", "uint64": "u64", "float64": "f64",
    "float8_e4m3fn": "f8e4m3fn", "float8_e5m2": "f8e5m2",
}

SHAPE_RE = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([\d,]*)\]")
DEF_RE = re.compile(r"^\s*(%?[\w.-]+)\s*=\s*(.*)$")
OPERAND_RE = re.compile(r"%[\w.-]+")
GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")

COLLECTIVE_KINDS = (
    "all-gather", "reduce-scatter", "all-reduce", "collective-permute",
    "all-to-all",
)


def parse_op(rhs: str) -> tuple[str | None, int]:
    """(opcode, index where the result type ends). The result type is
    either a balanced-paren tuple or dtype[dims] with an optional layout
    brace group (which itself nests parens, e.g. {1,0:T(8,128)(2,1)}) —
    consume it structurally, then the next identifier is the opcode."""
    s = rhs
    i = 0
    if s.lstrip().startswith("("):
        i = len(s) - len(s.lstrip())
        depth = 0
        for j in range(i, len(s)):
            if s[j] == "(":
                depth += 1
            elif s[j] == ")":
                depth -= 1
                if depth == 0:
                    i = j + 1
                    break
    else:
        m = re.match(r"\s*\w+\[[^\]]*\]", s)
        if m:
            i = m.end()
            if i < len(s) and s[i] == "{":
                depth = 0
                for j in range(i, len(s)):
                    if s[j] == "{":
                        depth += 1
                    elif s[j] == "}":
                        depth -= 1
                        if depth == 0:
                            i = j + 1
                            break
    m2 = re.match(r"\s*([\w-]+)\(", s[i:])
    if not m2:
        return None, i
    return m2.group(1), i


def elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def result_bytes_elems(rhs: str, op_pos: int) -> tuple[int, int]:
    """(bytes, elements) of the result type — every dtype[dims] that
    appears before the op name belongs to the result (tuple members
    included); operands are printed as bare %names in scheduled HLO."""
    total_b = total_e = 0
    for m in SHAPE_RE.finditer(rhs[:op_pos]):
        e = elems(m.group(2))
        total_e += e
        total_b += e * DTYPE_BYTES[m.group(1)]
    return total_b, total_e


def split_computations(hlo: str) -> dict[str, list[str]]:
    """computation name -> its instruction lines (ENTRY under 'ENTRY')."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        s = line.strip()
        if s.startswith("ENTRY"):
            cur = "ENTRY"
            comps[cur] = []
        elif re.match(r"^%?[\w.-]+\s*(\([^)]*\))?.*\{\s*$", s) and "=" not in s and s:
            name = s.split()[0].lstrip("%").split("(")[0]
            if name and not s.startswith(("HloModule", "//")):
                cur = name
                comps[cur] = []
        elif s == "}":
            cur = None
        elif cur is not None and "=" in s:
            comps[cur].append(s)
    return comps


def entry_lines(hlo: str) -> list[str]:
    """The scheduled ENTRY computation's instruction lines."""
    return split_computations(hlo).get("ENTRY", [])


def operands(rhs: str, type_end: int) -> list[str]:
    """Operand names from the opcode's own paren group (attributes like
    ``calls=%...`` after the close paren are excluded)."""
    start = rhs.find("(", type_end)
    if start < 0:
        return []
    depth = 0
    for j in range(start, len(rhs)):
        if rhs[j] == "(":
            depth += 1
        elif rhs[j] == ")":
            depth -= 1
            if depth == 0:
                return [a.lstrip("%") for a in
                        OPERAND_RE.findall(rhs[start:j])]
    return []


# -- executable metadata (module header) -------------------------------------


_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+)\s*,\s*\{[\d,\s]*\}\s*,\s*([\w-]+)\)"
)


def parse_input_output_aliases(hlo: str) -> list[tuple[str, int, str]]:
    """Donations the compiler actually honored, from the module header:

        input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, ...) }

    Returns ``[(output_index, param_number, kind), ...]`` where
    ``output_index`` is the tuple-index string inside the braces (e.g.
    ``"0"`` or ``"1,2"`` for nested outputs). Empty list = the executable
    aliases nothing (every donated buffer was silently copied)."""
    # the header is one logical line; the alias map's braces nest, so cut
    # from 'input_output_alias={' to its balanced close instead of regex
    start = hlo.find("input_output_alias={")
    if start < 0:
        return []
    i = hlo.find("{", start)
    depth = 0
    for j in range(i, len(hlo)):
        if hlo[j] == "{":
            depth += 1
        elif hlo[j] == "}":
            depth -= 1
            if depth == 0:
                body = hlo[i + 1 : j]
                return [
                    (m.group(1).replace(" ", ""), int(m.group(2)), m.group(3))
                    for m in _ALIAS_ENTRY_RE.finditer(body)
                ]
    return []


_PARAM_RE = re.compile(r"parameter\((\d+)\)")


def entry_parameters(hlo: str) -> list[tuple[int, str, tuple]]:
    """Entry parameters of the compiled module, in parameter-number order:
    ``[(number, hlo_dtype, dims), ...]``. With ``keep_unused=False`` (the
    jax default) unused arguments are dropped at compile time, so this
    list is a subset of the traced signature — the donation analyzer
    aligns it back to ``lowered.args_info`` order-preservingly."""
    params = []
    for line in entry_lines(hlo):
        dm = DEF_RE.match(line)
        if not dm:
            continue
        rhs = dm.group(2)
        pm = _PARAM_RE.search(rhs)
        if not pm or "= " not in line or " parameter(" not in line:
            continue
        sm = SHAPE_RE.search(rhs)
        if not sm:
            continue
        dims = tuple(int(d) for d in sm.group(2).split(",") if d)
        params.append((int(pm.group(1)), sm.group(1), dims))
    params.sort(key=lambda t: t[0])
    return params


# -- collectives -------------------------------------------------------------


@dataclass
class Collective:
    """One collective in the scheduled entry (``-done`` lines excluded:
    a start/done pair is one collective)."""

    name: str       # instruction name (the -start's, for async)
    kind: str       # all-gather | reduce-scatter | all-reduce | ...
    asynchronous: bool
    line_index: int  # index into the entry's instruction-def list
    payload_bytes: int  # input-side payload (what goes on the wire once)
    group_size: int     # replica-group size (1 if unannotated)
    payload_elems: int = 0  # element count of the payload (small-op filter)

    def wire_bytes(self) -> int:
        """Bytes-on-wire for a bidirectional-ring execution of this op —
        the impl-invariant cost :mod:`acco_tpu.analysis.census` diffs
        against its analytic model. all-reduce = reduce-scatter +
        all-gather = 2·(n-1)/n·payload; gather/scatter = (n-1)/n; a
        permute is one hop of an already-decomposed ring, so its payload
        crosses the wire exactly once."""
        n = max(self.group_size, 1)
        if self.kind == "collective-permute":
            return self.payload_bytes
        factor = (n - 1) / n
        if self.kind == "all-reduce":
            factor *= 2
        return int(self.payload_bytes * factor)


_COLL_START_RE = re.compile(
    r"\b(" + "|".join(COLLECTIVE_KINDS) + r")-start\b"
)
_COLL_DONE_RE = re.compile(r"\b(" + "|".join(COLLECTIVE_KINDS) + r")-done\b")


@dataclass
class ScheduleReport:
    """Collectives + async windows of one scheduled entry computation."""

    collectives: list[Collective] = field(default_factory=list)
    # (collective, done_line_index, ops_in_window, compute_ops_in_window)
    windows: list[dict] = field(default_factory=list)
    total_scheduled_ops: int = 0

    def async_pairs(self) -> list[Collective]:
        return [c for c in self.collectives if c.asynchronous]

    def blocking(self, min_payload_elems: int = 0) -> list[Collective]:
        return [
            c for c in self.collectives
            if not c.asynchronous and c.payload_elems > min_payload_elems
        ]


_COMPUTE_PREFIXES = ("fusion", "dot", "convolution")


def _is_compute(line: str) -> bool:
    parts = line.split(" = ", 1)
    if len(parts) != 2:
        return False
    head = parts[1].split("(")[0].strip()
    return (
        head.startswith(_COMPUTE_PREFIXES)
        or " fusion(" in line
        or " dot(" in line
    )


def analyze_entry(hlo: str) -> ScheduleReport:
    """Walk the scheduled entry once: every collective (async pairs
    matched to their windows, blocking ops classified), payload bytes
    from the *input* side (operand result-bytes where resolvable).

    This is the parse both the overlap verdict and the collective census
    consume; they differ only in what they assert about it."""
    lines = entry_lines(hlo)
    report = ScheduleReport(total_scheduled_ops=len(lines))
    defs_bytes: dict[str, int] = {}
    defs_elems: dict[str, int] = {}
    starts: dict[str, Collective] = {}

    def _elems_of(payload_bytes: int, names: list[str]) -> int:
        known = sum(defs_elems.get(a.lstrip("%"), 0) for a in names)
        return known or payload_bytes // 4

    for i, line in enumerate(lines):
        dm = DEF_RE.match(line)
        if not dm:
            continue
        name, rhs = dm.group(1).lstrip("%"), dm.group(2)
        op, type_end = parse_op(rhs)
        rb, re_ = result_bytes_elems(rhs, type_end)
        defs_bytes[name] = rb
        defs_elems[name] = re_
        if op is None:
            continue
        args = operands(rhs, type_end)
        operand_bytes = sum(defs_bytes.get(a.lstrip("%"), 0) for a in args)
        gm = GROUPS_RE.search(rhs)
        group = len(gm.group(1).split(",")) if gm else 1

        sm = _COLL_START_RE.search(op + "(")
        if op.endswith("-start") and sm:
            kind = sm.group(1)
            if kind == "collective-permute":
                # result tuple = (input, output[, contexts]): wire payload
                # is one side
                payload = (
                    defs_bytes.get(args[0].lstrip("%"), rb // 2)
                    if args else rb // 2
                )
            else:
                payload = max(operand_bytes, rb) if kind == "reduce-scatter" \
                    else (operand_bytes or rb)
                if kind == "all-gather":
                    payload = max(rb, operand_bytes)
            c = Collective(
                name=name, kind=kind, asynchronous=True, line_index=i,
                payload_bytes=payload, group_size=group,
                payload_elems=_elems_of(payload, args[:1]),
            )
            starts[name] = c
            report.collectives.append(c)
            continue
        if op.endswith("-done") and _COLL_DONE_RE.search(op + " "):
            src = args[0].lstrip("%") if args else None
            c = starts.get(src)
            if c is not None:
                inside = lines[c.line_index + 1 : i]
                report.windows.append({
                    "name": c.name,
                    "kind": c.kind,
                    "window_ops": len(inside),
                    "compute_ops_in_window": sum(
                        1 for ln in inside if _is_compute(ln)
                    ),
                })
            continue
        if op in COLLECTIVE_KINDS:
            if op == "collective-permute":
                payload = operand_bytes or rb
            elif op == "all-gather":
                payload = max(rb, operand_bytes)
            else:
                payload = max(operand_bytes, rb)
            report.collectives.append(Collective(
                name=name, kind=op, asynchronous=False, line_index=i,
                payload_bytes=payload, group_size=group,
                payload_elems=max(re_, _elems_of(payload, args)),
            ))
    return report
