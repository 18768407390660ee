"""Which device scope each instruction of a compiled program lies in.

The round programs and the model wrap their phases in ``jax.named_scope``
(:data:`~acco_tpu.telemetry.trace.ALL_DEVICE_SCOPES`); the name lands in the
``op_name`` metadata of the HLO instructions traced under it. A TPU profile
names each op by its instruction but does not carry that metadata, so a reader
of the profile needs a table from instruction to scope: :func:`scope_table`
builds it from ``compiled.as_text()``, and the trainer writes it beside the
capture (``device_scopes.json``).

Stdlib only: the text of a program goes in, a dict comes out.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Any, Dict, List, NamedTuple

from acco_tpu.telemetry.trace import ALL_DEVICE_SCOPES

# The scopes that only hold others: an op under model/block/model/mlp is
# the MLP's, and one under both is no mix of two layers' code.
_OUTER_SCOPES = frozenset({"acco/accumulate", "model/block"})
# Instructions that take no device time of their own: scopes flow through
# them, they are never a producer's or a consumer's reason to exist.
_PLUMBING = frozenset({"parameter", "get-tuple-element", "tuple", "constant", "bitcast"})


def innermost_scope(op_name: str) -> str:
    """The scope of ``ALL_DEVICE_SCOPES`` that an instruction's ``op_name``
    names last, ``""`` where it names none. Scopes nest left to right and
    transforms wrap them (``.../acco/accumulate/
    transpose(jvp(model/embed))/scatter-add``), so the last one named is
    the innermost."""
    best, at = "", -1
    for scope in ALL_DEVICE_SCOPES:
        i = op_name.rfind(scope)
        if i > at:
            best, at = scope, i
    return best


class _Instruction(NamedTuple):
    name: str
    scope: str  # by its own op_name; "" where it has none
    opcode: str
    refs: List[str]  # every %name on its right-hand side: operands and computations


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?(?P<name>[^\s=]+) = (?P<rest>.*)$")
_OP_NAME = re.compile(r'\bop_name="(?P<op_name>[^"]*)"')
_OPCODE = re.compile(r"[\s)}\]](?P<opcode>[a-z][a-z\-]*)\(")  # types hold no lowercase word before "("
_REF = re.compile(r"%([^\s,(){}=]+)")


def _parse(hlo_text: str) -> Dict[str, List[_Instruction]]:
    computations: Dict[str, List[_Instruction]] = {}
    current: List[_Instruction] = []
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ", "}"):  # a computation's header, at column 0
            header = _COMPUTATION.match(line)
            current = computations.setdefault(header["name"], []) if header else []
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        rest = m["rest"].split(", metadata={")[0].split(", backend_config=")[0]
        named = _OP_NAME.search(m["rest"])
        opcode = _OPCODE.search(" " + rest)
        current.append(
            _Instruction(
                m["name"],
                innermost_scope(named["op_name"]) if named else "",
                opcode["opcode"] if opcode else "",
                _REF.findall(rest),
            )
        )
    return computations


def _most_common(scopes) -> str:
    found = Counter(s for s in scopes if s)
    return found.most_common(1)[0][0] if found else ""


def scope_table(hlo_text: str) -> Dict[str, Any]:
    """Over the text of a compiled program (``compiled.as_text()``):

    - ``scopes``: ``{instruction name: innermost device scope}``,
      instructions in no scope left out.
    - ``inferred``: the instructions of ``scopes`` that carry no
      ``op_name`` of their own. The compiler's later passes make
      instructions without metadata (layout copies, a reshape turned into
      a loop of slices and updates); such an instruction goes by what
      reads its result (it exists for its consumer), else by what it
      reads, else by the instruction that calls its computation (a loop's
      body goes by the loop), most common scope first.
    - ``mixed``: ``{fusion name: [scopes]}`` for the fusions whose fused
      instructions lie in more than one scope, the scopes that only hold
      others (``acco/accumulate``, ``model/block``) not counted. XLA
      fuses across scopes (a speculative round's AdamW with the guard's norms); the
      fusion's own ``op_name``, the one ``scopes`` goes by, is one of
      them.
    """
    computations = _parse(hlo_text)
    fused = {
        ref
        for instructions in computations.values()
        for ins in instructions
        if ins.opcode == "fusion"
        for ref in ins.refs
        if ref in computations
    }
    scopes: Dict[str, str] = {}
    inferred: List[str] = []

    def resolve(name: str, caller_scope: str, seen: set) -> None:
        instructions = computations[name]
        local = {ins.name: ins.scope for ins in instructions if ins.scope}
        readers: Dict[str, List[str]] = {}
        for ins in instructions:
            for ref in ins.refs:
                readers.setdefault(ref, []).append(ins.name)
        # text order is operands first: consumers resolve in reverse, producers forward
        for ins in reversed(instructions):
            if ins.name not in local:
                local[ins.name] = _most_common(local.get(r, "") for r in readers.get(ins.name, ()))
        for ins in instructions:
            if not local[ins.name]:
                local[ins.name] = _most_common(local.get(r, "") for r in ins.refs) or caller_scope
        for ins in instructions:
            if local[ins.name] and ins.opcode not in _PLUMBING:
                scopes[ins.name] = local[ins.name]
                if not ins.scope:
                    inferred.append(ins.name)
            if ins.opcode != "fusion":
                for ref in ins.refs:
                    if ref in computations and ref not in seen:
                        seen.add(ref)
                        resolve(ref, local[ins.name], seen)

    called = {
        ref for instructions in computations.values() for ins in instructions
        for ref in ins.refs if ref in computations
    }
    seen: set = set()
    for name in computations:
        if name not in called:  # the entry computation (and any that nothing calls)
            resolve(name, "", seen)
    mixed = {}
    for name, instructions in computations.items():
        for ins in instructions:
            if ins.opcode != "fusion":
                continue
            found = {scopes.get(ins.name, "")}
            for ref in ins.refs:
                if ref in fused:
                    found |= {i.scope for i in computations[ref]}
            found -= _OUTER_SCOPES | {""}
            if len(found) > 1:
                mixed[ins.name] = sorted(found)
    # fused instructions are no ops of a profile, but name their own scope
    for name in fused:
        for ins in computations[name]:
            if ins.scope:
                scopes.setdefault(ins.name, ins.scope)
    return {"scopes": scopes, "inferred": sorted(inferred), "mixed": mixed}
