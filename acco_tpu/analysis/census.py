"""Collective census: every byte on the wire must be accounted for.

The round's communication has a closed form
(:func:`acco_tpu.analysis.programs.ring_comm_bytes`): the gradient path
moves exactly one reduce-scatter of fp32 gradients plus one all-gather
of param-dtype params per round, ``(ns-1)/ns · Pp · (4 + itemsize)``
bytes on the wire however the collectives are spelled (ring ppermutes,
async native ops, or blocking pairs). This gate diffs each compiled
program's census (op count + wire bytes from the scheduled entry)
against that model, so
an accidental extra all-reduce — a psum left in a loss path, a
re-gather of params someone adds in a refactor — fails CI with a byte
count instead of silently shipping a 2x comm regression.

Small collectives (count/health/loss psums, ≤ ``small_elems``
elements) are counted separately and capped rather than modeled:
they're latency-bound bookkeeping, not bandwidth.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from acco_tpu.analysis.hlo import analyze_entry

DEFAULT_TOLERANCE = 0.10
DEFAULT_MAX_SMALL_OPS = 16


@dataclass
class CensusReport:
    ok: bool
    measured_bytes: int
    expected_bytes: float
    large_ops: int
    small_ops: int
    kinds: dict = field(default_factory=dict)  # kind -> count (large only)
    errors: list[str] = field(default_factory=list)

    def summary(self) -> str:
        s = (
            f"{self.large_ops} large collectives, "
            f"{self.measured_bytes / 1e3:.1f} kB on wire "
            f"(model: {self.expected_bytes / 1e3:.1f} kB), "
            f"{self.small_ops} small"
        )
        if self.errors:
            s += f"; {'; '.join(self.errors)}"
        return s


def check_census(
    hlo: str,
    expected_bytes: float,
    expected_ops: tuple[int, int] | None = None,
    tolerance: float = DEFAULT_TOLERANCE,
    small_elems: int = 1_000_000,
    max_small_ops: int = DEFAULT_MAX_SMALL_OPS,
) -> CensusReport:
    """Diff one program's scheduled-entry collectives against the comm
    model. ``expected_bytes == 0`` asserts a collective-free program
    (serve's single-replica programs; eval's data psums are small)."""
    sched = analyze_entry(hlo)
    large = [c for c in sched.collectives if c.payload_elems > small_elems]
    small = [c for c in sched.collectives if c.payload_elems <= small_elems]
    measured = sum(c.wire_bytes() for c in large)
    kinds: dict[str, int] = {}
    for c in large:
        kinds[c.kind] = kinds.get(c.kind, 0) + 1

    errors = []
    if expected_bytes == 0:
        if large:
            errors.append(
                f"expected a collective-free gradient path, found "
                f"{len(large)} large collectives ({kinds}) moving "
                f"{measured / 1e3:.1f} kB"
            )
    else:
        lo = expected_bytes * (1 - tolerance)
        hi = expected_bytes * (1 + tolerance)
        if not (lo <= measured <= hi):
            errors.append(
                f"wire bytes {measured} outside model "
                f"[{lo:.0f}, {hi:.0f}] ({kinds}) — an extra or missing "
                "gradient-path collective"
            )
    if expected_ops is not None:
        olo, ohi = expected_ops
        if not (olo <= len(large) <= ohi):
            errors.append(
                f"large-collective op count {len(large)} outside "
                f"expected [{olo}, {ohi}]"
            )
    if len(small) > max_small_ops:
        errors.append(
            f"{len(small)} small collectives exceed the bookkeeping cap "
            f"{max_small_ops} — scalar psums are accreting"
        )
    return CensusReport(
        ok=not errors,
        measured_bytes=measured,
        expected_bytes=expected_bytes,
        large_ops=len(large),
        small_ops=len(small),
        kinds=kinds,
        errors=errors,
    )
