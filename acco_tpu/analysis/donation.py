"""Donation gate: declared ``donate_argnums`` must survive compilation.

jax treats donation as a *hint*: when XLA cannot alias a donated input
to an output (dtype change, layout mismatch, an op graph that still
reads the buffer after the output is produced), it silently copies —
the program stays correct but the buffer exists twice in HBM. For the
round state that is the difference between fitting and OOM (the
[ns·Pp] pending-grads vector alone is the largest allocation in the
ACCO round). This analyzer cross-checks three artifacts:

- ``lowered.args_info`` — the traced signature: which leaves the caller
  declared donated (flattened in order);
- the compiled module's entry parameters — the arguments that survived
  DCE (``keep_unused=False`` drops unused ones, order-preserved);
- the module header's ``input_output_alias`` map — the donations the
  compiler actually honored.

The traced-arg → entry-param alignment is a two-pointer walk in flat
order: a param matches the first unconsumed arg with the same dtype
whose element count it divides (SPMD partitioning shards some entry
params to 1/n of the traced aval, so equality is too strict). A donated
arg that matches no param was DCE'd (elided — harmless, reported); a
donated arg whose param is not in the alias map is a DROPPED donation
and fails the gate with its byte cost.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

from acco_tpu.analysis.hlo import (
    NUMPY_TO_HLO,
    entry_parameters,
    parse_input_output_aliases,
)


@dataclass
class DonationFinding:
    path: str
    dtype: str       # HLO dtype token
    shape: tuple
    nbytes: int      # full (unsharded) aval bytes
    status: str      # aliased | dropped | elided | undeclared


@dataclass
class DonationReport:
    ok: bool
    findings: list[DonationFinding] = field(default_factory=list)
    errors: list[str] = field(default_factory=list)

    @property
    def dropped(self) -> list[DonationFinding]:
        return [f for f in self.findings if f.status == "dropped"]

    @property
    def aliased(self) -> list[DonationFinding]:
        return [f for f in self.findings if f.status == "aliased"]

    @property
    def elided(self) -> list[DonationFinding]:
        return [f for f in self.findings if f.status == "elided"]

    def summary(self) -> str:
        drop_bytes = sum(f.nbytes for f in self.dropped)
        s = (
            f"{len(self.aliased)} donations aliased, "
            f"{len(self.dropped)} dropped"
        )
        if self.dropped:
            s += f" ({drop_bytes / 1e6:.2f} MB doubled in HBM)"
        if self.elided:
            s += f", {len(self.elided)} elided (arg unused)"
        if self.errors:
            s += f"; ERRORS: {'; '.join(self.errors)}"
        return s


def _flat_args(lowered) -> list[tuple[str, object, bool]]:
    """(path, aval, donated) per traced argument leaf, in flat order."""
    import jax

    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        lowered.args_info
    )[0]:
        aval = getattr(leaf, "aval", leaf)
        donated = bool(getattr(leaf, "donated", False))
        out.append((jax.tree_util.keystr(path), aval, donated))
    return out


def _kept_var_idx(compiled):
    """Indices of traced args kept after DCE, from the executable
    internals when exposed (jax 0.9.0 still has ``MeshExecutable
    ._kept_var_idx``; without it the caller aligns by shape) — the
    unambiguous entry-param alignment."""
    if compiled is None:
        return None
    for obj in (compiled, getattr(compiled, "_executable", None)):
        kept = getattr(obj, "_kept_var_idx", None)
        if kept is not None:
            try:
                return sorted(int(i) for i in kept)
            except TypeError:
                return None
    return None


def check_donation(lowered, compiled=None, hlo: str | None = None) -> DonationReport:
    """Verify every donation declared on ``lowered`` is honored by the
    executable. ``compiled``/``hlo`` are accepted to reuse an existing
    compile (the gate suite compiles each program once for all
    analyzers)."""
    if hlo is None:
        if compiled is None:
            compiled = lowered.compile()
        hlo = compiled.as_text()
    args = _flat_args(lowered)
    params = entry_parameters(hlo)
    aliased_params = {p for _out, p, _kind in parse_input_output_aliases(hlo)}

    report = DonationReport(ok=True)
    arg_status: list[str | None] = [None] * len(args)
    arg_param: list[int | None] = [None] * len(args)
    kept = _kept_var_idx(compiled)
    if kept is not None and len(kept) == len(params):
        # exact alignment: the executable records which traced args
        # survived DCE; entry params correspond to them in order
        for (pnum, _pd, _pdims), j in zip(params, sorted(kept)):
            if j < len(args):
                arg_param[j] = pnum
                arg_status[j] = "live"
    else:
        # fallback: two-pointer order-preserving alignment (see module
        # docstring) — ambiguous only when a DCE'd arg is adjacent to a
        # same-dtype live one
        ai = 0
        for pnum, pdtype, pdims in params:
            pelems = math.prod(pdims) if pdims else 1
            j = ai
            while j < len(args):
                path, aval, _don = args[j]
                adtype = NUMPY_TO_HLO.get(str(aval.dtype), str(aval.dtype))
                aelems = math.prod(aval.shape) if aval.shape else 1
                if adtype == pdtype and pelems and aelems % pelems == 0:
                    arg_param[j] = pnum
                    arg_status[j] = "live"
                    ai = j + 1
                    break
                j += 1
            else:
                report.errors.append(
                    f"entry parameter {pnum} ({pdtype}{list(pdims)}) "
                    "matched no traced argument — alignment failed"
                )
                report.ok = False
    for (path, aval, donated), status, pnum in zip(
        args, arg_status, arg_param
    ):
        if not donated:
            continue
        try:
            import numpy as np

            nbytes = int(
                math.prod(aval.shape or (1,)) * np.dtype(aval.dtype).itemsize
            )
        except Exception:
            nbytes = 0
        dt = NUMPY_TO_HLO.get(str(aval.dtype), str(aval.dtype))
        if status is None:
            report.findings.append(DonationFinding(
                path, dt, tuple(aval.shape), nbytes, "elided"
            ))
        elif pnum in aliased_params:
            report.findings.append(DonationFinding(
                path, dt, tuple(aval.shape), nbytes, "aliased"
            ))
        else:
            report.findings.append(DonationFinding(
                path, dt, tuple(aval.shape), nbytes, "dropped"
            ))
            report.ok = False
    return report
