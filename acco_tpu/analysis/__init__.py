"""Static analysis over compiled programs and host source.

Seven analyzers prove the invariants the paper's value proposition
rests on, every PR, from avals only (no chips):

- :mod:`~acco_tpu.analysis.overlap` — gradient-path collectives are
  async start/done pairs with compute scheduled in the window;
- :mod:`~acco_tpu.analysis.donation` — declared ``donate_argnums``
  actually alias outputs in the executable;
- :mod:`~acco_tpu.analysis.census` — collective op count and
  bytes-on-wire match the analytic comm model;
- :mod:`~acco_tpu.analysis.dtypes` — bf16-params / fp32-master-and-Adam
  policy over every state-pytree leaf (closed world);
- :mod:`~acco_tpu.analysis.rules` — sharding-rule coverage: every state
  leaf matches exactly one rule of its program's sharding rule table
  (acco_tpu/sharding), the placement analogue of the dtype walk;
- :mod:`~acco_tpu.analysis.host_lint` — AST lint for trace hazards
  (host syncs in loops, undonated state jits, unjoinable threads,
  unused imports);
- :mod:`~acco_tpu.analysis.metrics_gate` — every literal-named
  telemetry call site (``metrics.emit``, tracer spans) resolves against
  the closed-world declarations in :mod:`acco_tpu.telemetry` — the
  static mirror of the registry's runtime check.

:mod:`~acco_tpu.analysis.programs` builds the compiled-program registry
the gates walk; :mod:`~acco_tpu.analysis.slow_markers` audits the
tier-1 time budget. ``tools/lint.py --ci`` is the single entry point;
``tests/test_lint_gates.py`` proves each analyzer fails on its seeded
violation. HLO parsing lives in :mod:`~acco_tpu.analysis.hlo`, shared
with ``tools/overlap_hlo.py``.
"""

from acco_tpu.analysis.host_lint import Finding, lint_file, lint_paths  # noqa: F401
from acco_tpu.analysis.overlap import OverlapReport, check_overlap  # noqa: F401
from acco_tpu.analysis.rules import (  # noqa: F401
    RuleCoverageReport,
    check_rule_coverage,
)

__all__ = [
    "Finding",
    "lint_file",
    "lint_paths",
    "OverlapReport",
    "check_overlap",
    "RuleCoverageReport",
    "check_rule_coverage",
]
