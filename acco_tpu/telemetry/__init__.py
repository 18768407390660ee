"""Unified runtime telemetry: tracer and closed-world metrics.

Zero-dependency (stdlib only, no jax import) and zero-added-device-syncs
by construction — every timestamp wraps work the train/serve loops
already do, and the one per-cadence device fetch stays the trainer's
existing logging-boundary ``device_get``. Two surfaces:

* :mod:`~acco_tpu.telemetry.trace` — span/event tracer exporting a
  Chrome/Perfetto ``trace.json`` per run (``tools/trace_report.py``
  summarizes it), whose spans also land on a ``jax.profiler`` capture's
  host plane through an injected annotation, and the closed list of
  device scopes (``DEVICE_SCOPES``) the round programs and the model
  name their ops with (:mod:`~acco_tpu.telemetry.scopes` reads them back
  out of a compiled program's text);
* :mod:`~acco_tpu.telemetry.metrics` — the declared counter / gauge /
  histogram registry (unknown names raise; ``analysis/metrics_gate.py``
  proves call sites statically) with the Prometheus sink the server
  exposes.

Where a round's time went on the device, and what the host was doing in
each of the device's idle gaps, is read from the profiler's trace by the
benchmark's reducers (``benchmark/reducers/``), not modelled here.
"""

from acco_tpu.telemetry import metrics
from acco_tpu.telemetry.metrics import (
    REGISTRY,
    MetricSpec,
    MetricsRegistry,
    UndeclaredMetricError,
)
from acco_tpu.telemetry.scopes import innermost_scope, scope_table
from acco_tpu.telemetry.trace import (
    ALL_DEVICE_SCOPES,
    DECLARED_DEVICE_SCOPES,
    DEVICE_SCOPES,
    EXPERT_DEVICE_SCOPES,
    INSIDE_TRAINER_INIT,
    SETUP_SPANS,
    SPAN_NAMES,
    Tracer,
    UndeclaredSpanError,
    setup_phases,
    test_duration_records,
    validate_trace,
)

__all__ = [
    "metrics",
    "REGISTRY",
    "MetricSpec",
    "MetricsRegistry",
    "UndeclaredMetricError",
    "ALL_DEVICE_SCOPES",
    "DECLARED_DEVICE_SCOPES",
    "DEVICE_SCOPES",
    "EXPERT_DEVICE_SCOPES",
    "INSIDE_TRAINER_INIT",
    "SETUP_SPANS",
    "SPAN_NAMES",
    "Tracer",
    "UndeclaredSpanError",
    "innermost_scope",
    "scope_table",
    "setup_phases",
    "test_duration_records",
    "validate_trace",
]
