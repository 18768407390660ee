"""Compile-once subsystem: persistent compilation cache + parallel AOT
warmup for the round programs.

The trainer builds a fixed, enumerable set of XLA programs (seed, the
even/odd ACCO rounds, the DDP step, eval). This package makes their
compilation a one-time cost instead of a per-launch one:

- :mod:`cache` — wires JAX's persistent compilation cache (repeat
  launches and preemption-resumes of the same config compile nothing)
  and counts hits/misses via jax's monitoring events;
- :mod:`warmup` — lowers + compiles the programs concurrently on
  background threads from abstract avals, overlapped with dataset and
  state setup, instead of lazily inside the timed loop.

Entry points: ``setup_compilation_cache`` (main.py, tests/conftest.py),
``CompileWarmup``/``warmup_programs`` (trainer,
tools/compile_report.py), ``cache_stats``/``CacheStatsWindow``
(observability and the cache-key stability tests),
``attribute_cache_events`` (exact per-program hit/miss attribution for
the warmup records), ``trace_compiles`` (every backend compile of the
process as a ``compile/backend`` span on the run's tracer) and
``cache_dir_usage`` (how full the cache dir stands against its cap).
"""

from acco_tpu.compile.cache import (
    CacheStatsWindow,
    active_cache_dir,
    attribute_cache_events,
    cache_dir_usage,
    cache_stats,
    setup_compilation_cache,
    trace_compiles,
)
from acco_tpu.compile.warmup import (
    CompileWarmup,
    ProgramCompileRecord,
    WarmupReport,
    aot_call_with_fallback,
    drain_abandoned_compiles,
    warmup_programs,
)

__all__ = [
    "CacheStatsWindow",
    "CompileWarmup",
    "ProgramCompileRecord",
    "WarmupReport",
    "active_cache_dir",
    "aot_call_with_fallback",
    "attribute_cache_events",
    "cache_dir_usage",
    "cache_stats",
    "drain_abandoned_compiles",
    "setup_compilation_cache",
    "trace_compiles",
    "warmup_programs",
]
