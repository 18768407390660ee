"""Parallel ahead-of-time warmup of the round programs.

Without this, every program the trainer runs — seed, the even/odd
parity-specialized ACCO rounds, eval — compiles lazily inside the timed
loop at its first call, serially, with the TPU idle the whole time. XLA
releases the GIL during compilation, so the programs can instead be
lowered and compiled CONCURRENTLY on background threads at trainer
construction, overlapped with dataset tokenization, loader setup, and
state init. What that buys a launch on the chip is in the benchmark's
``setup_s`` and ``compile_lower_s`` (PERF.md §3; ROADMAP S7).

The warmup compiles from *abstract* inputs (``jax.ShapeDtypeStruct`` +
``NamedSharding`` — no state allocation, no data), via the steps'
``abstract_state()``/``abstract_block()``. The AOT ``lower().compile()``
result is not installed into jit's dispatch cache (jax keeps AOT and
just-in-time paths separate), so the first real call still goes through
compilation — but it is served from the persistent compilation cache
(cache.py) the warmup just populated: a disk deserialization in place
of a compile, and the trainer's startup path never blocks on XLA.

Failure policy: a warmup error NEVER fails training — the same program
will be compiled lazily at first call and raise there if genuinely
broken. Errors are captured per program in the returned records and
logged by the caller.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeoutError
from dataclasses import dataclass, field
from typing import Optional

from acco_tpu.telemetry.trace import Tracer

_log = logging.getLogger(__name__)

# Executors released by close(wait=False) with compiles still in flight.
# Their only product is a warmer persistent cache — safe to abandon —
# but their threads keep firing jax's cache monitoring events, which
# would land inside a LATER warmup's counting window (the
# test_same_config_twice flake). Registered here so any code about to
# count (or reset the cache object) can drain them first.
_ABANDONED: list = []
_ABANDONED_LOCK = threading.Lock()


def drain_abandoned_compiles() -> int:
    """Block until every abandoned warmup's in-flight compiles finish;
    returns how many executors were drained. Cheap when none are
    registered (the common case)."""
    with _ABANDONED_LOCK:
        executors, _ABANDONED[:] = list(_ABANDONED), []
    for executor in executors:
        executor.shutdown(wait=True)
    return len(executors)


@dataclass
class ProgramCompileRecord:
    """Per-program warmup outcome: lower/compile wall ms + the compiled
    executable (or the error)."""

    name: str
    lower_ms: Optional[float] = None
    compile_ms: Optional[float] = None
    error: Optional[str] = None
    # The jax.stages.Compiled executable. Callers SHOULD dispatch through
    # it (aot_call_with_fallback): jax's AOT and jit paths are separate,
    # so a plain jit call after warmup re-enters the compile path — an
    # avoidable persistent-cache deserialization. The AOT call touches
    # no cache at dispatch time.
    compiled: Optional[object] = None
    # Persistent-cache counters attributed to THIS program's compile at
    # event time (cache.attribute_cache_events): the compile thread
    # registers a window and the monitoring listeners credit it as each
    # event fires — exact even with other compiles running elsewhere in
    # the process, with no snapshot diff to race on.
    cache: Optional[dict] = None

    @property
    def ok(self) -> bool:
        return self.error is None

    @property
    def total_ms(self) -> float:
        return (self.lower_ms or 0.0) + (self.compile_ms or 0.0)


def _lower_and_compile(
    name: str, fn, args, kwargs, tracer: Tracer, submitted_us: float
) -> ProgramCompileRecord:
    """One warmup job: trace/lower then XLA-compile, each a span on this
    worker's track of the run's trace (``compile/lower``,
    ``compile/compile``); the record's wall times are the spans' own.

    The lowering (python tracing) holds the GIL, so concurrent jobs
    serialize there (and slow the main thread's set-up meanwhile); the
    compile releases it, which is where the parallelism pays.
    ``submitted_us`` is the trace clock at ``submit``: a span's start
    less that is how long the program waited for a worker."""
    from acco_tpu.compile.cache import attribute_cache_events

    rec = ProgramCompileRecord(name)
    args_of = {"program": name, "submitted_us": round(submitted_us, 1)}
    with attribute_cache_events() as window:
        try:
            t0 = tracer.now_us()
            lowered = fn.lower(*args, **kwargs)
            t1 = tracer.now_us()
            rec.lower_ms = (t1 - t0) / 1e3
            tracer.complete_event(
                "compile/lower", rec.lower_ms, cat="compile", ts_us=t0,
                args=dict(args_of),
            )
            rec.compiled = lowered.compile()
            rec.compile_ms = (tracer.now_us() - t1) / 1e3
            stats = window.stats()
            # deserialised (a hit) or compiled (a miss)
            tracer.complete_event(
                "compile/compile", rec.compile_ms, cat="compile", ts_us=t1,
                args={**args_of, "hits": stats["hits"], "misses": stats["misses"]},
            )
        except Exception as exc:  # never propagate: first real call will raise
            rec.error = f"{type(exc).__name__}: {exc}"
    rec.cache = window.stats()
    return rec


def aot_call_with_fallback(compiled, jit_fn, name: str, log=None):
    """Wrap an AOT ``Compiled`` so real dispatches use it directly, with
    a one-way fallback to the jit path if it ever rejects the inputs
    (AOT calls check avals strictly — shapes, dtypes, shardings must
    match the warmup's abstract args exactly; a mismatch means the
    warmup lowered a program the run doesn't dispatch, which must cost
    a recompile, not the run).

    Only the ARGUMENT-CHECK errors (TypeError/ValueError — raised before
    anything executes, so donated input buffers are still alive) trigger
    the fallback. Runtime failures propagate: by then donation has
    consumed the inputs, so retrying through jit would crash on deleted
    arrays and mask the real error."""
    state = {"aot": True}
    log = log or _log

    def call(*args):
        if state["aot"]:
            try:
                return compiled(*args)
            except (TypeError, ValueError) as exc:
                state["aot"] = False
                log.warning(
                    "AOT executable for %r rejected its inputs (%s); "
                    "falling back to the jit path — the warmup's "
                    "abstract avals drifted from the real call",
                    name,
                    exc,
                )
        return jit_fn(*args)

    return call


@dataclass
class WarmupReport:
    """Joined warmup outcome: per-program records + their cache counters
    (hits = programs served from the persistent cache instead of
    compiled). ``cache`` is the SUM of the per-program event-time
    attributed counters — not a global-counter window, so compiles
    running elsewhere in the process (another trainer's abandoned warmup
    threads) can't leak into it."""

    programs: dict = field(default_factory=dict)  # name -> record
    cache: dict = field(default_factory=dict)  # summed per-program deltas
    cache_dir: Optional[str] = None
    wall_ms: Optional[float] = None
    # False when join() timed out with programs still compiling: the
    # records are a snapshot, and a later join() can still complete.
    complete: bool = True

    @property
    def ok(self) -> bool:
        return all(rec.ok for rec in self.programs.values())

    def log_lines(self) -> list[str]:
        lines = []
        for name, rec in sorted(self.programs.items()):
            if rec.ok:
                lines.append(
                    f"compile[{name}]: lower {rec.lower_ms:.0f} ms, "
                    f"compile {rec.compile_ms:.0f} ms"
                )
            else:
                lines.append(f"compile[{name}]: FAILED ({rec.error})")
        if self.cache:
            lines.append(
                "compile cache: {hits} hit(s), {misses} miss(es)"
                " ({dir})".format(
                    hits=self.cache.get("hits", 0),
                    misses=self.cache.get("misses", 0),
                    dir=self.cache_dir or "disabled",
                )
            )
        return lines


class CompileWarmup:
    """Submit jit programs for background lower+compile; join for records.

    Jit objects must be CREATED on the caller thread (``round_fn()`` etc.
    memoize into their step objects, which is not thread-safe); only the
    lower/compile runs on the pool. ``join()`` is idempotent and never
    raises on program errors — inspect the records.
    """

    def __init__(
        self, max_workers: int = 4, log=None, tracer: Optional[Tracer] = None
    ) -> None:
        self._log = log or _log
        # the run's tracer (the trainer hands its own, as it does to the
        # CheckpointManager); without one the jobs time themselves on a
        # tracer that records nothing
        self._tracer = tracer if tracer is not None else Tracer(enabled=False)
        self._executor: Optional[ThreadPoolExecutor] = ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="acco-compile"
        )
        self._futures: dict[str, Future] = {}
        self._report: Optional[WarmupReport] = None
        self._t0 = time.perf_counter()
        from acco_tpu.compile.cache import active_cache_dir

        self._cache_dir = active_cache_dir()

    def submit(self, name: str, fn, *args, **kwargs) -> None:
        """Queue ``fn.lower(*args, **kwargs).compile()`` under ``name``."""
        if self._executor is None:
            raise RuntimeError("CompileWarmup already joined/closed")
        if name in self._futures:
            raise ValueError(f"duplicate warmup program name {name!r}")
        self._futures[name] = self._executor.submit(
            _lower_and_compile, name, fn, args, kwargs,
            self._tracer, self._tracer.now_us(),
        )

    @property
    def submitted(self) -> int:
        """How many programs have been queued."""
        return len(self._futures)

    @property
    def pending(self) -> bool:
        return any(not f.done() for f in self._futures.values())

    def join(self, timeout: Optional[float] = None) -> WarmupReport:
        """Wait for all submitted programs; return the report.

        ``timeout`` is a TOTAL deadline across all programs, not
        per-program. A completed join (no timeouts) is memoized and the
        pool released; a timed-out join returns a snapshot report with
        the unfinished programs marked — WITHOUT memoizing or closing,
        so a later join() can still collect them once the background
        compiles land."""
        if self._report is not None:
            return self._report
        deadline = (
            None if timeout is None else time.monotonic() + float(timeout)
        )
        programs = {}
        timed_out = False
        for name, fut in self._futures.items():
            remaining = (
                None if deadline is None else max(deadline - time.monotonic(), 0.0)
            )
            try:
                programs[name] = fut.result(timeout=remaining)
            except (FutureTimeoutError, TimeoutError):
                # (concurrent.futures.TimeoutError only aliases the
                # builtin from 3.11; catch both on 3.10)
                timed_out = True
                programs[name] = ProgramCompileRecord(
                    name, error="still compiling at join timeout"
                )
            except Exception as exc:  # executor teardown etc.
                programs[name] = ProgramCompileRecord(
                    name, error=f"{type(exc).__name__}: {exc}"
                )
        cache_totals = {"hits": 0, "requests": 0, "misses": 0,
                        "time_saved_s": 0.0}
        for rec in programs.values():
            if rec.cache:
                for key in cache_totals:
                    cache_totals[key] += rec.cache.get(key, 0)
        report = WarmupReport(
            programs=programs,
            cache=cache_totals,
            cache_dir=self._cache_dir,
            wall_ms=(time.perf_counter() - self._t0) * 1e3,
            complete=not timed_out,
        )
        if not timed_out:
            self._report = report
            self.close(wait=False)
        return report

    def close(self, wait: bool = False) -> None:
        """Shut the pool down. ``wait=False`` lets in-flight compiles
        finish in the background (their only effect is warming the
        persistent cache — safe to abandon); queued-but-unstarted jobs
        are cancelled so an abandoned warmup (e.g. a trainer whose
        constructor failed) never starts new compiles. Executors with
        compiles still running are registered for
        :func:`drain_abandoned_compiles` so later cache counting /
        cache resets can wait them out."""
        executor, self._executor = self._executor, None
        if executor is None:
            return
        executor.shutdown(wait=wait, cancel_futures=not wait)
        if not wait and any(not f.done() for f in self._futures.values()):
            with _ABANDONED_LOCK:
                _ABANDONED.append(executor)


def warmup_programs(
    programs: dict, *, max_workers: int = 4, log=None
) -> WarmupReport:
    """Synchronous convenience: ``{name: (fn, args...)}`` -> joined report.
    Each value is a tuple whose head is the jit fn and tail its abstract
    args."""
    runner = CompileWarmup(max_workers=max_workers, log=log)
    for name, spec in programs.items():
        fn, *args = spec
        runner.submit(name, fn, *args)
    return runner.join()
