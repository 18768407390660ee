"""Persistent XLA compilation cache wiring + hit/miss observability.

Every program the trainer builds — seed, the even/odd parity-specialized
ACCO round programs, the DDP step, eval — is a deterministic function of
(model config, mesh, batch shapes, step knobs): XLA recompiles it
byte-identically on every launch, every preemption-resume, and every test
that constructs a trainer. JAX ships a persistent compilation cache keyed
on the serialized HLO + compile options + jaxlib version that turns those
recompiles into disk deserializations (on a v5e, GPT-Neo-125M ACCO:
18-19 s per round program cold, 0.3-1.1 s warm; PERF.md); this module
is the one place that wires it up and counts what it does.

Where the cache lives is one rule (:func:`setup_compilation_cache`):
``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it, otherwise
``<checkout>/outputs/compile_cache``. The path is part of what makes a
relaunch hit, so no entry point derives it from the clock, the pid, a
temp dir or the working directory.

One deliberate deviation from JAX's defaults:
``min_compile_time_secs=0`` / ``min_entry_size_bytes=-1``. JAX skips
caching programs that compile in under a second; caching everything is
what lets a relaunch of a small config report zero misses.

Counters come from JAX's monitoring events (the same ones its own
telemetry uses): ``cache_hits`` / ``compile_requests`` /
``compile_time_saved_s``. They are process-global and monotonic.
Per-program readings (the trainer's warmup report) use
:class:`attribute_cache_events`, which credits events to the compiling
thread's registered window AT EVENT TIME — exact even when other
threads compile concurrently. :class:`CacheStatsWindow` remains the
coarse before/after delta for callers that own process quiescence (the
cache-key stability tests).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

from acco_tpu.telemetry import metrics

_log = logging.getLogger(__name__)

# Monotonic process-global counters fed by jax's monitoring events.
_COUNTS = {"hits": 0, "requests": 0, "time_saved_s": 0.0}
# Per-program attribution target: a thread about to compile registers a
# counts dict here (attribute_cache_events), and the listeners increment
# it AT EVENT TIME. The events fire synchronously on the compiling
# thread, so a warmup worker that runs one program inside one window
# gets exactly that program's hits/misses — no before/after snapshot of
# a shared counter is ever read, which is what made the old
# thread-ident-keyed deltas racy when test files share a process (a
# recycled thread ident, or an abandoned warmup's late events, landed
# inside another program's window: the test_same_config_twice flake).
_ATTRIBUTION = threading.local()
_LOCK = threading.Lock()
_LISTENERS_INSTALLED = False

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
# fired around compile_or_get_cached, on the thread that compiles, hit or
# miss: once per program XLA was asked for
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

# The run's tracer (trace_compiles): every backend-compile event lands on
# it as a compile/backend span on the compiling thread's track. The
# listeners are process-global and cannot be unregistered, so the tracer
# is what changes, not the listener.
_TRACER = None


def trace_compiles(tracer) -> None:
    """Write every backend-compile event of the process to ``tracer`` from
    now on (None stops it): a warmup thread's inside its
    ``compile/compile``, a lazy compile on the main thread bare."""
    global _TRACER
    _install_listeners()
    _TRACER = tracer


def _on_event(event: str, **kwargs) -> None:
    if event == _HIT_EVENT:
        key = "hits"
    elif event == _REQUEST_EVENT:
        key = "requests"
    else:
        return
    # the event fires on the compiling thread: attribute it to that
    # thread's registered window NOW, not via a later snapshot diff
    target = getattr(_ATTRIBUTION, "target", None)
    with _LOCK:
        _COUNTS[key] += 1
        if target is not None:
            target[key] += 1
    # registry mirror (declared names; its own lock — never taken under
    # _LOCK, the registry emit locks internally)
    metrics.emit(
        "compile_cache_hits_total"
        if key == "hits"
        else "compile_cache_requests_total",
        1,
    )


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _SAVED_EVENT:
        target = getattr(_ATTRIBUTION, "target", None)
        with _LOCK:
            _COUNTS["time_saved_s"] += float(duration)
            if target is not None:
                target["time_saved_s"] += float(duration)
        # jax reports sub-ms NEGATIVE savings on trivial programs (cache
        # overhead > compile time); the counter is monotone, so clamp —
        # _COUNTS above keeps the signed truth.
        metrics.emit("compile_cache_time_saved_s", max(0.0, float(duration)))
    elif event == _BACKEND_EVENT:
        tracer = _TRACER
        if tracer is not None:
            # duration from the event, end = now, thread = this one; of a
            # compile that began before this tracer's clock did (an
            # earlier run's abandoned warmup), the part on the clock
            now_us = tracer.now_us()
            start_us = max(0.0, now_us - float(duration) * 1e6)
            tracer.complete_event(
                "compile/backend", (now_us - start_us) / 1e3, cat="compile",
                ts_us=start_us,
            )


def _install_listeners() -> None:
    """Register the jax monitoring listeners once per process (idempotent;
    the registry has no unregister-by-name, so double registration would
    double-count)."""
    global _LISTENERS_INSTALLED
    with _LOCK:
        if _LISTENERS_INSTALLED:
            return
        from jax import monitoring

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENERS_INSTALLED = True


def cache_stats() -> dict:
    """Snapshot of the process-global persistent-cache counters:
    ``{"hits", "misses", "requests", "time_saved_s"}``. ``requests``
    counts compiles that consulted the cache; ``misses`` is the
    derived difference."""
    with _LOCK:
        hits = _COUNTS["hits"]
        requests = _COUNTS["requests"]
        saved = _COUNTS["time_saved_s"]
    return {
        "hits": hits,
        "requests": requests,
        "misses": max(requests - hits, 0),
        "time_saved_s": saved,
    }


class attribute_cache_events:
    """Event-time attribution window for the calling thread's compiles.

    Usage::

        with attribute_cache_events() as window:
            fn.lower(...).compile()
        per_program = window.stats()

    jax's monitoring events fire synchronously on the thread performing
    the compile, so every hit/request/saved-duration fired while the
    window is entered on this thread is credited to ``window.counts``
    *as the event fires*. Unlike the before/after counter snapshots this
    replaced, there is no shared counter to race on: events from other
    threads (an abandoned warmup still compiling, another trainer's
    workers) land in THEIR windows or only the global counters, never in
    this one. Windows nest (the inner window shadows the outer for its
    extent — reentrancy safety; nested attribution is not split)."""

    def __init__(self) -> None:
        self.counts = {"hits": 0, "requests": 0, "time_saved_s": 0.0}
        self._prev = None

    def __enter__(self) -> "attribute_cache_events":
        _install_listeners()
        self._prev = getattr(_ATTRIBUTION, "target", None)
        _ATTRIBUTION.target = self.counts
        return self

    def __exit__(self, *exc) -> None:
        _ATTRIBUTION.target = self._prev

    def stats(self) -> dict:
        """Attributed counters (same shape as :func:`cache_stats`)."""
        with _LOCK:
            counts = dict(self.counts)
        return {
            "hits": counts["hits"],
            "requests": counts["requests"],
            "misses": max(counts["requests"] - counts["hits"], 0),
            "time_saved_s": counts["time_saved_s"],
        }


class CacheStatsWindow:
    """Delta reader over the global counters: ``begin()`` (or construct),
    do compiles, ``delta()``. Used by the trainer's warmup report and the
    cache-key stability tests; NOT isolated against concurrent compiles
    elsewhere in the process — callers own the quiescence."""

    def __init__(self) -> None:
        self.begin()

    def begin(self) -> None:
        self._t0 = cache_stats()

    def delta(self) -> dict:
        now = cache_stats()
        return {
            key: now[key] - self._t0[key]
            for key in ("hits", "requests", "misses", "time_saved_s")
        }


def active_cache_dir() -> Optional[str]:
    """The currently configured persistent cache dir, or None."""
    import jax

    return jax.config.jax_compilation_cache_dir


def cache_dir_usage() -> tuple:
    """``(bytes under the active cache dir, the cap on them)``, either None
    where there is none: no dir, or ``jax_compilation_cache_max_size``
    unset (-1: jax never evicts). jax keeps the dir flat, so one
    ``scandir``. At the cap jax's LRU evicts on every write, and a launch's
    miss may be another launch's eviction (ROADMAP S7)."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    used = None
    if cache_dir and os.path.isdir(cache_dir):
        with os.scandir(cache_dir) as entries:
            used = sum(e.stat().st_size for e in entries if e.is_file())
    cap = int(jax.config.jax_compilation_cache_max_size)
    return used, (cap if cap > 0 else None)


#: The checkout this package lives in. A relative cache dir is resolved
#: against it, never against the working directory: the path is part of
#: what makes a relaunch hit, so it must not move with the caller's cwd.
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, "outputs", "compile_cache")


def setup_compilation_cache(
    cache_dir: str = DEFAULT_CACHE_DIR,
    *,
    min_compile_time_secs: float = 0.0,
    min_entry_size_bytes: int = -1,
    log=None,
) -> Optional[str]:
    """Turn JAX's persistent compilation cache on and return its dir.

    Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` if the
    environment sets it (the cache was placed from outside, and nothing
    in this repo points it anywhere else), otherwise ``cache_dir``, a
    relative one resolved against the checkout (default
    ``<checkout>/outputs/compile_cache``). A falsy ``cache_dir`` is the
    opt-out: the configuration is left as it is and the dir that is
    already active (or None) is returned.
    """
    log = log or _log
    _install_listeners()  # observability even when the dir was pre-set
    import jax

    existing = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        return existing or None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, os.path.expanduser(str(cache_dir))
    )
    if existing != cache_dir:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # An abandoned warmup (close(wait=False)) may still be compiling
        # on background threads; resetting the cache object under a live
        # compile is a race, and those threads' monitoring events would
        # land inside the NEXT warmup's counting window. Drain them first.
        from acco_tpu.compile.warmup import drain_abandoned_compiles

        drained = drain_abandoned_compiles()
        if drained:
            log.debug("drained %d abandoned warmup executor(s)", drained)
        # jax memoizes its is-the-cache-usable verdict at the FIRST
        # compile: a process that compiled anything before this call —
        # model init, a device_put — has the verdict frozen at "unused"
        # and would silently never read or write the dir we just
        # configured. Reset so the next compile re-evaluates.
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    jax.config.update("jax_enable_compilation_cache", True)
    # By default the cache's key leaves an instruction's metadata out, so
    # a program that differs from a cached one only in its named scopes
    # (telemetry.trace.DEVICE_SCOPES) or source lines is served the
    # cached executable with the OTHER source's names: a profile of it
    # would attribute device time to scopes this source does not have.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(min_compile_time_secs),
    )
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes", int(min_entry_size_bytes)
    )
    return cache_dir
