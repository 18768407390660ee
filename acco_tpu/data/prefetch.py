"""Async prefetching input pipeline: collate + host->device transfer
ahead of the compiled round.

The device side of ACCO already hides its communication behind compute
(OVERLAP.md: every in-flight collective window carries compute), but the
host side of the train loop was serial: each round blocked on
``stack_microbatches`` (Python/C++ collate) and then on
``jax.device_put`` before the next round could even be dispatched — the
classic residual input-pipeline stall once collectives are hidden. This
module moves that host work off the critical path: a background worker
pulls batches from the loader, stacks the microbatch block, and performs
the sharded device transfer into a bounded queue, so round N+1's input
is already device-resident while round N's compiled program executes.

Two hard invariants, both load-bearing for the trainer:

* **exact resume** — :meth:`PrefetchingBlockSource.iter_state` reports
  the loader position of the last *consumed* block, never the last
  *prefetched* one. A checkpoint written with blocks still in the queue
  therefore resumes by re-collating exactly those blocks, and the
  restored run consumes the identical batch sequence an uninterrupted
  run would have (the shuffle order is a pure function of seed+epoch, so
  re-collation is deterministic).
* **clean shutdown / error propagation** — worker exceptions (a raising
  dataset, the loader's resume-mismatch check, a failed device_put)
  surface on the consumer thread at the next pull; ``close()`` never
  deadlocks against a worker blocked on a full queue (the worker's put
  is a stop-aware timed loop) and the thread is a daemon, so it can
  never outlive the process even if close() is skipped.

JAX note: ``jax.device_put`` / ``make_array_from_process_local_data``
are thread-safe array constructors with no cross-program ordering
requirements (no collectives run on the host side of the transfer), so
issuing them from the worker thread is safe in single- and multi-process
runs alike — each process's worker produces blocks in the same
deterministic order its trainer consumes them.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, Iterable, Iterator

from acco_tpu.data.loader import infinite_batches, stack_microbatches
from acco_tpu.telemetry import metrics


class _Sentinel:
    def __init__(self, name: str) -> None:
        self.name = name

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<prefetch {self.name}>"


_DONE = _Sentinel("done")
_ERROR = _Sentinel("error")


class AsyncPrefetcher:
    """Run an iterator on a background thread into a bounded queue.

    ``depth`` bounds how far the producer may run ahead of the consumer
    (memory backpressure: at most ``depth`` items' host+device buffers
    are alive beyond the one being consumed). The producer thread is a
    daemon and stop-aware: ``close()`` wakes a put blocked on a full
    queue and joins the thread.
    """

    def __init__(
        self,
        items: Iterable[Any],
        depth: int = 2,
        name: str = "acco-prefetch",
    ) -> None:
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._queue: "queue.Queue[Any]" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self._thread = threading.Thread(
            target=self._run, args=(iter(items),), name=name, daemon=True
        )
        self._thread.start()

    # -- producer side ------------------------------------------------------

    def _run(self, it: Iterator[Any]) -> None:
        try:
            for item in it:
                if not self._put(item):
                    return  # closed while producing
            self._put(_DONE)
        except BaseException as exc:  # noqa: BLE001 — must cross the thread
            self._error = exc
            self._put(_ERROR)

    def _put(self, item: Any) -> bool:
        """Stop-aware bounded put: never deadlocks against close()."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    # -- consumer side ------------------------------------------------------

    def __iter__(self) -> "AsyncPrefetcher":
        return self

    def __next__(self) -> Any:
        if self._stop.is_set():
            raise RuntimeError("prefetcher is closed")
        while True:
            try:
                item = self._queue.get(timeout=0.1)
            except queue.Empty:
                if not self._thread.is_alive():
                    # the worker died without managing to enqueue its
                    # sentinel (e.g. killed mid-put by close from another
                    # consumer) — surface whatever it recorded
                    if self._error is not None:
                        raise self._error
                    raise RuntimeError(
                        "prefetch worker exited without a result"
                    )
                continue
            if item is _DONE:
                raise StopIteration
            if item is _ERROR:
                assert self._error is not None
                raise self._error
            return item

    def close(self, join_timeout: float = 10.0) -> None:
        """Stop the worker and join it; safe to call more than once."""
        self._stop.set()
        # Join BEFORE draining: the timed put already makes the worker
        # notice the stop within its next 50 ms tick, whereas draining
        # first would free a slot for a pending put and let the worker
        # produce one full extra block (collate + device transfer) after
        # close() was requested.
        self._thread.join(timeout=join_timeout)
        while True:  # free the queued blocks' host/device buffers
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    def __enter__(self) -> "AsyncPrefetcher":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


class PrefetchingBlockSource:
    """Device-resident microbatch blocks, prefetched ahead of the round.

    Wraps a :class:`~acco_tpu.data.loader.ShardedBatchIterator`: the
    worker pulls ``n_acc`` batches per block through
    ``stack_microbatches`` and runs ``put_block`` (the trainer's sharded
    device transfer) before queueing, so the consumer's
    :meth:`next_block` normally returns an already-transferred block
    without touching the host pipeline at all.

    With ``prefetch=False`` the same interface runs fully synchronously
    (the debugging opt-out): identical batch sequence, identical
    ``iter_state`` protocol, no background thread.
    """

    def __init__(
        self,
        loader,
        n_acc: int,
        put_block: Callable[[Dict[str, Any]], Dict[str, Any]],
        depth: int = 2,
        prefetch: bool = True,
    ) -> None:
        self._loader = loader
        self._n_acc = int(n_acc)
        self._put_block = put_block
        # position of the last CONSUMED block; starts at the loader's
        # current (possibly just-restored) position so a checkpoint
        # written before the first consume resumes correctly
        self._consumed_state: Dict[str, int] = dict(loader.iter_state())
        self._prefetch = bool(prefetch) and depth > 0
        if self._prefetch:
            self._worker: AsyncPrefetcher | None = AsyncPrefetcher(
                self._produce(), depth=depth
            )
            self._stream = None
        else:
            self._worker = None
            self._stream = infinite_batches(loader)

    def _produce(self) -> Iterator[tuple]:
        stream = infinite_batches(self._loader)
        while True:
            stacked = stack_microbatches(stream, self._n_acc)
            # capture the position AFTER this block's batches: once the
            # consumer takes the block, this is its resume point
            state = dict(self._loader.iter_state())
            yield self._put_block(stacked), state

    def next_block(self) -> Dict[str, Any]:
        t0 = time.perf_counter()
        if self._worker is not None:
            block, state = next(self._worker)
            self._consumed_state = state
        else:
            stacked = stack_microbatches(self._stream, self._n_acc)
            self._consumed_state = dict(self._loader.iter_state())
            block = self._put_block(stacked)
        # Host-side wall only (the registry never touches the arrays):
        # with prefetch on this is pure queue wait — the residual the
        # async pipeline failed to hide — and with prefetch off it is
        # the full collate+transfer cost on the critical path.
        metrics.emit("loader_blocks_total", 1)
        metrics.emit("loader_block_wait_ms", (time.perf_counter() - t0) * 1e3)
        return block

    def iter_state(self) -> Dict[str, int]:
        """Loader position of the last consumed block (exact resume:
        blocks sitting prefetched in the queue are NOT counted — they
        will be re-collated deterministically after restore)."""
        return dict(self._consumed_state)

    def close(self) -> None:
        if self._worker is not None:
            self._worker.close()

    def __enter__(self) -> "PrefetchingBlockSource":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
