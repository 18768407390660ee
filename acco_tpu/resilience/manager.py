"""Overlapped checkpointing: train while you commit.

``save_checkpoint`` (utils/checkpoint.py) is synchronous — Orbax's
``StandardCheckpointer`` *is* an ``AsyncCheckpointer``, but the old call
site immediately ran ``wait_until_finished()``, so every periodic
checkpoint stalled all three train loops for the full serialize + write
(the one remaining hard host stall once collectives and the input
pipeline overlap — OVERLAP.md). :class:`CheckpointManager` splits the
save at its natural seam:

* ``save()`` blocks only for Orbax's device->host snapshot
  (``ckpt_snapshot_ms``; the commit behind it is ``ckpt_commit_ms``. What
  a save stalls the chip has no benchmark metric yet: ROADMAP S10). The
  snapshot happens *inside* the Orbax ``save()`` call, so the train loop may
  immediately dispatch the next round even though the round programs
  donate their input state buffers — the checkpoint reads the copy,
  never the donated-away originals.
* a **finalize thread** waits for the background commit, writes any
  side artifacts (``params.npz``), then commits the checkpoint by
  writing ``meta.json`` atomically LAST (utils/checkpoint.finalize_meta
  — same contract as the sync path), and applies the retention policy.

Retention (``keep_last`` / ``keep_every_s``) and the startup GC of
incomplete ``step_*`` dirs share one completeness definition
(utils/checkpoint.validate_checkpoint): a dir without a committed
meta.json is garbage from a killed saver and is removed at startup (and
logged); a committed-but-truncated dir is left in place for forensics
but skipped by ``latest_checkpoint``'s fallback chain.

Failure semantics: an error in the background commit (disk full, torn
write) is recorded and re-raised on the train loop at the next
``save()``/``wait()`` — never swallowed, never from a daemon thread's
stack trace only. The step dir it leaves behind has no meta.json, so a
restart GCs it and resumes from the previous complete step.
"""

from __future__ import annotations

import logging
import os
import shutil
import threading
import time
from contextlib import nullcontext
from typing import Any, Callable, Optional

from acco_tpu.telemetry import metrics
from acco_tpu.utils.checkpoint import (
    _checkpointer as _make_checkpointer,
    checkpoint_candidates,
    finalize_meta,
    validate_checkpoint,
)

_module_log = logging.getLogger(__name__)


class CheckpointManager:
    """Async (or sync) committed checkpoints under ``ckpt_dir`` with
    retention and startup GC.

    Multi-process contract mirrors ``save_checkpoint``'s: every process
    calls :meth:`save` (the Orbax save of a multi-host sharded array is a
    collective) and runs its own finalize thread, but only ``rank`` 0
    writes meta.json, GCs, and deletes retired checkpoints
    (shared-filesystem layout, like the trainer's other rank-0 gates).
    ``extra_files`` runs on whichever ranks pass it — pass it on rank 0
    only unless the artifact is per-rank.

    ``keep_last=0`` keeps everything; ``keep_last=N`` keeps the newest N
    complete checkpoints plus, when ``keep_every_s > 0``, a sparse
    archive of older ones spaced at least that many seconds apart (by
    their ``saved_at_unix`` meta stamp) — the "every 30 min forever,
    last 3 always" production policy.
    """

    def __init__(
        self,
        ckpt_dir: str,
        *,
        async_save: bool = True,
        keep_last: int = 0,
        keep_every_s: float = 0.0,
        rank: int = 0,
        log: Optional[logging.Logger] = None,
        gc_on_init: bool = True,
        tracer=None,
    ) -> None:
        self.ckpt_dir = os.path.abspath(ckpt_dir)
        self.async_save = bool(async_save)
        self.keep_last = int(keep_last)
        self.keep_every_s = float(keep_every_s)
        self.rank = int(rank)
        self.log = log or _module_log
        # Telemetry: an optional span tracer (acco_tpu/telemetry). The
        # snapshot span lands on the caller (train-loop) thread, the
        # commit span on the finalize thread — Perfetto shows the commit
        # running UNDER the next rounds, which is the whole point of the
        # async split. Stall metrics go to the global registry either way.
        self.tracer = tracer
        self._ckptr = None  # lazy: orbax import only when saving
        self._pending: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        if gc_on_init:
            self.gc_incomplete()

    # -- startup GC ---------------------------------------------------------

    def gc_incomplete(self) -> list:
        """Remove ``step_*`` dirs a killed saver left without a committed
        meta.json (they can never be restored and would otherwise
        accumulate forever); returns the removed paths. Rank 0 only, and
        only before this manager's own saves start — an uncommitted dir
        at that point cannot be an in-flight save of this run.

        Contract: a ``ckpt_dir`` has at most ONE live writer. Launching a
        second run into the same run_dir/run_name was never supported
        (the two would overwrite each other's step dirs and ledgers);
        under this GC it is also destructive — the newcomer deletes the
        incumbent's in-flight, uncommitted save. Same stance as Orbax's
        own manager, which cleans tmp dirs at startup.

        Committed-but-corrupt dirs (truncated state files behind a valid
        meta.json) are NOT removed: they are skipped by
        ``latest_checkpoint`` with a reason, and kept for forensics.
        """
        if self.rank != 0:
            return []
        removed = []
        for path in checkpoint_candidates(self.ckpt_dir):
            # The delete decision is structural — meta.json, written
            # last, IS the commit marker — never a match on
            # validate_checkpoint's human-readable reason text. A dir
            # with a meta.json (even a corrupt one) is kept.
            if os.path.exists(os.path.join(path, "meta.json")):
                continue
            reason = validate_checkpoint(path) or "uncommitted"
            try:
                shutil.rmtree(path)
            except OSError as exc:
                self.log.warning("could not GC %s: %s", path, exc)
                continue
            removed.append(path)
            self.log.warning("GC dropped %s (%s)", path, reason)
        return removed

    # -- saving -------------------------------------------------------------

    def _checkpointer(self):
        if self._ckptr is None:
            self._ckptr = _make_checkpointer()  # one shared construction
        return self._ckptr

    def save(
        self,
        step: int,
        state: Any,
        meta: dict,
        *,
        extra_files: Optional[Callable[[str], None]] = None,
        blocking: Optional[bool] = None,
    ) -> str:
        """Checkpoint ``state`` + ``meta`` as ``step_<step>``.

        Async mode returns as soon as Orbax has snapshotted the arrays to
        host; the commit (file writes, ``extra_files(path)``, meta.json,
        retention) continues on the finalize thread while training runs.
        A still-running previous save is drained first (saves are
        serialized), surfacing any error it hit. ``extra_files`` must
        only touch host data captured before the call — the train state
        it closes over may be donated away by the very next round.
        """
        self.wait()
        blocking = (not self.async_save) if blocking is None else blocking
        path = os.path.join(self.ckpt_dir, f"step_{int(step)}")
        os.makedirs(path, exist_ok=True)
        meta = dict(meta)
        meta.setdefault("saved_at_unix", time.time())
        ckptr = self._checkpointer()
        # Blocks for the device->host snapshot only (async Orbax); the
        # donated round-state buffers are safe to reuse once this returns.
        t_snap = time.perf_counter()
        with (
            self.tracer.span("ckpt/snapshot", cat="ckpt", path=path)
            if self.tracer is not None
            else nullcontext()
        ):
            ckptr.save(os.path.join(path, "state"), state, force=True)
        metrics.emit("ckpt_saves_total", 1)
        metrics.emit("ckpt_snapshot_ms", (time.perf_counter() - t_snap) * 1e3)
        if blocking:
            self._finalize(path, meta, extra_files)
            err, self._error = self._error, None
            if err is not None:
                raise err
        else:
            self._pending = threading.Thread(
                target=self._finalize,
                args=(path, meta, extra_files),
                name="acco-ckpt-finalize",
                daemon=True,
            )
            self._pending.start()
        return path

    def _finalize(self, path: str, meta: dict, extra_files) -> None:
        t_commit = time.perf_counter()
        try:
            self._ckptr.wait_until_finished()
            if extra_files is not None:  # caller gates this by rank
                extra_files(path)
            if self.rank == 0:
                finalize_meta(path, meta)  # the commit point, written last
                self._retention()
        except BaseException as exc:  # noqa: BLE001 — must cross the thread
            self._error = exc
            self.log.error("async checkpoint %s failed: %s", path, exc)
        finally:
            commit_ms = (time.perf_counter() - t_commit) * 1e3
            metrics.emit("ckpt_commit_ms", commit_ms)
            if self.tracer is not None:
                # recorded from THIS thread: sync saves land on the train
                # loop's track, async commits on their finalize track
                self.tracer.complete_event(
                    "ckpt/commit", commit_ms, cat="ckpt", args={"path": path}
                )

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Drain the in-flight save (if any); re-raise its failure on the
        caller — the train loop, not the daemon thread, owns the error.

        With a ``timeout``, returns False (and keeps the save pending) if
        the commit is still running when it expires; the default None
        waits for durability unconditionally."""
        pending = self._pending
        if pending is not None:
            pending.join(timeout)
            if pending.is_alive():
                return False
            self._pending = None
        err, self._error = self._error, None
        if err is not None:
            raise err
        return True

    def close(self, timeout: float = 60.0) -> None:
        """Best-effort bounded drain for exit paths that may already be
        unwinding an exception: commit failures are logged, not raised
        (the original exception must not be masked), and a commit wedged
        past ``timeout`` is abandoned to its daemon thread rather than
        hanging the exit. KeyboardInterrupt/SystemExit propagate — a
        forced interrupt must never be swallowed here."""
        try:
            if not self.wait(timeout):
                self.log.warning(
                    "in-flight checkpoint still committing after %.0fs; "
                    "abandoning it to its daemon thread", timeout
                )
                # Detach for real: a later save()/wait() on this manager
                # must not rediscover the wedged thread and block on it
                # unbounded. Its error, if any, still surfaces via
                # self._error at the next wait().
                self._pending = None
        except Exception as exc:
            self.log.error("in-flight checkpoint failed during close: %s", exc)

    @property
    def in_flight(self) -> bool:
        return self._pending is not None and self._pending.is_alive()

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # -- retention ----------------------------------------------------------

    def _saved_at(self, path: str) -> float:
        import json

        try:
            with open(os.path.join(path, "meta.json")) as f:
                return float(json.load(f)["saved_at_unix"])
        except Exception:
            try:  # pre-manager checkpoints: fall back to the commit mtime
                return os.path.getmtime(os.path.join(path, "meta.json"))
            except OSError:
                return 0.0

    def _retention(self) -> None:
        """Apply keep_last/keep_every_s over the *complete* checkpoints
        (incomplete/corrupt dirs are the GC's and the fallback chain's
        concern, not retention's). Runs on the finalize thread after each
        commit; deletion failures are logged, never raised."""
        if self.keep_last <= 0:
            return
        complete = [
            p for p in checkpoint_candidates(self.ckpt_dir)
            if validate_checkpoint(p) is None
        ]  # newest first
        keep = set(complete[: self.keep_last])
        if self.keep_every_s > 0:
            last_kept_ts = None
            for path in reversed(complete):  # oldest -> newest
                ts = self._saved_at(path)
                if last_kept_ts is None or ts - last_kept_ts >= self.keep_every_s:
                    keep.add(path)
                    last_kept_ts = ts
        for path in complete:
            if path in keep:
                continue
            try:
                shutil.rmtree(path)
                self.log.info("retention dropped %s", path)
            except OSError as exc:
                self.log.warning("retention could not drop %s: %s", path, exc)
