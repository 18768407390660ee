"""Run logging: TensorBoard scalars, the results.csv ledger, progress lines.

Capability parity with the reference's observability layer
(`/root/reference/utils/logs_utils.py`): the same TensorBoard scalar names
(``loss_t`` / ``loss_step`` / ``loss_samples`` and the ``eval_loss_*``
family, `:187-224`), the append-with-schema-merge ``results.csv`` ledger
(`:83-138`), the per-N-grads progress log line (`:155-183`), and the
run-id scheme (`:19-40`). The TensorBoard files are written by this
module's own ``EventWriter`` over TensorBoard's protos and record framing:
the files ``torch.utils.tensorboard.SummaryWriter`` lays down for the same
calls, without importing ``torch`` (which pulls ``tensorflow`` and ``keras``
in where the image has them: 25-69 s of a launch on the chip's host,
PERF.md section 6). It degrades to a no-op writer where ``tensorboard`` is
unavailable, so training never depends on it.
"""

from __future__ import annotations

import csv
import datetime
import itertools
import os
import random
import socket
import struct
import sys
import time
from typing import Any, Dict, Iterable, Optional


class NoOpWriter:
    """Stand-in for SummaryWriter when tensorboard is unavailable."""

    def add_scalars(self, *args: Any, **kwargs: Any) -> None:
        pass

    def add_scalar(self, *args: Any, **kwargs: Any) -> None:
        pass

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


def _crc32c_table() -> tuple:
    """CRC-32C (Castagnoli), reflected: one entry a byte."""
    table = []
    for entry in range(256):
        for _ in range(8):
            entry = (entry >> 1) ^ 0x82F63B78 if entry & 1 else entry >> 1
        table.append(entry)
    return tuple(table)


_CRC32C = _crc32c_table()


def _masked_crc32c(data: bytes) -> bytes:
    crc = 0xFFFFFFFF
    for byte in data:
        crc = _CRC32C[(crc ^ byte) & 0xFF] ^ (crc >> 8)
    crc ^= 0xFFFFFFFF
    return struct.pack("<I", (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF)


def event_record(payload: bytes) -> bytes:
    """One record of an event file (TFRecord framing, little-endian): the
    payload's length as uint64, the masked crc32c of those eight bytes, the
    payload, its masked crc32c. What ``tensorboard.summary.writer.
    record_writer.RecordWriter`` writes; importing that module loads 98 more
    (every plugin's summary, a stub of ``tf``), 3 s of the launch's main
    thread under the lowering threads' GIL (PERF.md section 6, PR 38)."""
    header = struct.pack("<Q", len(payload))
    return header + _masked_crc32c(header) + payload + _masked_crc32c(payload)


class EventWriter:
    """The run's scalars as TensorBoard event files, laid down where
    ``torch.utils.tensorboard.SummaryWriter`` puts them: ``add_scalar``
    writes its tag into ``log_dir``'s own file, ``add_scalars(main_tag,
    {key: value}, step)`` writes ``main_tag`` into a file of
    ``log_dir/<main_tag>_<key>/``; ``step`` is ``int(step)`` and the wall
    time is the call's. A file is opened once, at the writer's making or at
    its directory's first scalar, and written through Python's buffer: a
    call costs no system call, ``flush()`` and ``close()`` hand the buffers
    to the OS (no ``fsync``), and so does the first call ``FLUSH_SECS``
    after the last hand-over."""

    FLUSH_SECS = 120.0  # torch's default
    _file_ids = itertools.count()  # the file name's last part, process-wide

    def __init__(self, log_dir: str) -> None:
        from tensorboard.compat.proto import event_pb2, summary_pb2

        self._event, self._summary = event_pb2.Event, summary_pb2.Summary
        self.log_dir = log_dir
        self._files: Dict[str, Any] = {}  # directory -> its open event file
        self._last_flush = time.time()
        self._file(log_dir)

    def _file(self, directory: str):
        file = self._files.get(directory)
        if file is None:
            os.makedirs(directory, exist_ok=True)
            now = time.time()
            name = "events.out.tfevents.%010d.%s.%s.%s" % (
                now, socket.gethostname(), os.getpid(), next(self._file_ids)
            )
            file = open(os.path.join(directory, name), "wb")
            version = self._event(wall_time=now, file_version="brain.Event:2")
            file.write(event_record(version.SerializeToString()))
            file.flush()  # a reader finds a whole file from the start
            self._files[directory] = file
        return file

    def _write(self, directory: str, tag: str, value: Any, step: Any) -> None:
        now = time.time()
        summary = self._summary(
            value=[self._summary.Value(tag=tag, simple_value=float(value))]
        )
        event = self._event(wall_time=now, step=int(step), summary=summary)
        self._file(directory).write(event_record(event.SerializeToString()))
        if now - self._last_flush >= self.FLUSH_SECS:
            self.flush()

    def add_scalar(self, tag: str, value: Any, step: Any) -> None:
        self._write(self.log_dir, tag, value, step)

    def add_scalars(self, main_tag: str, values: Dict[str, Any], step: Any) -> None:
        for key, value in values.items():
            directory = self.log_dir + "/" + main_tag.replace("/", "_") + "_" + key
            self._write(directory, main_tag, value, step)

    def flush(self) -> None:
        self._last_flush = time.time()
        for file in self._files.values():
            file.flush()

    def close(self) -> None:
        for file in self._files.values():
            file.close()
        self._files = {}


def make_summary_writer(log_dir: str):
    try:
        return EventWriter(log_dir)
    except Exception:
        return NoOpWriter()


HEAVY_MODULES = ("torch", "tensorflow", "keras")


def heavy_modules() -> list[str]:
    """Which of the packages that cost tens of seconds to import this
    process has loaded (``setup/summary_writer`` reports it: ``[]`` is a
    launch that paid for none)."""
    return [name for name in HEAVY_MODULES if name in sys.modules]


def create_id_run() -> str:
    """Timestamped run id with a random suffix to disambiguate simultaneous
    cluster launches (parity: `/root/reference/utils/logs_utils.py:19-40`)."""
    now = datetime.datetime.now()
    stamp = "_".join(
        str(part)
        for part in [now.year, now.month, now.day, now.hour, now.minute, now.second]
    )
    return f"{stamp}_{random.randint(0, 100)}"


def create_dict_result(
    args: Dict[str, Any],
    world_size: int,
    n_nodes: int,
    device_name: str,
    total_time: float,
    id_run: str,
    loss: float,
) -> Dict[str, Any]:
    """Flatten a finished run into one results-ledger row."""
    result = dict(args)
    result["0_id_run"] = id_run
    result["Tot_time"] = "{} min {:.1f} s".format(int(total_time // 60), total_time % 60)
    result["N_workers"] = world_size
    result["n_nodes"] = n_nodes
    result["device"] = device_name
    result["Loss_final"] = float(loss)
    return result


def save_result(path_to_result_csv: str, dict_result: Dict[str, Any]) -> None:
    """Append a row to results.csv, merging schemas across runs so rows with
    different config keys coexist (parity: logs_utils.py:83-138).

    Every row appended through this function is by definition a live
    machine append, so it defaults ``provenance='measured'``: a reader
    can tell it from a row someone wrote by hand."""
    dict_result = dict(dict_result)
    dict_result.setdefault("provenance", "measured")
    rows: list[Dict[str, Any]] = []
    fieldnames: set[str] = set()
    if os.path.exists(path_to_result_csv):
        with open(path_to_result_csv, "r", newline="") as f:
            for row in csv.DictReader(f):
                fieldnames.update(row.keys())
                rows.append(dict(row))
    fieldnames.update(dict_result.keys())
    rows.append({k: v for k, v in dict_result.items()})
    ordered = sorted(fieldnames)
    with open(path_to_result_csv, "w", newline="") as f:
        writer = csv.DictWriter(f, fieldnames=ordered)
        writer.writeheader()
        for row in rows:
            writer.writerow(row)


def save_grad_acc(
    id_run: str,
    path_logs: str,
    rank: int,
    list_grad_acc: Iterable[Any],
    list_grad_times: Iterable[Any] = (),
) -> None:
    """Dump per-rank grad-count / step-time traces for offline analysis
    (parity: logs_utils.py:248-259)."""
    folder = os.path.join(path_logs, "grad_counts")
    os.makedirs(folder, exist_ok=True)
    with open(os.path.join(folder, f"{id_run}_{rank}.txt"), "w") as f:
        f.write(f"{rank} # grad acc : {list(list_grad_acc)}\n")
        f.write(f"{rank} time step (ms) : {list(list_grad_times)}\n")


def print_training_evolution(
    log,
    nb_grad_local: int,
    nb_com_local: int,
    delta_step_for_log: int,
    rank: int,
    t_beg: float,
    t_last_epoch: float,
    loss: float,
    epoch: int,
) -> tuple[int, float]:
    """Emit the per-`delta_step_for_log`-grads progress line
    (parity: logs_utils.py:155-183)."""
    if nb_grad_local // delta_step_for_log > epoch:
        epoch += 1
        delta_t = time.time() - t_beg
        log.info(
            " Worker {}. {}th group of {} steps in {:.2f} s. "
            "Total time: {} min {:.2f} s. # grad : {} . # com : {}. loss {}".format(
                rank,
                epoch,
                delta_step_for_log,
                time.time() - t_last_epoch,
                int(delta_t // 60),
                delta_t % 60,
                nb_grad_local,
                nb_com_local,
                float(loss),
            )
        )
        t_last_epoch = time.time()
    return epoch, t_last_epoch


def log_health_to_tensorboard(
    writer,
    nb_step: int,
    grad_norm: float,
    skipped_rounds: int,
    consec_skipped: int,
    rollbacks: int,
) -> None:
    """Training-health scalars (the watchdog's columns), alongside the
    loss family at the same logging cadence."""
    writer.add_scalar("health/grad_norm", float(grad_norm), nb_step)
    writer.add_scalar("health/skipped_rounds", int(skipped_rounds), nb_step)
    writer.add_scalar("health/consec_skipped", int(consec_skipped), nb_step)
    writer.add_scalar("health/rollbacks", int(rollbacks), nb_step)


def log_to_tensorboard(
    writer,
    nb_step: int,
    nb_samples: int,
    rank: int,
    loss: float,
    eval_loss: Optional[float],
    t0: float,
    delta_step_for_log: int,
    epoch: int,
) -> None:
    """Scalar-name parity with logs_utils.py:187-224: loss and eval loss
    against wall-time, optimizer step, and sample count."""
    if nb_samples // delta_step_for_log <= epoch:
        return
    if eval_loss is not None:
        eval_loss = float(eval_loss)
        writer.add_scalars("eval_loss_step", {str(rank): eval_loss}, nb_step)
        writer.add_scalars("eval_loss_t", {str(rank): eval_loss}, time.time() - t0)
        writer.add_scalars("eval_loss_samples", {str(rank): eval_loss}, nb_samples)
    loss_f = float(loss)
    writer.add_scalars("loss_t", {str(rank): loss_f}, time.time() - t0)
    writer.add_scalars("loss_step", {str(rank): loss_f}, nb_step)
    writer.add_scalars("loss_samples", {str(rank): loss_f}, nb_samples)
