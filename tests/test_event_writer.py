"""The run's event writer (`acco_tpu/utils/logs.EventWriter`): the files
`torch.utils.tensorboard.SummaryWriter` lays down for the trainer's calls,
with neither `torch` nor `tensorflow` nor `keras` imported.

`fixtures/event_writer_torch.json` was recorded ONCE from torch's writer over
`trainer_calls` below and is never rewritten by a test:

    PYTHONPATH=. python tests/test_event_writer.py   # imports torch: not for tier-1
"""

from __future__ import annotations

import json
import os
import struct
import subprocess
import sys

import pytest

from acco_tpu.utils import logs

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "event_writer_torch.json")


def trainer_calls(writer) -> None:
    """Two logging boundaries as `trainer.py` makes them: the loss family
    (the second with an eval loss), then the health scalars. `loss_t`'s step
    is seconds since `t0`, a float: a `t0` 1000.5 s and 2000.75 s in the past
    lands on steps 1000 and 2000 whatever the call takes."""
    import time

    for step, samples, loss, eval_loss, age, epoch in (
        (10, 640, 10.8125, None, 1000.5, 0),
        (20, 1280, 9.4375, 9.5, 2000.75, 1),
    ):
        logs.log_to_tensorboard(
            writer, step, samples, 0, loss, eval_loss, time.time() - age, 64 * 10, epoch
        )
        logs.log_health_to_tensorboard(writer, step, 1.25 * step, step // 20, 0, 0)


def read_records(path: str) -> list:
    """Every event of one file, through the record framing: length, masked
    crc32c of the length, payload, masked crc32c of the payload."""
    from tensorboard.compat.proto import event_pb2
    from tensorboard.compat.tensorflow_stub.pywrap_tensorflow import masked_crc32c

    events = []
    with open(path, "rb") as f:
        data = f.read()
    at = 0
    while at < len(data):
        header = data[at:at + 8]
        (length,) = struct.unpack("<Q", header)
        assert struct.unpack("<I", data[at + 8:at + 12])[0] == masked_crc32c(header)
        payload = data[at + 12:at + 12 + length]
        assert len(payload) == length
        (crc,) = struct.unpack("<I", data[at + 12 + length:at + 16 + length])
        assert crc == masked_crc32c(payload)
        events.append(event_pb2.Event.FromString(payload))
        at += 16 + length
    return events


def read_run(log_dir: str) -> list:
    """The run directory as sorted [sub-directory, tag, step, value] rows
    ('' = the root's own file); every file starts with the version event."""
    rows = []
    for root, _, names in os.walk(log_dir):
        for name in names:
            assert name.startswith("events.out.tfevents.")
            first, *events = read_records(os.path.join(root, name))
            assert first.file_version == "brain.Event:2" and first.wall_time > 0
            for event in events:
                (value,) = event.summary.value
                assert value.WhichOneof("value") == "simple_value"
                assert event.wall_time > 0
                rows.append([
                    "" if root == log_dir else os.path.relpath(root, log_dir),
                    value.tag, event.step, value.simple_value,
                ])
    return sorted(rows)


def test_the_trainers_calls_land_where_torchs_writer_put_them(tmp_path):
    writer = logs.make_summary_writer(str(tmp_path / "run"))
    assert isinstance(writer, logs.EventWriter)
    trainer_calls(writer)
    writer.close()
    with open(FIXTURE) as f:
        recorded = json.load(f)
    rows = read_run(str(tmp_path / "run"))
    assert rows == recorded["rows"]
    assert {r[0] for r in rows} == {
        "", "loss_t_0", "loss_step_0", "loss_samples_0",
        "eval_loss_t_0", "eval_loss_step_0", "eval_loss_samples_0",
    }
    assert all(isinstance(r[2], int) for r in rows)
    # one file a directory, opened once: the second boundary opened none
    # but the eval family's
    for root, _, names in os.walk(tmp_path / "run"):
        assert len(names) == 1


@pytest.mark.parametrize("size", [0, 1, 57, 300])
def test_a_record_is_framed_as_tensorboards_record_writer_frames_it(size):
    import io

    from tensorboard.summary.writer.record_writer import RecordWriter

    payload = bytes((7 * i + size) % 256 for i in range(size))
    theirs = io.BytesIO()
    RecordWriter(theirs).write(payload)
    assert logs.event_record(payload) == theirs.getvalue()


@pytest.mark.parametrize("how", ["flush", "close", "neither"])
def test_what_was_added_is_on_disk(tmp_path, how):
    """After `flush()` and after `close()` every scalar can be read back; a
    process that does neither and ends cleanly leaves whole records (Python
    hands its buffers over at exit), never a torn one."""
    log_dir = str(tmp_path / "run")
    if how == "neither":
        subprocess.run(
            [sys.executable, "-c",
             "import sys; from acco_tpu.utils import logs\n"
             "w = logs.make_summary_writer(sys.argv[1])\n"
             "w.add_scalar('health/grad_norm', 1.5, 10)\n"
             "w.add_scalars('loss_step', {'0': 2.5}, 10)\n",
             log_dir],
            check=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(__file__)),
        )
    else:
        writer = logs.make_summary_writer(log_dir)
        # before any scalar the root's file holds its version event already
        assert read_run(log_dir) == []
        writer.add_scalar("health/grad_norm", 1.5, 10)
        writer.add_scalars("loss_step", {"0": 2.5}, 10)
        getattr(writer, how)()
    assert read_run(log_dir) == [
        ["", "health/grad_norm", 10, 1.5], ["loss_step_0", "loss_step", 10, 2.5],
    ]
    if how == "flush":  # the files stay open: more can follow, and is buffered
        writer.add_scalar("health/grad_norm", 2.5, 20)
        assert len(read_run(log_dir)) == 2
        writer.close()
        assert len(read_run(log_dir)) == 3


def test_a_write_hands_the_buffers_over_once_flush_secs_have_passed(tmp_path, monkeypatch):
    writer = logs.make_summary_writer(str(tmp_path / "run"))
    writer.add_scalar("health/rollbacks", 0, 10)
    assert read_run(str(tmp_path / "run")) == []
    monkeypatch.setattr(writer, "_last_flush", writer._last_flush - logs.EventWriter.FLUSH_SECS)
    writer.add_scalar("health/rollbacks", 1, 20)
    assert [r[2] for r in read_run(str(tmp_path / "run"))] == [10, 20]
    writer.add_scalar("health/rollbacks", 1, 30)  # the clock started again
    assert len(read_run(str(tmp_path / "run"))) == 2
    writer.close()


def test_a_training_launch_imports_no_heavy_package(tmp_path):
    """In a fresh interpreter: build, write, close, and neither `torch` nor
    `tensorflow` nor `keras` is loaded; the span's helper says so."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; from acco_tpu.utils import logs\n"
         "w = logs.make_summary_writer(sys.argv[1])\n"
         "assert type(w).__name__ == 'EventWriter', type(w)\n"
         "w.add_scalar('health/grad_norm', 1.0, 1)\n"
         "w.add_scalars('loss_t', {'0': 1.0}, 1.9)\n"
         "w.flush(); w.close()\n"
         "assert not {'torch', 'tensorflow', 'keras'} & set(sys.modules)\n"
         "print(logs.heavy_modules())\n",
         str(tmp_path / "run")],
        check=True, timeout=120, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert out.stdout.strip().splitlines()[-1] == "[]"
    assert read_run(str(tmp_path / "run")) == [
        ["", "health/grad_norm", 1, 1.0], ["loss_t_0", "loss_t", 1, 1.0],
    ]


def test_heavy_modules_names_what_the_process_has_loaded(monkeypatch):
    for name in logs.HEAVY_MODULES:
        monkeypatch.delitem(sys.modules, name, raising=False)
    assert logs.heavy_modules() == []
    monkeypatch.setitem(sys.modules, "keras", object())
    monkeypatch.setitem(sys.modules, "torch", object())
    assert logs.heavy_modules() == ["torch", "keras"]


def test_without_tensorboards_protos_the_run_gets_the_no_op_writer(tmp_path, monkeypatch):
    # None in sys.modules makes the import raise ImportError
    monkeypatch.setitem(sys.modules, "tensorboard.compat.proto", None)
    writer = logs.make_summary_writer(str(tmp_path / "run"))
    assert isinstance(writer, logs.NoOpWriter)
    trainer_calls(writer)
    writer.flush()
    writer.close()
    assert not os.path.exists(tmp_path / "run")


if __name__ == "__main__":  # the one-time recording, from torch's own writer
    import tempfile

    from torch.utils.tensorboard import SummaryWriter

    with tempfile.TemporaryDirectory() as tmp:
        torch_writer = SummaryWriter(tmp)
        trainer_calls(torch_writer)
        torch_writer.close()
        recorded = {
            "how": "PYTHONPATH=. python tests/test_event_writer.py: torch.utils.tensorboard."
                   "SummaryWriter over trainer_calls, read back by read_run "
                   "([sub-directory, tag, step, float32 value], sorted)",
            "torch": __import__("torch").__version__,
            "tensorboard": __import__("tensorboard").__version__,
            "rows": read_run(tmp),
        }
    with open(FIXTURE, "w") as f:
        json.dump(recorded, f, indent=1)
        f.write("\n")
