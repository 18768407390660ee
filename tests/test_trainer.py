"""Trainer/API layer end-to-end on the 8-virtual-device CPU mesh.

SURVEY.md §4.3 integration tier: each training method runs end-to-end
through the public ``DecoupledTrainer`` surface on a tiny model + synthetic
data; checkpoints round-trip through Orbax with real resume (the designed
improvement over the reference's save-only path, SURVEY.md §5).
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acco_tpu.configuration import config_from_dict
from acco_tpu.data.tokenizer import ByteTokenizer
from acco_tpu.models import LlamaConfig, LlamaModel
from acco_tpu.trainer import DecoupledTrainer
from acco_tpu.utils import logs as logs_utils

CFG = LlamaConfig(
    vocab_size=257, hidden_size=32, intermediate_size=64, num_layers=1,
    num_heads=2, num_kv_heads=2, max_position_embeddings=32,
)


def _docs(n=64, seed=0):
    rng = np.random.default_rng(seed)
    # input_ids-bearing rows: trainer passes them through untokenized
    return [
        {"input_ids": rng.integers(0, 256, size=int(rng.integers(8, 24))).tolist()}
        for _ in range(n)
    ]


def _args(method, tmp_path, **over):
    base = dict(
        method_name=method,
        batch_size=1,
        n_grad_accumulation=1,
        learning_rate=1e-3,
        weight_decay=0.0,
        adam_beta1=0.9,
        adam_beta2=0.95,
        nb_steps_tot=48,  # 8 devices x 1 acc -> 6 ddp steps / 6 acco commits
        label_smoothing_factor=0.0,
        max_length=16,
        scheduler_name="constant",
        warmup=0,
        use_mixed_precision=False,  # f32 for exact resume comparisons
        n_warmup_steps=0,
        eval=False,
        eval_step=0,
        save=False,
        const_len_batch=True,
        checkpoint_every_s=10_000,
        run_name=f"t-{method}",
    )
    base.update(over)
    return config_from_dict(base)


def _trainer(method, tmp_path, **over):
    model = LlamaModel(CFG, param_dtype=jnp.float32)
    return DecoupledTrainer(
        model,
        ByteTokenizer(),
        _docs(),
        _docs(16, seed=1),
        _args(method, tmp_path, **over),
        seed=0,
        run_dir=str(tmp_path),
    )


@pytest.mark.parametrize("method", ["ddp", "dpu", "acco"])
def test_method_trains_end_to_end(eight_devices, tmp_path, method):
    summary = _trainer(method, tmp_path).train()
    assert summary["method"] == method
    assert summary["count_grad_tot"] >= 48
    assert np.isfinite(summary["final_loss"])
    # results.csv ledger row written (logs_utils parity)
    assert os.path.exists(tmp_path / "results.csv")


def test_telemetry_disabled_is_silent(eight_devices, tmp_path):
    """telemetry.enabled=false: no tracer events, no trace file — the
    loop differs by short-circuited attribute reads only."""
    t = _trainer("ddp", tmp_path, nb_steps_tot=8,
                 telemetry={"enabled": False})
    summary = t.train()
    assert not t.tracer.enabled and t.tracer.events() == []
    assert not list(tmp_path.glob("trace_*.json"))
    assert np.isfinite(summary["final_loss"])


def test_acco_count_bookkeeping(eight_devices, tmp_path):
    # log every grad so the telemetry boundary sync (the device fence)
    # fires mid-run, not just at the end-of-train reconciliation
    t = _trainer("acco", tmp_path, delta_step_for_log=1)
    summary = t.train()
    # ACCO commits 2*ws*n_acc per odd round; rounds alternate, so total
    # committed grads are a multiple of 16 reaching >= 48.
    assert summary["count_grad_tot"] % 16 == 0
    # round parity: rounds = commits*2 (speculative+real), +seed not counted
    assert summary["rounds"] == 2 * (summary["count_grad_tot"] // 16)

    # -- ISSUE 38 (same run): the scalars are on disk when train() returns,
    # in the directories torch's writer gave them (rank 0's: "_0")
    (events_dir,) = glob.glob(str(tmp_path / "tensorboard" / "*" / t.id_run))
    assert sorted(os.listdir(events_dir))[-3:] == ["loss_samples_0", "loss_step_0", "loss_t_0"]
    for sub in ("", "loss_t_0", "loss_step_0", "loss_samples_0"):
        (name,) = [n for n in os.listdir(os.path.join(events_dir, sub)) if "tfevents" in n]
        # the version record is 40 bytes, a scalar's 55 or more
        assert os.path.getsize(os.path.join(events_dir, sub, name)) >= 95

    # -- ISSUE 19 / 23 acceptance (same run: one compile bill) --
    # the tiny smoke run writes a loadable Perfetto trace whose spans
    # tile the loop, the boundary's fence carrying what it learned
    import json

    from acco_tpu.telemetry import DECLARED_DEVICE_SCOPES, validate_trace

    paths = glob.glob(str(tmp_path / "trace_*.json"))
    assert len(paths) == 1, paths
    with open(paths[0], encoding="utf-8") as f:
        trace = json.load(f)
    assert validate_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert {"train/round", "train/dispatch", "loader/next_block",
            "train/log_boundary_sync", "train/log_boundary_host"} <= names
    events = sorted(
        (e for e in trace["traceEvents"] if e.get("ph") == "X"),
        key=lambda e: e["ts"],
    )
    fences = [e for e in events if e["name"] == "train/log_boundary_sync"]
    assert len(fences) == summary["rounds"]  # delta_step_for_log=1
    for fence in fences:
        assert set(fence["args"]) == {
            "round", "loss", "grad_norm", "committed", "skipped_rounds",
        }
        assert np.isfinite(fence["args"]["loss"])
    assert fences[-1]["args"]["round"] == summary["rounds"]
    assert fences[-1]["args"]["committed"] <= summary["count_grad_tot"]
    # each boundary's host span begins where its fence ended: on the loop's
    # thread it is the very next span to begin, after the fence's end. By
    # order and not by a bound on the gap: under six test workers the
    # thread waits more than a millisecond for its turn between the two
    # `with` blocks (timestamps are rounded to 0.1 us, hence the 0.2).
    hosts = [e for e in events if e["name"] == "train/log_boundary_host"]
    assert len(hosts) == len(fences)
    loop = [e for e in events if e["tid"] == fences[0]["tid"]]
    for fence, host in zip(fences, hosts):
        assert loop[loop.index(fence) + 1] is host
        assert host["ts"] - (fence["ts"] + fence["dur"]) >= -0.2
    # what a reader of the profile needs, named in the trace; no capture
    # ran here, so no directory
    other = trace["otherData"]
    assert other["device_scopes"] == list(DECLARED_DEVICE_SCOPES)
    assert other["profile_dir"] is None and other["profiled_rounds"] is None
    assert "attribution" not in other and "attribution" not in summary

    # -- ISSUE 37 (same run again): set-up is tiled by spans on the loop's
    # clock. On the main thread, from the constructor's start to the first
    # block, the top-level spans follow one another with nothing between
    # them, by ORDER as above (no bound in ms under six workers); a lazy
    # compile's compile/backend lies inside one of them.
    track = {e["tid"]: e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M"}
    first_block = next(e for e in loop if e["name"] == "loader/next_block")
    head = [e for e in loop if e["ts"] + e["dur"] <= first_block["ts"] + 0.2]
    top, end = [], -1.0
    for e in sorted(head, key=lambda e: (e["ts"], -e["dur"])):
        if e["ts"] >= end - 0.2:  # not inside the last top-level span
            top.append(e)
            end = e["ts"] + e["dur"]
    assert [e["name"] for e in top] == [
        "setup/trainer_init", "setup/state_init", "compile/warmup_join",
        "setup/seed",
    ]
    assert end <= first_block["ts"] + 0.2
    init = top[0]
    assert init["args"] == {"method": "acco", "world_size": 8}
    inside = [e for e in head if e is not init and init["ts"] <= e["ts"]
              and e["ts"] + e["dur"] <= init["ts"] + init["dur"] + 0.2]
    # these rows are shorter than max_length, so the const-len verdict flips
    # during tokenising and the warmup starts a second time
    assert [(e["name"], e.get("args")) for e in inside if e["name"] != "compile/backend"] == [
        ("setup/start_warmup", {"programs": 3}),
        ("setup/tokenize", {"rows": 64}),
        # the run's own event writer; what this process has loaded is the
        # collection's doing (test_hf_loader imports torch): a fresh process
        # reads [] (tests/test_event_writer.py)
        ("setup/summary_writer",
         {"writer": "events", "heavy_modules": logs_utils.heavy_modules()}),
        ("setup/start_warmup", {"programs": 3}),
    ]
    restart = inside[-1]["ts"]
    assert {"hits", "misses"} <= set(top[2]["args"])
    assert top[3]["args"] == {"rounds": 0}
    # every warmed program (the restart's; the abandoned ones finish in the
    # background): one lowering and one compile on a warmup thread's track,
    # each after its submit
    warmed = sorted(t.compile_report.programs)
    assert warmed == ["round_even", "round_odd", "seed"]
    for name in ("compile/lower", "compile/compile"):
        spans = [e for e in events
                 if e["name"] == name and e["args"]["submitted_us"] >= restart]
        assert sorted(e["args"]["program"] for e in spans) == warmed
        for e in spans:
            assert track[e["tid"]].startswith("acco-compile")
            assert e["args"]["submitted_us"] <= e["ts"]
            if name == "compile/compile":  # deserialised or compiled
                assert e["args"]["hits"] + e["args"]["misses"] >= 1
                rec = t.compile_report.programs[e["args"]["program"]]
                assert rec.compile_ms == pytest.approx(e["dur"] / 1e3, abs=1e-3)
    # nothing of set-up begins once the loop dispatches (the abandoned
    # warmup's threads apart: they may still be compiling)
    first_dispatch = next(e for e in events if e["name"] == "train/dispatch")
    of_this_run = loop + [e for e in events if e["name"] in ("compile/lower", "compile/compile")
                          and e["args"]["submitted_us"] >= restart]
    assert not [e["name"] for e in of_this_run
                if e["name"].startswith(("setup/", "compile/"))
                and e["ts"] > first_dispatch["ts"]]
    # the summary's phases are the spans'; a trainer built in code has no
    # main.run phases, and its top-level ones cannot outlast setup_s
    phases = summary["setup"]
    assert list(phases) == ["trainer_init", "start_warmup", "tokenize",
                            "summary_writer", "state_init", "warmup_join", "seed"]
    assert phases["start_warmup"] == pytest.approx(
        sum(e["dur"] for e in inside if e["name"] == "setup/start_warmup") / 1e6)
    assert phases["trainer_init"] == pytest.approx(init["dur"] / 1e6)
    assert (phases["trainer_init"] + phases["state_init"] + phases["warmup_join"]
            + phases["seed"]) <= summary["setup_s"] + 1e-3


def test_the_set_up_line_names_every_phase_and_what_the_join_learned():
    from acco_tpu.trainer import _setup_line

    def spans(phases, **args):
        return [{"ph": "X", "name": name, "ts": 0.0, "dur": s * 1e6,
                 **({"args": args[name.split("/")[1]]} if name.split("/")[1] in args else {})}
                for name, s in phases.items()]

    joined = {"hits": 3, "misses": 0, "cache_dir_bytes": 188 * 2**20,
              "cache_max_bytes": 192 * 2**20}
    events = spans(
        {"setup/config": 0.31, "setup/imports": 0.12, "setup/build_model": 0.4,
         "setup/load_data": 1.2, "setup/trainer_init": 9.8, "setup/start_warmup": 0.7,
         "setup/tokenize": 6.1, "setup/summary_writer": 2.2, "setup/state_init": 7.2,
         "compile/warmup_join": 58.3, "setup/seed": 2.9},
        config={"cache_dir_bytes": 201 * 2**20}, warmup_join=joined)
    assert _setup_line(84.1, events) == (
        "set-up 84.1 s: config 0.3, imports 0.1, model 0.4, data 1.2, trainer 9.8 "
        "(tokenize 6.1, writer 2.2), state 7.2, warmup join 58.3 [3 hits 0 misses, "
        "cache 201->188/192 MiB], seed 2.9"
    )
    # a launch that loaded a heavy package all the same says which, beside the writer
    events = spans({"setup/trainer_init": 40.0, "setup/tokenize": 6.1,
                    "setup/summary_writer": 30.5},
                   summary_writer={"writer": "events", "heavy_modules": ["torch", "keras"]})
    assert _setup_line(41.0, events) == (
        "set-up 41.0 s: trainer 40.0 (tokenize 6.1, writer 30.5 [heavy_modules torch, keras])"
    )
    events = spans({"setup/trainer_init": 2.0, "setup/summary_writer": 0.5},
                   summary_writer={"writer": "noop", "heavy_modules": []})
    assert _setup_line(2.0, events) == "set-up 2.0 s: trainer 2.0 (writer 0.5)"
    # no cap set, a trainer built in code (no main.run phases, nothing known of the launch)
    events = spans({"setup/trainer_init": 1.0, "compile/warmup_join": 2.0},
                   warmup_join={"hits": 0, "misses": 1, "cache_dir_bytes": 2**20})
    assert _setup_line(3.0, events) == (
        "set-up 3.0 s: trainer 1.0, warmup join 2.0 [0 hits 1 misses, cache 1 MiB]"
    )
    assert _setup_line(1.0, spans({"setup/state_init": 1.0})) == "set-up 1.0 s: state 1.0"


@pytest.mark.parametrize("method", ["ddp", "dpu", "acco"])
def test_heterogeneous_mask_bookkeeping(eight_devices, tmp_path, method):
    """Under a microbatch_mask, count_grad_tot / termination / summary
    counts come from VALID grads only (round-1 VERDICT Weak #3: the old
    host bookkeeping hardcoded ws*n_acc and inflated progress). Reference
    semantics: `trainer_decoupled.py:85-98,501-502`."""
    # 2 microbatches x 8 workers; 10 of 16 valid per round.
    mask = [
        [1, 1, 1, 0, 1, 0, 1, 1],
        [1, 0, 1, 1, 0, 1, 0, 0],
    ]
    per_round = 10  # sum(mask)
    t = _trainer(
        method,
        tmp_path,
        n_grad_accumulation=2,
        microbatch_mask=mask,
        nb_steps_tot=40,
    )
    summary = t.train()
    committed = float(
        jax.device_get(t.final_state.zero1.grads_committed)
    )
    # host count == device count (reconciled, not estimated)
    assert summary["count_grad_tot"] == int(committed)
    if method == "acco":
        # odd rounds commit two half-rounds of 10 -> multiples of 20;
        # termination at the first commit reaching >= 40.
        assert summary["count_grad_tot"] == 40
        assert summary["rounds"] == 4  # spec/real alternation
    else:
        # one round of 10 per round -> exactly ceil(40/10) rounds.
        assert summary["count_grad_tot"] == 40
        assert summary["rounds"] == 4
    assert np.isfinite(summary["final_loss"])


def test_profile_hooks_write_trace_and_step_times(eight_devices, tmp_path):
    """train.profile_steps=N dumps a jax.profiler trace dir, and per-round
    step times land in the grad_counts ledger (the reference's
    save_grad_acc intent, logs_utils.py:248-259)."""
    t = _trainer("ddp", tmp_path, profile_steps=2, nb_steps_tot=32)
    summary = t.train()
    profile_dir = os.path.join(str(tmp_path), "profile")
    assert os.path.isdir(profile_dir) and os.listdir(profile_dir)
    grad_dir = os.path.join(str(tmp_path), "grad_counts")
    files = os.listdir(grad_dir)
    assert len(files) == 1
    content = open(os.path.join(grad_dir, files[0])).read()
    assert "time step (ms)" in content
    # one wall-time entry per round
    times = content.split("time step (ms) : ")[1]
    assert len(eval(times)) == summary["rounds"]


def test_profiled_rounds_put_the_loops_spans_on_the_host_plane(
    eight_devices, tmp_path
):
    """ISSUE 23: while train.profile_steps captures, every span of the
    round loop is a TraceAnnotation too — an event on the profile's
    /host:CPU plane under the span's own name, on the trainer's thread,
    in the same file as the device's ops — and trace_<id>.json says
    where that file is and which rounds it holds."""
    import json

    from jax.profiler import ProfileData

    t = _trainer("ddp", tmp_path, profile_steps=3, nb_steps_tot=48,
                 delta_step_for_log=1)
    t.train()
    with open(glob.glob(str(tmp_path / "trace_*.json"))[0]) as f:
        other = json.load(f)["otherData"]
    assert other["profile_dir"] == os.path.join(str(tmp_path), "profile")
    assert other["profiled_rounds"] == [2, 4]  # DDP: after one compile round
    # the capture names an op by its instruction: the table beside it
    # says which device scope each instruction of the round program is in
    assert other["profiled_programs"] == ["step"] * 3
    with open(other["scope_table"]) as f:
        tables = json.load(f)
    assert set(tables) == {"step"}
    assert "acco/optimizer" in set(tables["step"]["scopes"].values())
    paths = glob.glob(os.path.join(
        other["profile_dir"], "plugins", "profile", "*", "*.xplane.pb"
    ))
    assert len(paths) == 1
    wanted = {"loader/next_block", "train/dispatch",
              "train/log_boundary_sync", "train/log_boundary_host"}
    host = next(
        p for p in ProfileData.from_file(paths[0]).planes
        if p.name == "/host:CPU"
    )
    lines = [
        [e.name for e in line.events if e.name in wanted]
        for line in host.lines
    ]
    lines = [names for names in lines if names]
    assert len(lines) == 1, "the loop's spans lie on one thread's line"
    names = lines[0]
    assert set(names) == wanted
    # rounds 2..4 dispatched inside the capture, a boundary after each
    # but the last (the capture stops right after the last dispatch)
    assert names.count("train/dispatch") == 3
    assert names.count("train/log_boundary_sync") == 2
    assert names.count("train/log_boundary_host") == 2
    # train/round is a synthetic tile recorded after the fact: no annotation
    assert all(
        e.name != "train/round" for line in host.lines for e in line.events
    )


def test_eval_loop_runs(eight_devices, tmp_path):
    t = _trainer("ddp", tmp_path, eval=True, eval_step=8, nb_steps_tot=24)
    t.train()
    loss = t.evaluate(t.final_state.flat_params)
    assert np.isfinite(loss)


def test_warmup_rounds_then_decoupled(eight_devices, tmp_path):
    t = _trainer("acco", tmp_path, n_warmup_steps=2, nb_steps_tot=64)
    summary = t.train()
    assert np.isfinite(summary["final_loss"])
    assert summary["count_grad_tot"] >= 64


def test_checkpoint_save_and_resume(eight_devices, tmp_path):
    # Phase 1: train and save.
    t1 = _trainer("dpu", tmp_path, save=True, nb_steps_tot=32)
    s1 = t1.train()
    ckpt_root = os.path.join(str(tmp_path), "checkpoints", "t-dpu")
    from acco_tpu.utils.checkpoint import latest_checkpoint

    path = latest_checkpoint(ckpt_root)
    assert path is not None and path.endswith(f"step_{s1['count_grad_tot']}")
    assert os.path.exists(os.path.join(path, "params.npz"))

    # Phase 2: resume into a longer run; counters continue, training works.
    t2 = _trainer(
        "dpu", tmp_path, save=False, nb_steps_tot=64, resume_from=ckpt_root
    )
    s2 = t2.train()
    assert s2["count_grad_tot"] >= 64
    assert s2["rounds"] > s1["rounds"]
    assert np.isfinite(s2["final_loss"])


def test_restore_is_bitexact(eight_devices, tmp_path):
    t1 = _trainer("acco", tmp_path, save=True, nb_steps_tot=32)
    t1.train()
    from acco_tpu.utils.checkpoint import latest_checkpoint, restore_checkpoint

    path = latest_checkpoint(os.path.join(str(tmp_path), "checkpoints", "t-acco"))
    state, meta = restore_checkpoint(path, t1.final_state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(t1.final_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert meta["method"] == "acco"


def test_restore_legacy_accumulator_layout(eight_devices, tmp_path):
    """Checkpoints written before the grad_accum/count_local removal (7
    AccoState leaves) restore through the legacy fallback: the redundant
    buffers are dropped, everything else lands bit-exactly."""
    from typing import Any, NamedTuple

    from acco_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint

    t1 = _trainer("acco", tmp_path, save=True, nb_steps_tot=16)
    t1.train()
    new = t1.final_state

    class LegacyAccoState(NamedTuple):
        flat_params: Any
        grad_accum: Any
        count_local: Any
        pending_grads: Any
        pending_count: Any
        zero1: Any
        round_idx: Any

    legacy_state = LegacyAccoState(
        flat_params=new.flat_params,
        grad_accum=jnp.zeros_like(new.pending_grads),
        count_local=jnp.zeros_like(new.pending_count),
        pending_grads=new.pending_grads,
        pending_count=new.pending_count,
        zero1=new.zero1,
        round_idx=new.round_idx,
    )
    path = save_checkpoint(
        os.path.join(str(tmp_path), "legacy-ckpt"), 16, legacy_state,
        {"method": "acco"},
    )
    restored, meta = restore_checkpoint(path, new)
    assert type(restored).__name__ == "AccoState"
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(new)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert meta["method"] == "acco"


def test_cp_rejects_padded_batches(eight_devices, tmp_path):
    """sp > 1 with const_len_batch=False must be refused: the CP attention
    path has no per-token mask, so padded batches would silently attend to
    pad tokens (round-1 ADVICE medium)."""
    from acco_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 4, "sp": 2})
    model = LlamaModel(CFG, param_dtype=jnp.float32, attention="ring",
                       sequence_axis="sp")
    with pytest.raises(ValueError, match="const_len_batch"):
        DecoupledTrainer(
            model, ByteTokenizer(), _docs(), None,
            _args("ddp", tmp_path, const_len_batch=False),
            seed=0, run_dir=str(tmp_path), mesh=mesh,
        )


def test_cp_rejects_variable_length_pretokenized(eight_devices, tmp_path):
    """Pre-tokenized variable-length rows bypass the const_len_batch flag
    (the trainer passes input_ids-bearing rows through untokenized, and
    the loader would pad them); the dataset-level CP check must catch
    them even with the flag at its default True."""
    from acco_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 4, "sp": 2})
    model = LlamaModel(CFG, param_dtype=jnp.float32, attention="ring",
                       sequence_axis="sp")
    with pytest.raises(ValueError, match="const-length rows"):
        DecoupledTrainer(
            model, ByteTokenizer(), _docs(), None,
            _args("ddp", tmp_path),  # const_len_batch=True, rows are 8-24
            seed=0, run_dir=str(tmp_path), mesh=mesh,
        )


def test_dense_downgrades_const_len_for_padded_pretokenized(
    eight_devices, tmp_path, caplog
):
    """Dense meshes (no sp/pp) with variable-length pre-tokenized rows:
    const_len_batch=True would statically drop the real padding masks
    (making pad tokens attendable), so the trainer downgrades to the
    mask-honoring program with a warning instead of erroring (the dense
    program CAN honor masks; CP/pp, which cannot, keep the hard error —
    tests above)."""
    import logging

    model = LlamaModel(CFG, param_dtype=jnp.float32)
    with caplog.at_level(logging.WARNING, logger="acco_tpu"):
        t = DecoupledTrainer(
            model, ByteTokenizer(), _docs(), None,
            _args("ddp", tmp_path),  # const_len_batch default True
            seed=0, run_dir=str(tmp_path),
        )
    assert t.const_len_batch is False
    assert any("downgrading to" in r.message for r in caplog.records)


def test_short_eval_rows_keep_train_const_len(eight_devices, tmp_path, caplog):
    """Per-dataset const-len verdicts (round-5 ADVICE #1): a short-row
    eval set downgrades EVAL to the pad-plumbed program but must not
    cost training its mask-free const-len programs — and the warning
    names the dataset that failed."""
    import logging

    # train rows all >= max_length (16); eval rows short (8-24 mixed)
    train_rows = [{"input_ids": list(range(i, i + 20))} for i in range(64)]
    model = LlamaModel(CFG, param_dtype=jnp.float32)
    with caplog.at_level(logging.WARNING, logger="acco_tpu"):
        t = DecoupledTrainer(
            model, ByteTokenizer(), train_rows, _docs(16, seed=1),
            _args("ddp", tmp_path, nb_steps_tot=16),
            seed=0, run_dir=str(tmp_path),
        )
    assert t.const_len_batch is True  # training keeps mask-free programs
    assert t.eval_const_len is False  # eval honors its padding masks
    assert any("eval dataset" in r.getMessage() for r in caplog.records)
    summary = t.train()
    assert np.isfinite(summary["final_loss"])
    assert np.isfinite(t.evaluate(t.final_state.flat_params))


def test_text_dataset_tokenization_path(eight_devices, tmp_path):
    # 'text'-column datasets go through const-len packing inside the trainer.
    import datasets as hf_datasets

    from acco_tpu.data.datasets import synthetic_corpus

    ds = hf_datasets.Dataset.from_dict({"text": synthetic_corpus(96, seed=3)})
    model = LlamaModel(CFG, param_dtype=jnp.float32)
    t = DecoupledTrainer(
        model, ByteTokenizer(), ds, None,
        _args("ddp", tmp_path, nb_steps_tot=16),
        seed=0, run_dir=str(tmp_path),
    )
    assert "input_ids" in t.train_dataset.column_names
    summary = t.train()
    assert np.isfinite(summary["final_loss"])


def test_restore_unrelated_failure_not_masked(tmp_path):
    """A restore failure that is NOT a structure mismatch (here: the state
    dir simply does not exist) must surface as itself, not be retried
    through the legacy-layout fallback and re-raised as a confusing
    structure error (round-2 ADVICE low #2)."""
    from acco_tpu.utils.checkpoint import restore_checkpoint

    missing = os.path.join(str(tmp_path), "step_000007")
    os.makedirs(missing)
    with open(os.path.join(missing, "meta.json"), "w") as f:
        f.write("{}")
    template = {"x": jnp.zeros((2,), jnp.float32)}
    with pytest.raises(Exception) as excinfo:
        restore_checkpoint(missing, template)
    msg = str(excinfo.value).lower()
    assert "legacy" not in msg
    assert "accostate" not in msg


def test_exact_resume_matches_uninterrupted(eight_devices, tmp_path):
    """A run interrupted mid-epoch and resumed consumes the identical batch
    sequence as an uninterrupted run — asserted the strongest way: the
    final parameters are bit-exact (round-2 VERDICT missing #4 / SURVEY §5
    "data iterator state"). 64 rows / global batch 8 = 8 batches per
    epoch; stopping at 32 grads = 4 rounds is mid-epoch."""
    t_full = _trainer("dpu", tmp_path / "full", nb_steps_tot=64)
    t_full.train()

    t_half = _trainer("dpu", tmp_path / "parts", save=True, nb_steps_tot=32)
    t_half.train()

    ckpt_root = os.path.join(str(tmp_path / "parts"), "checkpoints", "t-dpu")
    import json

    from acco_tpu.utils.checkpoint import latest_checkpoint

    meta = json.load(open(os.path.join(latest_checkpoint(ckpt_root), "meta.json")))
    loader_state = meta["loader"]  # position of the last CONSUMED block
    assert loader_state["epoch"] == 0 and 0 < loader_state["batch_pos"] < 8
    # the prefetch worker legitimately runs AHEAD of the consumed
    # position; the checkpoint must carry the consumed one, not the
    # loader's raw (prefetched) cursor
    raw = t_half.train_loader.iter_state()
    assert (raw["epoch"], raw["batch_pos"]) >= (
        loader_state["epoch"],
        loader_state["batch_pos"],
    )

    t_res = _trainer(
        "dpu", tmp_path / "parts", nb_steps_tot=64, resume_from=ckpt_root
    )
    t_res.train()
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(t_res.final_state.flat_params)),
        np.asarray(jax.device_get(t_full.final_state.flat_params)),
    )
