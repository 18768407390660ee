"""Training-health watchdog (ISSUE 7): in-program guards, fault
injection, auto-rollback.

Three tiers, mirroring the subsystem's layers:

- **guard unit tests** — direct round-program calls on the 8-device CPU
  mesh prove the acceptance contract: an injected anomaly at round k
  leaves params + optimizer state *bit-exact* to round k-1 (the skip is
  an on-device no-op), for ACCO (both half-round parities), DPU, and
  DDP; the staged-grads carry-in decontamination caps one bad batch at
  one skipped update; nan_guard=False compiles it all out.
- **host monitor / registry units** — spike-vs-drift classification
  from rolling statistics, escalation, fault-spec parsing.
- **end-to-end trainer runs** — config-driven ``fault_injection``
  through ``DecoupledTrainer``: transient NaN skips exactly one round
  and training completes; persistent corruption escalates into an
  auto-rollback through the checkpoint fallback chain with the data
  window fenced, and the run still finishes (bit-exact determinism of
  the recovery is the ``slow``-marked double-run).
"""

import logging
import os

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

import faults
from acco_tpu.configuration import config_from_dict
from acco_tpu.data.tokenizer import ByteTokenizer
from acco_tpu.models import LlamaConfig, LlamaModel
from acco_tpu.ops.schedules import get_schedule
from acco_tpu.parallel.acco import AccoTrainStep
from acco_tpu.parallel.ddp import DDPTrainStep
from acco_tpu.parallel.mesh import make_mesh
from acco_tpu.resilience.faults import FAULT_KINDS, FaultInjector, parse_fault_specs
from acco_tpu.resilience.watchdog import TrainingHealthMonitor
from acco_tpu.trainer import DecoupledTrainer
from acco_tpu.utils.checkpoint import (
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
    validate_checkpoint,
)

CFG = LlamaConfig(
    vocab_size=64, hidden_size=16, intermediate_size=32, num_layers=1,
    num_heads=2, num_kv_heads=2, max_position_embeddings=16,
)
WS, SEQ = 8, 8


def _batch(seed, n_acc=1, valid=None):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(
        rng.integers(0, CFG.vocab_size, (n_acc, WS, SEQ)), jnp.int32
    )
    return {
        "input_ids": ids,
        "attention_mask": jnp.ones_like(ids),
        "labels": ids,
        "valid": (
            jnp.ones((n_acc, WS), jnp.float32)
            if valid is None
            else jnp.asarray(valid, jnp.float32)
        ),
    }


def _nan_valid(n_acc=1):
    return np.full((n_acc, WS), np.nan, np.float32)


def _make(mode, **kw):
    mesh = make_mesh()
    model = LlamaModel(CFG, param_dtype=jnp.float32)
    sched = get_schedule("constant", 3e-3, 0, 1000)
    cls = DDPTrainStep if mode == "ddp" else AccoTrainStep
    extra = {} if mode == "ddp" else {"mode": mode}
    step = cls(
        model, mesh, sched, weight_decay=0.1, beta1=0.9, beta2=0.95,
        label_smoothing=0.0, param_dtype=jnp.float32, **extra, **kw,
    )
    state = step.init_state(model.init(jax.random.PRNGKey(0)))
    return step, state


def _snap(tree):
    """Host copies of every leaf (safe across donating dispatches)."""
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)


def _put(step, np_state):
    """Rebuild a device state (exact shardings) from a host snapshot."""
    return jax.device_put(np_state, step.state_shardings())


def _assert_guard_noop(np_before, state_after, metrics):
    """The acceptance contract: a guard-skipped round leaves params and
    the whole optimizer state BIT-EXACT, and says so in the metrics."""
    np.testing.assert_array_equal(
        np_before.flat_params, np.asarray(jax.device_get(state_after.flat_params))
    )
    for a, b in zip(
        jax.tree.leaves(np_before.zero1), jax.tree.leaves(_snap(state_after.zero1))
    ):
        np.testing.assert_array_equal(a, b)
    assert bool(metrics.skipped)
    # ACCO/DPU metrics also expose the commit flag; DDP's do not
    assert not bool(getattr(metrics, "is_real_update", False))
    assert int(state_after.health.skipped_rounds) == int(
        np.asarray(np_before.health.skipped_rounds)
    ) + 1


# -- guard unit tests: the in-program no-op ---------------------------------


def _acco_program(step, program):
    """``even`` / ``odd``: the parity-specialized round; ``generic-*``: the
    one program that reads its parity from ``state.round_idx``."""
    even = program.endswith("even")
    return step.round_fn(parity=None if program.startswith("generic") else even)


ACCO_PROGRAMS = ["even", "odd", "generic-even", "generic-odd"]


@pytest.mark.parametrize("program", ACCO_PROGRAMS)
def test_acco_nan_pending_skips_bitexact(eight_devices, program):
    """NaN in the consumed pending gradients: BOTH ACCO half-round
    programs (and the parity-generic one on either parity) commit
    nothing — even rounds keep θ (no poisoned estimate for the next
    half-round to compute against), odd rounds keep the optimizer state
    and the working vector they started from, bit-exactly: that vector
    is the speculative θ̃ of the even round before, NOT cast(θ), and a
    skipped commit must hand back the gathered slices of it."""
    step, state = _make("acco")
    state, _ = step.seed_fn()(state, _batch(1))
    if program.endswith("odd"):  # one healthy even round so parity matches
        state, _ = step.round_fn(parity=True)(state, _batch(2))
    before = _snap(state)
    if program.endswith("odd"):
        assert not np.array_equal(before.flat_params, before.zero1.opt.params)
    # Poison the staged grads AND record the verdict the staging path
    # would have recorded (pending_ok=0) — the organic pipeline version
    # of this (verdict set by the program itself) is
    # test_acco_one_bad_batch_costs_one_update.
    poisoned = _put(
        step,
        before._replace(
            pending_grads=np.full_like(before.pending_grads, np.nan),
            health=before.health._replace(
                pending_ok=np.zeros((), np.float32)
            ),
        ),
    )
    new_state, m = _acco_program(step, program)(poisoned, _batch(3))
    _assert_guard_noop(before, new_state, m)
    assert not np.isfinite(float(m.grad_norm))
    assert int(new_state.health.consec_skipped) == 1
    # the data pipeline moved on: fresh (finite) grads are staged (the
    # even round's carry-in decontamination refuses the flagged grads)
    assert np.isfinite(np.asarray(jax.device_get(new_state.pending_grads))).all()


def _round_program(program):
    """(guarded step, unguarded step, the round under test of each, a state
    on that round's parity) for ``ddp``, ``dpu`` or ``acco-<ACCO_PROGRAMS>``."""
    mode, _, acco_program = program.partition("-")
    step, state = _make(mode)
    plain, _ = _make(mode, nan_guard=False)
    if mode == "ddp":
        return step, plain, step.step_fn(), plain.step_fn(), state
    state, _ = step.seed_fn()(state, _batch(1))
    if mode == "dpu":
        return step, plain, step.round_fn(), plain.round_fn(), state
    if acco_program.endswith("odd"):
        state, _ = step.round_fn(parity=True)(state, _batch(2))
    return (
        step, plain, _acco_program(step, acco_program),
        _acco_program(plain, acco_program), state,
    )


ROUND_PROGRAMS = ["ddp", "dpu"] + [f"acco-{p}" for p in ACCO_PROGRAMS]


@pytest.mark.parametrize("program", ROUND_PROGRAMS)
def test_healthy_round_commits_the_unguarded_update(eight_devices, program):
    """The guard decides before it writes; what it then writes on a
    healthy round is the unguarded program's update, every schedule and
    parity: the same working vector, moments and parameters (to the last
    bit but one: the CPU backend contracts multiply-adds as its fusions
    fall, and the two programs fuse differently) and the same counters."""
    step, plain, fn, plain_fn, state = _round_program(program)
    before = _snap(state)
    got, m = fn(_put(step, before), _batch(3))
    want, _ = plain_fn(_put(plain, before), _batch(3))
    assert not bool(m.skipped) and np.isfinite(float(m.grad_norm))
    commits = not program.endswith("even")
    assert bool(getattr(m, "is_real_update", True)) == commits
    np.testing.assert_array_max_ulp(
        np.asarray(got.flat_params), np.asarray(want.flat_params), 1
    )
    assert not np.array_equal(np.asarray(got.flat_params), before.flat_params)
    for a, b in zip(jax.tree.leaves(got.zero1), jax.tree.leaves(want.zero1)):
        if a.dtype == jnp.float32 and a.ndim:
            np.testing.assert_array_max_ulp(np.asarray(a), np.asarray(b), 1)
        else:  # Adam step count, LR-schedule and committed-grads counters
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(got.zero1.opt.count) == int(before.zero1.opt.count) + commits


_PASS_THROUGH = ("dynamic_slice", "slice", "squeeze", "reshape", "convert_element_type")


def _flat_state_selects(jaxpr, state_vars, gathered=(), in_cond=False):
    """Every ``select_n`` of a (closed) jaxpr and the jaxprs it calls, as
    ``(takes a flat-state leaf, takes the all-gather's result, predicate
    is one scalar broadcast, sits in a cond branch)``. ``state_vars``:
    the jaxpr's variables that are the working vector, the fp32
    parameters or a moment, or a slice or cast of one."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    state_vars, gathered = set(state_vars), set(gathered)
    made_by = {v: e for e in jaxpr.eqns for v in e.outvars}

    def among(var, group):  # literals are unhashable, and never state
        return not isinstance(var, jax.extend.core.Literal) and var in group

    found = []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "select_n":
            pred, cases = eqn.invars[0], eqn.invars[1:]
            maker = made_by.get(pred)
            scalar = pred.aval.shape == () or (
                maker is not None
                and maker.primitive.name == "broadcast_in_dim"
                and maker.invars[0].aval.shape == ()
            )
            found.append((
                any(among(c, state_vars) for c in cases),
                any(among(c, gathered) for c in cases),
                scalar,
                in_cond,
            ))
        if prim == "all_gather":
            gathered.update(eqn.outvars)
        elif prim in _PASS_THROUGH and among(eqn.invars[0], state_vars):
            state_vars.update(eqn.outvars)
        elif prim in _PASS_THROUGH and among(eqn.invars[0], gathered):
            gathered.update(eqn.outvars)
        operands = eqn.invars[1:] if prim == "cond" else eqn.invars
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if not hasattr(inner, "eqns"):
                    continue
                pairs = (  # a loop's carry is lined up differently: no flat state in it
                    list(zip(operands, inner.invars))
                    if len(inner.invars) == len(operands) else []
                )
                found += _flat_state_selects(
                    inner,
                    [i for o, i in pairs if among(o, state_vars)],
                    [i for o, i in pairs if among(o, gathered)],
                    in_cond or prim == "cond",
                )
    return found


@pytest.mark.parametrize("program", ROUND_PROGRAMS)
def test_round_programs_select_the_flat_state_once(eight_devices, program):
    """Structure of the lowered round programs (the jaxpr, which the CPU
    backend shares with the chip; what XLA fuses from it is read on the
    chip): nothing is selected after the all-gather — the gathered shard
    is already the answer; every select that takes the working vector,
    the parameters or a moment goes by ONE scalar verdict, never by an
    elementwise mask; and a committing program has such selects only
    inside the conditional that is its single write pass (the
    parity-specialized ones have none at all: the branch IS the
    choice)."""
    step, _, fn, _, state = _round_program(program)
    closed = jax.make_jaxpr(fn)(state, _batch(3))
    paths = [
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(state)[0]
    ]
    flat_state = [
        v for v, path in zip(closed.jaxpr.invars, paths)
        if path.endswith(("flat_params", "opt.params", "opt.mu", "opt.nu"))
    ]
    assert len(flat_state) == 4
    selects = _flat_state_selects(closed, flat_state)
    on_state = [s for s in selects if s[0]]
    assert selects and not any(after_gather for _, after_gather, _, _ in selects)
    assert all(scalar for _, _, scalar, _ in on_state)
    if program.endswith("even") and not program.startswith("acco-generic"):
        # speculative: one select, on the shard, before the gather
        assert [in_cond for _, _, _, in_cond in on_state] == [False]
    elif "generic" in program:  # the traced commit chooses inside the write
        assert on_state and all(in_cond for _, _, _, in_cond in on_state)
    else:
        assert on_state == []


def test_dpu_nan_pending_skips_bitexact(eight_devices):
    step, state = _make("dpu")
    state, _ = step.seed_fn()(state, _batch(1))
    before = _snap(state)
    poisoned = _put(
        step,
        before._replace(
            pending_grads=np.full_like(before.pending_grads, np.nan)
        ),
    )
    new_state, m = step.round_fn()(poisoned, _batch(2))
    _assert_guard_noop(before, new_state, m)


def test_ddp_nan_valid_skips_bitexact_then_recovers(eight_devices):
    """DDP consumes its gradients in the same program: a NaN-valid block
    (the nan_grads data-path injection) poisons grads AND count through
    the compiled accumulation — that step commits nothing; the next
    healthy step commits and resets the consecutive counter."""
    step, state = _make("ddp")
    before = _snap(state)
    new_state, m = step.step_fn()(state, _batch(1, valid=_nan_valid()))
    _assert_guard_noop(before, new_state, m)
    assert int(new_state.health.consec_skipped) == 1
    new_state, m = step.step_fn()(new_state, _batch(2))
    assert not bool(m.skipped)
    assert int(new_state.health.consec_skipped) == 0
    assert int(new_state.zero1.opt.count) == 1  # exactly the healthy step


def test_static_norm_cap_skips_spikes(eight_devices):
    """guard_max_grad_norm: a finite but spiked gradient (scaled staged
    grads, the spike_grads injector) is skipped by the static cap; the
    same update with the cap off commits."""
    step, state = _make("dpu", guard_max_grad_norm=1e4)
    state, _ = step.seed_fn()(state, _batch(1))
    spiked_np = _snap(state)
    spiked_np = spiked_np._replace(
        pending_grads=spiked_np.pending_grads * np.float32(1e6)
    )
    new_state, m = step.round_fn()(_put(step, spiked_np), _batch(2))
    _assert_guard_noop(spiked_np, new_state, m)
    assert np.isfinite(float(m.grad_norm))  # finite — caught by the CAP

    uncapped, ustate = _make("dpu")  # finiteness-only guard
    ustate, _ = uncapped.seed_fn()(ustate, _batch(1))
    u_np = _snap(ustate)
    u_np = u_np._replace(pending_grads=u_np.pending_grads * np.float32(1e6))
    new_u, mu = uncapped.round_fn()(_put(uncapped, u_np), _batch(2))
    assert not bool(mu.skipped)  # no cap: finite spike commits


def test_corrupt_opt_caught_by_update_signal(eight_devices):
    """NaN in the Adam first moment: the gradients are finite but the
    UPDATE goes nonfinite — the guard's second signal must catch it
    (grad-norm-only guards miss this entire failure class)."""
    step, state = _make("dpu")
    state, _ = step.seed_fn()(state, _batch(1))
    state, block = FAULT_KINDS["corrupt_opt"](state, _batch(2), n=8)
    before = _snap(state)
    new_state, m = step.round_fn()(state, block)
    _assert_guard_noop(before, new_state, m)
    assert np.isfinite(float(m.grad_norm))  # grads were fine


def test_acco_one_bad_batch_costs_one_update(eight_devices):
    """Carry-in decontamination: a NaN batch poisons the grads staged at
    round k; round k+1 skips the update consuming them AND (when even)
    must NOT accumulate on top of them — so exactly ONE update is lost
    and training recovers by itself."""
    step, state = _make("acco")
    state, _ = step.seed_fn()(state, _batch(1))
    fns = {True: step.round_fn(parity=True), False: step.round_fn(parity=False)}
    skipped_per_round = []
    for r in range(4):
        batch = _batch(10 + r, valid=_nan_valid() if r == 0 else None)
        state, m = fns[r % 2 == 0](state, batch)
        skipped_per_round.append(bool(m.skipped))
    # round 0 consumed the HEALTHY seed grads (committed speculatively);
    # its own staged grads are the poison, consumed+skipped at round 1;
    # rounds 2/3 are clean because round 1 staged fresh grads from zero.
    assert skipped_per_round == [False, True, False, False]
    assert int(state.health.skipped_rounds) == 1
    assert int(state.health.consec_skipped) == 0
    assert np.isfinite(
        np.asarray(jax.device_get(state.flat_params))
    ).all()
    # round 3 (odd) committed the one real update that survived
    assert int(state.zero1.opt.count) == 1


def test_guard_off_compiles_out_and_propagates(eight_devices):
    """nan_guard=False restores the unguarded programs: the counters
    never move, the metrics read 0/False, and the NaN actually poisons
    the parameters — the behavior the guard exists to prevent."""
    step, state = _make("dpu", nan_guard=False)
    state, _ = step.seed_fn()(state, _batch(1))
    np_state = _snap(state)
    poisoned = _put(
        step,
        np_state._replace(
            pending_grads=np.full_like(np_state.pending_grads, np.nan)
        ),
    )
    new_state, m = step.round_fn()(poisoned, _batch(2))
    assert float(m.grad_norm) == 0.0 and not bool(m.skipped)
    assert int(new_state.health.skipped_rounds) == 0
    assert not np.isfinite(
        np.asarray(jax.device_get(new_state.flat_params))
    ).all()


# -- host monitor + fault registry units ------------------------------------


def test_monitor_spike_then_escalate():
    mon = TrainingHealthMonitor(
        escalate_after=3, warmup_obs=2, log=logging.getLogger("t")
    )
    for i in range(6):  # build a stable baseline around norm=1.0
        v = mon.observe(
            grad_norm=1.0 + 0.01 * i, loss=2.0,
            skipped_rounds=0, consec_skipped=0,
        )
        assert v.classification == "ok" and not v.escalate
    spike = mon.observe(
        grad_norm=1e6, loss=2.0, skipped_rounds=0, consec_skipped=0
    )
    assert spike.classification == "spike" and mon.spikes == 1
    # the spike must not poison the baseline it was judged against
    after = mon.observe(
        grad_norm=1.0, loss=2.0, skipped_rounds=0, consec_skipped=0
    )
    assert after.classification == "ok"
    # guard skips classify as anomalous; escalation is consec-driven
    v = mon.observe(grad_norm=1.0, loss=float("nan"),
                    skipped_rounds=2, consec_skipped=2)
    assert v.classification == "anomalous" and not v.escalate
    v = mon.observe(grad_norm=1.0, loss=float("nan"),
                    skipped_rounds=3, consec_skipped=3)
    assert v.escalate
    mon.note_rollback()
    assert mon.summary()["rollbacks"] == 1


def test_parse_fault_specs_formats():
    specs = parse_fault_specs(
        [{"kind": "nan_grads", "round": 3},
         "corrupt_params@5",
         {"kind": "corrupt_opt", "round": 7, "n": 16}]
    )
    assert [(s.kind, s.round) for s in specs] == [
        ("nan_grads", 3), ("corrupt_params", 5), ("corrupt_opt", 7)
    ]
    assert specs[2].params == {"n": 16}
    assert parse_fault_specs(None) == [] and parse_fault_specs("") == []
    with pytest.raises(ValueError, match="unknown fault kind"):
        parse_fault_specs("definitely_not_a_fault@1")
    with pytest.raises(ValueError, match="kind"):
        parse_fault_specs([{"round": 1}])
    assert FaultInjector.from_config(None) is None


def test_spike_grads_rejects_ddp_state(eight_devices):
    step, state = _make("ddp")
    with pytest.raises(ValueError, match="staged gradients"):
        FAULT_KINDS["spike_grads"](state, _batch(1))


# -- checkpoint compat + validation hardening -------------------------------


def test_restore_pre_watchdog_checkpoints(eight_devices, tmp_path):
    """Checkpoints from before the health leaf (5-leaf AccoState /
    2-leaf DDPState) restore with fresh all-healthy counters and every
    other leaf bit-exact."""
    from typing import Any, NamedTuple

    class PreAcco(NamedTuple):
        flat_params: Any
        pending_grads: Any
        pending_count: Any
        zero1: Any
        round_idx: Any

    class PreDDP(NamedTuple):
        flat_params: Any
        zero1: Any

    astep, astate = _make("acco")
    legacy_a = PreAcco(
        astate.flat_params, astate.pending_grads, astate.pending_count,
        astate.zero1, astate.round_idx,
    )
    path = save_checkpoint(str(tmp_path / "a"), 1, legacy_a, {"m": "acco"})
    restored, meta = restore_checkpoint(path, astate)
    assert meta["m"] == "acco"
    assert int(restored.health.skipped_rounds) == 0
    assert float(restored.health.pending_ok) == 1.0
    for a, b in zip(jax.tree.leaves(restored.zero1), jax.tree.leaves(astate.zero1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    dstep, dstate = _make("ddp")
    legacy_d = PreDDP(dstate.flat_params, dstate.zero1)
    path = save_checkpoint(str(tmp_path / "d"), 1, legacy_d, {"m": "ddp"})
    restored, meta = restore_checkpoint(path, dstate)
    assert meta["m"] == "ddp"
    assert int(restored.health.consec_skipped) == 0
    np.testing.assert_array_equal(
        np.asarray(restored.flat_params), np.asarray(dstate.flat_params)
    )


def test_validate_checkpoint_empty_manifest(tmp_path):
    """A committed meta.json whose manifest records ZERO state files must
    be refused (the per-file size loop would be vacuous), and the
    fallback chain must walk past it."""
    root = str(tmp_path)
    good = save_checkpoint(
        root, 1, {"w": np.arange(8, dtype=np.float32)}, {}
    )
    bad = save_checkpoint(
        root, 2, {"w": np.arange(8, dtype=np.float32)}, {}
    )
    faults.wipe_manifest(bad)
    reason = validate_checkpoint(bad)
    assert reason is not None and "manifest empty" in reason
    assert latest_checkpoint(root) == good


# -- end-to-end: config-driven fault injection through the trainer ----------


def _docs(n=64, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {"input_ids": rng.integers(0, 256, size=int(rng.integers(8, 24))).tolist()}
        for _ in range(n)
    ]


TRAIN_CFG = LlamaConfig(
    vocab_size=257, hidden_size=32, intermediate_size=64, num_layers=1,
    num_heads=2, num_kv_heads=2, max_position_embeddings=32,
)


def _trainer(run_dir, method="dpu", shutdown_handler=None, **over):
    base = dict(
        method_name=method,
        batch_size=1,
        n_grad_accumulation=1,
        learning_rate=1e-3,
        weight_decay=0.0,
        nb_steps_tot=64,  # 8 devices x 1 acc -> 8 grads/round
        max_length=16,
        scheduler_name="constant",
        warmup=0,
        use_mixed_precision=False,  # f32 for bit-exact comparisons
        eval=False,
        save=False,
        const_len_batch=True,
        checkpoint_every_s=10_000,
        delta_step_for_log=1,  # health observed at every round boundary
        run_name=f"w-{method}",
    )
    base.update(over)
    return DecoupledTrainer(
        LlamaModel(TRAIN_CFG, param_dtype=jnp.float32),
        ByteTokenizer(),
        _docs(),
        None,
        config_from_dict(base),
        seed=0,
        run_dir=str(run_dir),
        shutdown_handler=shutdown_handler,
    )


@pytest.mark.parametrize("method", ["dpu", "acco", "ddp"])
def test_nan_injection_end_to_end(eight_devices, tmp_path, method):
    """Transient NaN at round 2 (config-driven, through the data path):
    exactly one round is skipped in-program, training self-recovers and
    still reaches the grad target with finite loss."""
    t = _trainer(
        tmp_path, method=method,
        fault_injection=[{"kind": "nan_grads", "round": 2}],
    )
    summary = t.train()
    assert summary["skipped_rounds"] == 1
    assert summary["rollbacks"] == 0
    assert summary["count_grad_tot"] >= 64
    assert np.isfinite(summary["final_loss"])
    assert np.isfinite(
        np.asarray(jax.device_get(t.final_state.flat_params))
    ).all()


def test_corrupt_params_triggers_rollback_and_recovers(
    eight_devices, tmp_path, caplog
):
    """Persistent corruption at round 4: the guard freezes params (every
    round skips), the watchdog escalates after 2 consecutive skips, the
    trainer rolls back to the newest complete checkpoint (the anomalous
    boundaries must NOT have overwritten it), fences the data window,
    and the run completes clean."""
    with caplog.at_level(logging.WARNING, logger="acco_tpu"):
        t = _trainer(
            tmp_path,
            save=True,
            checkpoint_every_s=0.0,  # checkpoint at every boundary
            fault_injection=[{"kind": "corrupt_params", "round": 4, "n": 8}],
            rollback_after_skipped=2,
            rollback_max=2,
        )
        summary = t.train()
    text = " ".join(r.getMessage() for r in caplog.records)
    assert "fault injection: corrupt_params" in text
    assert "periodic checkpoint skipped" in text  # health-gated saves
    assert "rolled back" in text and "fenced" in text
    assert summary["rollbacks"] == 1
    assert summary["count_grad_tot"] >= 64
    assert np.isfinite(summary["final_loss"])
    assert np.isfinite(
        np.asarray(jax.device_get(t.final_state.flat_params))
    ).all()
    # the results ledger carries the health columns
    import csv

    with open(os.path.join(str(tmp_path), "results.csv"), newline="") as f:
        row = list(csv.DictReader(f))[-1]
    assert row["rollbacks"] == "1"


def test_final_save_despite_anomaly_when_no_checkpoint(
    eight_devices, tmp_path, caplog
):
    """A run that ends mid-anomaly with NOTHING on disk must still write
    its final checkpoint: the guard held params/opt bit-exact at the
    last healthy commit, so the state is good — and gating the only
    save the run would ever make loses all progress. (The anomalous-
    boundary gate exists to protect an EXISTING complete checkpoint
    from being overwritten; with none, there is nothing to protect.)"""
    with caplog.at_level(logging.WARNING, logger="acco_tpu"):
        t = _trainer(
            tmp_path,
            save=True,
            checkpoint_every_s=10_000,  # no periodic save fires
            # dpu consumes round 3's poisoned staged grads at round 4 —
            # the LAST round before the shutdown latch, so the run ends
            # with consec_skipped=1
            fault_injection=[{"kind": "nan_grads", "round": 3}],
            shutdown_handler=faults.ShutdownAfterRounds(5),
        )
        summary = t.train()
    assert summary["interrupted"] is True
    assert summary["skipped_rounds"] == 1  # round 4 skipped; run ends there
    text = " ".join(r.getMessage() for r in caplog.records)
    assert "final checkpoint saved DESPITE" in text
    path = latest_checkpoint(
        os.path.join(str(tmp_path), "checkpoints", "w-dpu")
    )
    assert path is not None  # progress preserved, resumable


def test_staged_verdict_nonfinite_grads_finite_loss(eight_devices):
    """pending_ok must come from the STAGED GRADS, not the loss alone: a
    backward-pass overflow can stage nonfinite grads under a finite
    forward loss, and the next even round would accumulate on top of
    them. The verdict is replication-exact — a scalar psum over the
    grad-reduction axes makes every rank read 0 when ANY rank staged
    nonfinite values."""
    from jax.sharding import PartitionSpec as P

    step, _ = _make("acco")

    def body(g):
        fin = jnp.float32(2.0)
        return (
            step._staged_ok(g, fin),
            step._staged_ok(jnp.zeros_like(g), fin),
            step._staged_ok(jnp.zeros_like(g), jnp.float32(np.nan)),
        )

    g = np.zeros((8, 4), np.float32)
    g[3, 2] = np.inf  # ONE rank's local staged grads are poisoned
    bad_grads, all_good, nan_loss = jax.shard_map(
        body,
        mesh=step.mesh,
        in_specs=(P(step.shard_axes),),
        out_specs=(P(), P(), P()),
    )(jnp.asarray(g))
    assert float(bad_grads) == 0.0
    assert float(all_good) == 1.0
    assert float(nan_loss) == 0.0


def test_monitor_sustained_shift_reseeds_baseline():
    """A sustained regime shift must not freeze the monitor: single
    spikes never fold into the baseline (an outlier must not normalize
    itself), but after spike_reseed consecutive spike-level readings the
    level is accepted as drift, the baseline re-seeds there, and the
    monitor stops warning at every boundary forever."""
    mon = TrainingHealthMonitor(
        escalate_after=3, warmup_obs=2, spike_reseed=3,
        log=logging.getLogger("t"),
    )
    for _ in range(6):
        mon.observe(grad_norm=1.0, loss=2.0, skipped_rounds=0, consec_skipped=0)
    cls = [
        mon.observe(
            grad_norm=1e6, loss=2.0, skipped_rounds=0, consec_skipped=0
        ).classification
        for _ in range(3)
    ]
    assert cls == ["spike", "spike", "drift"]
    after = mon.observe(
        grad_norm=1e6, loss=2.0, skipped_rounds=0, consec_skipped=0
    )
    assert after.classification == "ok"  # re-learned at the new level
    assert mon.spikes == 2 and mon.drifts == 1
    # and relative to the NEW baseline, an outlier is still a spike
    v = mon.observe(grad_norm=1.0, loss=2.0, skipped_rounds=0, consec_skipped=0)
    assert v.classification == "spike"


def test_escalation_without_checkpoint_raises(eight_devices, tmp_path):
    """rollback=True but save=False and persistent corruption: the guard
    holds params, but with nothing to roll back to the watchdog must
    fail loudly instead of spinning no-op rounds forever."""
    t = _trainer(
        tmp_path,
        fault_injection=[{"kind": "corrupt_params", "round": 1, "n": 8}],
        rollback_after_skipped=2,
    )
    with pytest.raises(RuntimeError, match="no complete checkpoint"):
        t.train()


@pytest.mark.slow
def test_rollback_recovery_is_deterministic(eight_devices, tmp_path):
    """The fenced recovery is a pure function of (seed, data, fence
    position): two identical faulted runs — each a full multi-round
    corrupt->skip->rollback->resume cycle — end with bit-identical
    parameters."""

    def run(d):
        t = _trainer(
            tmp_path / d,
            save=True,
            checkpoint_every_s=0.0,
            fault_injection=[{"kind": "corrupt_params", "round": 4, "n": 8}],
            rollback_after_skipped=2,
        )
        s = t.train()
        assert s["rollbacks"] == 1
        return np.asarray(jax.device_get(t.final_state.flat_params))

    np.testing.assert_array_equal(run("one"), run("two"))


def test_summary_and_results_health_columns_clean_run(
    eight_devices, tmp_path
):
    """A clean run reports zero skips/rollbacks through the same
    summary/CSV plumbing (the columns exist even when nothing fired)."""
    t = _trainer(tmp_path, nb_steps_tot=24)
    summary = t.train()
    assert summary["skipped_rounds"] == 0 and summary["rollbacks"] == 0
    import csv

    with open(os.path.join(str(tmp_path), "results.csv"), newline="") as f:
        row = list(csv.DictReader(f))[-1]
    assert row["skipped_rounds"] == "0" and row["rollbacks"] == "0"


def test_skip_in_final_window_still_reaches_target(eight_devices, tmp_path):
    """A guard-skip between the LAST logging boundary and the grad
    target must not end the run short: the host-side count is
    optimistic (it assumes every dispatched round committed), and only
    logging boundaries reconcile it — the exit check must reconcile
    once more against the device counter and keep training. Cadence is
    set so no boundary ever fires mid-run."""
    t = _trainer(
        tmp_path,
        fault_injection=[{"kind": "nan_grads", "round": 6}],
        delta_step_for_log=1000,
    )
    summary = t.train()
    assert summary["skipped_rounds"] == 1
    assert summary["count_grad_tot"] >= 64  # the skipped round was re-run
    assert np.isfinite(summary["final_loss"])


def test_escalation_with_rollback_disabled_raises(eight_devices, tmp_path):
    """rollback=False + persistent corruption must abort loudly instead
    of spinning forever: every round is guard-skipped and each boundary
    reconciles the host count back to the frozen device counter, so the
    loop's exit condition can never be met."""
    t = _trainer(
        tmp_path,
        fault_injection=[{"kind": "corrupt_params", "round": 4, "n": 8}],
        rollback=False,
        rollback_after_skipped=2,
    )
    with pytest.raises(RuntimeError, match="rollback=False"):
        t.train()


def test_drift_counts_episodes_not_boundaries():
    """grad_norm_drifts is an episode counter: a drift that persists
    across N logging boundaries is ONE event in the ledger (else the
    column scales with the log cadence and is incomparable across
    runs); a second distinct excursion counts again."""
    mon = TrainingHealthMonitor(
        escalate_after=8, warmup_obs=2, ema_beta=0.99, drift_obs=2,
        log=logging.getLogger("t"),
    )
    for _ in range(6):
        mon.observe(grad_norm=1.0, loss=2.0, skipped_rounds=0, consec_skipped=0)
    first = [
        mon.observe(
            grad_norm=1.34, loss=2.0, skipped_rounds=0, consec_skipped=0
        ).classification
        for _ in range(4)
    ]
    assert first.count("drift") >= 2  # several boundaries spent in drift...
    assert mon.drifts == 1            # ...one episode in the ledger
    for _ in range(4):  # back to baseline: the episode ends
        mon.observe(grad_norm=1.0, loss=2.0, skipped_rounds=0, consec_skipped=0)
    second = [
        mon.observe(
            grad_norm=1.5, loss=2.0, skipped_rounds=0, consec_skipped=0
        ).classification
        for _ in range(4)
    ]
    assert "drift" in second
    assert mon.drifts == 2
