"""Device scopes (ISSUE 23): every name of ``DEVICE_SCOPES`` is on the ops of
the round programs the benchmark's cells run (GPT-Neo's; ``EXPERT_DEVICE_SCOPES``
on an expert model's, ISSUE 25), and a scope is metadata only: the
lowered computation is the same with and without it."""

from __future__ import annotations

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel
from acco_tpu.models.llama import LlamaConfig, LlamaModel
from acco_tpu.ops.schedules import get_schedule
from acco_tpu.parallel.acco import AccoTrainStep
from acco_tpu.parallel.ddp import DDPTrainStep
from acco_tpu.parallel.mesh import make_mesh
from acco_tpu.telemetry import ALL_DEVICE_SCOPES, DEVICE_SCOPES, EXPERT_DEVICE_SCOPES

CFG = GPTNeoConfig(
    vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
    max_position_embeddings=32, window_size=8,
    attention_layers=["global", "local"],
)
EXPERTS = LlamaConfig(
    vocab_size=64, hidden_size=32, intermediate_size=16, num_layers=2, num_heads=2,
    num_kv_heads=2, max_position_embeddings=32, tie_word_embeddings=False, qk_norm=True,
    num_experts=4, num_experts_per_tok=2, router_aux_loss_coef=0.01, router_z_loss_coef=0.001,
)
SEQ, PER_DEVICE = 16, 2
MOE_SCOPES = EXPERT_DEVICE_SCOPES


def _lower(kind: str, experts: bool = False):
    """The tiny round program of ``kind``, lowered for the CPU mesh: ACCO's
    even (speculative) round or DDP's step, manual ring, guard on: the
    programs of the benchmark's cells. ``experts``: of the expert model
    (``LlamaModel`` with OLMoE's block) in place of GPT-Neo."""
    mesh = make_mesh()
    model = (
        LlamaModel(EXPERTS, param_dtype=jnp.bfloat16)
        if experts
        else GPTNeoModel(CFG, param_dtype=jnp.bfloat16)
    )
    kwargs = dict(
        weight_decay=0.1, beta1=0.9, beta2=0.95, label_smoothing=0.0,
        param_dtype=jnp.bfloat16, comm_impl="ring",
    )
    sched = get_schedule("constant", 1e-3, 0, 100)
    if kind == "ddp":
        step = DDPTrainStep(model, mesh, sched, **kwargs)
    else:
        step = AccoTrainStep(model, mesh, sched, mode="acco", **kwargs)
    state = step.init_state(model.init(jax.random.PRNGKey(0)))
    ws = mesh.devices.size
    ids = jnp.zeros((1, ws * PER_DEVICE, SEQ), jnp.int32)
    batch = {
        "input_ids": ids,
        "attention_mask": jnp.ones_like(ids),
        "labels": ids,
        "valid": jnp.ones((1, ws), jnp.float32),
    }
    fn = {
        "ddp": lambda: step.step_fn(),
        "acco_even": lambda: step.round_fn(parity=True),
        "acco_odd": lambda: step.round_fn(parity=False),
    }[kind]()
    return fn.lower(state, batch)


@pytest.fixture(scope="module")
def op_names(eight_devices):
    """``{program: set of op_name metadata strings of its compiled HLO}``."""
    def names(lowered):
        return set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))

    return {
        "acco_even": names(_lower("acco_even")),
        "ddp": names(_lower("ddp")),
        "experts_acco_even": names(_lower("acco_even", experts=True)),
    }


def _carries(names, scope) -> bool:
    return any(f"/{scope}/" in f"/{n}/" or f"({scope})" in n for n in names)


@pytest.mark.parametrize("kind", ["acco_even", "ddp"])
@pytest.mark.parametrize("scope", DEVICE_SCOPES)
def test_every_device_scope_names_ops_of_the_round_program(op_names, scope, kind):
    if (scope, kind) == ("acco/cast", "ddp"):
        # a guarded committing program (DDP's every step) casts inside its
        # one write pass, which is acco/optimizer's: no pass of its own
        assert not _carries(op_names[kind], scope)
        return
    assert _carries(op_names[kind], scope), f"no op of the {kind} program carries {scope!r}"


@pytest.mark.parametrize("scope", [s for s in ALL_DEVICE_SCOPES if s.startswith("model/")])
def test_every_model_scope_names_ops_of_an_expert_models_round(op_names, scope):
    """The Llama path carries the ``model/*`` scopes GPT-Neo has, and an
    expert block the three ``model/moe_*`` scopes inside ``model/mlp``."""
    assert len(MOE_SCOPES) == 3
    assert _carries(op_names["experts_acco_even"], scope), scope
    if scope in MOE_SCOPES:
        assert not _carries(op_names["acco_even"], scope)
        inside = [n for n in op_names["experts_acco_even"] if scope in n]
        assert inside and all("model/mlp/" + scope in n for n in inside)


def test_the_backward_pass_carries_the_forward_scopes_name(op_names):
    """A reader selects forward and backward with one name: JAX wraps the
    scope in the transform, ``transpose(jvp(model/lm_head_ce))``. Unpack's
    transpose is a rule of its own (FlatLayout.unravel: ``ravel`` of the
    cotangents), whose ops JAX names ``transpose(acco/accumulate)/
    jvp(acco/flat_unpack)/concatenate``: the same scope, innermost."""
    for kind, names in op_names.items():
        assert any(
            "transpose(acco/accumulate)/jvp(acco/flat_unpack)/" in n for n in names
        )
        assert any("transpose(jvp(model/lm_head_ce))" in n for n in names)
        assert any(
            "transpose(jvp(model/block))" in n and "model/mlp" in n for n in names
        )
        # the layer scan's own plumbing (stacking saved activations, slicing
        # them back out) is the block stack's: no op of the loop is left to
        # acco/accumulate alone
        assert not any("jvp()" in n and "/while" in n for n in names)


def test_innermost_scope_is_the_last_one_named(op_names):
    """What lets a regex per scope partition the ops: only acco/accumulate
    and model/block have scopes nested in them, and in an expert block
    model/mlp, which holds the three model/moe_* scopes."""
    for kind, names in op_names.items():
        outer = {"acco/accumulate", "model/block"}
        if kind.startswith("experts"):
            outer.add("model/mlp")
        for n in names:
            found = [s for s in ALL_DEVICE_SCOPES if s in n and s not in outer]
            assert len(found) <= 1, n
            if len(found) == 1 and kind.startswith("experts"):
                from acco_tpu.telemetry.scopes import innermost_scope

                assert innermost_scope(n) == found[0]


@pytest.mark.parametrize("kind", ["acco_even", "acco_odd", "ddp"])
def test_scopes_change_no_computation(eight_devices, monkeypatch, kind):
    """The lowered program, printed without locations, is the same text with
    ``jax.named_scope`` doing nothing: a scope names ops, it adds none."""
    scoped = _lower(kind).as_text()
    assert "acco/optimizer" not in scoped  # locations are not part of this text
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    assert _lower(kind).as_text() == scoped


# -- the table a reader of the profile joins on --------------------------------

HLO_TEXT = """
HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_computation.3 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %mul.7 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/shard_map/acco/optimizer/mul" source_file="adamw.py" source_line=70}
}

%fused_computation.4 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  %dus.1 = f32[8]{0} multiply(%p0.1, %p0.1), metadata={op_name="jit(step)/shard_map/acco/accumulate/jvp(model/block)/while/body/dynamic_update_slice"}
  ROOT %dot.1 = f32[8]{0} multiply(%dus.1, %p0.1), metadata={op_name="jit(step)/shard_map/acco/accumulate/jvp()/while/body/closed_call/model/block/model/mlp/dot_general"}
}

%fused_computation.5 (p0: f32[8]) -> f32[8] {
  %p0.2 = f32[8]{0} parameter(0)
  %mul.8 = f32[8]{0} multiply(%p0.2, %p0.2), metadata={op_name="jit(step)/shard_map/acco/optimizer/mul"}
  %convert.1 = f32[8]{0} convert(%mul.8), metadata={op_name="jit(step)/shard_map/acco/cast/convert_element_type"}
  ROOT %select.1 = f32[8]{0} select(%p0.2, %convert.1, %p0.2), metadata={op_name="jit(step)/shard_map/acco/guard/select_n"}
}

%wide.cond (p: (s32[], f32[8])) -> pred[] {
  %p.3 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%p.3, %p.3), direction=LT
}

%wide.body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.4 = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p.4), index=1
  %dynamic-update-slice.68 = f32[8]{0} dynamic-update-slice(%gte.1, %gte.1, %p.4)
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%p.4, %dynamic-update-slice.68)
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="state.flat_params"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/shard_map/acco/optimizer/mul"}
  %scatter.1 = f32[8]{0} scatter(%a), metadata={op_name="jit(step)/shard_map/acco/accumulate/transpose(jvp(model/embed))/scatter-add"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(step)/shard_map/acco/accumulate/jvp()/while/body/closed_call/model/block/model/mlp/dot_general"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(step)/shard_map/acco/guard/select_n"}
  %copy.2 = f32[8]{0:T(1024)} copy(%a)
  %tuple.1 = (s32[], f32[8]{0}) tuple(%constant.1, %copy.2)
  %while.1 = (s32[], f32[8]{0}) while(%tuple.1), condition=%wide.cond, body=%wide.body
  %get-tuple-element.9 = f32[8]{0} get-tuple-element(%while.1), index=1
  %slice.3 = f32[4]{0} slice(%get-tuple-element.9), slice={[0:4]}, metadata={op_name="jit(step)/shard_map/acco/reduce_scatter/slice"}
  %copy.7 = f32[8]{0} copy(%fusion.5)
  %copy.8 = f32[8]{0} copy(%unknown.1)
  ROOT %add.1 = f32[8]{0} add(%fusion.3, %fusion.4), metadata={op_name="jit(step)/shard_map/acco/accumulate/add"}
}
"""


def test_scope_table_names_each_instructions_innermost_scope():
    from acco_tpu.telemetry import innermost_scope, scope_table

    table = scope_table(HLO_TEXT)
    assert table["scopes"] == {
        "mul.7": "acco/optimizer",
        "fusion.3": "acco/optimizer",
        "scatter.1": "model/embed",
        "dus.1": "model/block",
        "dot.1": "model/mlp",
        "fusion.4": "model/mlp",
        "mul.8": "acco/optimizer",
        "convert.1": "acco/cast",
        "select.1": "acco/guard",
        "fusion.5": "acco/guard",
        "add.1": "acco/accumulate",
        # no op_name of their own: a relayout and the loop the compiler
        # turned a reshape into go by what reads their result, the loop's
        # body by the loop, a copy nobody reads by what it reads
        "copy.2": "acco/reduce_scatter",
        "while.1": "acco/reduce_scatter",
        "slice.3": "acco/reduce_scatter",
        "lt.1": "acco/reduce_scatter",
        "dynamic-update-slice.68": "acco/reduce_scatter",
        "copy.7": "acco/guard",
    }
    assert table["inferred"] == [
        "copy.2", "copy.7", "dynamic-update-slice.68", "lt.1", "while.1",
    ]
    assert "copy.8" not in table["scopes"]  # reads and is read by nothing named
    # XLA fused AdamW, the cast and the guard's select: the fusion goes by
    # its own op_name (the guard's) and the table says what else it holds;
    # fusion.4 holds the MLP and the block scan's plumbing: one layer's code
    assert table["mixed"] == {
        "fusion.5": ["acco/cast", "acco/guard", "acco/optimizer"]
    }
    assert innermost_scope("jit(f)/jit(main)/mul") == ""
    assert innermost_scope("") == ""
    assert (
        innermost_scope("a/acco/accumulate/transpose(jvp(acco/flat_unpack))/pad")
        == innermost_scope(
            "a/acco/accumulate/transpose(acco/accumulate)/jvp(acco/flat_unpack)/concatenate"
        )
        == "acco/flat_unpack"
    )


def test_scope_table_of_a_compiled_round_program(eight_devices):
    """On the real text: every scope owns some instruction of the DDP
    step, but ``acco/cast``: a guarded committing program casts inside
    ``acco/optimizer``'s one write pass."""
    from acco_tpu.telemetry import scope_table

    table = scope_table(_lower("ddp").compile().as_text())
    owners = set(table["scopes"].values())
    assert owners == set(DEVICE_SCOPES) - {"acco/cast"}
    experts = scope_table(_lower("ddp", experts=True).compile().as_text())
    assert set(experts["scopes"].values()) == set(ALL_DEVICE_SCOPES) - {"acco/cast"}
    for fusion, mix in table["mixed"].items():
        assert len(mix) > 1 and not {"acco/accumulate", "model/block"} & set(mix)
