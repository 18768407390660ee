"""The rule tables: per-mode train/serve state tables and per-model-
family parameter tables.

These tables are the single source of truth for placement.  Everything
that used to hand-wire PartitionSpecs — ``flat_state_specs`` in
``parallel/common.py``, the per-model ``tp_param_specs``/
``pp_param_specs`` dicts, the serve KV-pool specs, ``hbm_check``'s
per-mode sizing branches — now derives from here, and the ``rules``
lint gate (:mod:`acco_tpu.analysis.rules`) audits that every leaf of
every dispatched program's state tree matches exactly one rule.

Train-state geometry (kept bit-identical to the pre-engine code, which
checkpoint-restore compatibility depends on): the flat ZeRO-1 vectors
are sharded over the data axes (``dp`` or ``(dp, sp)``), with a leading
model-axis entry prepended under tp/pp (the flat vector is a stack of
per-model-shard segments).  ``flat`` params replicate within the data
axes but still split over model axes.

Imports from :mod:`acco_tpu.parallel` stay inside function bodies:
``parallel/common.py`` imports this package at module scope.
"""

from __future__ import annotations

from typing import Any, Optional, Union

from jax.sharding import PartitionSpec as P

from acco_tpu.sharding.rules import Rule, RuleTable, ShardingRuleError, split_dims

Axes = Union[str, tuple]


def _flat_specs(shard_axes: Axes, model_axis: Optional[Axes]) -> tuple[P, P]:
    """(sharded, replicated-within-data) specs for the flat ZeRO-1
    vectors — the exact arithmetic ``flat_state_specs`` used: a single
    leading dim sharded over ``model_axes + shard_axes`` (resp. just the
    model axes for the ``flat`` params)."""
    axes = (shard_axes,) if isinstance(shard_axes, str) else tuple(shard_axes)
    if model_axis:
        t = (model_axis,) if isinstance(model_axis, str) else tuple(model_axis)
        return P(t + axes), P(t)
    return P(shard_axes), P()


def flat_state_specs(
    shard_axes: Axes, tensor_axis: Optional[Axes] = None
) -> tuple[P, P]:
    """Back-compat shim for ``parallel.common.flat_state_specs`` callers:
    (shard, flat) specs straight from the table arithmetic."""
    return _flat_specs(shard_axes, tensor_axis)


def train_state_table(
    mode: str, shard_axes: Axes, model_axis: Optional[Axes] = None
) -> RuleTable:
    """Rule table for a train-state pytree (``AccoState`` for
    acco/dpu, ``DDPState`` for ddp). One table covers every mesh: the
    specs are parameterized by the step's ``shard_axes``/``model_axis``,
    so dp, dp×sp, dp×tp, dp×pp and dp×pp×tp all read from here."""
    shard, flat = _flat_specs(shard_axes, model_axis)
    from acco_tpu.parallel.mesh import DATA_AXIS

    common = [
        Rule(
            r"^flat_params$",
            flat,
            "flat param vector: replicated within data axes, split over model axes",
        ),
        Rule(
            r"^zero1/opt/(params|mu|nu)$",
            shard,
            "ZeRO-1 optimizer state: each data shard owns 1/num_shards",
        ),
        Rule(r"^zero1/opt/count$", P(), "scalar step counter"),
        Rule(
            r"^zero1/(sched_grads|grads_committed)$",
            P(),
            "scalar schedule/commit counters",
        ),
        Rule(
            r"^health/(skipped_rounds|consec_skipped|pending_ok)$",
            P(),
            "watchdog scalars, replicated",
        ),
    ]
    if mode in ("acco", "dpu"):
        rules = common + [
            Rule(
                r"^pending_grads$",
                shard,
                "delayed gradient buffer, sharded like the optimizer state",
            ),
            Rule(
                r"^pending_count$",
                P(DATA_AXIS),
                "per-data-replica contribution counter",
            ),
            Rule(r"^round_idx$", P(), "scalar round counter"),
        ]
    elif mode == "ddp":
        rules = common
    else:
        raise ShardingRuleError(f"unknown train mode {mode!r}")
    return RuleTable(name=f"train:{mode}", rules=tuple(rules))


def eval_state_table(
    shard_axes: Axes, model_axis: Optional[Axes] = None
) -> RuleTable:
    """Eval programs see only ``{"flat_params": ...}``."""
    _, flat = _flat_specs(shard_axes, model_axis)
    return RuleTable(
        name="eval",
        rules=(Rule(r"^flat_params$", flat, "eval reads the flat params"),),
    )


def serve_state_table(family: str = "any") -> RuleTable:
    """Serve is single-replica today: params and KV pools replicated.
    When TP decode lands (ROADMAP item 5) this is the ONE place the
    pool/param placement changes — engine, hbm_check and the lint gate
    all read from here."""
    return RuleTable(
        name=f"serve:{family}",
        rules=(
            Rule(r"^(k_pages|v_pages)$", P(), "paged KV pools, single replica"),
            Rule(r"^params(/|$)", P(), "serve params, single replica"),
        ),
    )


# --- per-model-family parameter tables ------------------------------------
#
# These encode the split-dim choices the per-model ``tp_param_specs`` /
# ``pp_param_specs`` dicts used to hand-write; the model methods are now
# thin shims over ``model_split_specs``.  The tp rules say WHICH dim of
# each weight carries the tensor axis (Megatron column/row split); the
# pp rules stack every per-layer weight over its leading layer dim.


def _llama_tp_rules(axis: str) -> tuple:
    return (
        Rule(r"^wte$", P(axis), "vocab-dim split embedding"),
        Rule(r"^layers/(attn_norm|mlp_norm)$", P(), "norm scales replicated"),
        Rule(
            r"^layers/(wq|wk|wv|w_gate|w_up)$",
            P(None, None, axis),
            "column-parallel: heads / ffn-in split on dim 2",
        ),
        Rule(
            r"^layers/(wo|w_down)$",
            P(None, axis),
            "row-parallel: contraction dim split on dim 1",
        ),
        Rule(r"^final_norm$", P(), "final norm replicated"),
        Rule(r"^lm_head$", P(None, axis), "untied head split on vocab dim"),
    )


def _llama_pp_rules(axis: str) -> tuple:
    return (
        Rule(r"^wte$", P(axis), "embedding rows spread over stages"),
        Rule(r"^layers/", P(axis), "layer stack split on the layer dim"),
        Rule(r"^final_norm$", P(), "final norm replicated"),
        Rule(r"^lm_head$", P(None, axis), "untied head split on vocab dim"),
    )


def _gpt_neo_tp_rules(axis: str) -> tuple:
    return (
        Rule(r"^wte$", P(axis), "vocab-dim split embedding"),
        Rule(r"^wpe$", P(), "position embedding replicated"),
        Rule(
            r"^layers/(ln1_scale|ln1_bias|wo_bias|ln2_scale|ln2_bias|b_proj)$",
            P(),
            "norms and output biases replicated",
        ),
        Rule(
            r"^layers/w_qkv$",
            P(None, None, None, axis),
            "fused qkv: head dim is dim 3",
        ),
        Rule(
            r"^layers/(wo|w_proj)$",
            P(None, axis),
            "row-parallel: contraction dim split on dim 1",
        ),
        Rule(r"^layers/w_fc$", P(None, None, axis), "ffn-in split on dim 2"),
        Rule(r"^layers/b_fc$", P(None, axis), "ffn-in bias split with w_fc"),
        Rule(r"^(lnf_scale|lnf_bias)$", P(), "final norm replicated"),
    )


def _gpt_neo_pp_rules(axis: str) -> tuple:
    return (
        Rule(r"^wte$", P(axis), "embedding rows spread over stages"),
        Rule(r"^wpe$", P(), "position embedding replicated"),
        Rule(r"^layers/", P(axis), "layer stack split on the layer dim"),
        Rule(r"^(lnf_scale|lnf_bias)$", P(), "final norm replicated"),
    )


def param_table(
    family: str,
    kind: str,
    *,
    tied: bool = True,
    axis: Optional[str] = None,
) -> RuleTable:
    """Parameter rule table for ``family`` ("llama" | "gpt_neo") and
    ``kind`` ("tp" | "pp").  ``tied`` drops the llama ``lm_head`` rule
    when the head shares the embedding (gpt_neo always ties)."""
    from acco_tpu.parallel.mesh import PIPELINE_AXIS, TENSOR_AXIS

    if axis is None:
        axis = {"tp": TENSOR_AXIS, "pp": PIPELINE_AXIS}.get(kind)
    builders = {
        ("llama", "tp"): _llama_tp_rules,
        ("llama", "pp"): _llama_pp_rules,
        ("gpt_neo", "tp"): _gpt_neo_tp_rules,
        ("gpt_neo", "pp"): _gpt_neo_pp_rules,
    }
    try:
        rules = builders[(family, kind)](axis)
    except KeyError:
        raise ShardingRuleError(
            f"no param table for family={family!r} kind={kind!r}"
        ) from None
    if family == "llama" and tied:
        rules = tuple(r for r in rules if "lm_head" not in r.pattern)
    return RuleTable(name=f"params:{family}:{kind}", rules=tuple(rules))


def model_family(model: Any) -> str:
    """Family dispatch covering both registries AND ``hf_loader``
    imports (the loader returns the same model classes, so class-name
    sniffing covers it)."""
    name = type(model).__name__.lower()
    if "llama" in name:
        return "llama"
    if "neo" in name or "gpt" in name:
        return "gpt_neo"
    raise ShardingRuleError(
        f"cannot infer model family from {type(model).__name__!r}; "
        "add it to acco_tpu.sharding.tables.model_family"
    )


_NOT_FOR_EXPERTS = {
    "tp": "tensor parallelism (train.tp > 1)",
    "pp": "pipeline parallelism (pp > 1)",
    "sp": "context parallelism (sp > 1)",
    "serve": "serving (serve.py: prefill / decode)",
}


def refuse_experts(model: Any, kind: str) -> None:
    """A model with sparse experts trains data-parallel (dp, ZeRO-1 over the
    flat vector) and nothing else: raise for ``kind`` ("tp" | "pp" | "sp" |
    "serve") and name what is missing; a model without experts passes. No
    table here holds a rule for the expert leaves, and no code path
    dispatches tokens between chips."""
    n_experts = getattr(model.config, "num_experts", 0)
    if n_experts:
        raise ShardingRuleError(
            f"{_NOT_FOR_EXPERTS[kind]} is not supported for a model with experts "
            f"({type(model).__name__}, num_experts={n_experts}): "
            "acco_tpu/sharding/tables.py has no rule for the expert leaves "
            "(layers/router [L, E, D]; layers/w_gate, w_up, w_down [L, E, ...]) "
            "and there is no expert-parallel dispatch (all-to-all). Run it "
            "data-parallel: train.mesh_shape={dp: N}, ZeRO-1 shards the state"
        )


def model_param_table(model: Any, kind: str, axis: Optional[str] = None) -> RuleTable:
    """Rule table for a model instance (family + tie inferred)."""
    tied = bool(getattr(model.config, "tie_word_embeddings", True))
    return param_table(model_family(model), kind, tied=tied, axis=axis)


def model_split_specs(model: Any, kind: str) -> Any:
    """Int/None split-dim pytree for ``TpLayout``/``ComposedLayout``,
    derived by matching the model's abstract init tree against its rule
    table (avals only — no params materialize)."""
    import jax
    import jax.numpy as jnp

    from acco_tpu.parallel.mesh import PIPELINE_AXIS, TENSOR_AXIS

    axis = {"tp": TENSOR_AXIS, "pp": PIPELINE_AXIS}[kind]
    table = model_param_table(model, kind, axis=axis)
    template = jax.eval_shape(
        model.init, jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    return split_dims(table, template, axis)
