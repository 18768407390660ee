"""Mesh/model pairing validation and ZeRO-1 shard-axis derivation.

Moved here from ``parallel/common.py`` so the whole placement story —
validation (this module), rule tables (:mod:`acco_tpu.sharding.tables`)
and matching (:mod:`acco_tpu.sharding.rules`) — lives in one package.
``parallel/common.py`` re-exports :func:`shard_layout` for existing
callers.
"""

from __future__ import annotations

from typing import Optional


def shard_layout(
    mesh,
    model,
    seq_axis: Optional[str],
    data_axis: str,
    tensor_axis: Optional[str] = None,
    pipeline_axis: Optional[str] = None,
):
    """Validate the model/mesh CP+TP+PP pairing and derive the ZeRO-1
    layout: ``(shard_axes, world_size, num_shards)``.

    ``world_size`` counts data-parallel groups (the reference's "workers");
    ``num_shards`` counts the devices ZeRO-1 shards over — dp x sp, and
    with CP the scatter's psum is also what sums the sequence shards'
    partial gradients. The tensor/pipeline axis is NOT part of the ZeRO-1
    layout: each tp shard / pp stage has its own local flat vector, and
    the optimizer shards it within the group (parallel/tp.py,
    parallel/pp.py). The resulting ``shard_axes`` feed
    :func:`acco_tpu.sharding.tables.train_state_table`, which generates
    every PartitionSpec downstream.
    """
    from acco_tpu.sharding.tables import refuse_experts

    for axis, kind in ((tensor_axis, "tp"), (pipeline_axis, "pp"), (seq_axis, "sp")):
        if axis is not None:
            refuse_experts(model, kind)
    if pipeline_axis is not None:
        if not hasattr(model, "pp_param_specs"):
            raise ValueError(
                f"{type(model).__name__} does not support pipeline "
                f"parallelism (no pp_param_specs)"
            )
        model_tp = getattr(model, "tensor_axis", None)
        if tensor_axis is None and model_tp is not None:
            raise ValueError(
                "pipeline parallelism without tensor_axis requires a "
                "model built WITHOUT tensor_axis (pass tensor_axis to "
                "the train step for tp x pp composition)"
            )
        if tensor_axis is not None and model_tp != tensor_axis:
            raise ValueError(
                f"tp x pp: the model must be built with "
                f"tensor_axis={tensor_axis!r} (its block psums run inside "
                f"the pipeline stages); got {model_tp!r}"
            )
        pp = mesh.shape[pipeline_axis]
        n_layers = model.config.num_layers
        if n_layers % pp:
            raise ValueError(
                f"pipeline size {pp} must divide num_layers={n_layers} "
                f"(contiguous equal stages)"
            )
    model_axis = getattr(model, "sequence_axis", None)
    if seq_axis is not None and model_axis != seq_axis:
        raise ValueError(
            f"seq_axis={seq_axis!r} (context parallelism) requires a "
            f"ring-attention model built with sequence_axis={seq_axis!r}; "
            f"got {model_axis!r}"
        )
    if seq_axis is None and model_axis is not None:
        raise ValueError(
            f"model was built for context parallelism "
            f"(sequence_axis={model_axis!r}) but the train step got "
            f"seq_axis=None — its ring attention would fail deep inside "
            f"tracing; pass seq_axis={model_axis!r} and a mesh with that axis"
        )
    if tensor_axis is not None and not hasattr(model, "tp_param_specs"):
        raise ValueError(
            f"{type(model).__name__} does not support tensor parallelism "
            f"(no tp_param_specs); use the Llama family"
        )
    model_tp = getattr(model, "tensor_axis", None)
    if (tensor_axis or model_tp) and tensor_axis != model_tp:
        raise ValueError(
            f"tensor_axis={tensor_axis!r} on the train step but the model "
            f"was built with tensor_axis={model_tp!r} — both must name the "
            f"same mesh axis (or neither)"
        )
    world_size = mesh.shape[data_axis]
    if seq_axis is None:
        return data_axis, world_size, world_size
    return (data_axis, seq_axis), world_size, world_size * mesh.shape[seq_axis]
