"""Per-chip HBM proof for large-model placement: AOT-compile the real
ACCO round for a TPU topology (no chips needed) and report the compiler's
memory analysis.

The tensor-parallelism README claims are verified here with the actual
compiled program, not arithmetic — ``compiled.memory_analysis()`` gives
the argument/output/temp/peak bytes per chip as XLA will allocate them.
Measured results (see README "Launching on TPU pods"): Llama-3-8B fits
best composed — **v5e-32 at ``{dp: 2, pp: 8, tp: 2}`` (12.89 of 16 GB,
re-proved round 4 with the fused attention kernel; 12.83 einsum;
13.13 with ``--fused-loss pallas``, the pipelined sharded-CE kernel —
memory-neutral at seq 512, its value is the removed per-tick f32
logits matmuls)** — or
pp-only on a
**v5e-32 at ``{dp: 2, pp: 16}`` (13.70 of 16 GB)** — half the pod of the
tensor-parallel placement — and a v5e-64 at ``{dp: 8, tp: 8}`` (14.62 GB,
ring collectives); GPT-Neo-2.7B fits a **v5e-8 at ``{dp: 2, pp: 4}``
(13.99 GB, full remat, flagship seq-1024 bs-8)** — again half its tp
pod — and a v5e-16 at ``{dp: 4, tp: 4}`` (13.68 GB); smaller meshes
exceed HBM because ACCO double-buffers full-precision gradients per
device (the sharded-state floor also rules out a v5e-16 for the 8B:
``{dp: 2, pp: 8}`` needs 21.06 GB, 11.2 GB of it state arguments). Knobs, in measured
order of leverage near the ceiling: deepen pp (v5e-32 {dp:4,pp:8} is
17.84 GB, {dp:2,pp:16} is 13.70 — per-stage state scales 1/pp and beats
the lost dp optimizer sharding), then full remat (−0.4 GB at pp=8),
then per-chip batch (−0.5 GB bs4→bs2); ``--comm ring`` is assumed (the
stock lowering costs an extra full-size f32 buffer).

    python tools/hbm_check.py --sweep --devices 32  # rule-table sieve: every
        # valid mesh factorization priced per state leaf, train + serve,
        # both model families, avals only (seconds, no compile)

    python tools/hbm_check.py --devices 32 --dp 2 --tp 1 --pp 16  # the 8B on half the pod

    python tools/hbm_check.py --devices 64 --dp 8 --tp 8   # the 8B fit
    python tools/hbm_check.py --model EleutherAI/gpt-neo-2.7B \
        --devices 16 --dp 4 --tp 4 --seq 1024 --bs 8 --remat 1
    python tools/hbm_check.py --model config/model/llama-125M.json \
        --devices 8 --dp 8 --tp 1 --seq 1024 --bs 8

Writes a summary line per configuration; ~2-6 min per compile for the 8B.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(model_json: str, n_devices: int, dp: int, tp: int, seq: int, bs: int,
          remat, fused_loss, comm: str = "ring", pp: int = 1,
          n_acc: int = 1, attn: str = "auto", sp: int = 1):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.acco import AccoTrainStep
    from acco_tpu.parallel.common import BATCH_KEYS, batch_specs
    from acco_tpu.parallel.mesh import DATA_AXIS

    assert dp * tp * pp * sp == n_devices, (
        f"dp*tp*pp*sp={dp * tp * pp * sp} != devices={n_devices}"
    )
    if sp > 1 and tp > 1:
        raise ValueError(
            "hbm_check --sp composes with --pp (pp x sp: ring attention "
            "inside pipeline stages) but not --tp"
        )
    from tools.overlap_hlo import v5e_mesh_devices

    topo_devices = v5e_mesh_devices(n_devices)
    if tp > 1 and pp > 1:  # composed: (dp, pp, tp) mesh
        grid = np.array(topo_devices).reshape(dp, pp, tp)
        mesh = Mesh(grid, (DATA_AXIS, "pp", "tp"))
        model_axis, axis_size = ("pp", "tp"), pp * tp
    elif sp > 1 and pp > 1:  # pp x sp: ring attention inside stages
        model_axis, axis_size = "pp", pp
        mesh = Mesh(
            np.array(topo_devices).reshape(dp, pp, sp),
            (DATA_AXIS, "pp", "sp"),
        )
    elif tp > 1 or pp > 1:
        model_axis = "tp" if tp > 1 else "pp"
        axis_size = tp if tp > 1 else pp
        grid = np.array(topo_devices).reshape(dp, axis_size)
        mesh = Mesh(grid, (DATA_AXIS, model_axis))
    elif sp > 1:  # context parallelism: (dp, sp) mesh, sequence sharded
        model_axis, axis_size = None, 1
        mesh = Mesh(
            np.array(topo_devices).reshape(dp, sp), (DATA_AXIS, "sp")
        )
    else:
        model_axis, axis_size = None, 1
        mesh = Mesh(np.array(topo_devices), (DATA_AXIS,))

    import dataclasses
    import json as _json

    from acco_tpu.models.gpt_neo import GPTNeoConfig
    from acco_tpu.models.registry import _MODEL_TYPES, _PRESETS

    tensor_axis = "tp" if tp > 1 else None
    pipeline_axis = "pp" if pp > 1 else None
    if model_json in _PRESETS:  # hub-name preset (e.g. the 2.7B)
        model_cls, overrides = _PRESETS[model_json]
        cfg_cls = LlamaConfig if model_cls is LlamaModel else GPTNeoConfig
        cfg = cfg_cls(**overrides)
    else:
        with open(model_json) as f:
            mtype = _json.load(f).get("model_type", "gpt_neo")
        cfg_cls, model_cls = _MODEL_TYPES[mtype]
        cfg = cfg_cls.from_json(model_json)
    if seq > cfg.max_position_embeddings:
        cfg = dataclasses.replace(cfg, max_position_embeddings=seq)
    from acco_tpu.parallel.tp import pad_vocab

    padded = (
        pad_vocab(cfg.vocab_size, axis_size)
        if (tensor_axis or pipeline_axis)
        else cfg.vocab_size
    )
    if padded != cfg.vocab_size:
        print(f"# vocab {cfg.vocab_size} -> {padded} (Megatron tp padding)")
    # Print the platform='tpu' resolution for the log, but hand the
    # model the RAW request with its platform pinned — the model's own
    # in-plan checks (GPT-Neo's banded-local gate requires the literal
    # 'auto') must see exactly what the pod's trainer passes, or the
    # proof compiles a program that never ships (see overlap_hlo).
    from acco_tpu.ops.attention import resolve_attention_impl

    print(
        "# attention impl: "
        + (
            "ring (zig-zag, VMEM block kernel)"
            if sp > 1
            else resolve_attention_impl(
                attn, seq, platform="tpu", remat=remat,
                head_dim=cfg.hidden_size // cfg.num_heads,
            )
        )
    )
    model = model_cls(
        cfg, param_dtype=jnp.bfloat16,
        remat=remat,
        # sp: the ring-attention model on the sequence axis (zig-zag
        # layout — the balanced causal ring); the block computation is
        # the VMEM Pallas kernel on TPU (ops/block_attention.py)
        attention="ring" if sp > 1 else attn,
        sequence_axis="sp" if sp > 1 else None,
        zigzag=sp > 1,
        tensor_axis=tensor_axis if tp > 1 else None,
        vocab_pad_to=padded,
        platform="tpu",
    )
    # Same platform pinning for the loss: 'auto' resolved on this
    # forced-CPU process would model the materialized CE instead of the
    # kernel the pod preset actually runs — the proof must compile the
    # shipped program.
    from acco_tpu.ops.losses import real_vocab_of, resolve_fused_loss

    fused_loss = resolve_fused_loss(
        fused_loss, model, real_vocab_of(model),
        warn=lambda m: print(f"# {m}"),
        n_vocab_shards=axis_size if (tensor_axis or pipeline_axis) else 1,
        seq_sharded=sp > 1,
        platform="tpu",
    )
    print(f"# fused_loss impl: {fused_loss}")
    step = AccoTrainStep(
        model,
        mesh,
        get_schedule("cosine", 6e-4, 1000, 50000),
        weight_decay=0.1,
        beta1=0.9,
        beta2=0.95,
        mode="acco",
        const_len_batch=True,  # pretrain contract: all-ones masks dropped
        seq_axis="sp" if sp > 1 else None,
        tensor_axis=tensor_axis,
        pipeline_axis=pipeline_axis,
        fused_loss=fused_loss,
        comm_impl=comm,
    )

    # Abstract geometry from a shape-only init — the whole point: the 8B
    # parameters are never materialized anywhere. Placement comes from
    # the step's sharding rule table (acco_tpu/sharding) in ONE call —
    # the per-mode hand-picked spec wiring this replaced had to mirror
    # state_specs leaf by leaf.
    template = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if tensor_axis and pipeline_axis:
        from acco_tpu.parallel.tp import ComposedLayout

        step.tp_layout = ComposedLayout(
            template, model.pp_param_specs(), pp, model.tp_param_specs(), tp
        )
    elif tensor_axis or pipeline_axis:
        from acco_tpu.parallel.tp import TpLayout

        split_specs = (
            model.tp_param_specs() if tensor_axis else model.pp_param_specs()
        )
        step.tp_layout = TpLayout(template, split_specs, axis_size)
    if step.tp_layout is not None:
        # model-sharded: init_state's host-side flat-stacking cannot
        # trace under eval_shape, so wire the layout by hand and let the
        # rule table place a plain shape template
        from acco_tpu.ops.adamw import AdamWState
        from acco_tpu.parallel.acco import AccoState
        from acco_tpu.parallel.common import abstract_health
        from acco_tpu.parallel.zero1 import ShardGeometry, Zero1State
        from acco_tpu.sharding import sharded_abstract

        step.unravel = step.tp_layout.unravel_local
        step.geom = ShardGeometry(step.tp_layout.n_local, step.num_shards)
        Pp, ns, tpn = step.geom.padded_size, step.num_shards, axis_size
        s = jax.ShapeDtypeStruct
        shapes = AccoState(
            flat_params=s((tpn * Pp,), jnp.bfloat16),
            pending_grads=s((tpn * ns * Pp,), jnp.float32),
            pending_count=s((step.world_size,), jnp.float32),
            zero1=Zero1State(
                opt=AdamWState(
                    params=s((tpn * Pp,), jnp.float32),
                    mu=s((tpn * Pp,), jnp.float32),
                    nu=s((tpn * Pp,), jnp.float32),
                    count=s((), jnp.int32),
                ),
                sched_grads=s((), jnp.int32),
                grads_committed=s((), jnp.float32),
            ),
            round_idx=s((), jnp.int32),
            health=abstract_health(mesh),
        )
        state = sharded_abstract(step.rule_table(), shapes, mesh)
    else:
        # pure data/context parallel: eval_shape straight through the
        # real init_state — avals arrive already placed by the table
        state = step.abstract_state(template)

    sds = lambda shape, dtype, spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec)
    )
    global_bs = bs * dp
    bspecs = dict(
        zip(BATCH_KEYS, batch_specs(DATA_AXIS, "sp" if sp > 1 else None))
    )
    batches = {
        "input_ids": sds((n_acc, global_bs, seq), jnp.int32, bspecs["input_ids"]),
        "attention_mask": sds(
            (n_acc, global_bs, seq), jnp.int32, bspecs["attention_mask"]
        ),
        "labels": sds((n_acc, global_bs, seq), jnp.int32, bspecs["labels"]),
        "valid": sds((n_acc, dp), jnp.float32, bspecs["valid"]),
    }
    return step, state, batches, cfg


GB = 1024**3

# The flagships the README placement claims are about — the sweep covers
# both model families so a rule-table regression in either one shows up.
SWEEP_PRESETS = ("meta-llama/Meta-Llama-3-8B", "EleutherAI/gpt-neo-2.7B")


def _spec_axes(spec) -> list:
    """Mesh axis names a PartitionSpec shards over (tuple entries — the
    composed ``P(("pp", "tp"))`` dim-0 — contribute each member)."""
    axes = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, tuple):
            axes.extend(entry)
        else:
            axes.append(str(entry))
    return axes


def _mesh_combos(n_devices: int, cfg):
    """Divisibility-valid (dp, tp, pp, sp) factorizations of the device
    count: heads must split over tp, layers over pp, and sp composes
    with pp but not tp (the same envelope build() enforces)."""
    for dp in range(1, n_devices + 1):
        if n_devices % dp:
            continue
        rest = n_devices // dp
        for tp in range(1, rest + 1):
            if rest % tp:
                continue
            rest2 = rest // tp
            for pp in range(1, rest2 + 1):
                if rest2 % pp:
                    continue
                sp = rest2 // pp
                if sp > 1 and tp > 1:
                    continue
                if tp > 1 and cfg.num_heads % tp:
                    continue
                if pp > 1 and cfg.num_layers % pp:
                    continue
                yield dp, tp, pp, sp


def sweep_report(n_devices: int, hbm_gb: float, mode: str = "acco") -> list:
    """Candidate-placement sweep from the sharding rule tables alone — no
    Mesh object, no compile, nothing materialized (runs in seconds).

    For every divisibility-valid dp x tp x pp x sp factorization of
    ``--devices``, build the mode's train-state rule table
    (``acco_tpu.sharding.train_state_table``), walk the abstract state
    leaf paths with it, and charge each leaf ``global_bytes / prod(mesh
    sizes of the axes its matched spec shards over)`` — the device-local
    state floor that placement implies. The serve tree is priced the same
    way through ``serve_state_table``. This replaced per-mode hand-coded
    sizing branches: the ONLY placement input is the rule table, so the
    sweep can never drift from what the trainer actually dispatches.

    The floor excludes activations/transients — it's the sieve; the
    compile mode (``memory_analysis`` of the real round) is the proof
    for survivors.
    """
    import math

    import jax
    import jax.numpy as jnp

    from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel
    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.models.registry import _PRESETS
    from acco_tpu.parallel.acco import _state_template
    from acco_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS
    from acco_tpu.serve.kv_cache import CacheSpec
    from acco_tpu.sharding import (
        leaf_paths,
        model_family,
        serve_state_table,
        train_state_table,
    )

    rows = []
    state_paths = [p for p, _ in leaf_paths(_state_template())]
    for preset in SWEEP_PRESETS:
        model_cls, overrides = _PRESETS[preset]
        cfg_cls = LlamaConfig if model_cls is LlamaModel else GPTNeoConfig
        cfg = cfg_cls(**overrides)
        model = model_cls(cfg, param_dtype=jnp.bfloat16)
        template = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        n_params = sum(int(l.size) for l in jax.tree.leaves(template))
        print(f"\n== {preset} ({model_family(model)}): "
              f"{n_params / 1e9:.2f}B params, v5e-{n_devices}, "
              f"train state floor by rule table (mode={mode}) ==")
        for dp, tp, pp, sp in _mesh_combos(n_devices, cfg):
            tpn = tp * pp
            shard_axes = (DATA_AXIS, SEQ_AXIS) if sp > 1 else (DATA_AXIS,)
            if tp > 1 and pp > 1:
                model_axis = ("pp", "tp")
            elif tp > 1 or pp > 1:
                model_axis = "tp" if tp > 1 else "pp"
            else:
                model_axis = None
            table = train_state_table(mode, shard_axes, model_axis)
            mesh_sizes = {"dp": dp, "tp": tp, "pp": pp, "sp": sp}
            # ZeRO-1 shards over every data axis; the model axes carry
            # 1/tpn of the flat vector each (TpLayout pads per leaf, so
            # this floor is exact to within padding).
            ns = dp * sp
            n_local = math.ceil(n_params / tpn)
            padded = math.ceil(n_local / ns) * ns
            global_bytes = {
                "flat_params": tpn * padded * 2,  # bf16
                "pending_grads": tpn * ns * padded * 4,
                "pending_count": ns * 4,
                "zero1/opt/params": tpn * padded * 4,
                "zero1/opt/mu": tpn * padded * 4,
                "zero1/opt/nu": tpn * padded * 4,
            }  # everything else in the state tree is a 4-byte scalar
            per_leaf, total = {}, 0
            for path in state_paths:
                if mode != "acco" and path not in global_bytes and (
                    path.startswith("pending") or path == "round_idx"
                ):
                    continue  # ddp state has no pending/round leaves
                spec = table.match(path)
                denom = 1
                for axis in _spec_axes(spec):
                    denom *= mesh_sizes[axis]
                local = global_bytes.get(path, 4) / denom
                per_leaf[path] = local
                total += local
            fits = total <= hbm_gb * GB
            big = ", ".join(
                f"{path} {per_leaf[path] / GB:.2f}"
                for path in sorted(global_bytes)
                if path in per_leaf
            )
            print(
                f"dp={dp} tp={tp} pp={pp} sp={sp}: state floor "
                f"{total / GB:.2f} GB of {hbm_gb:g} "
                f"-> {'candidate' if fits else 'over'}  [{big} GB]"
            )
            rows.append({
                "preset": preset, "dp": dp, "tp": tp, "pp": pp, "sp": sp,
                "per_leaf": per_leaf, "total": total, "fits": fits,
            })

        # serve placement from the same surface: the serve table prices
        # params + both KV pools (currently replicated per serving chip)
        n_layers, n_kv, head_dim = model.kv_spec()
        spec_kv = CacheSpec(
            n_layers=n_layers, n_kv_heads=n_kv, head_dim=head_dim,
            page_size=16, num_pages=256, max_pages_per_seq=8,
            dtype="bfloat16",
        )
        table = serve_state_table(model_family(model))
        param_bytes = sum(
            int(l.size) * l.dtype.itemsize for l in jax.tree.leaves(template)
        )
        serve_tree_bytes = {
            "params": param_bytes,
            "k_pages": spec_kv.total_bytes // 2,
            "v_pages": spec_kv.total_bytes // 2,
        }
        serve_total = 0
        for path, nbytes in serve_tree_bytes.items():
            # the match both validates coverage and yields the spec; the
            # serving mesh is single-replica today, so every axis a rule
            # could name has size 1 and the leaf lands whole
            spec = table.match(path if path != "params" else "params/wte")
            assert not _spec_axes(spec), (path, spec)
            serve_total += nbytes
        print(
            f"serve ({table.name}): params "
            f"{serve_tree_bytes['params'] / GB:.2f} GB + KV pool "
            f"{(serve_tree_bytes['k_pages'] + serve_tree_bytes['v_pages']) / GB:.2f} GB "
            f"= {serve_total / GB:.2f} GB per serving chip (replicated)"
        )
        rows.append({
            "preset": preset, "serve": True, "total": serve_total,
            "fits": serve_total <= hbm_gb * GB,
        })
    return rows


def serve_report(serve_config: str, hbm_gb: float) -> dict:
    """Per-chip serving budget from avals only (acceptance for the serve
    subsystem): parameter bytes from a shape-only init, KV-page pool and
    per-request page budget from the CacheSpec, and the two big transient
    workspaces (the decode step's full context gather and the top prefill
    bucket's f32 logits) from the same arithmetic the engine's program
    avals are built from. Nothing is materialized or compiled — this
    runs in seconds on a laptop and proves placement before burning
    accelerator time (the training modes' placement-as-proof story).
    """
    import yaml

    import jax
    import jax.numpy as jnp

    from acco_tpu.models.registry import build_model
    from acco_tpu.serve.engine import default_buckets
    from acco_tpu.serve.kv_cache import CacheSpec, band_pages

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(serve_config) as f:
        cfg = yaml.safe_load(f) or {}
    with open(
        os.path.join(repo_root, "config", "model", cfg.get("model", "tiny") + ".yaml")
    ) as f:
        model_cfg = yaml.safe_load(f)
    param_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        cfg.get("param_dtype", "bfloat16")
    ]
    model = build_model(model_cfg, repo_root=repo_root, param_dtype=param_dtype)

    n_layers, n_kv, head_dim = model.kv_spec()
    spec = CacheSpec(
        n_layers=n_layers, n_kv_heads=n_kv, head_dim=head_dim,
        page_size=int(cfg.get("page_size", 16)),
        num_pages=int(cfg.get("num_pages", 256)),
        max_pages_per_seq=int(cfg.get("max_pages_per_seq", 8)),
        dtype=str(jnp.dtype(cfg.get("cache_dtype") or param_dtype).name),
    )
    slots = int(cfg.get("max_slots", 4))
    buckets = sorted(
        int(b) for b in (
            cfg.get("buckets")
            or default_buckets(spec.page_size, spec.max_context)
        )
    )

    # params from a shape-only init — the 8B is never materialized
    template = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves = jax.tree.leaves(template)
    n_params = sum(int(l.size) for l in leaves)
    param_bytes = sum(int(l.size) * l.dtype.itemsize for l in leaves)

    kv_itemsize = jnp.dtype(spec.dtype).itemsize
    # decode gathers every slot's FULL logical context (K and V)
    ctx = spec.max_pages_per_seq * spec.page_size
    decode_ws = 2 * n_layers * slots * ctx * n_kv * head_dim * kv_itemsize
    mcfg = model.config
    windows = getattr(mcfg, "layer_windows", None)
    if windows and any(w > 0 for w in windows):
        bp = band_pages(mcfg.window_size, spec.page_size)
        if bp < spec.max_pages_per_seq:
            decode_ws += (
                2 * n_layers * slots * bp * spec.page_size * n_kv * head_dim
                * kv_itemsize
            )
    # the top prefill bucket's f32 logits dominate its transient state
    prefill_ws = buckets[-1] * model.padded_vocab * 4
    peak = param_bytes + spec.total_bytes + max(decode_ws, prefill_ws)

    concurrent_max = (spec.num_pages - 1) // spec.max_pages_per_seq
    print(
        f"serve model={cfg.get('model')} layers={mcfg.num_layers} "
        f"hidden={mcfg.hidden_size} vocab={mcfg.vocab_size} | "
        f"page_size={spec.page_size} num_pages={spec.num_pages} "
        f"max_pages_per_seq={spec.max_pages_per_seq} slots={slots} "
        f"buckets={buckets}"
    )
    print(
        f"params: {param_bytes / GB:.2f} GB "
        f"{jnp.dtype(param_dtype).name} ({n_params} params)"
    )
    print(
        f"kv pool: {spec.total_bytes / GB:.2f} GB ({spec.num_pages} pages "
        f"x {spec.page_bytes / 2**20:.2f} MiB; per-seq max "
        f"{spec.max_pages_per_seq * spec.page_bytes / GB:.2f} GB = "
        f"{spec.max_pages_per_seq} pages / {spec.max_context} tokens; "
        f"{concurrent_max} max-length sequences fit the pool)"
    )
    print(
        f"workspace: decode context gather {decode_ws / GB:.2f} GB, "
        f"prefill bucket-{buckets[-1]} logits {prefill_ws / GB:.2f} GB"
    )
    fits = peak <= hbm_gb * GB
    print(
        f"PEAK (avals lower bound): {peak / GB:.2f} GB of {hbm_gb:g} GB HBM "
        f"-> {'fits' if fits else 'DOES NOT FIT'}"
    )
    if not fits:
        # the page pool is the elastic knob: params + workspace are fixed
        spare = hbm_gb * GB - param_bytes - max(decode_ws, prefill_ws)
        if spare > spec.page_bytes:
            print(
                f"  (num_pages <= {int(spare // spec.page_bytes)} would "
                "fit; or serve on a larger-HBM part — pass --hbm-gb)"
            )
        else:
            print(
                "  (params + workspace alone exceed this HBM — this "
                "model needs a larger-HBM part per replica)"
            )
    return {
        "n_params": n_params, "param_bytes": param_bytes,
        "pool_bytes": spec.total_bytes, "decode_ws": decode_ws,
        "prefill_ws": prefill_ws, "peak": peak, "fits": fits,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--serve", action="store_true",
                    help="serving-budget mode: per-chip params + KV-page "
                    "budget from avals only (no compile); sized from "
                    "--serve-config")
    ap.add_argument("--serve-config", default="config/serve/llama3-8b.yaml")
    ap.add_argument("--sweep", action="store_true",
                    help="candidate sweep: price every divisibility-valid "
                    "dp x tp x pp x sp mesh for --devices through the "
                    "sharding rule tables (train state floor per leaf + "
                    "serve budget, both model families) — avals only, "
                    "no compile; the default compile mode is the proof "
                    "for survivors")
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="per-chip HBM for --serve (16 = v5e)")
    ap.add_argument("--model", default="config/model/llama-3-8B.json")
    ap.add_argument("--devices", type=int, default=16)
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages (parallel/pp.py); composes "
                    "with --tp (dp x pp x tp mesh)")
    ap.add_argument("--sp", type=int, default=1,
                    help="context-parallel shards (zig-zag ring "
                    "attention over a dp x sp mesh): the long-context "
                    "placement proof — --seq is the GLOBAL length")
    ap.add_argument("--n-acc", type=int, default=0,
                    help="microbatches per round (default: pp, so the "
                    "pipeline has one microbatch in flight per stage)")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--bs", type=int, default=4, help="per-dp-group batch")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--fused-loss", default="chunk",
                    help="False/chunk/pallas lm-head+CE mode "
                    "(128k-vocab logits do not fit materialized)")
    ap.add_argument("--attn", default="auto",
                    help="attention impl (auto resolves for TPU: the "
                    "fused kernel at its envelope)")
    ap.add_argument(
        "--comm", default="ring", choices=["ring", "xla"],
        help="ring = production TPU config (chunked async ppermutes); "
        "xla psum_scatter lowers to a full-size blocking all-reduce on "
        "this libtpu, costing an extra [n_local] f32 buffer",
    )
    args = ap.parse_args()

    if args.serve:
        serve_report(args.serve_config, args.hbm_gb)
        return
    if args.sweep:
        sweep_report(args.devices, args.hbm_gb)
        return

    from acco_tpu.ops.attention import normalize_remat

    remat = normalize_remat(args.remat)
    from acco_tpu.ops.losses import normalize_fused_loss

    step, state, batches, cfg = build(
        args.model, args.devices, args.dp, args.tp, args.seq, args.bs,
        remat, normalize_fused_loss(args.fused_loss), comm=args.comm,
        pp=args.pp, n_acc=args.n_acc or max(args.pp, 1), attn=args.attn,
        sp=args.sp,
    )
    compiled = step.round_fn(parity=False).lower(state, batches).compile()
    mem = compiled.memory_analysis()
    line = (
        f"model={os.path.basename(args.model)} layers={cfg.num_layers} "
        f"hidden={cfg.hidden_size} vocab={cfg.vocab_size} | "
        f"v5e-{args.devices} mesh dp={args.dp} tp={args.tp} pp={args.pp} "
        f"sp={args.sp} "
        f"seq={args.seq} bs/dp={args.bs} remat={args.remat} comm={args.comm} "
        f"fused_loss={args.fused_loss}\n"
        f"per-chip: args {mem.argument_size_in_bytes / GB:.2f} GB, "
        f"outputs {mem.output_size_in_bytes / GB:.2f} GB "
        f"(aliased {mem.alias_size_in_bytes / GB:.2f} GB), "
        f"temps {mem.temp_size_in_bytes / GB:.2f} GB, "
        f"PEAK {(mem.argument_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / GB:.2f} GB"
        f" of 16 GB HBM"
    )
    print(line)


if __name__ == "__main__":
    main()
