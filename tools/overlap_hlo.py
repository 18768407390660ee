"""Overlap evidence: does XLA schedule ACCO's collectives over the fwd/bwd?

The reference hides gradient communication behind compute with two CUDA
streams and a host thread (`/root/reference/trainer_decoupled.py:
129-168,447-520`). The TPU design claims XLA's async collectives do the
same for the compiled round (`acco_tpu/parallel/acco.py:18-22`). This tool
verifies the claim *structurally*, with no multi-chip hardware: it
AOT-compiles the real ACCO round for an 8-chip v5e topology
(`jax.experimental.topologies`) and inspects the optimized, scheduled HLO:

- every `all-gather` / `reduce-scatter` of the communication branch must
  appear as an async ``-start``/``-done`` pair (not a blocking op), and
- between each pair the schedule must place real compute (fusions/dots
  from the gradient branch) — that window IS the overlap: the collective
  is in flight on the ICI links while the MXU runs microbatch fwd/bwd.

Writes OVERLAP.md (summary table + per-collective windows). Run:

    python tools/overlap_hlo.py [--seq 1024] [--bs 8] [--layers 4]

The compile happens on the TPU toolchain (libtpu AOT) but needs no chips;
~1-3 min for the default 4-layer model.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from acco_tpu.analysis.overlap import analyze_schedule  # noqa: E402


def v5e_mesh_devices(n_devices: int):
    """``n_devices`` AOT device objects from the smallest v5e topology
    that holds them (the minimum valid topology is 2x2 — a mesh over a
    subset of a topology's devices compiles fine, which is how
    single-chip programs are AOT-compiled for calibration)."""
    from jax.experimental import topologies

    if n_devices <= 4:
        name = "v5e:2x2"
    elif n_devices % 8 == 0:
        # squarest factorization with BOTH dims even (libtpu's
        # chips_per_host_bounds is 2x2: an odd dim like 8x3 is rejected)
        # and capped at 16 chips per dim (a 32x4 request aborts the
        # compiler) — so 128 chips are 16x8 and 24 stay 4x6.
        x = 1
        while x * x < n_devices:
            x *= 2
        while x > 2 and (n_devices % x or (n_devices // x) % 2):
            x //= 2
        y = n_devices // x
        if n_devices % x or x % 2 or y % 2 or x > 16 or y > 16:
            raise ValueError(
                f"no v5e topology for {n_devices} devices "
                "(needs an even x even factorization with dims <= 16)"
            )
        name = f"v5e:{x}x{y}"
    else:
        raise ValueError(f"no v5e topology for {n_devices} devices")
    topo = topologies.get_topology_desc(platform="tpu", topology_name=name)
    return list(topo.devices)[:n_devices]


def build_round(
    n_devices: int,
    seq: int,
    bs_per_chip: int,
    n_layers: int,
    comm_impl: str = "xla",
    unroll: bool = False,
    model_json: str | None = None,
):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.acco import AccoTrainStep
    from acco_tpu.parallel.common import BATCH_KEYS, batch_specs
    from acco_tpu.parallel.mesh import DATA_AXIS

    from acco_tpu.parallel.mesh import ici_ring_gaps, make_mesh

    # make_mesh, not a raw reshape: the topology-aware assignment is
    # part of what this tool verifies — the ring collective's overlap
    # math assumes neighbor hops, so a mesh whose dp ring leaves the
    # ICI grid is reported loudly.
    mesh = make_mesh({DATA_AXIS: n_devices}, v5e_mesh_devices(n_devices))
    gaps = ici_ring_gaps(mesh, DATA_AXIS)
    if gaps is None:
        print("# dp ring: devices expose no coords — placement unverified")
    elif gaps:
        print(
            f"# WARNING: dp ring has {len(gaps)} non-ICI-neighbor hops "
            f"{gaps} — ppermute traffic will route through intermediate "
            "chips"
        )
    else:
        print("# dp ring: every hop ICI-adjacent (ici_ring_gaps: none)")
    build_round.last_ring_gaps = gaps  # reused by main()'s report

    if model_json:
        # estimator validation: a real arch config (e.g. the measured
        # Llama-350M) instead of the synthetic n_layers flagship clone
        cfg = LlamaConfig.from_json(model_json)
        if seq > cfg.max_position_embeddings:
            import dataclasses

            cfg = dataclasses.replace(cfg, max_position_embeddings=seq)
    else:
        cfg = LlamaConfig(
            num_layers=n_layers, max_position_embeddings=max(seq, 1024)
        )
    # Resolve attention for platform='tpu' explicitly: this builder runs
    # on a CPU host (AOT), where 'auto' would resolve to 'xla' and the
    # estimate would silently model the pre-kernel einsum program
    # instead of what the chip actually runs.
    from acco_tpu.ops.attention import resolve_attention_impl

    attn = resolve_attention_impl(
        "auto", seq, platform="tpu", remat="dots",
        head_dim=cfg.hidden_size // cfg.num_heads,
    )
    model = LlamaModel(
        cfg,
        param_dtype=jnp.bfloat16,
        remat="dots",
        attention=attn,
        scan_unroll=True if unroll else 1,
    )
    step = AccoTrainStep(
        model,
        mesh,
        get_schedule("cosine", 6e-4, 1000, 50000),
        weight_decay=0.1,
        beta1=0.9,
        beta2=0.95,
        mode="acco",
        const_len_batch=True,  # pretrain contract: all-ones masks dropped
        comm_impl=comm_impl,
    )

    # Abstract state: init on the CPU backend only to learn shapes/geometry
    # (AOT topologies expose no addressable devices to put arrays on).
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    from acco_tpu.parallel.flat_layout import FlatLayout
    from acco_tpu.parallel.zero1 import ShardGeometry

    # the layout needs shapes only, and unravel is only needed inside the loss
    step.layout = FlatLayout(params)
    step.unravel = step.layout.unravel
    step.geom = ShardGeometry(step.layout.n_flat, step.num_shards)

    Pp, ns, ws = step.geom.padded_size, step.num_shards, step.world_size
    specs = step.state_specs()
    sds = lambda shape, dtype, spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec)
    )
    from acco_tpu.ops.adamw import AdamWState
    from acco_tpu.parallel.acco import AccoState
    from acco_tpu.parallel.common import abstract_health
    from acco_tpu.parallel.zero1 import Zero1State

    state = AccoState(
        flat_params=sds((Pp,), jnp.bfloat16, specs.flat_params),
        pending_grads=sds((ns * Pp,), jnp.float32, specs.pending_grads),
        pending_count=sds((ws,), jnp.float32, specs.pending_count),
        zero1=Zero1State(
            opt=AdamWState(
                params=sds((Pp,), jnp.float32, specs.zero1.opt.params),
                mu=sds((Pp,), jnp.float32, specs.zero1.opt.mu),
                nu=sds((Pp,), jnp.float32, specs.zero1.opt.nu),
                count=sds((), jnp.int32, specs.zero1.opt.count),
            ),
            sched_grads=sds((), jnp.int32, specs.zero1.sched_grads),
            grads_committed=sds((), jnp.float32, specs.zero1.grads_committed),
        ),
        round_idx=sds((), jnp.int32, specs.round_idx),
        health=abstract_health(mesh),
    )
    n_acc, global_bs = 1, bs_per_chip * ws
    bspecs = dict(zip(BATCH_KEYS, batch_specs(DATA_AXIS, None)))
    batches = {
        "input_ids": sds((n_acc, global_bs, seq), jnp.int32, bspecs["input_ids"]),
        "attention_mask": sds(
            (n_acc, global_bs, seq), jnp.int32, bspecs["attention_mask"]
        ),
        "labels": sds((n_acc, global_bs, seq), jnp.int32, bspecs["labels"]),
        "valid": sds((n_acc, ws), jnp.float32, bspecs["valid"]),
    }
    return step, state, batches


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--bs", type=int, default=8)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--out", default="OVERLAP.md")
    ap.add_argument("--dump-hlo", default=None, help="also write raw HLO here")
    ap.add_argument("--comm", default="ring", choices=["xla", "ring"])
    ap.add_argument(
        "--unroll", action="store_true", default=True,
        help="fully unroll the layer scan (straight-line compute the "
        "scheduler can interleave with ring hops)",
    )
    ap.add_argument("--no-unroll", dest="unroll", action="store_false")
    ap.add_argument(
        "--opt",
        action="append",
        default=[],
        metavar="K=V",
        help="XLA compiler option override (repeatable), e.g. "
        "--opt xla_tpu_enable_async_collective_fusion=true",
    )
    args = ap.parse_args()

    step, state, batches = build_round(
        args.devices, args.seq, args.bs, args.layers,
        comm_impl=args.comm, unroll=args.unroll,
    )

    opts = dict(kv.split("=", 1) for kv in args.opt)
    # The trainer dispatches the two PARITY-SPECIALIZED programs
    # (round_fn(parity=True/False)), not the generic traced-parity one —
    # analyze exactly what production runs and require overlap in BOTH.
    reports = {}
    hlo = None
    for parity, tag in ((True, "even"), (False, "odd")):
        compiled = step.round_fn(parity=parity).lower(state, batches).compile(
            compiler_options=opts or None
        )
        hlo = compiled.as_text()
        if args.dump_hlo:
            with open(f"{args.dump_hlo}.{tag}", "w") as f:
                f.write(hlo)
        reports[tag] = analyze_schedule(hlo)
    # Headline report from the odd (committing) round; both gate the verdict.
    rep = reports["odd"]
    def verdict(r):
        cov = sum(1 for w in r["async_pairs"] if w["compute_ops_in_window"] > 0)
        # OVERLAPPED = no big blocking collective remains, the comm branch
        # is async, and a meaningful share of the in-flight windows have
        # compute scheduled inside (hops form a serial chain, so windows
        # past the available compute naturally run back-to-back).
        return (
            r["blocking_collectives"] == 0
            and r["async_pairs"]
            and cov * 4 >= len(r["async_pairs"])
        )

    ok = all(verdict(r) for r in reports.values())
    # Placement canary in the committed artifact, not just stdout: the
    # neighbor-hop overlap math below assumes the dp ring rides direct
    # ICI links, so a gapped ring invalidates the verdict. build_round
    # already computed this for the mesh it actually compiled — reuse,
    # and keep "unverifiable" distinct from "verified gapless".
    ring_gaps = getattr(build_round, "last_ring_gaps", None)
    if ring_gaps:
        ok = False
    if ring_gaps is None:
        gap_line = (
            "dp ring placement: devices expose no chip coords — "
            "placement UNVERIFIED (not a gapless claim)."
        )
    elif ring_gaps:
        gap_line = (
            f"dp ring placement: **{len(ring_gaps)} non-ICI-neighbor "
            f"hops** {ring_gaps} — ppermute traffic routes through "
            "intermediate chips; verdict forced to NOT overlapped."
        )
    else:
        gap_line = (
            "dp ring placement: every hop ICI-adjacent "
            "(`ici_ring_gaps`: none)."
        )
    covered = sum(
        1 for w in rep["async_pairs"] if w["compute_ops_in_window"] > 0
    )
    lines = [
        "# ACCO comm/compute overlap — scheduled-HLO evidence",
        "",
        f"AOT compile of the real ACCO round (`AccoTrainStep.round_fn`) for a",
        f"**{args.devices}-chip v5e topology** (no hardware attached), Llama",
        f"{args.layers}-layer, seq {args.seq}, per-chip batch {args.bs}, bf16,",
        f"ZeRO-1 over dp, comm_impl=**{args.comm}**, layer scan",
        f"{'fully unrolled' if args.unroll else 'as a while loop'}.",
        f"Generated by `python tools/overlap_hlo.py --devices {args.devices} "
        f"--seq {args.seq} --bs {args.bs} --layers {args.layers}"
        f"{'' if args.unroll else ' --no-unroll'} --comm {args.comm}`.",
        "",
        gap_line,
        "",
        "The reference implements overlap with CUDA streams + a host thread",
        "(`trainer_decoupled.py:129-168,447-520`); here the evidence that XLA's",
        "latency-hiding scheduler provides it: every collective of the",
        "communication branch is an async `-start`/`-done` pair, and between",
        "start and done the schedule places the gradient branch's compute — the",
        "collective is on the ICI links while the MXU runs fwd/bwd.",
        "",
        "Background (measured in this repo): the stock `psum_scatter`/"
        "`all_gather`",
        "path lowers on this libtpu to two *blocking* full-size all-reduces",
        "scheduled after the compute — zero overlap (run with `--comm xla",
        "--no-unroll` to reproduce). `comm_impl='ring'` re-expresses both",
        "collectives as bidirectional `ppermute` rings, which compile to async",
        "collective-permute pairs; with the layer scan unrolled the scheduler",
        "interleaves the hops with per-layer compute.",
        "",
        f"- async collective pairs: **{len(rep['async_pairs'])}**",
        f"- blocking (non-async) large collectives: "
        f"**{rep['blocking_collectives']}**",
        f"- blocking scalar-count collectives (grad-count psum, can't "
        f"overlap anything): {rep['blocking_small_collectives']}",
        f"- total scheduled ops in entry: {rep['total_scheduled_ops']}",
        f"- pairs with compute inside the in-flight window: "
        f"**{sum(1 for w in rep['async_pairs'] if w['compute_ops_in_window'] > 0)}"
        f"/{len(rep['async_pairs'])}**",
        f"- per-parity (the trainer runs BOTH specialized programs): "
        + ", ".join(
            f"{tag}: {len(r['async_pairs'])} pairs/"
            f"{r['blocking_collectives']} blocking -> "
            f"{'ok' if verdict(r) else 'NOT OK'}"
            for tag, r in reports.items()
        ),
        f"- verdict: **{'OVERLAPPED' if ok else 'NOT PROVEN'}**",
        "",
        "| collective | ops in flight window | compute ops in window |",
        "|---|---|---|",
    ]
    for w in rep["async_pairs"]:
        lines.append(
            f"| {w['kind']} ({w['name']}) | {w['window_ops']} | "
            f"{w['compute_ops_in_window']} |"
        )
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
