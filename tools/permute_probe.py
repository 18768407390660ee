"""Pin down WHAT makes XLA lower ``collective-permute`` blocking at 32
devices.

Counting the compiled programs established the cliff (28/60/0 async
start/done pairs at 8/16/32 chips, model-size-independent, flag-immune)
but not the trigger. This probe AOT-compiles minimal shard_map programs — one
ppermute chain + independent matmul compute to overlap — with controlled
permutation-table structure, and counts async pairs in the scheduled
HLO:

  cycle32     one 32-cycle over 32 devices          (the flat ring hop)
  2x16        two disjoint 16-cycles over 32 devices (hierarchical intra
              phase; also what a two-level mesh lowers to)
  4x8         four disjoint 8-cycles over 32 devices
  half16      one 16-cycle among devices 0..15, 16..31 idle
  cycle16_16d one 16-cycle over a 16-device topology  (control: known async)
  cycle8_8d   one 8-cycle over an 8-device topology   (control)

If `2x16` converts async, the dp=32 fix is program-side (hierarchical
rings are right, something else re-rolls them); if only `half16` or the
16-device control converts, the trigger is total participants and no
1-axis program structure can fix dp>=32 without compiler changes.

    python tools/permute_probe.py [--hops 8] [--payload-mb 4]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pairs(kind: str, n: int):
    if kind.startswith("cycle"):  # one n-cycle
        return [(i, (i + 1) % n) for i in range(n)]
    if kind == "2x16":
        return [(i, (i + 1) % 16 + 16 * (i // 16)) for i in range(n)]
    if kind == "4x8":
        return [(i, (i + 1) % 8 + 8 * (i // 8)) for i in range(n)]
    if kind == "half16":
        return [(i, (i + 1) % 16) for i in range(16)]
    raise ValueError(kind)


def probe(kind: str, n_devices: int, hops: int, payload_mb: float) -> dict:
    import jax
    import re

    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from tools.overlap_hlo import analyze_schedule, v5e_mesh_devices

    mesh = Mesh(np.array(v5e_mesh_devices(n_devices)), ("dp",))
    pairs = _pairs(kind, n_devices)
    elems = int(payload_mb * 1e6 / 4)

    def body(x, w):
        # independent compute the scheduler could overlap with the hops
        # (seeded from x[0] so it can't constant-fold; shape-independent
        # of the payload size)
        acc = jnp.zeros((512, 512), jnp.float32) + x[0]
        for _ in range(hops):
            x = lax.ppermute(x, "dp", pairs)
            acc = jnp.tanh(acc @ w)
        return x + 0.0, acc

    sharded = jax.shard_map(
        body, mesh=mesh, in_specs=(P("dp"), P()), out_specs=(P("dp"), P()),
        check_vma=False,
    )
    x = jax.ShapeDtypeStruct((n_devices * elems,), jnp.float32)
    w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    compiled = jax.jit(sharded).lower(x, w).compile()
    hlo = compiled.as_text()
    rep = analyze_schedule(hlo)
    # Count blocking permutes DIRECTLY, payload-independent:
    # analyze_schedule's blocking_collectives field filters out payloads
    # <= 1M elements (it exists to ignore scalar-count psums in full
    # round programs), which would silently zero this probe's whole
    # point at small --payload-mb.
    blocking = len(
        re.findall(r"= \S+ collective-permute\(", hlo)
    )
    return {
        "kind": kind,
        "devices": n_devices,
        "hops": hops,
        "async_pairs": len(rep["async_pairs"]),
        "blocking": blocking,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--hops", type=int, default=8)
    ap.add_argument("--payload-mb", type=float, default=4.0)
    ap.add_argument(
        "--cases",
        nargs="*",
        default=["cycle32", "2x16", "4x8", "half16", "cycle16_16d", "cycle8_8d"],
    )
    args = ap.parse_args()
    for case in args.cases:
        if case.endswith("_16d"):
            r = probe("cycle16", 16, args.hops, args.payload_mb)
        elif case.endswith("_8d"):
            r = probe("cycle8", 8, args.hops, args.payload_mb)
        else:
            r = probe(case, 32, args.hops, args.payload_mb)
            r["kind"] = case
        print(r, flush=True)


if __name__ == "__main__":
    main()
