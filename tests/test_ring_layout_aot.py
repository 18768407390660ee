"""The ring pair at the benchmark's size, compiled for the chip without it.

A ``[n*S]`` vector lives on the TPU tiled ``T(1024)``. Viewed as ``[n, S]``
with n < 8 it is re-tiled to ``T(4,128)`` by a loop over the whole vector:
at dp=4 and GPT-Neo-2.7B's widths that staging was 143 ms of a 391 ms round
(PERF.md, PR 23 and PR 24). ``_rs_body`` and ``_ag_body`` therefore address
the vector by offsets, and this test holds the compiled program to it:
reduce-scatter, scale and cast to bf16, all-gather of ``f32[448581120]`` a
device over a described ``v5e:2x2`` (no chip; the compile takes seconds).

A child process compiles: describing the topology takes libtpu's lock,
which this pytest process must leave to the other ``tpu_aot`` children.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 4
PER_DEVICE = 448_581_120  # gpt-neo-2.7b-l4's flat vector, padded (PERF.md §5)


def compile_ring_pair():
    """Runs in the child: what the compiled ring pair holds, as JSON."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from acco_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from acco_tpu.parallel.ring_collectives import (
        ring_all_gather,
        ring_reduce_scatter,
    )

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here: the test skips
        print(json.dumps({"__skip__": repr(e)}))
        return
    mesh = make_mesh({DATA_AXIS: N}, list(topo.devices))

    def pair(grads):
        shard = ring_reduce_scatter(grads, DATA_AXIS)
        return ring_all_gather((shard / N).astype(jnp.bfloat16), DATA_AXIS)

    fn = jax.jit(
        jax.shard_map(
            pair, mesh=mesh, in_specs=(P(DATA_AXIS),),
            out_specs=P(DATA_AXIS), check_vma=False,
        )
    )
    grads = jax.ShapeDtypeStruct(
        (N * PER_DEVICE,), jnp.float32,
        sharding=NamedSharding(mesh, P(DATA_AXIS)),
    )
    compiled = fn.lower(grads).compile()
    hlo = compiled.as_text()
    retiled = set()
    for dims in re.findall(r"\w+\[([\d,]+)\]\{[^}]*T\(4,128\)", hlo):
        elems = 1
        for d in dims.split(","):
            elems *= int(d)
        if elems > 1_000_000:
            retiled.add(dims)
    print(
        json.dumps(
            {
                "while": len(re.findall(r"\bwhile\(", hlo)),
                "retiled": sorted(retiled),
                "permute_start": hlo.count(" collective-permute-start("),
                "permute_done": hlo.count(" collective-permute-done("),
                "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            }
        )
    )


@pytest.mark.tpu_aot
def test_ring_pair_compiles_to_slices_of_the_flat_vector():
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    if "__skip__" in got:
        pytest.skip(f"v5e:2x2 cannot be described here: {got['__skip__']}")
    assert got["while"] == 0, f"the compiler made loops of the staging: {got}"
    assert got["retiled"] == [], f"arrays re-tiled to T(4,128): {got}"
    # (n-1) hops x 2 directions x (reduce-scatter, all-gather), all async
    assert got["permute_start"] == got["permute_done"] == 4 * (N - 1), got
    # one bf16 output's worth: the all-gather's updates alias, in place
    assert got["temp_bytes"] < 2**30, got


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    compile_ring_pair()
