"""Unpack at the benchmark's leaf shapes, compiled for the chip without it.

On the TPU a 1-D array is tiled ``T(1024)`` and a matrix ``T(8,128)``: taken
out of a row-major flat vector, every leaf was a slice, a re-tile and, for its
gradient, a re-tile back: 12 B a parameter a round (``flat_staging_ms`` 12.9 ms
at GPT-Neo-2.7B's widths, 16.0 in OLMoE: ledger, PR 27). The vector now holds
a big leaf tile by tile (acco_tpu/parallel/flat_layout.py), and this test
holds the compiled accumulate program to it, at ``gpt-neo-2.7b-l4``'s and
``olmoe-1b-7b-l1``'s leaf shapes over a described ``v5e:2x2`` (no chip): among
the instructions the device runs under ``acco/flat_unpack``, the forward
slices and ONE ``pad`` (GPT-Neo's 50257 embedding rows -> 50264) are all that
touches more than a million elements.

A child process compiles: describing the topology takes libtpu's lock,
which this pytest process must leave to the other ``tpu_aot`` children.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG = 1_000_000
SCOPE = "acco/flat_unpack"
RETILES = ("reshape", "copy", "transpose")

# name -> (model.json under benchmark/configs, rows x positions of its cell,
#          re-tiles above BIG that are tolerated, as {elements: how many})
CASES = {
    "gpt-neo-2.7b-l4": ((2, 2048), {}),
    # With ONE layer the scan is inlined and XLA hands the four [2048, 2048]
    # attention projections' gradients over in the order their matmul wrote
    # them: four copies of 4M elements (0.1 ms of a 126 ms round), against
    # 626M elements that move through bitcasts.
    "olmoe-1b-7b-l1": ((1, 4096), {2048 * 2048: 4}),
}


def _elements(shape_text: str) -> int:
    most = 0
    for dims in re.findall(r"\w+\[([\d,]*)\]", shape_text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        most = max(most, n)
    return most


def unpack_ops(hlo: str) -> list:
    """``[opcode, elements]`` of every instruction above BIG elements that the
    device runs in SCOPE: the instructions of computations that are not fusion
    bodies, and a fusion's re-tiling instructions in its stead."""
    bodies, name = {}, None
    for line in hlo.splitlines():
        if not line.startswith(" "):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", line)
            name = m.group(1) if m else None
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    inst = re.compile(r"\s+(?:ROOT\s+)?%[\w.\-]+ = (.*?) ([\w\-]+)\(")
    found = []
    for name, lines in bodies.items():
        if name is None or name.startswith("fused_"):
            continue
        for line in lines:
            m = inst.match(line)
            if not m or SCOPE not in line:
                continue
            shape, op = m.groups()
            if op == "fusion":
                callee = re.search(r"calls=%([\w.\-]+)", line).group(1)
                for inner in bodies.get(callee, []):
                    mi = inst.match(inner)
                    if mi and mi.group(2) in ("copy", "transpose"):
                        found.append([mi.group(2), _elements(mi.group(1))])
            found.append([op, _elements(shape)])
    return [f for f in found if f[1] > BIG]


def compile_accumulate(case: str) -> None:
    """Runs in the child: what the compiled accumulate program holds, as JSON."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from acco_tpu.models.registry import _MODEL_TYPES
    from acco_tpu.parallel.common import (
        MicrobatchBlock,
        accumulate_grads,
        make_flat_loss_fn,
    )
    from acco_tpu.parallel.flat_layout import FlatLayout

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: the test skips
        print(json.dumps({"__skip__": repr(e)}))
        return
    chip = SingleDeviceSharding(topo.devices[0])
    path = os.path.join(REPO, "benchmark", "configs", case, "model.json")
    with open(path) as f:
        cfg_cls, model_cls = _MODEL_TYPES[json.load(f).get("model_type", "gpt_neo")]
    model = model_cls(
        cfg_cls.from_json(path), param_dtype=jnp.bfloat16, remat="dots",
        attention="auto", platform="tpu",
    )
    layout = FlatLayout(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    loss_fn = make_flat_loss_fn(
        model, layout.unravel, layout.n_flat, 0.0, const_len=True, with_terms=True
    )

    def accumulate(flat, grad_sum, ids):
        block = MicrobatchBlock(ids, jnp.ones_like(ids), ids, jnp.ones((1,), jnp.float32))
        return accumulate_grads(loss_fn, flat, block, grad_init=grad_sum)[0]

    (rows, positions), _ = CASES[case]
    avals = [
        jax.ShapeDtypeStruct((layout.n_flat,), jnp.bfloat16, sharding=chip),
        jax.ShapeDtypeStruct((layout.n_flat,), jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct((1, rows, positions), jnp.int32, sharding=chip),
    ]
    hlo = jax.jit(accumulate, donate_argnums=1).lower(*avals).compile().as_text()
    print(
        json.dumps(
            {
                "ops": unpack_ops(hlo),
                "n_flat": layout.n_flat,
                "n_row_major": layout.n_row_major,
                "bitcast_share": layout.bitcast_share,
            }
        )
    )


def test_unpack_ops_reads_fusions_and_skips_their_bodies():
    meta = 'metadata={op_name="jit(f)/acco/flat_unpack/x"}'
    hlo = "\n".join(
        [
            "%fused_computation.1 (p: bf16[4096,512]) -> bf16[512,4096] {",
            f"  ROOT %transpose.1 = bf16[512,4096]{{1,0}} transpose(%p), dimensions={{1,0}}, {meta}",
            "}",
            "ENTRY %main (flat: bf16[4194304]) -> bf16[512,4096] {",
            f"  %slice.1 = bf16[2097152]{{0}} slice(%flat), slice={{[0:2097152]}}, {meta}",
            f"  %bitcast.1 = bf16[4096,512]{{1,0}} bitcast(%slice.1), {meta}",
            f"  %small.1 = bf16[8,128]{{1,0}} reshape(%slice.1), {meta}",
            "  %other.1 = bf16[4096,512]{1,0} copy(%bitcast.1)",
            f"  ROOT %fusion.1 = bf16[512,4096]{{1,0}} fusion(%bitcast.1), kind=kLoop, calls=%fused_computation.1, {meta}",
            "}",
        ]
    )
    assert unpack_ops(hlo) == [
        ["slice", 2097152], ["bitcast", 2097152], ["transpose", 2097152], ["fusion", 2097152],
    ]


@pytest.mark.tpu_aot
@pytest.mark.parametrize("case", sorted(CASES))
def test_unpack_is_slices_bitcasts_and_one_pad(case):
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), case],
        capture_output=True, text=True, timeout=900, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    if "__skip__" in got:
        pytest.skip(f"v5e:2x2 cannot be described here: {got['__skip__']}")
    _, tolerated = CASES[case]
    retiled = {}
    for op, elements in got["ops"]:
        if op in RETILES:
            retiled[elements] = retiled.get(elements, 0) + 1
    assert retiled == tolerated, f"re-tiled under {SCOPE}: {got['ops']}"
    assert sum(op == "pad" for op, _ in got["ops"]) <= 1, got["ops"]
    # what is left is the forward slices, and they are there: the scope did not vanish
    assert any(op.startswith("slice") or op == "fusion" for op, _ in got["ops"]), got["ops"]
    assert got["bitcast_share"] > 0.99
    assert got["n_flat"] - got["n_row_major"] < 3e-4 * got["n_row_major"]


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    compile_accumulate(sys.argv[1])
