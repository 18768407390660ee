"""Topology-aware mesh construction (parallel/mesh.py, VERDICT r4 #8).

The row-major reshape the framework used through round 4 does not
guarantee ICI-neighbor rings on a 2-D torus; make_mesh now delegates to
mesh_utils (and a bespoke Hamiltonian-cycle order for the 1-D ring
case). CPU/virtual meshes keep the deterministic row-major layout every
other test relies on, so these tests drive the TPU paths with fake
coordinate-bearing devices and (under the tpu_aot marker) real AOT
topology descriptors.
"""

import numpy as np
import pytest

from acco_tpu.parallel.mesh import (
    DATA_AXIS,
    _ring_order,
    ici_ring_gaps,
    make_mesh,
)


class FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def __init__(self, i, x, y, slice_index=None, z=0):
        self.id = i
        self.coords = [x, y, z]
        self.slice_index = slice_index
        self.process_index = slice_index or 0

    def __repr__(self):
        return f"FakeTpu({self.id})"


def grid_devices(R, C, slice_index=None, base=0):
    return [
        FakeTpu(base + x * C + y, x, y, slice_index)
        for x in range(R)
        for y in range(C)
    ]


def test_ring_order_is_hamiltonian_cycle():
    for R, C in ((2, 4), (4, 4), (2, 2), (4, 2), (3, 4), (8, 4)):
        ds = grid_devices(R, C)
        ring = _ring_order(ds)
        assert ring is not None, (R, C)
        assert sorted(d.id for d in ring) == sorted(d.id for d in ds)
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(ring, dtype=object).reshape(len(ds)), ("dp",))
        assert ici_ring_gaps(mesh, "dp") == [], (R, C)


def test_ring_order_refuses_impossible_grids():
    # odd x odd: no Hamiltonian cycle on a bipartite grid
    assert _ring_order(grid_devices(3, 3)) is None
    # 1-wide: no cycle without wraparound links
    assert _ring_order(grid_devices(1, 4)) is None
    # subset of a rectangle (hole): refuse rather than guess
    assert _ring_order(grid_devices(2, 4)[:-1]) is None
    # no coords (cpu-like)
    assert _ring_order([object()]) is None


def test_make_mesh_1d_tpu_ring_has_no_gaps():
    ds = grid_devices(2, 4)
    mesh = make_mesh({DATA_AXIS: 8}, ds)
    assert ici_ring_gaps(mesh, DATA_AXIS) == []


def test_make_mesh_cpu_stays_row_major(eight_devices):
    import jax

    ds = jax.devices()
    mesh = make_mesh({DATA_AXIS: 4, "tp": 2}, ds)
    assert [d.id for d in mesh.devices.flat] == [d.id for d in ds]
    assert ici_ring_gaps(mesh, DATA_AXIS) is None  # no coords: no claim


def test_make_mesh_multislice_dp_spans_slices():
    ds = grid_devices(2, 2, slice_index=0) + grid_devices(
        2, 2, slice_index=1, base=4
    )
    mesh = make_mesh({DATA_AXIS: 4, "tp": 2}, ds)
    # dp index pairs (0,1) then (2,3) must land on slice 0 then slice 1:
    # gradient all-reduce crosses DCN, tp stays inside a slice
    slices = np.array(
        [[d.slice_index for d in row] for row in mesh.devices]
    )
    assert (slices == np.array([[0, 0], [0, 0], [1, 1], [1, 1]])).all()


def test_make_mesh_multislice_requires_divisible_dp():
    ds = grid_devices(2, 2, slice_index=0) + grid_devices(
        2, 2, slice_index=1, base=4
    )
    with pytest.raises(ValueError, match="divisible by the slice count"):
        make_mesh({"tp": 8}, ds)  # no dp axis at all over 2 slices


_AOT_RING_SCRIPT = r"""
import sys
sys.path.insert(0, {repo!r})
from jax.experimental import topologies
from acco_tpu.parallel.mesh import DATA_AXIS, ici_ring_gaps, make_mesh

for name, n in (("v5e:2x4", 8), ("v5e:4x4", 16)):
    ds = list(
        topologies.get_topology_desc(
            platform="tpu", topology_name=name
        ).devices
    )
    mesh = make_mesh({{DATA_AXIS: n}}, ds)
    gaps = ici_ring_gaps(mesh, DATA_AXIS)
    assert gaps == [], (name, gaps)
print("RING_OK")
"""


@pytest.mark.tpu_aot
def test_make_mesh_aot_topology_ring():
    """Real v5e topology descriptors (no chips needed): the 1-D dp mesh
    is a gapless ICI ring on 2x4 and 4x4. Runs in a SUBPROCESS like
    every other tpu_aot test: acquiring libtpu inside the pytest
    process would hold /tmp/libtpu_lockfile for the rest of the session
    and starve the other canaries' subprocesses."""
    import os
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [_sys.executable, "-c", _AOT_RING_SCRIPT.format(repo=repo)],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo,
    )
    assert proc.returncode == 0 and "RING_OK" in proc.stdout, (
        proc.stderr[-3000:]
    )
