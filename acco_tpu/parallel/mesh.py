"""Runtime / mesh layer: the TPU-native equivalent of the reference's NCCL
bootstrap (`/root/reference/trainer_base.py:135-180`).

The reference reads SLURM env vars, derives MASTER_ADDR from the expanded
hostlist, and calls ``dist.init_process_group("nccl")``. On TPU the
substrate is `jax.distributed` (ICI within a slice, DCN across slices) and
collectives are emitted by XLA from mesh-annotated programs; this module:

- initializes `jax.distributed` from the environment — TPU metadata when
  available, else SLURM variables with the same hostlist/port derivation as
  the reference, else single-process;
- builds the device mesh (default: one ``dp`` axis over all devices — the
  reference's world group);
- exposes process/world info with the reference's naming (rank/world_size).
"""

from __future__ import annotations

import logging
import os
from typing import Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

log = logging.getLogger(__name__)

DATA_AXIS = "dp"
SEQ_AXIS = "sp"  # sequence/context-parallel axis (ring attention)
TENSOR_AXIS = "tp"  # tensor-parallel axis (Megatron head/ffn splits, parallel/tp.py)
PIPELINE_AXIS = "pp"  # pipeline-parallel axis (layer stages, parallel/pp.py)


def initialize_distributed(log=log) -> dict:
    """Initialize multi-process JAX if the environment calls for it.

    Returns {rank, world_size, n_nodes, id_run} — the fields the reference
    pulls from SLURM (`trainer_base.py:137-146`). Single-process (no SLURM,
    no JAX coordinator env) is a no-op with rank 0 / world 1.
    """
    if "SLURM_PROCID" in os.environ and int(os.environ.get("SLURM_NTASKS", "1")) > 1:
        from acco_tpu.utils.hostlist import expand_hostlist

        rank = int(os.environ["SLURM_PROCID"])
        world = int(os.environ["SLURM_NTASKS"])
        hosts = expand_hostlist(os.environ["SLURM_JOB_NODELIST"])
        # Same derivation as the reference: first host, fixed base port
        # (trainer_base.py:148-153). GPU-id offsetting doesn't apply on
        # TPU; ACCO_COORD_PORT overrides when 12346 is taken (shared
        # hosts, parallel CI).
        port = int(os.environ.get("ACCO_COORD_PORT", "12346"))
        coordinator = f"{hosts[0]}:{port}"
        jax.distributed.initialize(
            coordinator_address=coordinator, num_processes=world, process_id=rank
        )
        return {
            "rank": rank,
            "world_size": world,
            "n_nodes": len(hosts),
            "id_run": os.environ.get("SLURM_JOBID", "local"),
        }
    # A single TPU host can carry the worker variables too (one name in
    # TPU_WORKER_HOSTNAMES): one process drives all of its chips there,
    # and a rendezvous with nobody would only wait.
    tpu_hosts = [
        h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if h.strip()
    ]
    if "JAX_COORDINATOR_ADDRESS" in os.environ or (
        len(tpu_hosts) > 1 and "TPU_WORKER_ID" in os.environ
    ):
        # TPU pod slice: jax.distributed autodetects from TPU metadata.
        jax.distributed.initialize()
        return {
            "rank": jax.process_index(),
            "world_size": jax.process_count(),
            "n_nodes": jax.process_count(),
            "id_run": os.environ.get("TPU_NAME", "tpu"),
        }
    return {"rank": 0, "world_size": 1, "n_nodes": 1, "id_run": "local"}


def sharded_zeros(mesh: Mesh, spec, shape, dtype):
    """Zeros created directly under a NamedSharding (jit out_shardings) —
    no full-size transient on the default device, which matters for the
    [ns*Pp]-scale gradient buffers of large models."""
    from jax.sharding import NamedSharding

    import jax.numpy as jnp

    return jax.jit(
        lambda: jnp.zeros(shape, dtype),
        out_shardings=NamedSharding(mesh, spec),
    )()


def make_mesh(
    mesh_shape: Optional[Mapping[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the device mesh, topology-aware on TPU.

    Default: 1-D ``dp`` over all devices — the shape of the reference's
    world process group. ``mesh_shape`` (e.g. ``{"dp": 4, "tp": 2}``)
    orders axes outer-to-inner; put the most bandwidth-hungry axis last.

    On TPU the physical assignment is delegated to
    ``mesh_utils.create_device_mesh``, which reads chip coordinates so
    the inner axis lands on ICI neighbors — a row-major reshape does
    NOT guarantee that on a 2-D torus, and the async ring collectives'
    overlap win (parallel/ring_collectives.py) depends on neighbor
    hops. When ``jax.devices()`` spans multiple slices (multislice via
    DCN: device.slice_index differs), ``create_hybrid_device_mesh``
    places the ``dp`` axis across slices — gradient all-reduces ride
    DCN, model axes (tp/pp/sp) stay inside a slice on ICI, which is the
    README's scale-out guidance made mechanical. CPU/virtual meshes
    (tests) keep the deterministic row-major layout.
    """
    devices = list(devices if devices is not None else jax.devices())
    if not mesh_shape:
        mesh_shape = {DATA_AXIS: len(devices)}
    names = tuple(mesh_shape.keys())
    sizes = list(mesh_shape.values())
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(
            f"mesh_shape {dict(mesh_shape)} needs {total} devices, "
            f"have {len(devices)}"
        )
    return Mesh(_topology_grid(names, sizes, devices), names)


def _topology_grid(names, sizes, devices) -> np.ndarray:
    """Device grid for ``Mesh``: ICI/DCN-aware on TPU, row-major off it."""
    row_major = np.asarray(devices, dtype=object).reshape(sizes)
    if getattr(devices[0], "platform", None) != "tpu" or len(devices) == 1:
        return row_major
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    n_slices = 1 if None in slice_ids else len(slice_ids)
    if n_slices > 1:
        # Multislice: dp spans the DCN; every other axis must fit in a
        # slice. This is a user-facing placement contract, not a
        # best-effort optimization — misplacement errors out.
        shape = dict(zip(names, sizes))
        if shape.get(DATA_AXIS, 1) % n_slices:
            raise ValueError(
                f"multislice mesh over {n_slices} slices: the "
                f"'{DATA_AXIS}' axis ({shape.get(DATA_AXIS, 1)}) must be "
                f"divisible by the slice count — keep data parallelism "
                f"on DCN and model axes (tp/pp/sp) inside a slice"
            )
        from jax.experimental import mesh_utils

        dcn = [n_slices if n == DATA_AXIS else 1 for n in names]
        inner = [s // d for s, d in zip(sizes, dcn)]
        return mesh_utils.create_hybrid_device_mesh(
            inner, dcn, devices=devices
        )
    if sum(s > 1 for s in sizes) <= 1:
        # Effectively 1-D (the plain-dp flagship case): the collective
        # that rides this axis is the bidirectional ppermute RING
        # (ring_collectives.py), and create_device_mesh optimizes
        # generic all-reduce, not ring adjacency (counted on a v5e
        # 2x4: its 1-D order leaves 4 non-neighbor hops where a
        # perimeter cycle has 0). Use a Hamiltonian cycle on the chip
        # grid when one exists.
        ring = _ring_order(devices)
        if ring is not None:
            return np.asarray(ring, dtype=object).reshape(sizes)
    try:
        from jax.experimental import mesh_utils

        return mesh_utils.create_device_mesh(sizes, devices=devices)
    except Exception as exc:  # unusual shapes/counts: keep running
        log.warning(
            "mesh_utils.create_device_mesh failed for shape %s (%s); "
            "falling back to row-major device order — ring collectives "
            "may hop non-neighbor chips",
            sizes, exc,
        )
        return row_major


def _ring_order(devices):
    """Devices in a Hamiltonian-cycle order of the 2-D chip grid (every
    consecutive pair, wrap included, ICI neighbors), or None when no
    such cycle exists (odd x odd grids, 1-wide grids without wrap, 3-D
    coords, or a device set that isn't a full rectangle).

    Construction (R rows x C cols, C even; transposed when only R is
    even): serpentine through rows 1..R-1 column by column, return along
    row 0 — e.g. a v5e 2x4: (0,0) (1,0) (1,1) (0,1)->no — concretely
    [(1,0) (1,1) .. serpentine .. (1,C-1)] + [(0,C-1) .. (0,0)]."""
    coords = []
    for d in devices:
        c = getattr(d, "coords", None)
        if c is None:
            return None
        coords.append(tuple(c))
    arr = np.array(coords)
    if arr.shape[1] == 3:
        if (arr[:, 2] != arr[0, 2]).any():
            return None  # true 3-D topology: defer to mesh_utils
        arr = arr[:, :2]
    lo = arr.min(axis=0)
    arr = arr - lo
    R, C = arr.max(axis=0) + 1
    if R * C != len(devices) or len(set(map(tuple, arr))) != len(devices):
        return None  # not a full rectangle (subset slice)
    transpose = C % 2 == 1
    if transpose:
        arr = arr[:, ::-1]
        R, C = C, R
    if C % 2 == 1 or R < 2:
        return None  # odd x odd has no cycle; 1-wide has no wrapless cycle
    by_coord = {tuple(a): d for a, d in zip(arr, devices)}
    cycle = []
    for y in range(C):
        xs = range(1, R) if y % 2 == 0 else range(R - 1, 0, -1)
        cycle += [(x, y) for x in xs]
    cycle += [(0, y) for y in range(C - 1, -1, -1)]
    return [by_coord[c] for c in cycle]


def ici_ring_gaps(mesh: Mesh, axis: str):
    """Non-neighbor hops in ``axis``'s rings: ``[(id_a, id_b, dist), ...]``.

    For each consecutive (wrapping) device pair along ``axis``, the
    plain Manhattan distance between chip coords. Deliberately NO
    wraparound credit: small v5e slices are meshes, not tori, and a
    checker that assumes wrap links certifies hops that physically
    route through intermediate chips — on a real torus slice a genuine
    wrap link shows up as a conservative false gap instead, which is
    the safe direction for a canary. (_ring_order's cycles use no wrap
    links, so the shipped meshes score gapless under this metric.)
    Empty list = every hop of the ring collective rides a direct ICI
    link. None = devices expose no coords (CPU/virtual meshes) —
    nothing to check."""
    devs = mesh.devices
    if not hasattr(devs.flat[0], "coords"):
        return None
    ax = mesh.axis_names.index(axis)
    moved = np.moveaxis(devs, ax, -1)
    n = moved.shape[-1]
    gaps = []
    for ring in moved.reshape(-1, n):
        if n < 2:
            continue
        for i in range(n):
            a, b = ring[i], ring[(i + 1) % n]
            if n == 2 and i == 1:
                break  # a 2-ring has one link, not two
            d = sum(abs(ca - cb) for ca, cb in zip(a.coords, b.coords))
            if d > 1:
                gaps.append((a.id, b.id, int(d)))
    return gaps
