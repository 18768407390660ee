"""acco_tpu — a TPU-native training framework with the capabilities of the
ACCO reference (edouardoyallon/acco, arXiv 2406.02613).

Three training modes over a `jax.sharding.Mesh`:

- ``acco`` — communication-overlapped, ZeRO-1-sharded AdamW data-parallel
  training. The reference drives the overlap with CUDA streams plus a host
  communication thread (`/root/reference/trainer_decoupled.py:431-598`); here
  the whole round is one compiled XLA program in which the collective branch
  has no data dependency on the compute branch, so XLA's async collectives
  overlap them natively.
- ``dpu`` — delayed parameter update (one-round-stale gradients), the
  sequential arrangement of the same kernels
  (`/root/reference/trainer_decoupled.py:605-730`).
- ``ddp`` — the synchronous baseline: grad psum + ZeRO-1 sharded AdamW
  (capability parity with DDP + ZeroRedundancyOptimizer,
  `/root/reference/trainer_decoupled.py:732-833`).
"""

__version__ = "0.1.0"

from acco_tpu.configuration import ConfigNode, compose_config  # noqa: F401
