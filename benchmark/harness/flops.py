"""Operations and bytes the algorithm needs, from shapes alone: the part that
holds for any model. What one family of models counts sits in a module of its
own, which the configuration's ``config.json`` names by its path under
``flops`` (GPT-Neo: ``benchmark/reference/gpt_neo_flops.py``;
``manifest.family_module`` loads it). Such a module provides

* ``train_flops_per_token(cfg, seq_len)``: model FLOPs of one forward and one
  backward pass per token, and
* ``attention_kernel_work(cfg, seq_len, batch, kinds)``: ``(FLOPs, bytes)`` of
  one round's attention in the layers of the given kinds, where the family
  has attention kernels to hold against a roofline.

Conventions for every family: model FLOPs are matmul FLOPs, backward = 2 x
forward. Recomputation (``remat``, a kernel that rebuilds its scores in the
backward pass) is NOT counted: it makes the hardware do more, not the model
bigger. Attention is counted as masked: a query counts the keys it may read.
"""

from __future__ import annotations


def mean_keys_per_query(seq_len: int, window: int) -> float:
    """Mean number of keys a query attends to under a causal mask, global
    (``window`` 0) or sliding window."""
    if window <= 0 or window >= seq_len:
        return (seq_len + 1) / 2
    # rows 0..W-1 see 1..W keys, the other L-W rows see W
    return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len


def mfu_pct(tokens_per_s_per_chip: float, flops_per_token: float, peaks: dict) -> float:
    return 100.0 * tokens_per_s_per_chip * flops_per_token / peaks["bf16_flops_per_s"]


def roofline(flops: float, bytes_: float, peaks: dict) -> tuple[float, str]:
    """Least seconds the chip could take, and which bound binds."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
