"""GPT-Neo's operations and bytes, from shapes alone.

The yardstick's own count (``acco_tpu/utils/flops.py`` counts the full [L, L]
block for causal and for window layers, which overstates MFU now that the
kernels skip that work). Besides the conventions of ``harness/flops.py``:

* Attention is counted causally: query ``i`` of a global layer reads ``i+1``
  keys, of a window-``W`` layer ``min(i+1, W)``. QK^T and PV are ``2 x keys
  x head_dim`` FLOPs each per query and head.
* The lm-head is counted on all ``L`` positions (the program computes them;
  only the loss drops the last).
* Embedding lookups, layer norms, GELU, softmax and the optimizer are not
  matmuls and are not counted.

``cfg`` is the configuration's ``model.json`` as a dict.
"""

from __future__ import annotations

from benchmark.harness.flops import mean_keys_per_query


def layer_windows(cfg: dict) -> list[int]:
    return [
        0 if kind == "global" else int(cfg["window_size"])
        for kind in cfg["attention_layers"]
    ]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    D = cfg["hidden_size"]
    F = cfg.get("intermediate_size") or 4 * D
    weights = 3 * D * D + D * D + 2 * D * F  # qkv, out, fc, proj
    fwd = 0.0
    for window in layer_windows(cfg):
        fwd += 2 * weights + 4 * D * mean_keys_per_query(seq_len, window)
    fwd += 2 * D * cfg["vocab_size"]  # tied lm-head
    return 3.0 * fwd


def attention_kernel_work(
    cfg: dict, seq_len: int, batch: int, kinds: set[str], itemsize: int = 2
) -> tuple[float, float]:
    """``(FLOPs, bytes)`` of one round's attention, forward and backward, in
    the layers whose kind ('global' / 'local') is in ``kinds``: the layers a
    kernel computed. FLOPs: QK^T and PV forward, their four gradient matmuls
    backward (12 x keys x head_dim per query and head; the scores a kernel
    recomputes are not counted). Bytes: the least traffic, each tensor once:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv (the per-row log-sum-exp is 1/head_dim of a tensor and
    left out)."""
    D = cfg["hidden_size"]
    flops = bytes_ = 0.0
    for kind, window in zip(cfg["attention_layers"], layer_windows(cfg)):
        if kind not in kinds:
            continue
        flops += 12 * D * mean_keys_per_query(seq_len, window) * batch * seq_len
        bytes_ += 12 * batch * seq_len * D * itemsize
    return flops, bytes_
