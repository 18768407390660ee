"""OLMoE's operations and bytes, from shapes alone.

Besides the conventions of ``harness/flops.py`` (matmul FLOPs, backward = 2 x
forward, no recomputation, attention counted as masked):

* A token multiplies the ``num_experts_per_tok`` experts it was routed to and
  no other: the experts' FLOPs are those of 8 SwiGLU MLPs of width 1024, not
  of 64. The router's own matmul ([hidden, num_experts]) is counted.
* Attention is causal and global in every layer: query ``i`` reads ``i + 1`` keys.
* The head is untied and counted once, on all ``L`` positions.
* Embedding lookups, norms, RoPE, softmax, top-k, the sort and the
  permutations of the dispatch, and the optimizer are not matmuls and are not
  counted.

At the benchmark's depth 1 and L = 4096 that is 1.072 GFLOP a token, of which
the head is 57.7%, the experts 28.2%, the attention projections 9.4% and the
scores 4.7%; at the published depth 16 the head is 7.8% of 7.9 GFLOP.

``cfg`` is the configuration's ``model.json`` as a dict.
"""

from __future__ import annotations

from benchmark.harness.flops import mean_keys_per_query


def depth_of(cfg: dict) -> int:
    """``num_layers`` where the file has it (the depth as run), else the
    published ``num_hidden_layers``."""
    return int(cfg.get("num_layers", cfg.get("num_hidden_layers")))


def expert_weights_per_token(cfg: dict) -> int:
    """Expert parameters one token is multiplied by in one layer."""
    return cfg["num_experts_per_tok"] * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    D = cfg["hidden_size"]
    layer = (
        2 * 4 * D * D  # q, k, v, o (16 heads x 128 = hidden: MHA)
        + 4 * D * mean_keys_per_query(seq_len, 0)  # QK^T and PV
        + 2 * D * cfg["num_experts"]  # router
        + 2 * expert_weights_per_token(cfg)
    )
    fwd = depth_of(cfg) * layer + 2 * D * cfg["vocab_size"]
    return 3.0 * fwd


def attention_kernel_work(
    cfg: dict, seq_len: int, batch: int, kinds: set[str], itemsize: int = 2
) -> tuple[float, float]:
    """``(FLOPs, bytes)`` of one round's attention, forward and backward, as
    ``gpt_neo_flops.attention_kernel_work`` counts them; every layer of this
    family is 'global'."""
    if "global" not in kinds:
        return 0.0, 0.0
    D, n = cfg["hidden_size"], depth_of(cfg)
    flops = n * 12 * D * mean_keys_per_query(seq_len, 0) * batch * seq_len
    return flops, n * 12 * batch * seq_len * D * itemsize


def expert_matmul_work(
    cfg: dict, seq_len: int, batch: int, itemsize: int = 2
) -> tuple[float, float]:
    """``(FLOPs, bytes)`` of one round's grouped matmuls, forward and backward,
    over all layers. ``R = batch x seq_len x num_experts_per_tok`` rows pass
    through three matmuls (gate and up ``[hidden, width]``, down ``[width,
    hidden]``) forward, and each has two gradient matmuls backward: nine in
    all. FLOPs: ``3 x 2 x R x 3 x hidden x width``. Bytes, the least traffic:
    every matmul reads its two operands and writes its result once, so each
    expert's weights move once a pass (read forward, read for the data
    gradient, written as the weight gradient) and each ``[R, hidden]`` or
    ``[R, width]`` activation once per matmul it enters or leaves:
    ``9 x (R x (hidden + width) + num_experts x hidden x width)`` elements."""
    D, F, E = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    rows = batch * seq_len * cfg["num_experts_per_tok"]
    n = depth_of(cfg)
    flops = n * 3.0 * 2 * rows * 3 * D * F
    return flops, n * 9.0 * (rows * (D + F) + E * D * F) * itemsize
