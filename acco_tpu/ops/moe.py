"""Sparse-expert MLP: float32 router, dropless sort-based dispatch, grouped
matmuls, weighted combine. Static shapes throughout.

One block's expert half, for ``T`` tokens, ``E`` experts and ``k`` experts
per token::

    z      = h @ router.T                     [T, E] float32
    p      = softmax(z);  (w, e) = top_k(p, k)
    y[t]   = sum_j w[t, j] * swiglu(h[t]; expert e[t, j])

**Dropless**: there is no capacity factor and no padding. The ``T * k``
(token, expert) assignments are sorted by expert (stable, so an expert's
rows keep token order), the tokens are gathered into that order, and three
grouped matmuls run over ``group_sizes[E]``, the number of rows each expert
got. Every assignment is computed whatever the imbalance: all tokens on one
expert is one group of ``T * k`` rows and 63 empty ones.

**No scatter**: the sorted order is a permutation of the ``T * k``
assignments, so going into expert order and coming back are both gathers
(:func:`_permute` carries the inverse permutation for its transpose); the
``k`` copies of a token are a broadcast one way and a sum over ``k`` the
other. XLA cannot know that a gather's indices are a permutation and would
transpose it to a serialised scatter-add.

The grouped matmul, bf16 operands, float32 accumulation, output in the
activation dtype, as the dense MLP's matmuls: the stock Pallas
``megablox.gmm`` kernel (the way ops/attention.py uses the stock flash
kernel), compiled on the TPU and run in Pallas's interpret mode elsewhere (the
CPU of the tests and the virtual meshes), so there is one implementation and
the tests' float32 comparison with the plain reference runs its tiling and its
group boundaries. ``jax.lax.ragged_dot`` was measured beside it on a v5e at
the published widths, 32,768 rows over 64 groups (my chip runs, PR 25, in
PERF.md §6) and not
kept: forward and backward of the three matmuls take 32.1 ms through
``ragged_dot`` (its transposes are the slow part: 6.7 ms forward) and 14.3 ms
through ``gmm`` at tiles of (256, 1024, 1024), against 146.6 at the kernel's
default 128-tiles.
Their outputs carry ``checkpoint_name``s (``moe_gate``, ``moe_up``,
``moe_down``) which the ``dots`` remat policy saves (models/layers.py
wrap_remat): a grouped matmul is a matmul, but not a ``dot_general`` the
stock dots policy would recognise.

The three device scopes (telemetry/trace.py EXPERT_DEVICE_SCOPES): ``model/moe_router``
(router matmul, softmax, top-k, the auxiliary statistics),
``model/moe_dispatch`` (sort, both permutations, weighting and the sum over
``k``), ``model/moe_experts`` (grouped matmuls and SwiGLU).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


class RouterStats(NamedTuple):
    """Per-sequence router statistics of one layer, each ``[B]`` float32.

    Per sequence and not per microbatch: the objective must not depend on
    how the schedule divides sequences among half-rounds, chips and
    microbatches (parallel/common.py averages microbatch means), and a loss
    that is a mean over sequences can be checked one sequence at a time.
    """

    lb_loss: jax.Array  # E * sum_i f_i * P_i; 1.0 under a uniform router
    z_loss: jax.Array  # mean_t logsumexp(z_t) ** 2
    max_load: jax.Array  # E * max_i f_i; 1.0 = balanced, E / k = one expert set takes all


def route(
    h: jax.Array,  # [B, L, D] activation dtype
    router: jax.Array,  # [E, D]
    top_k: int,
    norm_topk_prob: bool,
    mask: Optional[jax.Array] = None,  # [B, L] 1 = real token
) -> tuple[jax.Array, jax.Array, RouterStats]:
    """``(gates [B, L, k] float32, experts [B, L, k] int32, stats)``.

    Logits and softmax are float32: the operands are the (bf16-valued)
    activations and working weights, whose products are exact in the MXU's
    float32 accumulator. ``f_i`` (the share of the sequence's assignments
    that expert ``i`` got) carries no gradient; ``P_i`` and the z-term do.
    Padding tokens (``mask`` 0) are routed like any other, since shapes are
    static, but count in no statistic.
    """
    n_experts = router.shape[0]
    logits = jnp.einsum("bld,ed->ble", h, router, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        gates = gates / gates.sum(axis=-1, keepdims=True)

    weight = (
        jnp.ones(h.shape[:2], jnp.float32) if mask is None else mask.astype(jnp.float32)
    )
    n_tokens = jnp.maximum(weight.sum(axis=1), 1.0)  # [B]
    # assignments per expert and sequence, as a sum of one-hots: [B, E]
    chosen = jax.nn.one_hot(experts, n_experts, dtype=jnp.float32).sum(axis=2)
    load = (chosen * weight[..., None]).sum(axis=1) / (n_tokens * top_k)[:, None]
    load = jax.lax.stop_gradient(load)
    mean_prob = (probs * weight[..., None]).sum(axis=1) / n_tokens[:, None]
    lse = jax.nn.logsumexp(logits, axis=-1)  # [B, L]
    stats = RouterStats(
        lb_loss=n_experts * (load * mean_prob).sum(axis=-1),
        z_loss=(jnp.square(lse) * weight).sum(axis=1) / n_tokens,
        max_load=n_experts * load.max(axis=-1),
    )
    return gates, experts.astype(jnp.int32), stats


@jax.custom_vjp
def _permute(x: jax.Array, perm: jax.Array, inverse: jax.Array) -> jax.Array:
    """``x[perm]`` over rows, where ``inverse`` is ``perm``'s inverse
    permutation: the transpose is the gather ``g[inverse]``, not a scatter."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


GMM_TILES = (256, 1024, 1024)  # rows, contraction, columns: the best of six tried on the v5e (module docstring)


def grouped_matmul(
    rows: jax.Array,  # [M, K] rows sorted by group
    weights: jax.Array,  # [G, K, N]
    group_sizes: jax.Array,  # [G] int32, sums to M
    platform: Optional[str] = None,  # pin 'tpu' for AOT proof builders
) -> jax.Array:  # [M, N] in rows.dtype
    """Row ``r`` of group ``g`` times ``weights[g]``; float32 accumulation.
    One implementation everywhere: the stock Pallas ``megablox.gmm`` and its
    custom VJP (``gmm`` on the transposed weights and ``tgmm``), compiled for
    the TPU and interpreted elsewhere, so the CPU's tests run the tiling and
    the group boundaries the chip runs. The tiles are clipped to the
    operands; the kernel wants the rows a multiple of their tile."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = rows.shape
    tiles = (math.gcd(m, GMM_TILES[0]), min(GMM_TILES[1], k), min(GMM_TILES[2], weights.shape[-1]))
    interpret = (platform or jax.devices()[0].platform) != "tpu"
    return gmm(
        rows, weights, group_sizes, preferred_element_type=rows.dtype, tiling=tiles,
        interpret=interpret,
    )


def dropless_experts(
    h: jax.Array,  # [T, D] activation dtype
    gates: jax.Array,  # [T, k] float32
    experts: jax.Array,  # [T, k] int32
    w_gate: jax.Array,  # [E, D, F]
    w_up: jax.Array,  # [E, D, F]
    w_down: jax.Array,  # [E, F, D]
    platform: Optional[str] = None,  # as grouped_matmul's
) -> jax.Array:  # [T, D] in h.dtype
    """``sum_j gates[:, j] * swiglu(h; expert experts[:, j])``, every
    assignment computed."""
    n_tokens, top_k = experts.shape
    n_experts = w_gate.shape[0]
    with jax.named_scope("model/moe_dispatch"):
        flat = experts.reshape(-1)  # assignment a = t * k + j
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)  # sorted position -> a
        inverse = jnp.argsort(order).astype(jnp.int32)  # a -> sorted position
        group_sizes = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
        copies = jnp.broadcast_to(h[:, None, :], (n_tokens, top_k, h.shape[-1]))
        rows = _permute(copies.reshape(n_tokens * top_k, -1), order, inverse)
    with jax.named_scope("model/moe_experts"):
        gate = checkpoint_name(grouped_matmul(rows, w_gate, group_sizes, platform), "moe_gate")
        up = checkpoint_name(grouped_matmul(rows, w_up, group_sizes, platform), "moe_up")
        out = grouped_matmul(jax.nn.silu(gate) * up, w_down, group_sizes, platform)
        out = checkpoint_name(out, "moe_down")
    with jax.named_scope("model/moe_dispatch"):
        back = _permute(out, inverse, order).reshape(n_tokens, top_k, -1)
        # float32 gates and sum, as the dense path's residual add is not
        return (back.astype(jnp.float32) * gates[..., None]).sum(axis=1).astype(h.dtype)
