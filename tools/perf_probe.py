"""Decompose the flagship step's cost on the attached chip.

Times, in isolation: fwd loss, fwd+bwd, the ZeRO-1 optimizer update, the
lm-head+CE tail, one transformer block, and the embed gather — so
bench.py regressions can be attributed to a component instead of A/B-ing
whole-step variants blind. Run on the real TPU:
``python tools/perf_probe.py``.
"""

from __future__ import annotations

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, warmup=3, iters=10):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main():
    from acco_tpu.models.llama import LlamaConfig, LlamaModel
    from acco_tpu.ops.losses import causal_lm_loss

    B, L = 8, 1024
    cfg = LlamaConfig(max_position_embeddings=max(L, 1024))
    model = LlamaModel(cfg, param_dtype=jnp.bfloat16, remat="dots")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, L)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, L)), jnp.int32)

    def loss_fn(p):
        logits = model.apply(p, ids, None)
        return causal_lm_loss(logits, labels)

    fwd = jax.jit(loss_fn)
    print(f"fwd loss            : {timeit(fwd, params):8.2f} ms")
    vg = jax.jit(jax.value_and_grad(loss_fn))
    print(f"fwd+bwd             : {timeit(vg, params):8.2f} ms")

    # lm-head + CE tail alone (bf16 matmul -> f32 logits -> CE), fwd+bwd
    h = jnp.asarray(rng.standard_normal((B, L, cfg.hidden_size)), jnp.bfloat16)
    w = jnp.asarray(
        rng.standard_normal((cfg.hidden_size, cfg.vocab_size)) * 0.02, jnp.bfloat16
    )

    def head_loss(h, w):
        logits = jnp.einsum("bld,dv->blv", h, w, preferred_element_type=jnp.float32)
        return causal_lm_loss(logits, labels)

    head = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1)))
    print(f"lm-head+CE f+b      : {timeit(head, h, w):8.2f} ms")

    # one transformer block (xla attention path), fwd+bwd
    layer0 = jax.tree.map(lambda a: a[0], params["layers"])
    from acco_tpu.models.layers import (
        apply_rope, merge_heads, rms_norm, rope_angles, split_heads,
    )
    from acco_tpu.ops.attention import attention_mask_bias, dot_product_attention

    cos, sin = rope_angles(L, cfg.head_dim, cfg.rope_theta, 0)
    bias = attention_mask_bias(L, 0, None)

    def block_loss(layer, x):
        hh = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        q = split_heads(hh @ layer["wq"], cfg.num_heads)
        k = split_heads(hh @ layer["wk"], cfg.num_kv_heads)
        v = split_heads(hh @ layer["wv"], cfg.num_kv_heads)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        ctx = dot_product_attention(q, k, v, bias)
        x = x + merge_heads(ctx) @ layer["wo"]
        hh = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
        mlp = (jax.nn.silu(hh @ layer["w_gate"]) * (hh @ layer["w_up"])) @ layer["w_down"]
        return (x + mlp).astype(jnp.float32).sum()

    bfn = jax.jit(jax.value_and_grad(block_loss, argnums=(0, 1)))
    ms = timeit(bfn, layer0, h)
    print(f"1 block f+b         : {ms:8.2f} ms  (x{cfg.num_layers} = {ms * cfg.num_layers:.1f})")

    # embed table: fwd gather + bwd scatter-add
    def emb_loss(e):
        return e[ids].astype(jnp.float32).sum()

    efn = jax.jit(jax.value_and_grad(emb_loss))
    print(f"embed gather f+b    : {timeit(efn, params['wte']):8.2f} ms")

    # optimizer round alone: zero1 update on the flat vector (inside the
    # same shard_map environment the train step uses, so the collectives
    # have their mesh axes bound)
    from jax.sharding import PartitionSpec as P

    from acco_tpu.ops.schedules import get_schedule
    from acco_tpu.parallel.acco import AccoTrainStep
    from acco_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from acco_tpu.parallel.zero1 import UpdateHealth, zero1_update_shard

    mesh = make_mesh({DATA_AXIS: jax.device_count()})
    step = AccoTrainStep(
        model, mesh, get_schedule("cosine", 6e-4, 1000, 50000),
        weight_decay=0.1, beta1=0.9, beta2=0.95,
    )
    state = step.init_state(params)
    shard = P(step.shard_axes)
    opt_specs = jax.tree.map(lambda _: shard, state.zero1.opt)
    opt_specs = opt_specs._replace(count=P())

    # the guarded commit as a DDP / DPU / odd ACCO round runs it: the
    # verdict pass, then the one write pass
    def opt_only(pending, opt, flat):
        return zero1_update_shard(
            pending, opt, jnp.float32(8.0), jnp.float32(6e-4), step.geom,
            0.1, 0.9, 0.95, 1e-8, step.shard_axes, jnp.bfloat16,
            with_health=True, old_flat=flat,
        )

    ofn = jax.jit(
        jax.shard_map(
            opt_only,
            mesh=mesh,
            in_specs=(shard, opt_specs, P()),
            out_specs=(P(), opt_specs, UpdateHealth(P(), P())),
            check_vma=False,
        )
    )
    ms = timeit(ofn, state.pending_grads, state.zero1.opt, state.flat_params)
    print(f"zero1 guarded commit: {ms:8.2f} ms")


if __name__ == "__main__":
    main()
