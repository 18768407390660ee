"""Plain float32 OLMoE: forward pass, objective and gradients.

A straightforward ``jax.numpy`` transcription of the published architecture
(allenai/OLMoE-1B-7B, arXiv:2409.02060, as Hugging Face's ``OlmoeForCausalLM``
implements the released ``config.json``), independent of ``acco_tpu``: a Python
loop over the layers, a plain loop over ALL the experts for every token
(``lax.scan`` over the expert axis, its body under ``jax.checkpoint`` so that a
``[1, 4096]`` row's gradients fit one chip beside the weights), explicit masks,
no sort, no dispatch, no grouped matmul, no kernel, no mixed precision. It
shares only the parameter layout with the program (the pytree ``LlamaModel.init`` returns for
``model_type: "olmoe"``), because the comparison is on the same seeded weights.

Per layer (pre-norm residual blocks; ``T`` tokens, ``E`` experts, ``k`` a token)::

    h = rms_norm(x; attn_norm)
    q = rms_norm(h wq; q_norm)        over ALL hidden features, before the heads split
    k = rms_norm(h wk; k_norm);  v = h wv
    x = x + softmax(causal(rope(q) rope(k)^T / sqrt(head_dim))) v wo
    h = rms_norm(x; mlp_norm)
    z = h router^T                    [T, E]
    p = softmax(z);  chosen = the k largest of p per token;  g = p where chosen, else 0
    x = x + sum_e g[:, e] * (silu(h w_gate[e]) * (h w_up[e])) w_down[e]

``g`` is NOT renormalised (``norm_topk_prob: false`` as published; true divides
it by its sum per token). Every expert is applied to every token and weighted
by ``g``, which is 0 where the expert was not chosen: 8 times the program's
work at 8 of 64, and no dispatch to get wrong. The ``k`` largest are found by
``k`` rounds of arg-max (ties go to the lower index, as ``lax.top_k``'s do).

The objective, with ``f_i`` the share of a sequence's ``T x k`` assignments that
went to expert ``i`` (a count: no gradient) and ``P_i = mean_t p[t, i]``::

    loss = CE + router_aux_loss_coef * mean_layers,sequences( E * sum_i f_i P_i )
              + router_z_loss_coef   * mean_layers,sequences( mean_t logsumexp(z_t)^2 )

Departures from the published code, each on purpose and each listed under
``assumed`` in the configuration's ``config.json``:

* The router statistics are taken per SEQUENCE and averaged; the published
  code takes them over all tokens of a device's microbatch. The program's
  objective must not depend on how a schedule divides sequences among
  half-rounds, chips and microbatches, and a loss that is a mean over
  sequences can be checked one sequence at a time (``harness/refcheck.py``).
* The auxiliary terms are means over the layers (HF sums the load-balancing
  term over all layers' tokens at once, which is the same mean; the z-loss
  and its coefficient are the paper's, HF's model class has none).
* RoPE is the half-rotation form HF uses for this model; no dropout.

On a TPU a float32 matmul runs in reduced precision unless told otherwise, so
every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """``x`` [B, H, L, Dh]: rotate the pairs ``(x[i], x[i + Dh/2])`` by
    ``position * theta ** (-2 i / Dh)``."""
    L, Dh = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., : Dh // 2], x[..., Dh // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def depth_of(cfg: dict) -> int:
    """``num_layers`` where the file has it (the depth as run), else the
    published ``num_hidden_layers``."""
    return int(cfg.get("num_layers", cfg.get("num_hidden_layers")))


def chosen_experts(p, k: int):
    """``[..., E]`` bool: the ``k`` largest of ``p`` along the last axis."""
    chosen = jnp.zeros(p.shape, bool)
    rest = p
    for _ in range(k):
        pick = jax.nn.one_hot(jnp.argmax(rest, axis=-1), p.shape[-1], dtype=bool)
        chosen = chosen | pick
        rest = jnp.where(pick, -jnp.inf, rest)
    return chosen


def forward(params: dict, input_ids, cfg: dict, matmul=jnp.matmul):
    """``(logits [B, L, V] float32, lb [layers, B], z [layers, B])``: the
    logits and each layer's per-sequence load-balancing and z terms. ``cfg``
    is the configuration's JSON as a dict; ``params`` the program's pytree,
    any float dtype."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    B, L = input_ids.shape
    H = cfg["num_attention_heads"]
    D = cfg["hidden_size"]
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    eps = cfg.get("rms_norm_eps", 1e-5)
    theta = float(cfg.get("rope_theta", 10000.0))
    layers = p["layers"]
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    x = p["wte"][input_ids]
    lb_terms, z_terms = [], []
    for n in range(depth_of(cfg)):
        h = _rms_norm(x, layers["attn_norm"][n], eps)
        q, kk, v = (matmul(h, layers[w][n]) for w in ("wq", "wk", "wv"))
        if cfg.get("qk_norm", True):
            q = _rms_norm(q, layers["q_norm"][n], eps)
            kk = _rms_norm(kk, layers["k_norm"][n], eps)

        def heads(t):  # [B, L, D] -> [B, H, L, D/H]
            return t.reshape(B, L, H, D // H).transpose(0, 2, 1, 3)

        q, kk, v = _rope(heads(q), theta), _rope(heads(kk), theta), heads(v)
        scores = matmul(q, kk.transpose(0, 1, 3, 2)) / math.sqrt(D // H)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        attn = matmul(jax.nn.softmax(scores, axis=-1), v)
        x = x + matmul(attn.transpose(0, 2, 1, 3).reshape(B, L, D), layers["wo"][n])

        h = _rms_norm(x, layers["mlp_norm"][n], eps)
        z = matmul(h, layers["router"][n].T)  # router is [E, D], as HF's gate.weight: [B, L, E]
        prob = jax.nn.softmax(z, axis=-1)
        chosen = chosen_experts(prob, k)
        gate = jnp.where(chosen, prob, 0.0)
        if cfg.get("norm_topk_prob", False):
            gate = gate / gate.sum(axis=-1, keepdims=True)

        # every expert on every token, one after another: a scan over the
        # expert axis (one compiled body: a Python loop over 64 experts costs
        # the TPU's compiler 200 s), its body checkpointed so that the backward
        # pass holds one expert's [B, L, width] intermediates at a time
        @jax.checkpoint
        def add_expert(mlp, expert):
            w_gate, w_up, w_down, g = expert  # g: this expert's gate, [B, L]
            out = matmul(jax.nn.silu(matmul(h, w_gate)) * matmul(h, w_up), w_down)
            return mlp + g[..., None] * out, None

        experts = (layers["w_gate"][n], layers["w_up"][n], layers["w_down"][n],
                   jnp.moveaxis(gate, -1, 0))
        mlp, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), experts)
        x = x + mlp

        share = chosen.astype(jnp.float32).sum(axis=1) / (L * k)  # f_i: [B, E], a count
        lb_terms.append(E * (share * prob.mean(axis=1)).sum(axis=-1))
        z_terms.append((jax.nn.logsumexp(z, axis=-1) ** 2).mean(axis=1))
    x = _rms_norm(x, p["final_norm"], eps)
    head = p["wte"].T if cfg.get("tie_word_embeddings", False) else p["lm_head"]
    logits = matmul(x, head[:, : cfg["vocab_size"]])
    return logits, jnp.stack(lb_terms), jnp.stack(z_terms)


def loss_terms(params: dict, input_ids, cfg: dict, matmul=jnp.matmul) -> dict:
    """``{"ce", "lb", "z"}``: mean next-token cross-entropy over the ``L-1``
    predicting positions, and the two auxiliary terms, unweighted (means over
    layers and sequences)."""
    logits, lb, z = forward(params, input_ids, cfg, matmul)
    logits, targets = logits[:, :-1], input_ids[:, 1:]
    logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
    return {"ce": ce, "lb": lb.mean(), "z": z.mean()}


def loss(params: dict, input_ids, cfg: dict, matmul=jnp.matmul):
    """The objective: CE + the two weighted auxiliary terms."""
    t = loss_terms(params, input_ids, cfg, matmul)
    return (
        t["ce"]
        + cfg.get("router_aux_loss_coef", 0.0) * t["lb"]
        + cfg.get("router_z_loss_coef", 0.0) * t["z"]
    )


# What the reference check multiplies the router's weights by (``well_conditioned``).
CHECK_ROUTER_SCALE = 4.0


def well_conditioned(params: dict, cfg: dict) -> tuple[dict, float]:
    """Weights on which a bfloat16 gradient can be held to the check's tolerance.

    Choosing the ``k`` largest of 64 probabilities is discontinuous. The
    program rounds its activations to bf16, so the router's logits differ from
    the float32 ones by about 2e-3 of their spread, and wherever a token's
    eighth and ninth probabilities lie closer than that the two sides choose
    another last expert: on the v5e at the published widths, 3.7-4.2% of the
    tokens of a [2, 4096] batch (0.46-0.52% of the assignments), whatever the
    scale of the router. With the seeded initial router (normal, 0.02: logits
    of standard deviation 0.9) the eighth gate is a third of the first, the
    expert branch is as large as the residual stream, and those tokens alone
    put 4e-2 of relative error on every gradient (5.0e-2 to 5.8e-2 measured
    with bf16's own: refused at 4.7e-2), which no bf16 implementation can
    avoid. So the check, and only the check, multiplies the router's weights
    by ``CHECK_ROUTER_SCALE``: the same tokens still differ in their last
    expert, but its gate is then about a hundredth of the first and the
    gradients read bf16's own level (2.5e-2 to 2.8e-2 at 4; 3.7e-2 to 4.2e-2
    at 2; builder's chip runs, PR 25). Everything else stays as initialised;
    the measured rounds train the seeded weights untouched. What the check
    still tells apart at 4, read on the chip at [2, 4096] on three seeds
    (builder's controls, PR 25; PERF.md section 6 has every scale): top-7 for
    top-8 reads 6.9e-2 to 7.7e-2 on the embedding and the block and is
    refused (the sound program: 2.5e-2 to 3.3e-2), a capacity limit that
    drops the rows past T*k/E an expert reads 0.6. What it does not: ONE
    dropped assignment of 65,536 (+3e-3) and router logits taken in bf16
    (+2e-3) read as the sound program does; those are held in float32 by
    tests/test_moe.py. Returns ``(params, 1.0)``: the second value is the harness's
    name for a scale of the query and key projections, which are untouched."""
    layers = dict(params["layers"])
    router = layers["router"]
    layers["router"] = (jnp.asarray(router, jnp.float32) * CHECK_ROUTER_SCALE).astype(router.dtype)
    print(
        f"reference check: router weights scaled by {CHECK_ROUTER_SCALE} on both sides "
        "(benchmark/reference/olmoe_ref.py well_conditioned; config.json assumed: "
        "reference_check_router_scale)",
        flush=True,
    )
    return {**params, "layers": layers}, 1.0


def loss_and_grads(params: dict, input_ids, cfg: dict):
    """``(loss, gradients)`` in float32 at the highest matmul precision; the
    gradients have the pytree of ``params``."""
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(f32, input_ids, cfg)


def compared_groups(grads: dict) -> dict:
    """The tensors the reference check compares: embedding, untied head, and
    the first and (where there is more than one) last block, each block as
    ONE flat float32 vector of all its leaves, router and experts included.

    Not the experts alone: top-k is discontinuous, so a float32 reference
    and a bf16 program disagree on a token's last expert for a few percent
    of tokens, which is several percent of the experts' gradient in relative
    L2 and more than the check's tolerance for any group; within the whole
    block that error is a small part of the norm. The experts' and the
    router's gradients alone are held by the CPU tests, float32 on both
    sides (tests/test_moe.py)."""
    import numpy as np

    def block(i):
        return np.concatenate(
            [np.asarray(leaf[i], np.float32).ravel() for _, leaf in sorted(grads["layers"].items())]
        )

    groups = {"embedding": np.asarray(grads["wte"], np.float32).ravel()}
    if "lm_head" in grads:
        groups["lm_head"] = np.asarray(grads["lm_head"], np.float32).ravel()
    groups["first_block"] = block(0)
    if next(iter(grads["layers"].values())).shape[0] > 1:
        groups["last_block"] = block(-1)
    return groups
