"""Plain float32 GPT-Neo: forward pass, next-token loss and gradients.

A straightforward ``jax.numpy`` transcription of the published architecture
(EleutherAI GPT-Neo, as Hugging Face's ``GPTNeoForCausalLM`` implements the
released checkpoints' ``config.json``), independent of ``acco_tpu``: a Python
loop over the layers, explicit masks, no kernels, no scan, no cache, no remat,
no mixed precision. It shares only the parameter layout with the program (the
pytree ``GPTNeoModel.init`` returns), because the comparison is on the same
seeded weights.

Per layer ``l`` (pre-norm residual blocks)::

    h   = LN(x; ln1)                                 LN over the hidden dim, eps
    q,k,v = h Wq, h Wk, h Wv                          no bias, split into heads
    S   = q k^T                                       NOT divided by sqrt(head_dim)
    S   = where(allowed, S, -inf)                     causal; local layers also i-j < window
    x   = x + (softmax(S) v) Wo + bo
    x   = x + gelu_new(LN(x; ln2) Wfc + bfc) Wproj + bproj

with ``x0 = wte[ids] + wpe[0..L-1]``, a final LN, and logits ``x wte^T`` (tied
head). The loss is the mean cross-entropy of position ``t`` predicting token
``t+1`` over the ``L-1`` positions of every sequence.

Departures from the published description, each on purpose:

* The released model applies dropout layers with the published rate 0.0;
  they are left out.
* Hugging Face masks with the dtype's most negative finite number; ``-inf``
  is used here, which gives the same softmax whenever a row has an allowed
  key, and under a causal mask every row has one.
* q/k/v are read from the program's fused ``w_qkv[D, 3, D]`` tensor
  (``[:, 0]``, ``[:, 1]``, ``[:, 2]``): a layout, not a change of the
  mathematics.
* ``intermediate_size: null`` means ``4 x hidden_size``, as published.

On a TPU a float32 matmul runs in reduced precision unless told otherwise, so
every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def allowed_mask(seq_len: int, window: int):
    """``[L, L]`` bool: query ``i`` may read key ``j``. ``window`` 0 is a
    global layer."""
    i = jnp.arange(seq_len)[:, None]
    j = jnp.arange(seq_len)[None, :]
    allowed = j <= i
    if window > 0:
        allowed = allowed & (i - j < window)
    return allowed


def forward(params: dict, input_ids, cfg: dict, matmul=jnp.matmul):
    """Float32 logits ``[B, L, V]``. ``cfg`` is the configuration's JSON as
    a dict; ``params`` the program's pytree, any float dtype. Every matrix
    product goes through ``matmul``: the tests pass one that rounds its
    operands, to show which precisions the reference check tells apart."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    B, L = input_ids.shape
    H = cfg["num_heads"]
    D = cfg["hidden_size"]
    eps = cfg.get("layer_norm_epsilon", 1e-5)
    x = p["wte"][input_ids] + p["wpe"][:L][None]
    layers = p["layers"]
    for n, kind in enumerate(cfg["attention_layers"]):
        window = 0 if kind == "global" else cfg["window_size"]
        h = _layer_norm(x, layers["ln1_scale"][n], layers["ln1_bias"][n], eps)
        w_qkv = layers["w_qkv"][n]  # [D, 3, D]

        def heads(t):  # [B, L, D] -> [B, H, L, D/H]
            return t.reshape(B, L, H, D // H).transpose(0, 2, 1, 3)

        q, k, v = (heads(matmul(h, w_qkv[:, i, :])) for i in range(3))
        scores = matmul(q, k.transpose(0, 1, 3, 2))  # unscaled: GPT-Neo's own choice
        scores = jnp.where(allowed_mask(L, window)[None, None], scores, -jnp.inf)
        attn = matmul(jax.nn.softmax(scores, axis=-1), v)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, L, D)
        x = x + matmul(attn, layers["wo"][n]) + layers["wo_bias"][n]
        h = _layer_norm(x, layers["ln2_scale"][n], layers["ln2_bias"][n], eps)
        h = _gelu_new(matmul(h, layers["w_fc"][n]) + layers["b_fc"][n])
        x = x + matmul(h, layers["w_proj"][n]) + layers["b_proj"][n]
    x = _layer_norm(x, p["lnf_scale"], p["lnf_bias"], eps)
    return matmul(x, p["wte"][: cfg["vocab_size"]].T)


# Standard deviation of the unscaled scores of the published 125M model at
# its initialisation (sqrt(64) x 0.02^2 x 768 = 2.46), rounded up.
SCORE_STD_CAP = 2.5


def well_conditioned(params: dict, cfg: dict) -> tuple[dict, float]:
    """Weights on which a bfloat16 gradient can be held to a tight tolerance.

    GPT-Neo does not divide its scores by sqrt(head_dim), so at initialisation
    (normal weights of std ``initializer_range``, unit-variance normed input)
    they have the standard deviation ``sqrt(head_dim) x range^2 x hidden``:
    2.46 at the 125M widths, 11.6 at the 2.7B widths. At 11.6 the softmax is
    saturated: which key wins a row flips on a rounding error, and the bf16
    gradient of ANY implementation is 45-60% away from the float32 one (plain
    einsum attention on the CPU: 0.52 / 0.52 / 0.45; the same model code in
    float32: 7e-5; builder's runs, PR 22). A comparison there can only be so
    loose that it would pass a wrong mask. So where the configuration's
    initial scores are wider than ``SCORE_STD_CAP``, the query and key
    projections (``w_qkv[:, :, 0:2]``) are scaled down until they are not
    (measured then, same CPU run: 2.4e-2 to 2.6e-2). Everything else stays as
    initialised. Returns ``(params, scale)``; scale 1.0 means untouched."""
    head_dim = cfg["hidden_size"] // cfg["num_heads"]
    std = math.sqrt(head_dim) * cfg.get("initializer_range", 0.02) ** 2 * cfg["hidden_size"]
    if std <= SCORE_STD_CAP:
        return params, 1.0
    scale = math.sqrt(SCORE_STD_CAP / std)  # the scores go with its square
    w = params["layers"]["w_qkv"]
    layers = {**params["layers"], "w_qkv": w.at[:, :, 0:2, :].multiply(scale).astype(w.dtype)}
    return {**params, "layers": layers}, scale


def compared_groups(grads: dict) -> dict:
    """The tensors the reference check compares: embedding, first block, last
    block, each as one flat float32 vector. Blocks are the stacked layer
    leaves at index 0 and -1."""
    import numpy as np

    def block(i):
        return np.concatenate(
            [np.asarray(leaf[i], np.float32).ravel() for _, leaf in sorted(grads["layers"].items())]
        )

    return {
        "embedding": np.asarray(grads["wte"], np.float32).ravel(),
        "first_block": block(0),
        "last_block": block(-1),
    }


def loss(params: dict, input_ids, cfg: dict, matmul=jnp.matmul):
    """Mean next-token cross-entropy over the ``L-1`` predicting positions."""
    logits = forward(params, input_ids, cfg, matmul)[:, :-1]
    targets = input_ids[:, 1:]
    logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def loss_and_grads(params: dict, input_ids, cfg: dict):
    """``(loss, gradients)`` in float32 at the highest matmul precision; the
    gradients have the pytree of ``params``."""
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(f32, input_ids, cfg)
