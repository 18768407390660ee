"""Model-FLOPs accounting and MFU (model FLOPs utilization).

The reference publishes no quantitative numbers (`BASELINE.md`), so the
TPU bench needs its own absolute yardstick: MFU = model matmul FLOPs per
second / the chip's peak bf16 FLOPs. Model FLOPs follow the standard
convention (PaLM appendix B): count the *algorithmic* matmul FLOPs of one
forward+backward (backward = 2x forward), excluding rematerialisation
recompute — remat makes the hardware do extra work, it doesn't make the
model bigger.
"""

from __future__ import annotations


def llama_train_flops_per_token(cfg, seq_len: int) -> float:
    """Matmul train-FLOPs per token for acco_tpu's Llama family.

    Per token, forward: 2 * (weight matmul params) + 4 * L * D per layer
    for the QK^T and PV attention contractions; backward doubles it twice
    (grads wrt inputs and weights) => x3 total.
    """
    D, F, N = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    Dkv = cfg.num_kv_heads * cfg.head_dim
    per_layer_weights = D * D + 2 * D * Dkv + D * D + 3 * D * F
    attn = 4 * seq_len * D  # scores + PV, per token, per layer
    head = 2 * D * cfg.vocab_size  # lm head (tied or not: same matmul)
    fwd = 2 * N * per_layer_weights + N * attn + head
    return 3.0 * fwd


def gpt_neo_train_flops_per_token(cfg, seq_len: int) -> float:
    """Same accounting for the GPT-Neo family (fused qkv, 4D FFN default).

    Local-window layers do fewer *useful* score FLOPs, but the einsum path
    computes the full [L, L] block and masks — count the full block, since
    MFU measures how well the program uses the hardware it occupies.
    """
    D, F, N = cfg.hidden_size, cfg.ffn_dim, cfg.num_layers
    per_layer_weights = D * 3 * D + D * D + 2 * D * F
    attn = 4 * seq_len * D
    head = 2 * D * cfg.vocab_size
    fwd = 2 * N * per_layer_weights + N * attn + head
    return 3.0 * fwd


# Peak dense bf16 TFLOP/s of one JAX device, keyed by the exact
# ``jax.Device.device_kind``, each with where the figure comes from. A
# kind that is not listed is an error, not a default.
PEAK_BF16_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
    # libtpu reports the chip as "TPU v5 lite"; "TPU v5e" is the name
    # jax's mesh_utils also knows it by.
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    # Google Cloud documentation, "TPU v5p": 459 TFLOP/s bf16 per chip.
    "TPU v5p": 459.0,
    # Google Cloud documentation, "TPU v4": 275 TFLOP/s bf16 per chip.
    "TPU v4": 275.0,
    # Google Cloud documentation, "TPU v6e": 918 TFLOP/s bf16 per chip.
    "TPU v6 lite": 918.0,
}


def peak_bf16_tflops(device_kind: str) -> float:
    """Peak bf16 TFLOP/s for an exact ``device_kind``; raises on a kind
    the table does not hold."""
    try:
        return PEAK_BF16_TFLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak bf16 FLOP/s on record for device_kind "
            f"{device_kind!r}; add it to PEAK_BF16_TFLOPS with its source "
            f"(known: {sorted(PEAK_BF16_TFLOPS)})"
        ) from None


def mfu(
    tokens_per_sec_per_chip: float, flops_per_token: float, device_kind: str
) -> float:
    """Model FLOPs utilization in [0, 1]. A device with no peak on
    record (every CPU) raises: such a run has no MFU."""
    peak = peak_bf16_tflops(device_kind)
    return tokens_per_sec_per_chip * flops_per_token / (peak * 1e12)
