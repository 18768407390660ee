"""Model construction from the config group (parity with main.py's model
build: from an arch JSON when pretraining, by name when finetuning —
`/root/reference/main.py:33-41` and `/root/reference/config/model/*.yaml`).
"""

from __future__ import annotations

import json
import os

import jax.numpy as jnp

from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel
from acco_tpu.models.llama import LlamaConfig, LlamaModel

# Known hub names the reference's model group points at, mapped to local
# architecture parameters (no network access needed).
_PRESETS: dict[str, tuple[type, dict]] = {
    "EleutherAI/gpt-neo-125M": (GPTNeoModel, {}),
    "EleutherAI/gpt-neo-2.7B": (
        GPTNeoModel,
        dict(
            hidden_size=2560,
            num_layers=32,
            num_heads=20,
            max_position_embeddings=2048,
            attention_layers=["global", "local"] * 16,
        ),
    ),
    "meta-llama/Meta-Llama-3-8B": (
        LlamaModel,
        dict(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            max_position_embeddings=8192,
            rope_theta=500000.0,
            tie_word_embeddings=False,
        ),
    ),
}

_MODEL_TYPES = {
    "llama": (LlamaConfig, LlamaModel),
    "gpt_neo": (GPTNeoConfig, GPTNeoModel),
    # OLMoE is Llama's block with QK-norm and sparse experts (models/llama.py)
    "olmoe": (LlamaConfig, LlamaModel),
}


def build_model(
    model_cfg: dict,
    repo_root: str = ".",
    param_dtype=jnp.bfloat16,
    remat=False,
    attention: str = "auto",
    sequence_axis=None,
    scan_unroll=1,
    zigzag=False,
    tensor_axis=None,
    vocab_pad_multiple: int = 1,
):
    """Return a model (init/apply) from a ``config/model/*.yaml`` node.

    ``config_path`` may be a repo-relative ``/config/model/*.json`` arch
    file (the reference's pretrain path) or a known hub name (the
    reference's 2.7B/llama3 variants). ``vocab_pad_multiple`` (the tp
    size under tensor parallelism) pads the embedding/lm-head tables to a
    tp-divisible vocab (parallel/tp.pad_vocab); the config's vocab_size
    stays the real one and padded positions never enter the loss.
    """
    from acco_tpu.parallel.tp import pad_vocab
    config_path = model_cfg["config_path"]
    if config_path.endswith(".json"):
        path = config_path
        if not os.path.exists(path):
            path = os.path.join(repo_root, config_path.lstrip("/"))
        with open(path) as f:
            model_type = json.load(f).get("model_type", "gpt_neo")
        if model_type not in _MODEL_TYPES:
            raise ValueError(f"Unknown model_type {model_type!r} in {path}")
        cfg_cls, model_cls = _MODEL_TYPES[model_type]
        cfg = cfg_cls.from_json(path)
        kw = {
            "zigzag": zigzag,
            "tensor_axis": tensor_axis,
            "vocab_pad_to": pad_vocab(cfg.vocab_size, vocab_pad_multiple),
        }
        return model_cls(
            cfg,
            param_dtype=param_dtype,
            remat=remat,
            attention=attention,
            sequence_axis=sequence_axis,
            scan_unroll=scan_unroll,
            **kw,
        )
    if config_path in _PRESETS:
        model_cls, overrides = _PRESETS[config_path]
        cfg_cls = LlamaConfig if model_cls is LlamaModel else GPTNeoConfig
        cfg = cfg_cls(**overrides)
        kw = {
            "zigzag": zigzag,
            "tensor_axis": tensor_axis,
            "vocab_pad_to": pad_vocab(cfg.vocab_size, vocab_pad_multiple),
        }
        return model_cls(
            cfg,
            param_dtype=param_dtype,
            remat=remat,
            attention=attention,
            sequence_axis=sequence_axis,
            scan_unroll=scan_unroll,
            **kw,
        )
    raise ValueError(
        f"config_path {config_path!r} is neither a .json arch file nor a "
        f"known preset ({sorted(_PRESETS)})"
    )
