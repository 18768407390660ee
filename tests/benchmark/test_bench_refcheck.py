"""What the reference check's tolerance passes and what it refuses, at a tiny
size on the CPU: the program in bf16 with and without its kernels against
wrong masks, a dropped layer and 8-bit matmuls."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from _paths import ROOT

from benchmark.harness import refcheck
from benchmark.reference import gpt_neo_ref

SEQ = 128


@pytest.fixture(scope="module")
def config():
    """The rehearsal's model (heads of 64, so the kernels take it) at four
    layers: two periods of global / window-64. GPT-Neo does not scale its
    scores, so their spread at initialisation goes with the width; a range of
    0.05 at h=128 gives them the published 125M model's (standard deviation
    sqrt(64) x 0.05^2 x 128 = 2.56 against 2.46), and with it a softmax as
    peaked as the one the chip's check sees."""
    with open(os.path.join(ROOT, "benchmark", "harness", "rehearsal.json")) as f:
        model = json.load(f)["model"]
    model = {**model, "num_layers": 4, "attention_layers": ["global", "local"] * 2,
             "initializer_range": 0.05}
    return {"name": "tiny", "model": model,
            "root": ROOT, "meta": {"reference": "benchmark/reference/gpt_neo_ref.py"}}


def program(model_cfg: dict, dtype, attention: str = "xla"):
    """The program's own model object for a ``model.json`` given as a dict."""
    import dataclasses

    from acco_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel

    fields = {f.name for f in dataclasses.fields(GPTNeoConfig)}
    cfg = GPTNeoConfig(**{k: v for k, v in model_cfg.items() if k in fields})
    return GPTNeoModel(cfg, param_dtype=dtype, attention=attention)


def test_bf16_passes_with_the_kernels_and_without(config, monkeypatch):
    """The error the check allows is bf16's own: the plain einsum path and the
    fused + banded kernels (interpreted) sit at the same level, under it."""
    monkeypatch.setenv("ACCO_FUSED_ATTN_INTERPRET", "1")
    said = []
    einsum = refcheck.compare(program(config["model"], jnp.bfloat16, "xla"), False, config, SEQ, 7,
                              say=said.append)
    kernels = refcheck.compare(program(config["model"], jnp.bfloat16, "fused"), False, config, SEQ, 7,
                               say=said.append)
    assert einsum["ok"] and kernels["ok"], said
    for group in ("embedding", "first_block", "last_block"):
        a, b = einsum["errors"][group], kernels["errors"][group]
        assert refcheck.U_BF16 / 2 < a < refcheck.GRAD_RTOL  # bf16's level, not float32's
        assert 2 / 3 < a / b < 3 / 2, (group, a, b)


def test_a_window_off_by_one_fails_in_the_program(config):
    wrong = program({**config["model"], "window_size": config["model"]["window_size"] + 1},
                    jnp.bfloat16)
    said = []
    assert not refcheck.compare(wrong, False, config, SEQ, 7, say=said.append)["ok"]
    assert "DISAGREE" in said[-1]


def rounded_to(dtype):
    """A matmul whose operands are rounded to ``dtype`` and whose sum is kept
    in float32: what a matrix unit fed that type computes."""

    def rounded(x):  # the rounding's own derivative is taken as 1
        return x + jax.lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)

    return lambda a, b: jnp.matmul(rounded(a), rounded(b))


def head_only(dtype):
    """Only the lm-head's product in ``dtype`` (its right operand is [D, V])."""
    low = rounded_to(dtype)
    return lambda a, b: low(a, b) if b.shape[-1] == 257 else jnp.matmul(a, b)


CASES = {
    # name: (cfg changes, matmul, passes)
    "float32": ({}, jnp.matmul, True),
    "bf16_operands_everywhere": ({}, rounded_to(jnp.bfloat16), True),
    "e4m3_operands_everywhere": ({}, rounded_to(jnp.float8_e4m3fn), False),
    "e5m2_operands_everywhere": ({}, rounded_to(jnp.float8_e5m2), False),
    # the limit, stated in refcheck.py: ONE product in 8 bits (2.8e-2 here) stays
    # under the tolerance even with bf16's own 2.2e-2 added in quadrature
    "e4m3_operands_in_the_lm_head_only": ({}, head_only(jnp.float8_e4m3fn), True),
    "window_one_wider": ({"window_size": 65}, jnp.matmul, False),
    "window_one_narrower": ({"window_size": 63}, jnp.matmul, False),
    "last_layer_dropped": ({"attention_layers": ["global", "local", "global"]}, jnp.matmul, False),
    "local_layers_run_global": ({"attention_layers": ["global"] * 4}, jnp.matmul, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_what_the_tolerance_tells_apart(config, case):
    """The float32 reference against itself with one thing changed; the
    verdict is ``refcheck.agree`` on ``refcheck.errors_between``, as on the
    chip."""
    changed, matmul, passes = CASES[case]
    cfg = config["model"]
    params = program(cfg, jnp.float32).init(jax.random.PRNGKey(7))  # the seeded weights' layout
    ids = jax.random.randint(jax.random.PRNGKey(8), (2, SEQ), 0, cfg["vocab_size"], jnp.int32)

    def side(cfg, matmul):
        loss, grads = jax.value_and_grad(gpt_neo_ref.loss)(params, ids, cfg, matmul)
        return {"loss": float(loss), **gpt_neo_ref.compared_groups(jax.device_get(grads))}

    errors = refcheck.errors_between(side({**cfg, **changed}, matmul), side(cfg, jnp.matmul))
    assert refcheck.agree(errors) is passes, errors
