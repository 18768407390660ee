"""The program's model and loss against the plain float32 reference.

Run outside the window, on the cell's seeded initial weights (what the trainer
starts from: ``model.init(PRNGKey(seed))``) and a seeded ``[2, L]`` batch, at
the configuration's published widths and the cell's sequence length. The
program side is the trainer's own model object (same attention plan, remat and
dtype as the rounds just measured) through ``ops.losses.model_ce``, the loss
every train path dispatches through.

The weights are the initial ones except where the reference's
``well_conditioned`` tempers them (GPT-Neo at the 2.7B widths: the unscaled
initial scores saturate the softmax and no bf16 gradient is meaningful there;
see its docstring). The run prints the scale it applied.

Tolerances, from bfloat16's unit roundoff u = 2^-8 (8 significand bits), as
``chip_smoke.py`` derives its own:

* loss: the program holds weights and activations in bf16 and takes the
  softmax in f32; the reference is f32 throughout on the same (bf16-valued)
  weights. A logit carries a few roundoffs of relative size u; the loss is a
  mean over 2 x (L-1) tokens, so they average down by sqrt(2(L-1)) >= 45.
  Allowed: u / 8 relative (4.9e-4). Measured on the v5e: 5e-6 to 3e-5.
* gradients (embedding, first block, last block): the backward pass rounds
  every matmul output and every activation gradient to bf16, so an element
  carries a few independent roundoffs of size u per layer it passed through,
  and the relative L2 error of a tensor grows with its depth in the backward
  pass. What bf16 itself costs was measured where no kernel runs: the
  program's model with plain einsum attention on the CPU, at the 125M widths
  and [2, 1024], is 1.9e-2 (last block, 5.0 u), 3.3e-2 (embedding) and 3.4e-2
  (first block, 8.8 u) from the float32 reference. The v5e with the fused and
  banded kernels reads the same level: 1.7e-2 / 3.0e-2 / 3.2e-2, to two digits
  alike on three seeds; at the 2.7B widths (4 layers, tempered) 2.3e-2 to
  2.5e-2. Allowed: 12 u (4.7e-2), 1.4 times bf16's own level at 12 layers.

What that tolerance tells apart (CPU control at the 125M widths, PR 22, and
``tests/benchmark/test_bench_refcheck.py`` at a tiny size): a window off by
one key reads 5.4e-2 / 5.7e-2 / 3.1e-2 in bf16 and fails (in float32 alone it
is 4.5e-2: the tolerance cannot be met by a wrong mask plus exact arithmetic
either once bf16's own error is added). Matmul operands rounded to an 8-bit
float (e4m3, u = 2^-4) in every matmul fail by a wide margin, a dropped layer
or a missing causal mask gives an error of order 1. What it does NOT tell
apart: ONE tensor rounded once to e4m3 adds about 3.6e-2 in quadrature, which
lands at 4.8e-2, on the tolerance's edge; a check that caught that for certain
would need bf16's own level subtracted, and the level depends on depth and
width. Such a change is for the tier-1 tests of the kernel that makes it.
"""

from __future__ import annotations

import math

from benchmark.harness.manifest import family_module

U_BF16 = 2.0**-8
LOSS_RTOL = U_BF16 / 8
GRAD_RTOL = 12 * U_BF16
BATCH = 2


def reference_of(config: dict):
    """The configuration's plain reference: the file its ``config.json``
    names under ``reference``. What such a module provides:
    ``loss_and_grads(params, ids, cfg)``, ``compared_groups(grads)`` (name ->
    flat float32 vector: the tensors whose gradients are compared) and,
    optionally, ``well_conditioned(params, cfg)``."""
    return family_module(config, "reference")


def program_side(model, fused_loss, reference, params, ids) -> dict:
    """Loss and compared gradients of the program's own model object through
    ``ops.losses.model_ce``, the loss every train path dispatches through."""
    import jax

    from acco_tpu.ops.losses import model_ce, real_vocab_of, resolve_fused_loss

    fused = resolve_fused_loss(fused_loss, model, real_vocab_of(model))

    def program_loss(p, ids):
        # const-len packed batches carry no mask: None, as the train path passes
        return model_ce(model, p, ids, None, ids, label_smoothing=0.0, fused=fused)

    loss, grads = jax.jit(jax.value_and_grad(program_loss))(params, ids)
    return {"loss": float(loss), **reference.compared_groups(jax.device_get(grads))}


def reference_side(reference, params, ids, cfg: dict) -> dict:
    """The reference one sequence at a time: equal token counts, so the
    batch's loss and gradients are the means over the sequences, and the
    [H, L, L] float32 scores of one sequence are what must fit."""
    import jax

    ref_fn = jax.jit(lambda p, row: reference.loss_and_grads(p, row, cfg))
    want = None
    for b in range(ids.shape[0]):
        loss, grads = ref_fn(params, ids[b : b + 1])
        part = {"loss": float(loss), **reference.compared_groups(jax.device_get(grads))}
        del grads
        want = part if want is None else {k: want[k] + part[k] for k in want}
    return {k: v / ids.shape[0] for k, v in want.items()}


def errors_between(got: dict, want: dict) -> dict:
    """Relative difference of the loss; relative L2 error of each group."""
    import numpy as np

    errors = {"loss": abs(got["loss"] - want["loss"]) / abs(want["loss"])}
    for key in want:
        if key != "loss":
            errors[key] = float(
                np.linalg.norm(got[key] - want[key]) / max(np.linalg.norm(want[key]), 1e-30)
            )
    return errors


def agree(errors: dict) -> bool:
    return (
        math.isfinite(errors["loss"])
        and errors["loss"] <= LOSS_RTOL
        and all(v <= GRAD_RTOL for k, v in errors.items() if k != "loss")
    )


def compare(model, fused_loss, config: dict, seq_len: int, seed: int, say=print) -> dict:
    """``{"ok": bool, "errors": {...}, ...}`` for a model object of the
    program; prints what it compared and the tolerance it applied."""
    import jax
    import jax.numpy as jnp

    cfg = config["model"]
    reference = reference_of(config)
    condition = getattr(reference, "well_conditioned", lambda params, cfg: (params, 1.0))
    with jax.default_device(jax.local_devices()[0]):
        params, tempered = condition(model.init(jax.random.PRNGKey(seed)), cfg)
        ids = jax.random.randint(
            jax.random.PRNGKey(seed + 1), (BATCH, seq_len), 0, cfg["vocab_size"], jnp.int32
        )
        got = program_side(model, fused_loss, reference, params, ids)
        want = reference_side(reference, params, ids, cfg)
    errors = errors_between(got, want)
    ok = agree(errors)
    groups = [k for k in errors if k != "loss"]
    say(
        f"reference check on [{BATCH}, {seq_len}] at seed {seed}"
        + (f" (query and key projections scaled by {tempered:.3f})" if tempered != 1.0 else "")
        + ": loss program "
        f"{got['loss']:.6f} vs float32 reference {want['loss']:.6f}, relative "
        f"difference {errors['loss']:.2e} (tolerance 2^-8 / 8 = {LOSS_RTOL:.2e}); "
        "gradient relative L2 error "
        + ", ".join(f"{k} {errors[k]:.2e}" for k in groups)
        + f" (tolerance 12 x 2^-8 = {GRAD_RTOL:.2e}): {'agree' if ok else 'DISAGREE'}"
    )
    return {"ok": ok, "qk_scale": tempered, "loss_program": got["loss"],
            "loss_reference": want["loss"], "errors": errors}


def check(trainer, config: dict, seq_len: int, seed: int, say=print) -> dict:
    """The trainer's own model object (same attention plan, remat and dtype
    as the rounds just measured) against the configuration's reference."""
    return compare(trainer.model, trainer.fused_loss, config, seq_len, seed, say=say)
