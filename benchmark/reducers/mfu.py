"""Model FLOPs per token (the configuration's ``flops`` module) x tokens/s/chip
over the chip's bf16 peak (``harness/peaks.json``). No args."""

from benchmark.harness import flops
from benchmark.harness.manifest import family_module


def reduce(ctx: dict, args: dict):
    rate = ctx["quantities"].get("tokens_per_s_per_chip")
    if rate is None or ctx["peaks"] is None:
        return None
    per_token = family_module(ctx["config"], "flops").train_flops_per_token(
        ctx["config"]["model"], ctx["cell"]["seq_len"]
    )
    return flops.mfu_pct(rate, per_token, ctx["peaks"])
