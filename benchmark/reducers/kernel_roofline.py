"""A group of kernels' share of their roofline. args: ``kernels`` maps a regex
of kernel names to the kind of layer ('global', 'local') those kernels
compute. The layers whose kernels ran in the trace set the work (the
configuration's ``flops`` module); the least time the chip could take for it,
over the kernels' device time, is the share."""

from benchmark.harness import flops
from benchmark.harness.manifest import family_module


def reduce(ctx: dict, args: dict):
    trace = ctx.get("device_trace")
    if trace is None or ctx["peaks"] is None:
        return None
    times = {regex: trace.op_ms_per_round(regex) for regex in args["kernels"]}
    kinds = {args["kernels"][regex] for regex, ms in times.items() if ms > 0}
    total_ms = sum(times.values())
    if not kinds or total_ms <= 0:
        return None
    work = family_module(ctx["config"], "flops").attention_kernel_work(
        ctx["config"]["model"], ctx["cell"]["seq_len"], ctx["cell"]["batch_per_chip"], kinds
    )
    least_s, bound = flops.roofline(*work, ctx["peaks"])
    ctx["say"](
        f"attention kernels ({', '.join(sorted(kinds))} layers): {work[0]:.3e} FLOPs and "
        f"{work[1]:.3e} bytes a round need at least {least_s * 1e3:.3f} ms ({bound} bound); "
        f"the kernels took {total_ms:.3f} ms"
    )
    return 100.0 * least_s * 1e3 / total_ms
