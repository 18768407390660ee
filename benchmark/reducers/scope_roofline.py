"""A device scope's share of its roofline: the least time the chip could take
for the work the scope exists for, over the scope's device time
(``scope_op_time``'s reading of the same trace). args: ``scopes`` and
``except_ops`` as ``scope_op_time`` takes them; ``work``: the name of a
function of the configuration's ``flops`` module, called as ``work(model,
seq_len, batch_per_chip)`` and returning ``(FLOPs, bytes)`` of one round.

The scope's time holds everything traced under it, not the matmuls alone, so
the share cannot pass 100% unless the work is counted too high. A program
that has no such scope (every commit before the one that added it), a
configuration whose family counts no such work, or a run without peaks (a
rehearsal) gives nothing to read: None.
"""

import os

from benchmark.harness import flops
from benchmark.harness.manifest import BENCH_DIR, family_module, load_module


def reduce(ctx: dict, args: dict):
    if ctx.get("peaks") is None:
        return None
    scope_op_time = load_module(os.path.join(BENCH_DIR, "reducers", "scope_op_time.py"))
    scope_ms = scope_op_time.reduce(ctx, args)
    work_fn = getattr(family_module(ctx["config"], "flops"), args["work"], None)
    if not scope_ms or work_fn is None:
        return None
    work = work_fn(ctx["config"]["model"], ctx["cell"]["seq_len"], ctx["cell"]["batch_per_chip"])
    least_s, bound = flops.roofline(*work, ctx["peaks"])
    ctx["say"](
        f"{', '.join(args['scopes'])}: {work[0]:.3e} FLOPs and {work[1]:.3e} bytes a round need "
        f"at least {least_s * 1e3:.3f} ms ({bound} bound); the scope took {scope_ms:.3f} ms"
    )
    return 100.0 * least_s * 1e3 / scope_ms
