"""The ``batched_lowrank_apply`` Pallas kernel's share of its roofline:
the least time of one step's apply calls (left and right side of every
block; the larger of nominal operations over the bf16 peak and least bytes
over the HBM bandwidth, ``bench/flops.py``) over the kernel's summed device
time per step, over every step of the window."""
from bench import flops, trace

KERNEL = "batched_lowrank"


def read(ctx):
    plain, refresh = trace.steps_op_ns(ctx, lambda name, text: KERNEL in text)
    steps = plain + refresh
    if not steps or not sum(steps):
        return None
    least = sum(flops.least_seconds(flops.apply_cost(*c), ctx.peak)[0]
                for c in flops.apply_calls(ctx.pool_groups, ctx.rank))
    return 100.0 * least / (1e-9 * sum(steps) / len(steps))
