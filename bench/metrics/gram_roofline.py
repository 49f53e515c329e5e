"""The ``batched_gram`` Pallas kernel's share of its roofline in the
refresh steps: the least time of one refresh's Gram calls (the larger of
their nominal operations over the bf16 peak and their least bytes over the
HBM bandwidth, ``bench/flops.py``) over the kernel's summed device time
per refresh step."""
from bench import flops, trace

KERNEL = "batched_gram"


def read(ctx):
    _, refresh = trace.steps_op_ns(ctx, lambda name, text: KERNEL in text)
    if not refresh or not sum(refresh):
        return None
    least = sum(flops.least_seconds(flops.gram_cost(*c), ctx.peak)[0]
                for c in flops.gram_calls(ctx.pool_groups, ctx.rank))
    return 100.0 * least / (1e-9 * sum(refresh) / len(refresh))
