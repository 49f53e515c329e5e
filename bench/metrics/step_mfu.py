"""The whole training step's share of the chip's bf16 peak: the model's
training FLOPs per token (``flops_per_token`` of the configuration's
reference, ``bench/configs/<config>.py``; recomputation and the input
embedding gather not counted) times the window's tokens per second per
chip, over the peak of ``bench/peaks.json``."""


def read(ctx):
    return 100.0 * ctx.flops_per_token * ctx.tokens_per_s / ctx.chips \
        / ctx.peak["bf16_flops"]
