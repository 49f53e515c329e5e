"""Device time per plain step under the model's ``model/attention`` scope
(``models/model.py``): each block's first half (norm, QKV, RoPE, scores,
softmax, PV, out-projection, residual add) in the forward, the recomputed
forward and the backward.  ``None`` where the program names no such
scope."""
from bench import trace

SCOPE = "model/attention"


def read(ctx):
    plain, _ = trace.steps_op_ns(ctx, lambda name, text: SCOPE in text)
    total = sum(plain)
    return 1e-6 * total / len(plain) if plain and total else None
