"""Device time per plain step of the recomputed forward: the operations
that ``jax.checkpoint`` (``models/model._remat``) replays in the backward
pass, which JAX names ``rematted_computation``.  ``None`` where nothing is
recomputed."""
from bench import trace

SCOPE = "rematted_computation"


def read(ctx):
    plain, _ = trace.steps_op_ns(ctx, lambda name, text: SCOPE in text)
    total = sum(plain)
    return 1e-6 * total / len(plain) if plain and total else None
