"""Device time per plain step of the operations outside the optimizer
engine's ``precond/`` scopes: the model's forward and backward passes, and
the optimizer chain's elementwise stages (clip, grafting, momentum, decay),
which carry no scope of their own."""
from bench import trace


def read(ctx):
    plain, _ = trace.steps_op_ns(ctx, lambda name, text: "precond/" not in text)
    return 1e-6 * sum(plain) / len(plain) if plain else None
