"""Device time per plain step under the engine's ``precond/update_stats``
and ``precond/precondition`` scopes (``core/api.py``): the low-rank apply
of every block's sketch pair."""
from bench import trace


def _in_scope(name, text):
    return "precond/update_stats" in text or "precond/precondition" in text


def read(ctx):
    plain, _ = trace.steps_op_ns(ctx, _in_scope)
    total = sum(plain)
    return 1e-6 * total / len(plain) if plain and total else None
