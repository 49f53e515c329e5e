"""Device time per refresh step under the engine's ``precond/refresh``
scope (``core/api.py``): the Frequent-Directions update of every block's
sketch pair."""
from bench import trace


def read(ctx):
    _, refresh = trace.steps_op_ns(
        ctx, lambda name, text: "precond/refresh" in text)
    total = sum(refresh)
    return 1e-6 * total / len(refresh) if refresh and total else None
