"""Device time per refresh step of the operations under
``precond/refresh`` whose operation metadata names the eigendecomposition
(``eigh``) of the Frequent-Directions Grams (``core/fd.py``)."""
from bench import trace


def _eigh(name, text):
    return "precond/refresh" in text and "eigh" in text.lower()


def read(ctx):
    _, refresh = trace.steps_op_ns(ctx, _eigh)
    total = sum(refresh)
    return 1e-6 * total / len(refresh) if refresh and total else None
