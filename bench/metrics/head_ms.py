"""Device time per plain step under the model's ``model/head`` scope
(``models/model.py``): the final norm, the vocabulary-wide logits and the
loss (float32 cast, ``logsumexp``, gather, mean), forward and backward.
``None`` where the program names no such scope."""
from bench import trace

SCOPE = "model/head"


def read(ctx):
    plain, _ = trace.steps_op_ns(ctx, lambda name, text: SCOPE in text)
    total = sum(plain)
    return 1e-6 * total / len(plain) if plain and total else None
