"""Device time per plain step under the optimizer's ``opt/`` scopes
(``core/api.named_chain``, ``train/trainer.py``): every stage of the chain
(``opt/clip``, ``opt/direction`` with the engine's ``precond/`` scopes
inside it, ``opt/momentum``, ``opt/weight_decay``, ``opt/lr``), the
parameter update (``opt/apply``) and the reported gradient norm
(``opt/grad_norm``).  ``None`` where the program names no such scope."""
from bench import trace

SCOPE = "opt/"


def read(ctx):
    plain, _ = trace.steps_op_ns(ctx, lambda name, text: SCOPE in text)
    total = sum(plain)
    return 1e-6 * total / len(plain) if plain and total else None
