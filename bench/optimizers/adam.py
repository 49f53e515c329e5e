"""Plain reference of the Adam training chain, and the readings the check
takes from the program's Adam state.

The chain: clip the gradient to global norm ``clip``; Adam with bias
correction (``m / (1 - beta1^t) / sqrt(v / (1 - beta2^t) + eps^2)``, both
moments in float32); decoupled weight decay; the warmup-cosine learning
rate.  Parameters are stored in their own type.
"""
import jax
import jax.numpy as jnp

from bench.optimizers.sketchy import clip, lr_at


def init(params, h):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"mu": [z(p) for p in params], "nu": [z(p) for p in params]}


def make_update(h: dict, mode: str):
    del mode                      # elementwise: no contraction to lower

    def update(params, grads, state, count, lr):
        g = clip([x.astype(jnp.float32) for x in grads], h["clip"])
        b1, b2, t = h["beta1"], h["beta2"], count + 1.0
        mus = [b1 * m + (1 - b1) * x for m, x in zip(state["mu"], g)]
        nus = [b2 * v + (1 - b2) * x * x for v, x in zip(state["nu"], g)]
        d = [(m / (1 - b1 ** t)) * jax.lax.rsqrt(v / (1 - b2 ** t)
                                                 + h["eps"] ** 2)
             for m, v in zip(mus, nus)]
        new_p = [(p.astype(jnp.float32) - lr * (di + h["weight_decay"]
                                                * p.astype(jnp.float32))
                  ).astype(p.dtype) for p, di in zip(params, d)]
        return new_p, {"mu": mus, "nu": nus}, d

    step = jax.jit(update)

    def run(params, grads, state, count):
        return step(params, grads, state, jnp.float32(count),
                    jnp.float32(lr_at(h, count)))

    return run


def ref_first_grad_sq(state, h) -> list:
    """Per leaf ``||g||^2`` of the first clipped gradient, from ``v``."""
    return [float(jnp.sum(v)) / (1 - h["beta2"]) for v in state["nu"]]


def program_first_grad_sq(opt_state, params, h):
    from repro.core import api
    pre = api.get_stage(opt_state, "precond")
    return jnp.stack([jnp.sum(api.untag(leaf.stats).nu) / (1 - h["beta2"])
                      for leaf in pre.leaves])


program_first_direction = None
