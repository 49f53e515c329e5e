"""Shared pieces of the benchmark's plain references.

Nothing here imports the program.  The references rebuild the weights from
the seed by the rule the harness fixes (``init_params``), compute in float32
at ``HIGHEST`` matmul precision, and round to the storage types that the
configuration states (bfloat16 parameters and momentum) at the points where
a training step stores them.

``make_mm(mode)`` is the one contraction every reference uses:

* ``"highest"``: float32 operands, ``Precision.HIGHEST`` (the reference);
* ``"control"``: each operand rounded to float8 e4m3 with a per-tensor
  scale, then contracted in float32 — the model computed one precision
  below the bfloat16 that the configuration states, and the optimizer's
  float32 matrix path at ``HIGH``.  It is the control that a sound check
  has to refuse.

``fd_precision(mode)`` is the precision of the optimizer references' float32
matrix path (Grams, projections, the low-rank apply).  The modes
``"fd_high"`` (three bfloat16 passes) and ``"fd_default"`` (one) lower that
path alone and keep the model at ``HIGHEST``: they show whether the check
sees the statistics computed below the float32 the mix states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("highest", "control", "fd_high", "fd_default")
_FD_PRECISION = {"highest": HIGHEST, "control": jax.lax.Precision.HIGH,
                 "fd_high": jax.lax.Precision.HIGH,
                 "fd_default": jax.lax.Precision.DEFAULT}
_F8_MAX = 448.0          # largest finite float8 e4m3fn


def seed_key(seed: int):
    """The PRNG key of a run: ``--seed`` may exceed 32 bits, so it is hashed
    to one 32-bit word rather than truncated."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def _q8(x):
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / _F8_MAX + 1e-30)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def make_mm(mode: str):
    if mode not in MODES:
        raise ValueError(f"unknown reference mode {mode!r}")
    if mode == "control":
        return lambda eq, a, b: jnp.einsum(eq, _q8(a), _q8(b),
                                           precision=HIGHEST)
    return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)


def fd_precision(mode: str):
    """Contraction precision of the optimizer references' float32 matrix
    path under ``mode``."""
    if mode not in MODES:
        raise ValueError(f"unknown reference mode {mode!r}")
    return _FD_PRECISION[mode]


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


# ------------------------------------------------------------- weights

def init_params(shapes: dict, key, dtype):
    """Weights from the seed: one key per leaf in flattened (sorted-key)
    order; vectors start at zero, every other leaf is normal with scale
    ``fan_in ** -0.5`` where ``fan_in`` is the second-to-last dim."""
    flat, treedef = jax.tree.flatten(shapes,
                                     is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    leaves = []
    for s, k in zip(flat, keys):
        if len(s) == 1:
            leaves.append(jnp.zeros(s, dtype))
        else:
            leaves.append((jax.random.normal(k, s, jnp.float32)
                           * (s[-2] ** -0.5)).astype(dtype))
    return jax.tree.unflatten(treedef, leaves)


def stack(shapes: dict, n: int) -> dict:
    return jax.tree.map(lambda s: (n,) + tuple(s), shapes,
                        is_leaf=lambda x: isinstance(x, tuple))


# ------------------------------------------------------ loss and gradient

def make_loss_and_grad(loss_sum, model: dict, mode: str, rows: int):
    """``fn(params, tokens, labels, row_w) -> (loss, grads)``: the mean
    next-token loss over every token of the rows weighted 1 (a row weighted
    0 is left out), accumulated over blocks of ``rows`` sequences so that
    the float32 logits of one block at a time are live."""
    mm = make_mm(mode)

    def block(params, tok, lab, w):
        def f(p):
            return loss_sum(jax.tree.map(lambda x: x.astype(jnp.float32), p),
                            tok, lab, w, model, mm)
        return jax.value_and_grad(f)(params)

    block = jax.jit(block)

    def fn(params, tokens, labels, row_w):
        B, S = tokens.shape
        n_rows = float(np.sum(row_w))
        total, grads = 0.0, None
        for r in range(0, B, rows):
            sl = slice(r, r + rows)
            if not np.any(row_w[sl]):
                continue
            l, g = block(params, tokens[sl], labels[sl],
                         jnp.asarray(row_w[sl], jnp.float32))
            total = total + l
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        denom = n_rows * S
        return total / denom, jax.tree.map(lambda g: g / denom, grads)

    return fn


def nll_sum(logits, labels, row_w):
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * row_w[:, None])


def remat_scan(layer_fn, x, stacked):
    """``x`` through the stacked layers, one layer recomputed at a time in
    the backward pass (values are unchanged; only memory is)."""
    body = jax.checkpoint(lambda h, p: (layer_fn(p, h), None))
    return jax.lax.scan(body, x, stacked)[0]


# --------------------------------------------------------------- blocking

def block_layout(shape: tuple, block_size: int):
    """``None`` for a vector leaf (diagonal statistics), else
    ``(stack, m, n, bm, bn, mb, nb)``: leading dims flattened into a stack,
    the last two tiled into zero-padded blocks of at most ``block_size``."""
    if len(shape) < 2 or min(shape[-2:]) == 1:
        return None
    *lead, m, n = shape
    stack_n = int(math.prod(lead)) if lead else 1

    def tile(d):
        return (1, d) if d <= block_size else (math.ceil(d / block_size),
                                               block_size)
    mb, bm = tile(m)
    nb, bn = tile(n)
    return stack_n, m, n, bm, bn, mb, nb


def to_blocks(x, lay):
    S, m, n, bm, bn, mb, nb = lay
    x = x.reshape(S, m, n)
    x = jnp.pad(x, ((0, 0), (0, mb * bm - m), (0, nb * bn - n)))
    x = x.reshape(S, mb, bm, nb, bn).transpose(0, 1, 3, 2, 4)
    return x.reshape(S * mb * nb, bm, bn)


def from_blocks(b, lay, shape):
    S, m, n, bm, bn, mb, nb = lay
    x = b.reshape(S, mb, nb, bm, bn).transpose(0, 1, 3, 2, 4)
    return x.reshape(S, mb * bm, nb * bn)[:, :m, :n].reshape(shape)


def leaf_norms(tree) -> list:
    """Per-leaf float64 L2 norms of a host or device pytree."""
    return [float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
            for x in jax.tree.leaves(tree)]
