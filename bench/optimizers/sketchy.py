"""Plain reference of the Sketchy training chain, and the readings the
check takes from the program's Sketchy state.

The chain: clip the gradient to global norm ``clip``; per matrix leaf, cut
the (stacked) matrix into zero-padded blocks of at most ``block_size`` and
keep for each block a left and a right Frequent-Directions sketch of rank
``rank`` (the seed Sketchy: the FD update eigendecomposes the Gram of
``[sqrt(beta2) U diag(sqrt(s)), G]``, deflates by the ``rank``-th
eigenvalue and carries it in ``rho``), refreshed on steps where
``count % update_every == 0``; precondition each block as
``(L + (rho_L + eps) I)^(-1/4) G (R + (rho_R + eps) I)^(-1/4)``; graft the
result to the norm of the RMSprop-normalized direction; vectors take the
diagonal RMSprop direction.  Then EMA momentum ``beta1`` (stored in the
parameters' type), decoupled weight decay, and the warmup-cosine learning
rate.  Eigendecompositions of Grams of one size run as one batched call.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench import reflib


def lr_at(h: dict, count: int) -> float:
    warm = max(int(h["total_steps"] * h["warmup_frac"]), 1)
    if count < warm:
        return h["lr"] * count / warm
    frac = min(max((count - warm) / max(h["total_steps"] - warm, 1), 0.0), 1.0)
    return h["lr"] * 0.5 * (1.0 + np.cos(np.pi * frac))


def clip(grads, max_norm):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads))
    return [g * jnp.minimum(1.0, max_norm / (gn + 1e-16)) for g in grads]


def _fd_gram(U, s, A, beta2, prec):
    B = U * jnp.sqrt(jnp.maximum(beta2 * s, 0.0))[:, None, :]
    M = jnp.concatenate([B, A], axis=2)
    C = jnp.matmul(jnp.swapaxes(M, 1, 2), M, precision=prec)
    return M, 0.5 * (C + jnp.swapaxes(C, 1, 2))


def _fd_finish(M, lam, V, rho, ell, beta2, prec):
    lam = jnp.maximum(lam[..., ::-1], 0.0)
    V = V[..., ::-1]
    top = lam[..., :ell]
    rho_t = top[..., ell - 1]
    inv = jnp.where(top > 1e-30, jax.lax.rsqrt(jnp.maximum(top, 1e-30)), 0.0)
    U = jnp.matmul(M, V[..., :ell], precision=prec) * inv[:, None, :]
    return (U, top - rho_t[:, None], beta2 * rho + rho_t)


def _apply(U, s, rho, G, eps, prec):
    damp = rho + eps
    base = jnp.where(damp > 1e-10, jnp.power(jnp.maximum(damp, 1e-10), -0.25),
                     0.0)
    lam = s + damp[:, None]
    c = jnp.where(lam > 1e-10, jnp.power(jnp.maximum(lam, 1e-10), -0.25),
                  0.0) - base[:, None]
    proj = jnp.matmul(jnp.swapaxes(U, 1, 2), G, precision=prec)
    return base[:, None, None] * G + jnp.matmul(U, c[..., None] * proj,
                                                precision=prec)


def _layouts(shapes, h):
    return [reflib.block_layout(tuple(s), h["block_size"]) for s in shapes]


def init(params, h):
    out = []
    for p in params:
        lay = reflib.block_layout(tuple(p.shape), h["block_size"])
        if lay is None:
            out.append({"acc": jnp.zeros(p.shape, jnp.float32)})
            continue
        S, m, n, bm, bn, mb, nb = lay
        N = S * mb * nb
        side = lambda d: {"U": jnp.zeros((N, d, min(h["rank"], d))),
                          "s": jnp.zeros((N, min(h["rank"], d))),
                          "rho": jnp.zeros((N,))}
        out.append({"L": side(bm), "R": side(bn),
                    "graft": jnp.zeros(p.shape, jnp.float32)})
    return {"leaves": out,
            "mu": [jnp.zeros(p.shape, p.dtype) for p in params]}


def _refresh(leaves, blocks, h, prec):
    """FD update of every sketch; the Grams are eigendecomposed as one
    batched call per Gram size."""
    b2 = h["beta2"]
    jobs = []          # (leaf, side, M, C)
    for i, st in enumerate(leaves):
        if "L" not in st:
            continue
        gb = blocks[i]
        for side, A in (("L", gb), ("R", jnp.swapaxes(gb, 1, 2))):
            M, C = _fd_gram(st[side]["U"], st[side]["s"], A, b2, prec)
            jobs.append((i, side, M, C))
    by_size = {}
    for j, (_, _, _, C) in enumerate(jobs):
        by_size.setdefault(C.shape[-1], []).append(j)
    eig = {}
    for k, js in by_size.items():
        lam, V = jnp.linalg.eigh(jnp.concatenate([jobs[j][3] for j in js]))
        off = 0
        for j in js:
            n = jobs[j][3].shape[0]
            eig[j] = (lam[off:off + n], V[off:off + n])
            off += n
    new = [dict(st) for st in leaves]
    for j, (i, side, M, _) in enumerate(jobs):
        old = leaves[i][side]
        U, s, rho = _fd_finish(M, *eig[j], old["rho"], old["s"].shape[-1], b2,
                               prec)
        new[i][side] = {"U": U, "s": s, "rho": rho}
    return new


def direction(grads, leaves, count, h, prec):
    """Sketchy's direction for the clipped float32 gradients."""
    lays = _layouts([g.shape for g in grads], h)
    blocks = [None if lay is None else reflib.to_blocks(g, lay)
              for g, lay in zip(grads, lays)]
    if count % h["update_every"] == 0:
        leaves = _refresh(leaves, blocks, h, prec)
    b2, out, new = h["beta2"], [], []
    for g, st, lay, gb in zip(grads, leaves, lays, blocks):
        if lay is None:
            acc = b2 * st["acc"] + (1 - b2) * jnp.square(g)
            out.append(g * jax.lax.rsqrt(acc + h["graft_eps"]))
            new.append({"acc": acc})
            continue
        L, R = st["L"], st["R"]
        tmp = _apply(L["U"], L["s"], L["rho"], gb, h["matrix_eps"], prec)
        tmp = _apply(R["U"], R["s"], R["rho"], jnp.swapaxes(tmp, 1, 2),
                     h["matrix_eps"], prec)
        pre = reflib.from_blocks(jnp.swapaxes(tmp, 1, 2), lay, g.shape)
        gn = g / (jnp.linalg.norm(g) + 1e-16)
        acc = b2 * st["graft"] + (1 - b2) * jnp.square(gn)
        graft = gn * jax.lax.rsqrt(acc + h["graft_eps"])
        out.append(pre * (jnp.linalg.norm(graft)
                          / (jnp.linalg.norm(pre) + 1e-16)))
        new.append(dict(st, graft=acc))
    return out, new


def make_update(h: dict, mode: str):
    """``update(params, grads, state, count) -> (params, state, d)``:
    parameters and momentum stored in the parameters' type, ``d`` the
    float32 Sketchy direction of this step."""
    prec = reflib.fd_precision(mode)

    def update(params, grads, state, count, lr):
        g = clip([x.astype(jnp.float32) for x in grads], h["clip"])
        d, leaves = direction(g, state["leaves"], count, h, prec)
        b1, mus, new_p = h["beta1"], [], []
        for p, m, di in zip(params, state["mu"], d):
            mu = (b1 * m.astype(jnp.float32) + (1 - b1) * di).astype(p.dtype)
            u = mu.astype(jnp.float32) + h["weight_decay"] * p.astype(
                jnp.float32)
            new_p.append((p.astype(jnp.float32) - lr * u).astype(p.dtype))
            mus.append(mu)
        return new_p, {"leaves": leaves, "mu": mus}, d

    jits = {}

    def run(params, grads, state, count):
        refresh = count % h["update_every"] == 0
        if refresh not in jits:
            jits[refresh] = jax.jit(
                lambda p, g, s, lr, c=count: update(p, g, s, c, lr))
        return jits[refresh](params, grads, state,
                             jnp.float32(lr_at(h, count)))

    return run


def ref_first_grad_sq(state, h) -> list:
    """Per leaf, the squared norm of the first clipped gradient as the
    sketch keeps it: the top-``rank`` eigenvalue mass of every block's
    left Gram (``sum(s) + ell * rho``), or ``||g||^2`` for a vector."""
    out = []
    for st in state["leaves"]:
        if "L" in st:
            L = st["L"]
            out.append(float(jnp.sum(L["s"]) + L["s"].shape[-1]
                             * jnp.sum(L["rho"])))
        else:
            out.append(float(jnp.sum(st["acc"])) / (1 - h["beta2"]))
    return out


def program_first_grad_sq(opt_state, params, h):
    """The same quantity read from the program's state after its first
    step (a jittable function of the state)."""
    from repro.core import api, pool

    pre = api.get_stage(opt_state, "precond")
    flat = jax.tree.leaves(params)
    index = pool.build_index(tuple(tuple(p.shape) for p in flat),
                             h["block_size"])
    out = []
    for i, plan in enumerate(index.leaves):
        if plan.group is None:
            acc = api.untag(pre.leaves[i].stats)
            out.append(jnp.sum(acc) / (1 - h["beta2"]))
            continue
        grp = index.groups[plan.group]
        left = api.untag(pre.pools[grp.key]).left
        sl = slice(plan.offset, plan.offset + plan.info.num_blocks)
        s, rho = left.eigvals[sl], left.rho[sl]
        out.append(jnp.sum(s) + s.shape[-1] * jnp.sum(rho))
    return jnp.stack(out)


def program_first_direction(opt_state, h) -> list:
    """The program's first direction, from its momentum after one step
    (momentum starts at zero: ``mu_1 = (1 - beta1) d_0``)."""
    from repro.core import api
    mom = api.untag(api.get_stage(opt_state, "momentum").momentum)
    return [m.astype(jnp.float32) / (1 - h["beta1"])
            for m in jax.tree.leaves(mom)]
