"""Frozen copies of the SEED (pre-unification) optimizer monoliths, plus the
PR-1 PER-LEAF engine (pre-pool).

Test fixture only: the parity tests in test_preconditioner_api.py assert the
new ``scale_by_preconditioner``-based sketchy/shampoo/adam produce
numerically identical updates to these originals, and test_pool.py pins the
pooled engine *bitwise* to ``per_leaf_scale_by_preconditioner`` (the PR-1
engine that dispatched once per parameter leaf).  Do not import from
production code.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import blocking
from repro.core.adam import AdamConfig
from repro.core.fd import FDState, fd_apply_inverse_root, fd_init, fd_update
from repro.core.shampoo import ShampooConfig
from repro.core.sketchy import SketchyConfig
from repro.core.transform import GradientTransformation


# --------------------------------------------------------------------- sketchy

class MatrixLeafState(NamedTuple):
    left: FDState
    right: FDState
    graft_acc: jnp.ndarray


class DiagLeafState(NamedTuple):
    acc: jnp.ndarray


class SketchyState(NamedTuple):
    count: jnp.ndarray
    leaves: tuple


def _graft_direction(g, acc, cfg: SketchyConfig):
    if cfg.graft == "none":
        return g, acc
    if cfg.graft == "rmsprop_normalized":
        gn = g / (jnp.linalg.norm(g) + 1e-16)
    else:
        gn = g
    acc = cfg.beta2 * acc + (1.0 - cfg.beta2) * jnp.square(gn)
    return gn * jax.lax.rsqrt(acc + cfg.graft_eps), acc


def _vmapped_fd_update(states: FDState, factors: jnp.ndarray,
                       beta2: float) -> FDState:
    return jax.vmap(lambda s, a: fd_update(s, a, beta2))(states, factors)


def _precondition_blocks(left: FDState, right: FDState, gb: jnp.ndarray,
                         cfg: SketchyConfig) -> jnp.ndarray:
    def one(ls, rs, G):
        tmp = fd_apply_inverse_root(ls, G, exponent=cfg.exponent,
                                    eps=cfg.matrix_eps)
        tmpT = fd_apply_inverse_root(rs, tmp.T, exponent=cfg.exponent,
                                     eps=cfg.matrix_eps)
        return tmpT.T

    return jax.vmap(one)(left, right, gb)


def seed_sketchy(cfg: SketchyConfig = SketchyConfig()) -> GradientTransformation:
    def init_leaf(p):
        info = blocking.analyze(p.shape, cfg.block_size)
        if info.kind == "diag":
            return DiagLeafState(acc=jnp.zeros(p.shape, cfg.state_dtype))
        S = info.num_blocks
        ell_l = min(cfg.rank, info.bs_m)
        ell_r = min(cfg.rank, info.bs_n)

        def batched_fd(d, ell):
            base = fd_init(d, ell, cfg.state_dtype)
            return FDState(*[jnp.broadcast_to(x, (S,) + x.shape) for x in base])

        return MatrixLeafState(
            left=batched_fd(info.bs_m, ell_l),
            right=batched_fd(info.bs_n, ell_r),
            graft_acc=jnp.zeros(p.shape, cfg.state_dtype),
        )

    def init_fn(params):
        leaves = tuple(init_leaf(p) for p in jax.tree.leaves(params))
        return SketchyState(count=jnp.zeros([], jnp.int32), leaves=leaves)

    def update_leaf(g, st, count):
        g32 = g.astype(jnp.float32)
        info = blocking.analyze(g.shape, cfg.block_size)
        if info.kind == "diag":
            acc = cfg.beta2 * st.acc + (1.0 - cfg.beta2) * jnp.square(g32)
            direction = g32 * jax.lax.rsqrt(acc + cfg.graft_eps)
            return direction.astype(g.dtype), DiagLeafState(acc=acc)

        gb = blocking.to_blocks(g32, info)
        gbT = jnp.swapaxes(gb, -1, -2)

        do_stats = (count % cfg.update_every) == 0

        def with_stats(s):
            return MatrixLeafState(
                left=_vmapped_fd_update(s.left, gb, cfg.beta2),
                right=_vmapped_fd_update(s.right, gbT, cfg.beta2),
                graft_acc=s.graft_acc,
            )

        st = jax.lax.cond(do_stats, with_stats, lambda s: s, st)

        pb = _precondition_blocks(st.left, st.right, gb, cfg)
        precond = blocking.from_blocks(pb, info)

        graft_dir, new_acc = _graft_direction(g32, st.graft_acc, cfg)
        if cfg.graft != "none":
            pnorm = jnp.linalg.norm(precond)
            gnorm = jnp.linalg.norm(graft_dir)
            precond = precond * (gnorm / (pnorm + 1e-16))

        use_precond = count >= cfg.start_preconditioning_step
        direction = jnp.where(use_precond, precond, graft_dir)
        return direction.astype(g.dtype), MatrixLeafState(st.left, st.right,
                                                          new_acc)

    def update_fn(updates, state, params=None):
        del params
        flat, treedef = jax.tree.flatten(updates)
        out_flat, new_leaves = [], []
        for g, st in zip(flat, state.leaves):
            d, ns = update_leaf(g, st, state.count)
            out_flat.append(d)
            new_leaves.append(ns)
        return (jax.tree.unflatten(treedef, out_flat),
                SketchyState(count=state.count + 1, leaves=tuple(new_leaves)))

    return GradientTransformation(init_fn, update_fn)


# --------------------------------------------------------------------- shampoo

class ShampooMatrixLeaf(NamedTuple):
    L: jnp.ndarray
    R: jnp.ndarray
    PL: jnp.ndarray
    PR: jnp.ndarray
    graft_acc: jnp.ndarray


class ShampooDiagLeaf(NamedTuple):
    acc: jnp.ndarray


class ShampooState(NamedTuple):
    count: jnp.ndarray
    leaves: tuple


def _inv_root(mats: jnp.ndarray, eps: float, power: float) -> jnp.ndarray:
    def one(m):
        d = m.shape[-1]
        lam, V = jnp.linalg.eigh(m + eps * jnp.eye(d, dtype=m.dtype))
        lam = jnp.maximum(lam, eps)
        return (V * jnp.power(lam, power)[None, :]) @ V.T

    return jax.vmap(one)(mats)


def seed_shampoo(cfg: ShampooConfig = ShampooConfig()) -> GradientTransformation:
    graft_cfg = SketchyConfig(beta2=cfg.beta2, graft=cfg.graft,
                              graft_eps=cfg.graft_eps)

    def init_leaf(p):
        info = blocking.analyze(p.shape, cfg.block_size)
        if info.kind == "diag":
            return ShampooDiagLeaf(acc=jnp.zeros(p.shape, cfg.state_dtype))
        S = info.num_blocks
        eye_m = jnp.eye(info.bs_m, dtype=cfg.state_dtype)
        eye_n = jnp.eye(info.bs_n, dtype=cfg.state_dtype)
        zeros = lambda d: jnp.zeros((S, d, d), cfg.state_dtype)
        return ShampooMatrixLeaf(
            L=zeros(info.bs_m), R=zeros(info.bs_n),
            PL=jnp.broadcast_to(eye_m, (S, info.bs_m, info.bs_m)),
            PR=jnp.broadcast_to(eye_n, (S, info.bs_n, info.bs_n)),
            graft_acc=jnp.zeros(p.shape, cfg.state_dtype),
        )

    def init_fn(params):
        leaves = tuple(init_leaf(p) for p in jax.tree.leaves(params))
        return ShampooState(count=jnp.zeros([], jnp.int32), leaves=leaves)

    def update_leaf(g, st, count):
        g32 = g.astype(jnp.float32)
        info = blocking.analyze(g.shape, cfg.block_size)
        if info.kind == "diag":
            acc = cfg.beta2 * st.acc + (1.0 - cfg.beta2) * jnp.square(g32)
            return (g32 * jax.lax.rsqrt(acc + cfg.graft_eps)).astype(g.dtype), \
                ShampooDiagLeaf(acc=acc)

        gb = blocking.to_blocks(g32, info)
        gbT = jnp.swapaxes(gb, -1, -2)
        # L is the Gram of the transposed blocks, contracted in the order
        # the engine's batched Gram uses (the CPU backend rounds an
        # in-place minor-dim contraction differently, and the inverse 4th
        # root amplifies that difference in the null space)
        L = cfg.beta2 * st.L + jnp.einsum("sji,sjk->sik", gbT, gbT)
        R = cfg.beta2 * st.R + jnp.einsum("sji,sjk->sik", gb, gb)

        def refresh(_):
            return (_inv_root(L, cfg.matrix_eps, -0.25),
                    _inv_root(R, cfg.matrix_eps, -0.25))

        do_roots = (count % cfg.root_every) == 0
        PL, PR = jax.lax.cond(do_roots, refresh, lambda _: (st.PL, st.PR),
                              None)

        pb = jnp.einsum("sij,sjk,skl->sil", PL, gb, PR)
        precond = blocking.from_blocks(pb, info)

        graft_dir, new_acc = _graft_direction(g32, st.graft_acc, graft_cfg)
        if cfg.graft != "none":
            precond = precond * (jnp.linalg.norm(graft_dir)
                                 / (jnp.linalg.norm(precond) + 1e-16))
        use_precond = count >= cfg.start_preconditioning_step
        direction = jnp.where(use_precond, precond, graft_dir)
        return direction.astype(g.dtype), ShampooMatrixLeaf(L, R, PL, PR,
                                                            new_acc)

    def update_fn(updates, state, params=None):
        del params
        flat, treedef = jax.tree.flatten(updates)
        out, leaves = [], []
        for g, st in zip(flat, state.leaves):
            d, ns = update_leaf(g, st, state.count)
            out.append(d)
            leaves.append(ns)
        return (jax.tree.unflatten(treedef, out),
                ShampooState(count=state.count + 1, leaves=tuple(leaves)))

    return GradientTransformation(init_fn, update_fn)


# ------------------------------------------------------------------------ adam

class AdamState(NamedTuple):
    count: jnp.ndarray
    mu: object
    nu: object


def seed_adam(cfg: AdamConfig = AdamConfig()) -> GradientTransformation:
    def init_fn(params):
        zeros = lambda p: jnp.zeros(p.shape, cfg.state_dtype)
        return AdamState(count=jnp.zeros([], jnp.int32),
                         mu=jax.tree.map(zeros, params),
                         nu=jax.tree.map(zeros, params))

    def update_fn(updates, state, params=None):
        del params
        count = state.count + 1
        mu = jax.tree.map(
            lambda m, g: cfg.beta1 * m + (1 - cfg.beta1) * g.astype(m.dtype),
            state.mu, updates)
        nu = jax.tree.map(
            lambda v, g: cfg.beta2 * v
            + (1 - cfg.beta2) * jnp.square(g.astype(v.dtype)),
            state.nu, updates)
        bc1 = 1 - cfg.beta1 ** count.astype(jnp.float32)
        bc2 = 1 - cfg.beta2 ** count.astype(jnp.float32)
        out = jax.tree.map(
            lambda m, v, g: ((m / bc1)
                             * jax.lax.rsqrt(v / bc2 + cfg.eps ** 2)
                             ).astype(g.dtype),
            mu, nu, updates)
        return out, AdamState(count=count, mu=mu, nu=nu)

    return GradientTransformation(init_fn, update_fn)


# ----------------------------------------------------- PR-1 per-leaf engine
# Frozen copy of core/api.scale_by_preconditioner BEFORE the block-pool
# rebase: one vmapped update/refresh/precondition dispatch per parameter
# leaf.  Tags are stripped (state leaves are raw arrays) — the pooled engine
# must be bitwise-identical to this on directions and statistics under
# refresh_schedule="synchronized".

class PerLeafState(NamedTuple):
    count: jnp.ndarray
    leaves: tuple


class PerLeafLeaf(NamedTuple):
    stats: object
    graft: object


def per_leaf_scale_by_preconditioner(precond, cfg) -> GradientTransformation:
    """cfg is an api.EngineConfig; precond a production Preconditioner."""
    from repro.core import api

    def leaf_info(shape):
        return blocking.analyze_leaf(
            tuple(shape), cfg.block_size,
            vectors_as_columns=cfg.treat_vectors_as_columns)

    def init_leaf(p):
        info = leaf_info(p.shape)
        if info.kind == "diag":
            return PerLeafLeaf(stats=jnp.zeros(p.shape, cfg.state_dtype),
                               graft=None)
        base = api.untag(precond.init_block(info))
        S = info.num_blocks
        stats = jax.tree.map(lambda x: jnp.broadcast_to(x, (S,) + x.shape),
                             base)
        graft = (jnp.zeros(p.shape, cfg.state_dtype)
                 if cfg.graft != "none" else None)
        return PerLeafLeaf(stats=stats, graft=graft)

    def init_fn(params):
        return PerLeafState(
            count=jnp.zeros([], jnp.int32),
            leaves=tuple(init_leaf(p) for p in jax.tree.leaves(params)))

    def update_leaf(g, leaf, count):
        g32 = g.astype(jnp.float32)
        info = leaf_info(g.shape)

        if info.kind == "diag":
            acc = cfg.beta2 * leaf.stats + (1.0 - cfg.beta2) * jnp.square(g32)
            direction = g32 * jax.lax.rsqrt(acc + cfg.graft_eps)
            return (direction.astype(g.dtype),
                    PerLeafLeaf(stats=acc, graft=None))

        gb = blocking.to_blocks(g32, info)
        raw = jax.vmap(
            lambda s, G: precond.update_stats(s, G, count=count))(leaf.stats,
                                                                  gb)

        def do_refresh(s):
            return jax.vmap(
                lambda ss, G: precond.refresh(ss, G, count=count))(s, gb)

        if cfg.update_every <= 1:
            raw = do_refresh(raw)
        else:
            raw = jax.lax.cond((count % cfg.update_every) == 0,
                               do_refresh, lambda s: s, raw)

        pb = jax.vmap(
            lambda s, G: precond.precondition(s, G, count=count))(raw, gb)
        direction = blocking.from_blocks(pb, info)

        if cfg.graft != "none":
            graft_dir, new_acc = api.graft_direction(
                g32, leaf.graft, graft=cfg.graft, beta2=cfg.beta2,
                graft_eps=cfg.graft_eps)
            pnorm = jnp.linalg.norm(direction)
            gnorm = jnp.linalg.norm(graft_dir)
            direction = direction * (gnorm / (pnorm + 1e-16))
        else:
            graft_dir = g32
            new_acc = None

        if cfg.start_preconditioning_step > 0:
            use_precond = count >= cfg.start_preconditioning_step
            direction = jnp.where(use_precond, direction, graft_dir)
        return (direction.astype(g.dtype),
                PerLeafLeaf(stats=raw, graft=new_acc))

    def update_fn(updates, state, params=None):
        del params
        flat, treedef = jax.tree.flatten(updates)
        out, new_leaves = [], []
        for g, leaf in zip(flat, state.leaves):
            d, nl = update_leaf(g, leaf, state.count)
            out.append(d)
            new_leaves.append(nl)
        return (jax.tree.unflatten(treedef, out),
                PerLeafState(count=state.count + 1,
                             leaves=tuple(new_leaves)))

    return GradientTransformation(init_fn, update_fn)
