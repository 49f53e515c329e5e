"""Sketchy Shampoo (paper Alg. 3 + Obs. 6 EMA variant) as a small
``Preconditioner`` on the shared ``scale_by_preconditioner`` engine.

Per matrix block (paper §3.4 blocking, default 1024):
  every ``update_every`` steps (paper observes only every 10th gradient —
  the "harder setting" of §6):
      (rho_L, L-sketch) <- FD-update(beta2 * L-sketch, G G^T)
      (rho_R, R-sketch) <- FD-update(beta2 * R-sketch, G^T G)
  every step:
      P = (L-sketch + (rho_L+eps) I)^{-1/4}  G  (R-sketch + (rho_R+eps) I)^{-1/4}
computed entirely in factored (U, s, rho) form — the d x d preconditioner is
never materialized and the second-moment state is O((m+n) * ell) per block
instead of O(m^2 + n^2) (Shampoo) or O(mn) (Adam).

Blocking, the diagonal (RMSProp) path for vectors/scalars, grafting (paper
App. C: RMSPROP_NORMALIZED), and the ``update_every`` /
``start_preconditioning_step`` gating all live in the engine (core/api.py);
this module only supplies the FD sketch pair.  The engine injects its
resolved ``KernelSet`` (``kernel_backend`` knob: pallas | xla | auto) into
``kernels``; the ``*_batched`` methods — the pooled hot path — route the
Gram and the fused low-rank apply through the grid-over-N batched kernels,
one call per packed pool stack instead of a vmap over single-block kernels.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, ClassVar, NamedTuple, Optional

import jax.numpy as jnp

from repro.core import api, blocking, pool
from repro.core.fd import (FDState, fd_apply_inverse_root,
                           fd_apply_inverse_root_batched, fd_init,
                           fd_resize_batched, fd_update, fd_update_batched)
from repro.core.transform import GradientTransformation
from repro.kernels.registry import KernelSet

RANK_POLICIES = ("static", "rho_greedy")

DEFAULT_RANK = 256                  # paper fixes 256 (untuned)


@dataclasses.dataclass(frozen=True)
class RankBudget:
    """The rank API: one fixed total sketch-rank budget over all blocks.

    Every pooled block stores its FD sketch pair at *capacity*
    ``ell = min(max_k, dim)`` — the packed ``(N, d, ell)`` stacks (and
    therefore ``second_moment_bytes``) are sized by ``max_k`` alone — but
    each block's *active* rank ``k_b`` is a masked ladder prefix, with
    ``sum_b k_b == total`` held fixed.

    Policies:
      * ``"static"`` — every block keeps ``k_b`` at capacity forever;
        bitwise-identical to the pre-budget engine (the deprecated
        ``SketchyConfig(rank=r)`` spelling maps here with
        ``min_k == max_k == r``).
      * ``"rho_greedy"`` — at refresh boundaries (every
        ``realloc_every * update_every`` steps) the budget is re-poured
        across blocks by descending escaped-mass pressure
        ``rho / (trace + rho)``: blocks whose sketch is dropping the most
        mass grow (zero columns unmask), blocks that are over-provisioned
        shrink via exact Robust-FD deflation (dropped eigenvalue mass
        folds into ``rho``, preserving the per-block FD bound).

    ``total=None`` resolves to ``N_blocks * max_k`` at init (full capacity,
    useful with ``min_k`` to carve slack); an explicit total must satisfy
    ``N * min_k <= total <= N * max_k``.
    """
    total: Optional[int] = None
    min_k: int = 1
    max_k: int = DEFAULT_RANK
    realloc_every: int = 1          # in refresh windows (update_every steps)
    policy: str = "static"          # static | rho_greedy

    def __post_init__(self):
        if self.policy not in RANK_POLICIES:
            raise ValueError(f"unknown RankBudget policy {self.policy!r}; "
                             f"expected one of {RANK_POLICIES}")
        if not (1 <= self.min_k <= self.max_k):
            raise ValueError(f"need 1 <= min_k <= max_k, got "
                             f"min_k={self.min_k} max_k={self.max_k}")
        if self.realloc_every < 1:
            raise ValueError(f"realloc_every must be >= 1, got "
                             f"{self.realloc_every}")

    def resolve_total(self, num_blocks: int) -> int:
        """Concrete ``K_total`` once the model's block count is known."""
        total = self.total if self.total is not None \
            else num_blocks * self.max_k
        if not (num_blocks * self.min_k <= total <= num_blocks * self.max_k):
            raise ValueError(
                f"rank budget total={total} infeasible for {num_blocks} "
                f"blocks with min_k={self.min_k} max_k={self.max_k}: need "
                f"{num_blocks * self.min_k} <= total <= "
                f"{num_blocks * self.max_k}")
        return total


@dataclasses.dataclass(frozen=True)
class SketchyConfig:
    # Deprecated alias for ``rank_budget=RankBudget(min_k=r, max_k=r,
    # policy="static")``; after construction this field always reads as the
    # normalized capacity ``rank_budget.max_k`` (legacy consumers keep
    # working).  Pass ``rank_budget`` instead.
    rank: Optional[int] = None
    block_size: int = 1024          # paper App. C
    beta2: float = 0.999            # second-moment EMA (paper §5.2)
    update_every: int = 10          # FD observes every k-th gradient (paper §6)
    start_preconditioning_step: int = 0   # paper App. C uses 101 at scale
    matrix_eps: float = 1e-6
    graft_eps: float = 1e-8
    diag_eps: Optional[float] = None      # diag-fallback damping (None => graft_eps)
    graft: str = "rmsprop_normalized"     # rmsprop_normalized | rmsprop | none
    refresh_schedule: str = "synchronized"  # synchronized | staggered
    # "inline" (parity default) | "async": launch the FD refresh at step t
    # from the just-updated stats, commit it at t+1 — the eigh and the
    # butterfly merge rounds leave the update direction's critical path
    # (engine refresh pipeline, core/api.py)
    refresh_mode: str = "inline"
    exponent: float = -0.25         # per-side inverse root (Alg. 3)
    state_dtype: Any = jnp.float32
    # kernel backend for the pooled hot path (engine-resolved KernelSet):
    # "pallas" | "xla" | "auto" — replaces the old private use_kernels flag
    kernel_backend: str = "auto"
    # storage dtype for the pooled FD sketches between steps
    # (core/quantize.py): "fp32" (bitwise parity) | "bf16" | "int8"
    second_moment_dtype: str = "fp32"
    # second-moment maintenance across data-parallel shards
    # (src/repro/distributed/): "replicated" (parity default) | "sharded"
    # (local FD updates + log-depth butterfly sketch merge over stats_axis
    # at refresh time)
    stats_reduction: str = "replicated"
    stats_axis: str = "data"
    # exchange precision for the merge wire (sketch_merge.pack_wire):
    # "int8" (default, ~(ell-1)*d int8 per block per round) | "fp32"
    # (exact merge — the FD error bound holds with no quantization slack)
    stats_wire_dtype: str = "int8"
    # fused int8 compute (core/api.py): "auto" (on when second_moment_dtype
    # is int8, the pallas backend is resolved, and stats are replicated) |
    # "off" (always dequantize at the boundary) | "on" (force; any backend)
    quantized_epilogue: str = "auto"
    # The primary rank spelling: fixed total budget + per-block active-rank
    # policy (see RankBudget).  None => normalized from the deprecated
    # ``rank`` field (or the paper default 256) in __post_init__.
    rank_budget: Optional[RankBudget] = None

    def __post_init__(self):
        budget = self.rank_budget
        if budget is None:
            rank = self.rank
            if rank is not None:
                warnings.warn(
                    "SketchyConfig(rank=...) is deprecated; use "
                    "rank_budget=RankBudget(min_k=r, max_k=r) (see the "
                    "CHANGES.md migration table)",
                    DeprecationWarning, stacklevel=3)
            else:
                rank = DEFAULT_RANK
            budget = RankBudget(min_k=rank, max_k=rank, policy="static")
        elif self.rank is not None and self.rank != budget.max_k:
            raise ValueError(
                f"pass either rank (deprecated) or rank_budget, not both "
                f"(got rank={self.rank}, rank_budget.max_k={budget.max_k})")
        # normalize: cfg.rank always reads as the capacity for legacy
        # consumers (e.g. tests/reference_impls.py reads cfg.rank)
        object.__setattr__(self, "rank_budget", budget)
        object.__setattr__(self, "rank", budget.max_k)


class SketchyBlockStats(NamedTuple):
    """Per-block FD sketch pair; in engine state these are batched over the
    leaf's block stack: eigvecs (S, d, ell), eigvals (S, ell), rho (S,)."""
    left: FDState
    right: FDState


class BudgetedSketchStats(NamedTuple):
    """``SketchyBlockStats`` plus the per-block active-rank vector ``k``
    (rank-budget policies other than static).  ``k`` is shared by both
    sides — the budget counts each block once; per side the effective
    column count is ``min(k_b, ell_side)`` via the masked-rank update."""
    left: FDState
    right: FDState
    k: Any              # Tagged (N,) int32, role="count", label="active_rank"


def _tag_fd(st: FDState) -> FDState:
    # rho / eigvals carry telemetry labels so api.rank_allocation can
    # traverse them without type dispatch
    return FDState(
        eigvecs=api.tag(st.eigvecs, "second_moment", blocked=True),
        eigvals=api.tag(st.eigvals, "second_moment", blocked=True,
                        label="eigvals"),
        rho=api.tag(st.rho, "second_moment", blocked=True, label="rho"))


def _sketch_pressure(fd: FDState) -> jnp.ndarray:
    """(N,) escaped-mass ratio ``rho / (trace + rho)`` — high means this
    block's sketch is dropping mass and is starving for columns."""
    trace = jnp.sum(jnp.maximum(fd.eigvals.astype(jnp.float32), 0.0), axis=-1)
    rho = jnp.maximum(fd.rho.astype(jnp.float32), 0.0)
    return rho / (trace + rho + 1e-30)


@dataclasses.dataclass(frozen=True)
class SketchyPreconditioner:
    """FD sketch pair (paper Alg. 3) — the whole optimizer-specific surface.

    ``kernels`` is injected by the engine (``EngineConfig.kernel_backend``);
    ``None`` means plain jnp.  The batched methods run once per packed
    ``(N, bs_m, bs_n)`` pool stack.
    """
    cfg: SketchyConfig
    kernels: Optional[KernelSet] = None

    diagonal: ClassVar[bool] = False
    # the batched FD methods dispatch on QuantizedPool eigvec stacks
    # (core/fd.py), so the engine's fused int8 mode can hand this
    # preconditioner the storage containers directly
    supports_quantized_compute: ClassVar[bool] = True

    def init_block(self, info: blocking.BlockInfo):
        budget = self.cfg.rank_budget
        ell_l = min(budget.max_k, info.bs_m)
        ell_r = min(budget.max_k, info.bs_n)
        left = _tag_fd(fd_init(info.bs_m, ell_l, self.cfg.state_dtype))
        right = _tag_fd(fd_init(info.bs_n, ell_r, self.cfg.state_dtype))
        if budget.policy == "static":
            return SketchyBlockStats(left=left, right=right)
        # adaptive policies carry a per-block active rank; the engine
        # broadcasts this scalar over the pool dim and finalize_init_pools
        # replaces it with the uniform initial allocation
        k = api.tag(jnp.asarray(budget.min_k, jnp.int32), "count",
                    blocked=True, label="active_rank")
        return BudgetedSketchStats(left=left, right=right, k=k)

    def finalize_init_pools(self, groups, stacks: dict) -> dict:
        """Engine init hook: seed the cross-pool uniform rank allocation.

        ``stacks`` maps group key -> broadcast Tagged stats stack.  The
        budget is global — one ``K_total`` over every block in every pool —
        so the uniform seed is computed over the concatenated block list
        (and feasibility is validated here, the first point where N is
        known)."""
        budget = self.cfg.rank_budget
        if budget.policy == "static":
            return stacks
        ns = [g.num_blocks for g in groups]
        total = budget.resolve_total(sum(ns))
        k_all = pool.uniform_ranks(sum(ns), total, budget.min_k,
                                   budget.max_k)
        out, offset = dict(stacks), 0
        for g, n in zip(groups, ns):
            st = stacks[g.key]
            out[g.key] = st._replace(
                k=api.Tagged(k_all[offset:offset + n], st.k.meta))
            offset += n
        return out

    def realloc_pools(self, groups, stacks: dict) -> dict:
        """Engine refresh-boundary hook: re-pour the fixed rank budget.

        ``stacks`` holds the just-refreshed raw (untagged) stats per group.
        Pressure is the per-block escaped-mass ratio summed over sides;
        the greedy waterfill (core/pool.py) is exact and deterministic, so
        every data-parallel shard computes the identical allocation from
        the merged (replicated) statistics — no extra communication.
        Shrunk blocks fold the dropped eigenvalue mass into ``rho``
        (fd_resize_batched), grown blocks unmask zero columns."""
        budget = self.cfg.rank_budget
        ns = [g.num_blocks for g in groups]
        total = budget.resolve_total(sum(ns))
        pressure = jnp.concatenate([
            _sketch_pressure(stacks[g.key].left)
            + _sketch_pressure(stacks[g.key].right) for g in groups])
        k_all = pool.allocate_ranks(pressure, total=total,
                                    min_k=budget.min_k, max_k=budget.max_k)
        out, offset = dict(stacks), 0
        for g, n in zip(groups, ns):
            st = stacks[g.key]
            k = k_all[offset:offset + n]
            out[g.key] = st._replace(
                left=fd_resize_batched(st.left, k),
                right=fd_resize_batched(st.right, k), k=k)
            offset += n
        return out

    # ------------------------------------------------- per-block (reference)

    def update_stats(self, state, G, *, count):
        return state  # FD observation is the gated refresh, not per-step

    def refresh(self, state, G, *, count):
        return SketchyBlockStats(
            left=fd_update(state.left, G, self.cfg.beta2,
                           kernels=self.kernels),
            right=fd_update(state.right, G.T, self.cfg.beta2,
                            kernels=self.kernels))

    def precondition(self, state, G, *, count):
        tmp = fd_apply_inverse_root(state.left, G,
                                    exponent=self.cfg.exponent,
                                    eps=self.cfg.matrix_eps,
                                    kernels=self.kernels)
        tmpT = fd_apply_inverse_root(state.right, tmp.T,
                                     exponent=self.cfg.exponent,
                                     eps=self.cfg.matrix_eps,
                                     kernels=self.kernels)
        return tmpT.T

    # ------------------------------------------- pooled-stack (kernel path)

    def update_stats_batched(self, state, G, *, count):
        return state

    def refresh_batched(self, state, G, *, count):
        # budgeted stats carry the per-block active rank; the static
        # container has no ``k`` and takes the unmasked (bitwise-pinned)
        # path through fd_update_batched
        active_k = getattr(state, "k", None)
        return state._replace(
            left=fd_update_batched(state.left, G, self.cfg.beta2,
                                   kernels=self.kernels, active_k=active_k),
            right=fd_update_batched(state.right, jnp.swapaxes(G, -1, -2),
                                    self.cfg.beta2, kernels=self.kernels,
                                    active_k=active_k))

    def refresh_sharded_batched(self, state, G, *, count, axis, axis_size):
        """Sharded-statistics refresh (engine ``stats_reduction="sharded"``):
        FD-update both sketch stacks on this shard's LOCAL gradient stack,
        then butterfly-merge each across the data axis so every shard ends
        the refresh holding the identical combined sketch.  Must run inside
        ``shard_map`` with ``axis`` bound (the engine guarantees it).

        The incoming sketch is replicated over the axis (the previous merge
        left it so), and the butterfly *sums* covariances — so the carried
        state is pre-scaled by 1/P to enter the merged total exactly once:
        merged ~= beta2 * S_prev + (1/P) sum_i G_i G_i^T (the engine already
        scaled the local gradient stack by 1/sqrt(P)), which coincides with
        the replicated ``beta2 * S_prev + Gbar Gbar^T`` when shards agree.
        """
        from repro.distributed import reduce as dreduce
        inv = 1.0 / axis_size
        scale = lambda fd: FDState(eigvecs=fd.eigvecs,
                                   eigvals=fd.eigvals * inv,
                                   rho=fd.rho * inv)
        state = state._replace(left=scale(state.left),
                               right=scale(state.right))
        local = self.refresh_batched(state, G, count=count)
        merge = lambda st: dreduce.butterfly_merge_fd(
            st, axis=axis, axis_size=axis_size, kernels=self.kernels,
            wire_dtype=self.cfg.stats_wire_dtype)
        merged = local._replace(left=merge(local.left),
                                right=merge(local.right))
        active_k = getattr(merged, "k", None)
        if active_k is not None:
            # the butterfly re-sketches at full capacity ell, so the merged
            # ladder can spill past the block's active rank — re-mask it,
            # folding the spilled mass into rho (exact Robust-FD deflation)
            merged = merged._replace(
                left=fd_resize_batched(merged.left, active_k),
                right=fd_resize_batched(merged.right, active_k))
        return merged

    def precondition_batched(self, state, G, *, count):
        tmp = fd_apply_inverse_root_batched(
            state.left, G, exponent=self.cfg.exponent,
            eps=self.cfg.matrix_eps, kernels=self.kernels)
        tmpT = fd_apply_inverse_root_batched(
            state.right, jnp.swapaxes(tmp, -1, -2),
            exponent=self.cfg.exponent, eps=self.cfg.matrix_eps,
            kernels=self.kernels)
        return jnp.swapaxes(tmpT, -1, -2)


def sketchy(cfg: SketchyConfig = SketchyConfig()) -> GradientTransformation:
    """S-Shampoo direction transform (emits a descent direction, no lr)."""
    budget = cfg.rank_budget
    realloc_every = budget.realloc_every if budget.policy != "static" else 0
    return api.scale_by_preconditioner(
        SketchyPreconditioner(cfg),
        api.EngineConfig(
            block_size=cfg.block_size, beta2=cfg.beta2,
            update_every=cfg.update_every,
            realloc_every=realloc_every,
            start_preconditioning_step=cfg.start_preconditioning_step,
            graft=cfg.graft, graft_eps=cfg.graft_eps, diag_eps=cfg.diag_eps,
            refresh_schedule=cfg.refresh_schedule,
            refresh_mode=cfg.refresh_mode,
            kernel_backend=cfg.kernel_backend,
            second_moment_dtype=cfg.second_moment_dtype,
            quantized_epilogue=cfg.quantized_epilogue,
            stats_reduction=cfg.stats_reduction,
            stats_axis=cfg.stats_axis,
            state_dtype=cfg.state_dtype))


def second_moment_bytes(state) -> int:
    """Covariance-tracking bytes — the paper's headline memory quantity
    (excludes grafting/momentum, as Fig. 1 does)."""
    return api.second_moment_bytes(state)
