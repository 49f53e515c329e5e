"""Full (blocked) Shampoo baseline — the paper's primary comparison — as a
small ``Preconditioner`` on the shared ``scale_by_preconditioner`` engine.

Kronecker-factored preconditioning with *dense* per-block factors
L (bm x bm), R (bn x bn), EMA statistics accumulated every step, inverse 4th
roots recomputed every ``root_every`` steps via eigh (the ``eigh=True`` path
the paper uses, App. E).  Second-moment memory is O(bm^2 + bn^2) per block —
what Sketchy reduces.  Blocking, grafting, the diagonal fallback, and gating
live in the engine (core/api.py).

Shampoo's L/R statistic updates are the same Gram contraction as the FD
update (cf. Morwani et al., *A New Perspective on Shampoo's Preconditioner*):
L += G G^T is the Gram of G^T and R += G^T G the Gram of G.  The engine
injects its resolved ``KernelSet`` into ``kernels``, so the batched methods
route both contractions through the grid-over-N batched gram kernel — the
same kernel path Sketchy uses, one call per packed pool stack.
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import api, blocking
from repro.core.transform import GradientTransformation
from repro.kernels.registry import KernelSet

# full-f32 contractions (a TPU's default f32 dot is one bf16 pass, and the
# inverse 4th root amplifies error in the statistics' small eigenvalues)
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ShampooConfig:
    block_size: int = 1024
    beta2: float = 0.999
    root_every: int = 10            # paper: preconditioning_compute_steps=10
    start_preconditioning_step: int = 0
    matrix_eps: float = 1e-6
    graft_eps: float = 1e-8
    diag_eps: Optional[float] = None    # diag-fallback damping (None => graft_eps)
    graft: str = "rmsprop_normalized"
    refresh_schedule: str = "synchronized"  # synchronized | staggered
    # "inline" (parity default) | "async": root recompute launched at step t
    # commits at t+1 via the engine's pending slot (core/api.py)
    refresh_mode: str = "inline"
    state_dtype: Any = jnp.float32
    # kernel backend for the pooled stat-update Grams: "pallas"|"xla"|"auto"
    kernel_backend: str = "auto"
    # storage dtype for the pooled L/R statistics between steps
    # (core/quantize.py): "fp32" (bitwise parity) | "bf16" | "int8"
    second_moment_dtype: str = "fp32"


class ShampooBlockStats(NamedTuple):
    L: jnp.ndarray       # (bm, bm) EMA statistic
    R: jnp.ndarray       # (bn, bn)
    PL: jnp.ndarray      # cached L^{-1/4}
    PR: jnp.ndarray      # cached R^{-1/4}


def _inv_root(m: jnp.ndarray, eps: float, power: float) -> jnp.ndarray:
    """(..., d, d) PSD -> (M + eps*I)^{power} via eigh (batch-polymorphic)."""
    d = m.shape[-1]
    lam, V = jnp.linalg.eigh(m + eps * jnp.eye(d, dtype=m.dtype))
    lam = jnp.maximum(lam, eps)
    return jnp.matmul(V * jnp.power(lam, power)[..., None, :],
                      jnp.swapaxes(V, -1, -2), precision=_HIGHEST)


@dataclasses.dataclass(frozen=True)
class ShampooPreconditioner:
    """Dense L/R factors + cached inverse roots (per block).

    ``kernels`` is injected by the engine (``EngineConfig.kernel_backend``);
    the batched methods run once per packed ``(N, bs_m, bs_n)`` pool stack.
    """
    cfg: ShampooConfig
    kernels: Optional[KernelSet] = None

    diagonal: ClassVar[bool] = False

    def init_block(self, info: blocking.BlockInfo) -> ShampooBlockStats:
        dt = self.cfg.state_dtype
        return ShampooBlockStats(
            L=api.tag(jnp.zeros((info.bs_m, info.bs_m), dt),
                      "second_moment", blocked=True),
            R=api.tag(jnp.zeros((info.bs_n, info.bs_n), dt),
                      "second_moment", blocked=True),
            PL=api.tag(jnp.eye(info.bs_m, dtype=dt),
                       "preconditioner", blocked=True),
            PR=api.tag(jnp.eye(info.bs_n, dtype=dt),
                       "preconditioner", blocked=True))

    # ------------------------------------------------- per-block (reference)

    def update_stats(self, state, G, *, count):
        # statistics every step (classic Shampoo; the FD variant is
        # restricted to every 10th — see paper §6 "more difficult setting")
        # un-normalized EMA (distributed-Shampoo convention; matches the
        # FD recursion of Obs. 6 so rank>=dim recovers Shampoo exactly)
        return ShampooBlockStats(
            L=self.cfg.beta2 * state.L
            + jnp.matmul(G, G.T, precision=_HIGHEST),
            R=self.cfg.beta2 * state.R
            + jnp.matmul(G.T, G, precision=_HIGHEST),
            PL=state.PL, PR=state.PR)

    def refresh(self, state, G, *, count):
        return ShampooBlockStats(
            L=state.L, R=state.R,
            PL=_inv_root(state.L, self.cfg.matrix_eps, -0.25),
            PR=_inv_root(state.R, self.cfg.matrix_eps, -0.25))

    def precondition(self, state, G, *, count):
        return jnp.matmul(jnp.matmul(state.PL, G, precision=_HIGHEST),
                          state.PR, precision=_HIGHEST)

    # ------------------------------------------- pooled-stack (kernel path)

    def update_stats_batched(self, state, G, *, count):
        # L += gram(G^T), R += gram(G): the FD paper's tall-skinny Gram,
        # batched over the pool dim by the injected kernel set.
        if self.kernels is not None:
            L_inc = self.kernels.batched_gram(jnp.swapaxes(G, -1, -2))
            R_inc = self.kernels.batched_gram(G)
        else:
            L_inc = jnp.matmul(G, jnp.swapaxes(G, -1, -2),
                               precision=_HIGHEST)
            R_inc = jnp.matmul(jnp.swapaxes(G, -1, -2), G,
                               precision=_HIGHEST)
        return ShampooBlockStats(
            L=self.cfg.beta2 * state.L + L_inc,
            R=self.cfg.beta2 * state.R + R_inc,
            PL=state.PL, PR=state.PR)

    def refresh_batched(self, state, G, *, count):
        return ShampooBlockStats(
            L=state.L, R=state.R,
            PL=_inv_root(state.L, self.cfg.matrix_eps, -0.25),
            PR=_inv_root(state.R, self.cfg.matrix_eps, -0.25))

    def precondition_batched(self, state, G, *, count):
        return jnp.matmul(jnp.matmul(state.PL, G, precision=_HIGHEST),
                          state.PR, precision=_HIGHEST)


def shampoo(cfg: ShampooConfig = ShampooConfig()) -> GradientTransformation:
    return api.scale_by_preconditioner(
        ShampooPreconditioner(cfg),
        api.EngineConfig(
            block_size=cfg.block_size, beta2=cfg.beta2,
            update_every=cfg.root_every,
            start_preconditioning_step=cfg.start_preconditioning_step,
            graft=cfg.graft, graft_eps=cfg.graft_eps, diag_eps=cfg.diag_eps,
            refresh_schedule=cfg.refresh_schedule,
            refresh_mode=cfg.refresh_mode,
            kernel_backend=cfg.kernel_backend,
            second_moment_dtype=cfg.second_moment_dtype,
            state_dtype=cfg.state_dtype))


def second_moment_bytes(state) -> int:
    return api.second_moment_bytes(state)
