"""Optimizer factory: name -> full training transformation chain.

Chain layout (paper App. C conventions), as a labelled ``named_chain``:
  clip -> precond (sketchy | shampoo | adam direction)
  -> momentum (EMA "moving_average_for_momentum") -> weight_decay
  -> lr (negated schedule)

The whole chain is wrapped in ``inject_hyperparams`` so ``learning_rate`` and
``beta2`` live in optimizer state: serve/elastic code can read or mutate them
at runtime (``api.set_hyperparams``) without rebuilding the chain.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core import api
from repro.core import adam as adam_lib
from repro.core import shampoo as shampoo_lib
from repro.core import sketchy as sketchy_lib
from repro.core import schedules, transform


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    name: str = "sketchy"              # sketchy | shampoo | adam
    learning_rate: float = 1e-3
    total_steps: int = 1000
    warmup_frac: float = 0.05
    beta1: float = 0.9
    beta2: float = 0.999
    weight_decay: float = 0.0
    grad_clip: Optional[float] = 1.0
    schedule: str = "warmup_cosine"    # warmup_cosine | constant
    # sketchy/shampoo specific
    rank: int = 256
    # Sketch-rank budget (sketchy only; see core/sketchy.RankBudget): a
    # fixed total rank shared across all pooled blocks plus the per-block
    # allocation policy.  None keeps the uniform static allocation at
    # ``rank`` (exactly the pre-budget behavior); a RankBudget supersedes
    # ``rank`` for the direction stage.
    rank_budget: Optional[sketchy_lib.RankBudget] = None
    block_size: int = 1024
    update_every: int = 10
    start_preconditioning_step: int = 0
    # kernel backend for the pooled matrix hot path (kernels/registry.py):
    # "pallas" | "xla" | "auto" (pallas on TPU, xla elsewhere;
    # REPRO_KERNEL_BACKEND env overrides the platform default).  Replaces
    # the old sketchy-private use_kernels flag; applies to shampoo too.
    kernel_backend: str = "auto"
    # refresh phasing over the pooled block stacks (core/pool.py):
    # synchronized reproduces the seed exactly; staggered spreads the eigh
    # cost uniformly (one 1/update_every slice of blocks per step).
    refresh_schedule: str = "synchronized"
    # when the refresh lands (core/api.py): "inline" (same step, parity
    # default) | "async" (launched at t into a double-buffered pending
    # slot, committed at t+1 — eigh + merge leave the step's critical path)
    refresh_mode: str = "inline"
    # diagonal-fallback damping for vector/scalar leaves; None keeps the
    # historical graft_eps coupling (seed parity).
    diag_eps: Optional[float] = None
    # storage dtype for pooled second-moment stacks between steps
    # (core/quantize.py): "fp32" (bitwise parity) | "bf16" (2x) | "int8"
    # (per-block symmetric quantization of the matrix factors, ~4x).
    # Applies to sketchy and shampoo; adam's elementwise state is untouched.
    second_moment_dtype: str = "fp32"
    # fused int8 compute (core/api.py quantized_epilogue): "auto" | "off" |
    # "on" — sketchy only (shampoo's root solve needs f32 factors)
    quantized_epilogue: str = "auto"
    # Second-moment maintenance across data-parallel shards
    # (src/repro/distributed/): "replicated" keeps every replica's
    # statistics identical from dp-mean gradients (parity default);
    # "sharded" has each shard FD-update on its local gradients and merge
    # sketches in a log-depth butterfly at refresh time.  Only sketchy
    # implements the merge (``refresh_sharded_batched``) — shampoo/adam
    # fall back to replicated statistics under this knob.
    stats_reduction: str = "replicated"


def _direction(cfg: OptimizerConfig, beta2) -> transform.GradientTransformation:
    if cfg.name == "sketchy":
        # construct the budget explicitly (the deprecated rank= spelling
        # would warn on every step — _direction runs inside the injected
        # chain's update)
        budget = cfg.rank_budget if cfg.rank_budget is not None \
            else sketchy_lib.RankBudget(min_k=cfg.rank, max_k=cfg.rank,
                                        policy="static")
        return sketchy_lib.sketchy(sketchy_lib.SketchyConfig(
            rank_budget=budget, block_size=cfg.block_size, beta2=beta2,
            update_every=cfg.update_every,
            start_preconditioning_step=cfg.start_preconditioning_step,
            refresh_schedule=cfg.refresh_schedule,
            refresh_mode=cfg.refresh_mode,
            diag_eps=cfg.diag_eps,
            kernel_backend=cfg.kernel_backend,
            second_moment_dtype=cfg.second_moment_dtype,
            quantized_epilogue=cfg.quantized_epilogue,
            stats_reduction=cfg.stats_reduction))
    if cfg.name == "shampoo":
        return shampoo_lib.shampoo(shampoo_lib.ShampooConfig(
            block_size=cfg.block_size, beta2=beta2,
            root_every=cfg.update_every,
            start_preconditioning_step=cfg.start_preconditioning_step,
            refresh_schedule=cfg.refresh_schedule,
            refresh_mode=cfg.refresh_mode,
            diag_eps=cfg.diag_eps,
            kernel_backend=cfg.kernel_backend,
            second_moment_dtype=cfg.second_moment_dtype))
    if cfg.name == "adam":
        return adam_lib.adam(adam_lib.AdamConfig(
            beta1=cfg.beta1, beta2=beta2))
    raise ValueError(f"unknown optimizer {cfg.name!r}")


def make_optimizer(cfg: OptimizerConfig) -> transform.GradientTransformation:
    def build(learning_rate, beta2):
        stages = []
        if cfg.grad_clip:
            stages.append(("clip", transform.clip_by_global_norm(cfg.grad_clip)))
        stages.append(("precond", _direction(cfg, beta2)))
        if cfg.name != "adam":  # adam has built-in beta1 momentum
            stages.append(("momentum", transform.momentum(cfg.beta1, ema=True)))
        if cfg.weight_decay:
            stages.append(("weight_decay",
                           transform.add_decayed_weights(cfg.weight_decay)))
        stages.append(("lr", transform.scale(-1.0 * learning_rate)))
        return api.named_chain(*stages)

    if cfg.schedule == "warmup_cosine":
        lr_hyper = schedules.warmup_cosine(cfg.learning_rate, cfg.total_steps,
                                           cfg.warmup_frac)
    else:
        # constant lr is stored as a plain value => runtime-mutable via
        # api.set_hyperparams (serve-time schedule changes, elastic re-mesh)
        lr_hyper = cfg.learning_rate
    return api.inject_hyperparams(build)(learning_rate=lr_hyper,
                                         beta2=cfg.beta2)


def second_moment_bytes(state) -> int:
    """Second-moment memory of the direction stage, found by StateMeta
    traversal — works on any chain nesting, no type dispatch."""
    total = api.second_moment_bytes(state)
    if total == 0:
        raise ValueError("no second-moment state found (state carries no "
                         "StateMeta annotations)")
    return total
