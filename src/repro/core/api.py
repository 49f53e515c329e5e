"""Unified Preconditioner API: one engine, metadata-driven state.

The paper frames Sketchy, Shampoo, and Adam as points on a single
memory/quality trade-off curve over the same Kronecker-factored second-moment
statistics.  This module makes that framing first-class:

  * ``Preconditioner`` — the protocol an optimizer variant implements.  It is
    deliberately tiny: per matrix *block*, how to initialize statistics, how
    to accumulate them every step, how to refresh the (expensive) derived
    preconditioner on a cadence, and how to apply it to a gradient block.

  * ``scale_by_preconditioner(precond, cfg)`` — the one shared engine.  It
    owns everything the per-optimizer monoliths used to duplicate: parameter
    blocking (paper §3.4), the diagonal fallback for vectors/scalars,
    grafting (App. C), and ``update_every`` / ``start_preconditioning_step``
    gating.  Execution is *pooled* (core/pool.py): every matrix block in the
    model is packed into one ``(N, bs_m, bs_n)`` stack per unique block
    shape, and the three Preconditioner methods run once per shape group —
    not once per parameter leaf — so a 400-leaf model compiles a handful of
    kernel sets and the pooled blocks dim spans the whole model for mesh
    sharding.  Refresh is either ``synchronized`` (all blocks on
    ``count % update_every == 0``, the parity default) or ``staggered``
    (per-block phase, ~N/update_every blocks per step — same amortized work
    with no global eigh spike).

  * ``StateMeta`` / ``Tagged`` — every engine state leaf is wrapped in a
    ``Tagged`` pytree node carrying a static ``StateMeta`` (role, blocked
    layout, owning-parameter index).  Downstream consumers — sharding
    assignment, checkpoint manifests, memory accounting — traverse this
    metadata instead of ``isinstance``-dispatching on optimizer-specific
    NamedTuples, so a new optimizer variant needs zero consumer changes.

  * ``named_chain`` / ``inject_hyperparams`` — labelled composition and
    hyperparameters-in-state, so serving/elastic re-mesh code can read or
    mutate e.g. the learning rate at runtime without rebuilding the chain.

``Tagged`` wraps exactly one array leaf.  It is transparent to single-tree
``jax.tree.map`` (the map recurses into it and reconstructs it, preserving
the metadata), to ``jax.vmap``/``jax.lax.cond`` (metadata is static aux
data), and to flattening (it contributes exactly one leaf, so flat orders
match the untagged tree).  When an implementation needs typed containers of
raw arrays (e.g. ``FDState``), the engine strips tags with ``untag`` before
compute and restores them with ``tag_like`` after.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import blocking, pool, quantize
from repro.core.transform import GradientTransformation
from repro.kernels import registry as kernel_registry

PyTree = Any

# Roles a state leaf can play.  second_moment is the paper's headline memory
# quantity (Fig. 1); preconditioner covers derived caches (e.g. Shampoo's
# inverse roots) that are excluded from it.
ROLES = ("second_moment", "preconditioner", "grafting", "momentum", "count",
         "hyperparam")


@dataclasses.dataclass(frozen=True)
class StateMeta:
    """Static annotation attached to one optimizer-state array leaf."""
    role: str
    blocked: bool = False          # leading axis is the stacked-blocks dim
    param_index: Optional[int] = None  # flat index of the owning parameter
    shard: str = "auto"            # auto | blocks | param | replicate
    # Transient leaves are re-derivable scratch (the async refresh pending
    # slot): excluded from ``second_moment_bytes`` (they never hold the only
    # copy of a statistic) and dropped by checkpoint save/restore
    # (train/checkpoint.py zero-fills them on load).
    transient: bool = False
    # Telemetry labels for metadata-driven read APIs (``rank_allocation``):
    # implementations mark e.g. the per-block active-rank vector
    # ("active_rank"), the escaped-mass scalar ("rho"), or the eigenvalue
    # ladder ("eigvals").  ``group`` is stamped by the engine with the
    # owning pool's group key at init.  Neither is persisted in checkpoint
    # manifests (restore templates re-derive them from code).
    label: Optional[str] = None
    group: Optional[str] = None

    def __post_init__(self):
        if self.role not in ROLES:
            raise ValueError(f"unknown state role {self.role!r}")


@jax.tree_util.register_pytree_with_keys_class
class Tagged:
    """Pytree node wrapping a single array leaf plus its static StateMeta."""
    __slots__ = ("value", "meta")

    def __init__(self, value, meta: StateMeta):
        self.value = value
        self.meta = meta

    def tree_flatten_with_keys(self):
        return ((jax.tree_util.GetAttrKey("value"), self.value),), self.meta

    @classmethod
    def tree_unflatten(cls, meta, children):
        return cls(children[0], meta)

    def __repr__(self):
        return f"Tagged({self.value!r}, {self.meta})"


def tag(value, role: str, **kw) -> Tagged:
    return Tagged(value, StateMeta(role=role, **kw))


def _is_tagged(x) -> bool:
    return isinstance(x, Tagged)


def untag(tree: PyTree) -> PyTree:
    """Strip Tagged wrappers, leaving a plain array pytree."""
    return jax.tree.map(lambda x: x.value if _is_tagged(x) else x, tree,
                        is_leaf=_is_tagged)


def tag_like(template: PyTree, values: PyTree) -> PyTree:
    """Re-attach ``template``'s tags onto a congruent untagged tree."""
    return jax.tree.map(
        lambda t, v: Tagged(v, t.meta) if _is_tagged(t) else v,
        template, values, is_leaf=_is_tagged)


def leaves_with_meta(tree: PyTree) -> list:
    """Flat ``[(StateMeta | None, leaf), ...]`` in ``jax.tree.leaves`` order.

    Tagged nodes contribute their meta; plain leaves get ``None``.  Because a
    Tagged node holds exactly one leaf, the ordering is identical to a full
    flatten of the same tree.
    """
    out = []
    for x in jax.tree.leaves(tree, is_leaf=_is_tagged):
        if _is_tagged(x):
            out.append((x.meta, x.value))
        else:
            out.append((None, x))
    return out


def map_with_meta(fn: Callable[[Optional[StateMeta], Any], Any],
                  tree: PyTree) -> PyTree:
    """Map ``fn(meta_or_None, leaf) -> leaf`` over a tree, keeping tags."""
    def one(x):
        if _is_tagged(x):
            return Tagged(fn(x.meta, x.value), x.meta)
        return fn(None, x)
    return jax.tree.map(one, tree, is_leaf=_is_tagged)


def second_moment_bytes(state: PyTree) -> int:
    """Second-moment memory by metadata traversal — the paper's Fig. 1
    quantity (excludes grafting/momentum/derived preconditioners).  Works on
    any state pytree: a bare engine state, a named chain, a full injected
    optimizer state, or shape structs from ``jax.eval_shape``.

    Transient leaves (the async-refresh pending slot) are excluded: they
    double-buffer statistics already counted in the live pools, so counting
    them again would report the paper's Fig. 1 quantity double."""
    total = 0
    for meta, leaf in leaves_with_meta(state):
        if meta is not None and meta.role == "second_moment" \
                and not meta.transient:
            total += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
    return total


def rank_allocation(state: PyTree) -> dict:
    """Per-block sketch-rank allocation by metadata traversal — the read
    API of the rank-budget allocator, mirroring ``second_moment_bytes``:
    works on any state pytree (bare engine state, named chain, injected
    optimizer state) with no isinstance-dispatch on optimizer containers.

    Returns ``{"total": K, "groups": {group_key: {"k", "rho",
    "budget_share"}}}`` with per-block (N,) arrays per pool group:
    ``k`` the active ranks (for static engines, the ladder capacity —
    every block at the configured rank), ``rho`` the per-block escaped
    mass summed over sketch sides, and ``budget_share = k / K``.  On
    ``jax.eval_shape`` structs the rank vector falls back to capacity and
    ``rho`` to zeros (shapes carry no values).
    """
    import numpy as np

    concrete = lambda x: not isinstance(x, jax.ShapeDtypeStruct)
    per: dict = {}
    for meta, leaf in leaves_with_meta(state):
        if meta is None or meta.transient or meta.group is None \
                or meta.label is None:
            continue
        g = per.setdefault(meta.group, {"k": None, "rho": [], "ladder": []})
        if meta.label == "active_rank":
            g["k"] = leaf
        elif meta.label == "rho":
            g["rho"].append(leaf)
        elif meta.label == "eigvals":
            g["ladder"].append(leaf)
    if not per:
        raise ValueError("no sketch state found (state carries no labelled "
                         "StateMeta leaves)")

    ks = {}
    for key, g in sorted(per.items()):
        if g["k"] is not None and concrete(g["k"]):
            ks[key] = np.asarray(g["k"], dtype=np.int64)
        else:
            # static engine (or shape structs): active rank == ladder
            # capacity, i.e. the configured rank clipped per side — report
            # the wider side
            n = g["ladder"][0].shape[0] if g["ladder"] \
                else g["k"].shape[0]
            cap = max((l.shape[-1] for l in g["ladder"]), default=0)
            ks[key] = np.full((n,), cap, dtype=np.int64)
    total = int(sum(int(k.sum()) for k in ks.values()))

    groups = {}
    for key, g in sorted(per.items()):
        k = ks[key]
        rho_leaves = [r for r in g["rho"] if concrete(r)]
        rho = (np.sum([np.asarray(r, np.float64) for r in rho_leaves],
                      axis=0)
               if rho_leaves else np.zeros(k.shape, np.float64))
        groups[key] = {"k": k, "rho": rho,
                       "budget_share": k / max(total, 1)}
    return {"total": total, "groups": groups}


# ---------------------------------------------------------------------------
# Preconditioner protocol


@runtime_checkable
class Preconditioner(Protocol):
    """One optimizer variant = one small implementation of this protocol.

    ``diagonal = False`` (kron-style: sketchy, shampoo, sadagrad): the engine
    blocks each matrix leaf into a ``(S, bm, bn)`` stack and vmaps the three
    methods over blocks; vector/scalar leaves take the engine's shared
    diagonal (RMSProp) fallback.

    ``diagonal = True`` (adam): every leaf is handled whole by the
    implementation's own elementwise logic; blocking, grafting, and gating
    are skipped.

    Engine call order per step (mirrors the seed monoliths exactly):
      state = update_stats(state, G)        # every step (cheap accumulation)
      state = refresh(state, G)             # every cfg.update_every steps
      P     = precondition(state, G)        # every step (apply)

    Batched execution: the engine dispatches each method once per pooled
    ``(N, bs_m, bs_n)`` shape group.  An implementation may provide
    ``update_stats_batched`` / ``refresh_batched`` / ``precondition_batched``
    taking the whole stacked state + gradient stack — sketchy and shampoo do,
    routing the hot contractions through the grid-over-N batched kernels of
    their injected ``KernelSet`` — in which case the engine calls the batched
    entry point directly (no vmap).  Without them the engine falls back to
    ``jax.vmap`` of the per-block method, so minimal implementations keep
    working unchanged.

    Kernel injection: implementations that declare a ``kernels`` dataclass
    field (default ``None``) receive the engine's resolved ``KernelSet``
    (``EngineConfig.kernel_backend``) at transform-build time — one knob
    selects the backend uniformly for every kron-style optimizer.
    """
    diagonal: bool

    def init_block(self, info: blocking.BlockInfo) -> PyTree:
        """State for ONE block (Tagged leaves). The engine broadcasts it over
        the leaf's block stack."""
        ...

    def update_stats(self, state: PyTree, G: jnp.ndarray, *,
                     count: jnp.ndarray) -> PyTree:
        ...

    def refresh(self, state: PyTree, G: jnp.ndarray, *,
                count: jnp.ndarray) -> PyTree:
        ...

    def precondition(self, state: PyTree, G: jnp.ndarray, *,
                     count: jnp.ndarray) -> jnp.ndarray:
        ...


REFRESH_SCHEDULES = ("synchronized", "staggered")
STATS_REDUCTIONS = ("replicated", "sharded")
REFRESH_MODES = ("inline", "async")
QUANTIZED_EPILOGUES = ("auto", "off", "on")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Everything the shared engine owns (formerly duplicated per optimizer)."""
    block_size: int = 1024
    beta2: float = 0.999            # diag-fallback / grafting EMA decay
    update_every: int = 10          # refresh cadence (paper §6)
    start_preconditioning_step: int = 0
    graft: str = "rmsprop_normalized"   # rmsprop_normalized | rmsprop | none
    graft_eps: float = 1e-8
    # Diagonal-fallback damping for vector/scalar leaves.  None keeps the
    # historical coupling to graft_eps (bitwise parity with the seed).
    diag_eps: Optional[float] = None
    # synchronized: all blocks refresh on count % update_every == 0 (parity
    # default).  staggered: one synchronized warm refresh at count 0, then
    # block b refreshes when (count + b) % update_every == 0 — each block
    # exactly once per window, ~N/update_every eighs every step instead of N
    # on spike steps.
    refresh_schedule: str = "synchronized"
    # Kernel backend for the pooled matrix hot path: "pallas" (grid-over-N
    # batched kernels; interpret mode off-TPU), "xla" (pure-jnp batched
    # refs), or "auto" (pallas on TPU, xla elsewhere; REPRO_KERNEL_BACKEND
    # overrides the platform default).  Resolved once at transform build.
    kernel_backend: str = "auto"
    # Storage dtype for the pooled second-moment stacks BETWEEN steps
    # (core/quantize.py): "fp32" (identity, bitwise parity), "bf16" (2x), or
    # "int8" (per-block symmetric quantization of the matrix factors, ~4x).
    # By default compute dequantizes to f32 at the batched-method boundary,
    # so kernels and Preconditioner implementations never see quantized
    # arrays; see ``quantized_epilogue`` for the fused exception.
    second_moment_dtype: str = "fp32"
    # Fused int8 compute: hand the batched methods the QuantizedPool
    # containers themselves (quantize.compute_view) instead of dequantizing
    # the big factor stacks at the boundary — the implementation's batched
    # methods dispatch to fused kernels that upcast int8 in-registers and
    # re-quantize refreshed factors in-kernel, so the f32 stack never
    # materializes in HBM.  "auto": on iff second_moment_dtype is int8, the
    # resolved backend is pallas, the implementation opts in
    # (``supports_quantized_compute``), and stats are replicated (the
    # sharded merge needs f32 factors on the wire).  "off": always
    # dequantize (the PR-4 behaviour).  "on": force the fused path on any
    # backend (the xla refs implement the same fused entries — used by the
    # CPU parity tests).
    quantized_epilogue: str = "auto"
    # Second-moment maintenance across data-parallel shards
    # (src/repro/distributed/):
    #   "replicated" — every shard sees the dp-mean gradients and maintains
    #     identical statistics (the parity default).
    #   "sharded"    — each shard FD-updates on its *local* gradients
    #     (scaled 1/sqrt(P)) and refreshes end in a log-depth butterfly
    #     sketch merge over ``stats_axis``.  Requires the Preconditioner to
    #     implement ``refresh_sharded_batched`` (sketchy does); otherwise —
    #     or when ``stats_axis`` is unbound or 1-sized at trace time — the
    #     engine falls back to the replicated path bitwise.
    stats_reduction: str = "replicated"
    stats_axis: str = "data"
    # When the refresh lands relative to the step that triggered it:
    #   "inline" — the refreshed statistics precondition the SAME step's
    #     gradient (the parity default, bitwise-pinned to the references).
    #   "async"  — the refresh for step t's cohort is *launched* at t into a
    #     double-buffered pending slot (``PrecondState.pending``) and
    #     *committed* at t+1: the parameter update at t preconditions with
    #     the pre-refresh (one-step-stale) statistics, so the eigh and the
    #     butterfly merge rounds have no data dependency on the update
    #     direction and XLA is free to overlap them with the next step's
    #     forward/backward.  The committed statistics at step t+1 equal
    #     inline's at step t exactly (step-shifted parity, including int8
    #     storage: the pending slot is quantized with the step-t keys).
    refresh_mode: str = "inline"
    # Cross-pool rank-budget reallocation cadence, in refresh windows: every
    # ``realloc_every * update_every`` steps the engine hands ALL refreshed
    # pool stacks to the implementation's ``realloc_pools(groups, stacks)``
    # hook (rank-budget allocator, core/sketchy.py) right after the refresh
    # and before precondition/requantize.  0 (default) disables the hook —
    # the engine loop is then exactly the pre-budget one.  Under
    # ``refresh_mode="async"`` the reallocation rides the pending-slot
    # refresh and commits at t+1 with it (step-shifted parity preserved).
    realloc_every: int = 0
    state_dtype: Any = jnp.float32
    # OCO learners (S-AdaGrad, Alg. 2) precondition a d-vector with a full
    # d x d sketch: treat 1-D leaves as a single (d, 1) matrix block instead
    # of the diagonal fallback.
    treat_vectors_as_columns: bool = False

    def __post_init__(self):
        if self.refresh_schedule not in REFRESH_SCHEDULES:
            raise ValueError(
                f"unknown refresh_schedule {self.refresh_schedule!r}; "
                f"expected one of {REFRESH_SCHEDULES}")
        if self.kernel_backend not in kernel_registry.BACKENDS:
            raise ValueError(
                f"unknown kernel_backend {self.kernel_backend!r}; "
                f"expected one of {kernel_registry.BACKENDS}")
        if self.second_moment_dtype not in quantize.SECOND_MOMENT_DTYPES:
            raise ValueError(
                f"unknown second_moment_dtype {self.second_moment_dtype!r}; "
                f"expected one of {quantize.SECOND_MOMENT_DTYPES}")
        if self.stats_reduction not in STATS_REDUCTIONS:
            raise ValueError(
                f"unknown stats_reduction {self.stats_reduction!r}; "
                f"expected one of {STATS_REDUCTIONS}")
        if self.refresh_mode not in REFRESH_MODES:
            raise ValueError(
                f"unknown refresh_mode {self.refresh_mode!r}; "
                f"expected one of {REFRESH_MODES}")
        if self.quantized_epilogue not in QUANTIZED_EPILOGUES:
            raise ValueError(
                f"unknown quantized_epilogue {self.quantized_epilogue!r}; "
                f"expected one of {QUANTIZED_EPILOGUES}")
        if self.realloc_every < 0:
            raise ValueError(
                f"realloc_every must be >= 0, got {self.realloc_every}")


class LeafState(NamedTuple):
    """Per-leaf residue that cannot be pooled: param-shaped diagonal stats
    (diag fallback / diagonal preconditioners) and grafting accumulators.
    Pooled matrix leaves carry ``stats=None`` — their block statistics live
    in ``PrecondState.pools``."""
    stats: Any          # implementation-defined, Tagged leaves, or None
    graft: Any          # Tagged grafting accumulator, or None


class PendingSlot(NamedTuple):
    """One shape group's in-flight refresh (``refresh_mode="async"``): the
    refreshed stats stack launched at step t, in storage layout (same
    quantized structure as ``PrecondState.pools[key]``, tags marked
    ``transient``), plus a one-bit valid flag.  ``valid=False`` — the init
    state, or a checkpoint restore that dropped the slot — makes the commit
    a no-op and the engine falls back to the on-schedule refresh."""
    stats: Any          # storage-layout stack, transient StateMeta tags
    valid: Tagged       # bool scalar, role="count", transient


class PrecondState(NamedTuple):
    """Engine state: one packed stats stack per unique block shape (keyed by
    ``pool.group_key``; leading dim spans every matrix block in the model)
    plus the per-leaf residue.  ``pending`` is ``None`` under
    ``refresh_mode="inline"`` (contributing no pytree leaves, so inline
    checkpoints/manifests are unchanged) and a ``{group key: PendingSlot}``
    dict under ``"async"``."""
    count: Tagged
    pools: dict         # group key -> stats pytree (Tagged, leading dim N)
    leaves: tuple       # LeafState per flat param leaf
    pending: Any = None  # async refresh double-buffer, or None (inline)


def committed_pools(state: PrecondState) -> dict:
    """The storage-layout pools the NEXT update will precondition from.

    Inline mode: the live pools.  Async mode: each group's pending refresh
    committed over the live stack where its valid bit is set — exactly the
    select the engine performs at the top of the next step, so async state
    after step t satisfies ``committed_pools(async_t) == inline_t.pools``
    bitwise (the step-shifted parity contract)."""
    if state.pending is None:
        return state.pools
    out = {}
    for key, live in state.pools.items():
        slot = state.pending[key]
        out[key] = tag_like(live, pool.commit_select(
            slot.valid.value, untag(slot.stats), untag(live)))
    return out


def pool_stats(state: PrecondState, key: Optional[str] = None) -> Any:
    """Untagged f32 stats stack for one pool group (default: the only
    group).  Quantized storage (core/quantize.py) is dequantized, so callers
    always see the compute-layout tree regardless of second_moment_dtype."""
    if key is None:
        if len(state.pools) != 1:
            raise ValueError(
                f"state has {len(state.pools)} pools {sorted(state.pools)}; "
                "pass an explicit key")
        key = next(iter(state.pools))
    return quantize.dequantize_pool(state.pools[key])


def graft_direction(g: jnp.ndarray, acc: jnp.ndarray, *, graft: str,
                    beta2, graft_eps: float):
    """Grafting direction + updated accumulator (paper App. C,
    RMSPROP_NORMALIZED). ``g``/``acc`` are float32."""
    if graft == "none":
        return g, acc
    if graft == "rmsprop_normalized":
        gn = g / (jnp.linalg.norm(g) + 1e-16)
    else:
        gn = g
    acc = beta2 * acc + (1.0 - beta2) * jnp.square(gn)
    return gn * jax.lax.rsqrt(acc + graft_eps), acc


def _inject_kernels(precond: "Preconditioner",
                    kernels: kernel_registry.KernelSet) -> "Preconditioner":
    """Hand the engine's resolved KernelSet to implementations that want it.

    Any dataclass Preconditioner declaring a ``kernels`` field (sketchy,
    shampoo) gets the set injected — unless the caller already supplied one
    explicitly, which wins.  Everything else passes through untouched.
    """
    if dataclasses.is_dataclass(precond) and not isinstance(precond, type):
        names = {f.name for f in dataclasses.fields(precond)}
        if "kernels" in names and getattr(precond, "kernels") is None:
            return dataclasses.replace(precond, kernels=kernels)
    return precond


def _batched_method(precond: "Preconditioner", name: str):
    """``fn(stacked_state, G_stack, count)`` for one Preconditioner method.

    Prefers the implementation's ``<name>_batched`` (single call over the
    whole packed pool stack — the kernel-backed hot path); falls back to
    ``jax.vmap`` of the per-block method for minimal implementations.
    """
    batched = getattr(precond, name + "_batched", None)
    if batched is not None:
        return lambda s, G, count: batched(s, G, count=count)
    per_block = getattr(precond, name)
    return lambda s, G, count: jax.vmap(
        lambda ss, GG: per_block(ss, GG, count=count))(s, G)


def _stamp_group(tree: PyTree, key: str) -> PyTree:
    """Copy of a tagged tree with every StateMeta stamped with its pool
    group key — what lets ``rank_allocation`` bucket leaves per pool
    without touching optimizer-specific containers."""
    def one(x):
        if _is_tagged(x):
            return Tagged(x.value, dataclasses.replace(x.meta, group=key))
        return x
    return jax.tree.map(one, tree, is_leaf=_is_tagged)


def _mark_transient(tree: PyTree) -> PyTree:
    """Copy of a tagged tree with every StateMeta marked ``transient`` — the
    pending-slot layout: same structure/sharding as the live pools, excluded
    from memory accounting and checkpoints."""
    def one(x):
        if _is_tagged(x):
            return Tagged(x.value,
                          dataclasses.replace(x.meta, transient=True))
        return x
    return jax.tree.map(one, tree, is_leaf=_is_tagged)


def _index_unblocked(tree: PyTree, i: int) -> PyTree:
    """Record the owning-param index on param-shaped (non-blocked) tags."""
    def one(x):
        if _is_tagged(x) and not x.meta.blocked and x.meta.param_index is None:
            return Tagged(x.value, dataclasses.replace(x.meta, param_index=i))
        return x
    return jax.tree.map(one, tree, is_leaf=_is_tagged)


def scale_by_preconditioner(precond: Preconditioner,
                            cfg: EngineConfig = EngineConfig()
                            ) -> GradientTransformation:
    """The ONE shared direction engine (emits a descent direction, no lr).

    Matrix blocks execute *pooled*: ``core/pool.py`` groups every block in
    the model by block shape and the three Preconditioner methods run once
    per shape group over a packed ``(N, bs_m, bs_n)`` stack — via the
    implementation's ``*_batched`` entry points (batched grid-over-N kernels
    from the ``cfg.kernel_backend`` KernelSet) when it has them, else a vmap
    fallback.  Only the per-leaf residue (diag fallback, grafting norms,
    gating) stays leafwise.
    """
    diag_eps = cfg.graft_eps if cfg.diag_eps is None else cfg.diag_eps
    qdtype = cfg.second_moment_dtype
    precond = _inject_kernels(precond,
                              kernel_registry.get_kernels(cfg.kernel_backend))
    # Fused int8 compute resolution (build time, like the backend itself):
    # the batched methods receive quantize.compute_view (containers kept)
    # instead of quantize.dequantize_pool (f32 at the boundary).
    fused_q = (
        qdtype == "int8"
        and cfg.quantized_epilogue != "off"
        and getattr(precond, "supports_quantized_compute", False)
        and cfg.stats_reduction != "sharded"
        and (cfg.quantized_epilogue == "on"
             or kernel_registry.resolve_backend(cfg.kernel_backend)
             == "pallas"))
    pool_compute = quantize.compute_view if fused_q \
        else quantize.dequantize_pool
    update_stats_b = _batched_method(precond, "update_stats")
    refresh_b = _batched_method(precond, "refresh")
    precondition_b = _batched_method(precond, "precondition")
    refresh_sharded_b = getattr(precond, "refresh_sharded_batched", None)

    def sharded_ctx():
        """(reduce module, axis size) when the sharded-stats path is live.

        Live means: the knob is on, the implementation can merge
        (``refresh_sharded_batched``), and ``cfg.stats_axis`` is bound with
        size > 1 at trace time.  Anything else returns (None, 1) and the
        engine takes the replicated path — bitwise-identical to the
        default, which is also what makes ``"sharded"`` on a 1-sized data
        axis exactly equal to ``"replicated"`` (a merge with one
        participant is the identity).
        """
        if cfg.stats_reduction != "sharded" or refresh_sharded_b is None:
            return None, 1
        from repro.distributed import reduce as dreduce
        size = dreduce.bound_axis_size(cfg.stats_axis)
        if size is None or size <= 1:
            return None, 1
        return dreduce, size

    def index_of(shapes) -> pool.PoolIndex:
        return pool.build_index(
            tuple(tuple(s) for s in shapes), cfg.block_size,
            vectors_as_columns=cfg.treat_vectors_as_columns)

    def init_fn(params):
        flat = jax.tree.leaves(params)
        count = tag(jnp.zeros([], jnp.int32), "count")
        if precond.diagonal:
            leaves = tuple(
                LeafState(stats=_index_unblocked(precond.init_block(
                    blocking.BlockInfo(kind="diag", shape=tuple(p.shape))), i),
                    graft=None)
                for i, p in enumerate(flat))
            return PrecondState(count=count, pools={}, leaves=leaves)

        index = index_of([p.shape for p in flat])
        stacks = {}
        for grp in index.groups:
            base = precond.init_block(grp.info)
            stacks[grp.key] = jax.tree.map(
                lambda x, n=grp.num_blocks:
                    jnp.broadcast_to(x, (n,) + x.shape), base)
        # cross-pool init hook (rank-budget allocator): the implementation
        # sees every broadcast stack at once — the first point where the
        # total block count (and so the resolved budget) is known
        finalize = getattr(precond, "finalize_init_pools", None)
        if finalize is not None:
            stacks = finalize(index.groups, stacks)
        pools = {}
        for grp in index.groups:
            # storage layout: quantized between steps (deterministic at init
            # — the stats are zeros/identity, nothing to dither)
            pools[grp.key] = _stamp_group(
                quantize.quantize_pool(stacks[grp.key], qdtype), grp.key)
        leaves = []
        for i, (p, plan) in enumerate(zip(flat, index.leaves)):
            if plan.group is None:
                # diag-fallback accumulator; stored quantized like the
                # pools (deterministic at init — zeros)
                leaves.append(LeafState(
                    stats=quantize.quantize_leaf_state(
                        tag(jnp.zeros(p.shape, cfg.state_dtype),
                            "second_moment", param_index=i), qdtype),
                    graft=None))
            else:
                graft = None
                if cfg.graft != "none":
                    graft = tag(jnp.zeros(p.shape, cfg.state_dtype),
                                "grafting", param_index=i)
                leaves.append(LeafState(stats=None, graft=graft))
        pending = None
        if cfg.refresh_mode == "async":
            # double buffer: same storage layout (and therefore sharding)
            # as the live pools, transient tags => not counted, not saved.
            # Fresh zero arrays, NOT references to the live pool buffers —
            # donated opt_state must not contain the same buffer twice.
            pending = {
                key: PendingSlot(
                    stats=_mark_transient(jax.tree.map(jnp.zeros_like, stack)),
                    valid=Tagged(jnp.zeros([], bool),
                                 StateMeta(role="count", transient=True)))
                for key, stack in pools.items()}
        return PrecondState(count=count, pools=pools, leaves=tuple(leaves),
                            pending=pending)

    def refresh_group(grp: pool.PoolGroup, raw, gb, count, vrefresh):
        """Gated refresh over one packed stack (raw = untagged stats);
        ``vrefresh(stats, G_stack)`` is the ungated refresh — the plain
        batched method, or its sharded-merge variant."""
        if cfg.update_every <= 1:
            return vrefresh(raw, gb)
        if cfg.refresh_schedule == "synchronized":
            return jax.lax.cond((count % cfg.update_every) == 0,
                                lambda s: vrefresh(s, gb), lambda s: s, raw)
        # staggered: block b is due when (count + b) % update_every == 0 —
        # at most ceil(N/k) blocks per step.  Gather the due blocks into a
        # fixed-capacity sub-stack, refresh only those, scatter back.  Fill
        # slots use the out-of-range index N: gathers clamp (the dummy
        # refresh result is discarded) and scatters drop, so no valid block
        # is ever clobbered.
        N, k = grp.num_blocks, cfg.update_every
        cap = -(-N // k)

        def staggered(s):
            due = (count + pool.block_ids(grp)) % k == 0
            idx = jnp.nonzero(due, size=cap, fill_value=N)[0]
            sub = vrefresh(jax.tree.map(lambda x: x[idx], s), gb[idx])
            return jax.tree.map(lambda x, ns: x.at[idx].set(ns), s, sub)

        # Cold start: off-phase blocks must not precondition with their
        # zero-initialized stats for up to k-1 steps, so count 0 does one
        # synchronized warm refresh (exactly what the synchronized schedule's
        # first step costs); phased refresh takes over from count 1.
        return jax.lax.cond(count == 0, lambda s: vrefresh(s, gb),
                            staggered, raw)

    def update_fn(updates, state, params=None):
        del params
        flat, treedef = jax.tree.flatten(updates)
        count = state.count.value
        new_count = Tagged(count + 1, state.count.meta)

        if precond.diagonal:
            out, new_leaves = [], []
            for g, leaf in zip(flat, state.leaves):
                g32 = g.astype(jnp.float32)
                raw = untag(leaf.stats)
                raw = precond.update_stats(raw, g32, count=count)
                direction = precond.precondition(raw, g32, count=count)
                out.append(direction.astype(g.dtype))
                new_leaves.append(LeafState(stats=tag_like(leaf.stats, raw),
                                            graft=None))
            return (jax.tree.unflatten(treedef, out),
                    PrecondState(count=new_count, pools={},
                                 leaves=tuple(new_leaves)))

        index = index_of([g.shape for g in flat])
        g32 = [g.astype(jnp.float32) for g in flat]

        # Sharded statistics (src/repro/distributed/): the direction /
        # grafting path keeps consuming dp-MEAN gradients, while the stats
        # path sees this shard's LOCAL gradients scaled 1/sqrt(P) (so the
        # butterfly-merged sketch estimates (1/P) sum_i G_i G_i^T — the
        # covariance of the mean-gradient stream the replicated path
        # sketches when shards agree).  The trainer hands the locals over
        # via ``distributed.reduce.local_gradients``; called without that
        # context, ``updates`` themselves are taken as local and the mean
        # is recovered with a pmean.
        dreduce, axis_size = sharded_ctx()
        g32_local = g32
        if dreduce is not None:
            ctx = dreduce.current_local_gradients()
            if ctx is None:
                g32 = [dreduce.pmean(g, cfg.stats_axis) for g in g32_local]
            else:
                g32_local = [g.astype(jnp.float32)
                             for g in jax.tree.leaves(ctx)]
        packed = pool.pack(index, g32)
        packed_stats = packed
        if dreduce is not None:
            inv_sqrt_p = axis_size ** -0.5
            packed_stats = pool.pack(index,
                                     [g * inv_sqrt_p for g in g32_local])

        # One update/refresh/precondition dispatch per SHAPE GROUP — the
        # whole model's same-shaped blocks in one batched call each, straight
        # into the implementation's batched (kernel-backed) entry points.
        # Pools are stored quantized (cfg.second_moment_dtype) between steps:
        # dequantize to f32 at this boundary, requantize the result.  For
        # fp32 both transforms are exactly untag/tag_like (bitwise parity).
        qkey = None
        if qdtype == "int8":
            # stochastic requantization keyed by step: unbiased across the
            # repeated quantize-accumulate cycle of the EMA statistics
            qkey = jax.random.fold_in(jax.random.PRNGKey(0x0517), count)
        if dreduce is None:
            vrefresh = lambda s, G: refresh_b(s, G, count)
        else:
            vrefresh = lambda s, G: refresh_sharded_b(
                s, G, count=count, axis=cfg.stats_axis, axis_size=axis_size)
        is_async = cfg.refresh_mode == "async" and state.pending is not None
        realloc_fn = getattr(precond, "realloc_pools", None)
        do_realloc = (cfg.realloc_every > 0 and realloc_fn is not None
                      and len(index.groups) > 0)

        def gkey_of(gi):
            return None if qkey is None else jax.random.fold_in(qkey, gi)

        def maybe_realloc(raws):
            """Gated cross-pool rank-budget reallocation over ALL refreshed
            stacks at once (the budget is global, so the hook must see every
            pool): a no-op unless the implementation opts in via
            ``realloc_pools`` and ``cfg.realloc_every > 0``."""
            if not do_realloc:
                return raws
            period = max(cfg.update_every, 1) * cfg.realloc_every
            return jax.lax.cond(
                ((count % period) == 0) & (count > 0),
                lambda r: realloc_fn(index.groups, r), lambda r: r, raws)

        # the precond/... name scopes are HLO op metadata, free on the
        # device; the benchmark's trace readers (bench/metrics) match them
        new_pools, pooled_dirs = {}, {}
        new_pending = {} if is_async else None
        if not is_async:
            # pass 1: accumulate + (gated) refresh every pool stack
            raws = {}
            for grp in index.groups:
                gb_stats = packed_stats[grp.key]
                raw = pool_compute(state.pools[grp.key])
                with jax.named_scope("precond/update_stats"):
                    raw = update_stats_b(raw, gb_stats, count)
                with jax.named_scope("precond/refresh"):
                    raws[grp.key] = refresh_group(grp, raw, gb_stats, count,
                                                  vrefresh)
            raws = maybe_realloc(raws)
            # pass 2: precondition + requantize from the (possibly
            # reallocated) refreshed stacks.  With realloc off this computes
            # exactly what the former single fused loop did, value for value.
            for gi, grp in enumerate(index.groups):
                raw = raws[grp.key]
                with jax.named_scope("precond/precondition"):
                    pooled_dirs[grp.key] = precondition_b(
                        raw, packed[grp.key], count)
                new_pools[grp.key] = quantize.requantize_pool(
                    state.pools[grp.key], raw, key=gkey_of(gi))
        else:
            # async one-step-stale pipeline.  Per step t:
            #   1. commit: fold the refresh launched at t-1 (pending slot)
            #      over the live stack — a cheap elementwise select in
            #      storage layout, no eigh on this path;
            #   2. accumulate this step's statistics on the committed stack;
            #   3. precondition with those PRE-refresh stats — the update
            #      direction has no data dependency on this step's refresh,
            #      so the eigh + merge rounds below are free to overlap with
            #      the next step's forward/backward;
            #   4. launch: run the (gated) refresh into the pending slot,
            #      committed at t+1.
            # The commit therefore lands exactly what inline computed at t-1
            # (same refresh, same quantization keys), one step later.
            raws_pre, refreshed = {}, {}
            for grp in index.groups:
                gb_stats = packed_stats[grp.key]
                slot = state.pending[grp.key]
                live = state.pools[grp.key]
                with jax.named_scope("precond/commit"):
                    committed = tag_like(live, pool.commit_select(
                        slot.valid.value, untag(slot.stats), untag(live)))
                raw = pool_compute(committed)
                with jax.named_scope("precond/update_stats"):
                    raw = update_stats_b(raw, gb_stats, count)
                with jax.named_scope("precond/precondition"):
                    pooled_dirs[grp.key] = precondition_b(
                        raw, packed[grp.key], count)
                with jax.named_scope("precond/refresh_launch"):
                    refreshed[grp.key] = refresh_group(grp, raw, gb_stats,
                                                       count, vrefresh)
                raws_pre[grp.key] = raw
            # reallocation rides the refresh pipeline: it lands in the
            # pending slot and commits at t+1 together with the refresh, so
            # the step-shifted parity contract is preserved
            refreshed = maybe_realloc(refreshed)
            for gi, grp in enumerate(index.groups):
                slot = state.pending[grp.key]
                live = state.pools[grp.key]
                gkey = gkey_of(gi)
                # live stack stores the pre-refresh stats, pending the
                # refreshed ones — both under the step-t quantization keys,
                # so whichever side the next commit selects is bitwise what
                # inline stored
                new_pools[grp.key] = quantize.requantize_pool(
                    live, raws_pre[grp.key], key=gkey)
                new_pending[grp.key] = PendingSlot(
                    stats=quantize.requantize_pool(slot.stats,
                                                   refreshed[grp.key],
                                                   key=gkey),
                    valid=Tagged(jnp.ones([], bool), slot.valid.meta))

        # Per-leaf residue: diag fallback, grafting norms, gating.
        out, new_leaves = [], []
        for i, (g, leaf, plan) in enumerate(zip(flat, state.leaves,
                                                index.leaves)):
            gi = g32[i]
            if plan.group is None:   # diagonal (RMSProp) fallback
                # storage may be quantized (satellite of the pool-level
                # scheme): dequantize/requantize are exact pass-throughs
                # for fp32 (bitwise parity)
                if dreduce is None:
                    sq = jnp.square(gi)
                else:
                    # the diag residue travels in the sharded reduction
                    # too: mean of per-shard squares over the data axis
                    sq = dreduce.pmean(jnp.square(g32_local[i]),
                                       cfg.stats_axis)
                acc = cfg.beta2 * quantize.dequantize_pool(leaf.stats) \
                    + (1.0 - cfg.beta2) * sq
                direction = gi * jax.lax.rsqrt(acc + diag_eps)
                out.append(direction.astype(g.dtype))
                lkey = None if qkey is None \
                    else jax.random.fold_in(qkey, len(index.groups) + i)
                new_leaves.append(LeafState(
                    stats=quantize.requantize_pool(leaf.stats, acc,
                                                   key=lkey), graft=None))
                continue

            direction = pool.unpack_leaf(index, pooled_dirs, i)
            if cfg.graft != "none":
                graft_dir, new_acc = graft_direction(
                    gi, leaf.graft.value, graft=cfg.graft, beta2=cfg.beta2,
                    graft_eps=cfg.graft_eps)
                pnorm = jnp.linalg.norm(direction)
                gnorm = jnp.linalg.norm(graft_dir)
                direction = direction * (gnorm / (pnorm + 1e-16))
                new_graft = Tagged(new_acc, leaf.graft.meta)
            else:
                graft_dir = gi
                new_graft = None

            if cfg.start_preconditioning_step > 0:
                use_precond = count >= cfg.start_preconditioning_step
                direction = jnp.where(use_precond, direction, graft_dir)
            out.append(direction.astype(g.dtype))
            new_leaves.append(LeafState(stats=None, graft=new_graft))

        return (jax.tree.unflatten(treedef, out),
                PrecondState(count=new_count, pools=new_pools,
                             leaves=tuple(new_leaves),
                             pending=new_pending))

    return GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Labelled composition + hyperparameters-in-state


def stage_scope(name: str) -> str:
    """The name scope of a ``named_chain`` stage's update: ``opt/<name>``,
    except that the direction stage (state key ``precond``) is
    ``opt/direction``, so that no scope outside the engine's own contains
    ``precond/``."""
    return "opt/" + ("direction" if name == "precond" else name)


def named_chain(*stages) -> GradientTransformation:
    """``chain`` with labelled stages: state is ``{name: member_state}``.

    Stage names become checkpoint-manifest path components and are the lookup
    key for ``get_stage`` — no positional index guessing.  Each stage's
    update runs under the name scope ``stage_scope(name)``.
    """
    names = [n for n, _ in stages]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate stage names: {names}")

    def init_fn(params):
        return {name: t.init(params) for name, t in stages}

    def update_fn(updates, state, params=None):
        new_state = {}
        for name, t in stages:
            with jax.named_scope(stage_scope(name)):
                updates, new_state[name] = t.update(updates, state[name],
                                                    params)
        return updates, new_state

    return GradientTransformation(init_fn, update_fn)


class InjectState(NamedTuple):
    count: Tagged
    hyperparams: dict    # name -> Tagged scalar (role 'hyperparam')
    inner: Any


def inject_hyperparams(inner_factory: Callable[..., GradientTransformation]):
    """optax-style wrapper: numeric hyperparameters live in optimizer state.

    ``inner_factory(**hypers)`` must build a GradientTransformation whose
    *state structure* does not depend on the hyperparameter values.  Each
    declared hyper is either a number (stored in state, mutable at runtime
    via ``set_hyperparams`` — no chain rebuild) or a callable schedule
    ``count -> value`` (re-evaluated every step from the injected count; the
    current value is still mirrored into state for observability).
    """
    def make(**hypers):
        def resolve(count, current: dict) -> dict:
            out = {}
            for k, v in hypers.items():
                if callable(v):
                    out[k] = jnp.asarray(v(count), jnp.float32)
                else:
                    out[k] = current[k]
            return out

        def init_fn(params):
            count0 = jnp.zeros([], jnp.int32)
            vals = {k: jnp.asarray(v(count0) if callable(v) else v,
                                   jnp.float32)
                    for k, v in hypers.items()}
            inner = inner_factory(**vals).init(params)
            return InjectState(
                count=tag(count0, "count"),
                hyperparams={k: tag(v, "hyperparam")
                             for k, v in vals.items()},
                inner=inner)

        def update_fn(updates, state, params=None):
            count = state.count.value
            current = {k: t.value for k, t in state.hyperparams.items()}
            vals = resolve(count, current)
            tx = inner_factory(**vals)
            updates, inner = tx.update(updates, state.inner, params)
            return updates, InjectState(
                count=Tagged(count + 1, state.count.meta),
                hyperparams={k: Tagged(v, state.hyperparams[k].meta)
                             for k, v in vals.items()},
                inner=inner)

        return GradientTransformation(init_fn, update_fn)

    return make


def set_hyperparams(state: InjectState, **overrides) -> InjectState:
    """Mutate stored hyperparameter values at runtime (serve/elastic) without
    rebuilding the chain.  Schedule-driven hypers are recomputed from the
    step count each update; overriding those here only affects the mirrored
    value until the next step."""
    hp = dict(state.hyperparams)
    for k, v in overrides.items():
        if k not in hp:
            raise KeyError(f"unknown hyperparameter {k!r}; have {list(hp)}")
        t = hp[k]
        hp[k] = Tagged(jnp.asarray(v, t.value.dtype), t.meta)
    return state._replace(hyperparams=hp)


def get_hyperparams(state: InjectState) -> dict:
    return {k: t.value for k, t in state.hyperparams.items()}


def get_stage(state, name: str):
    """Fetch a named stage's state from a (possibly injected) chain state."""
    if isinstance(state, InjectState):
        return get_stage(state.inner, name)
    if isinstance(state, dict):
        if name not in state:
            raise KeyError(f"no stage {name!r}; have {sorted(state)}")
        return state[name]
    raise TypeError(f"not a named-chain state: {type(state)}")
