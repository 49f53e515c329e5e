"""Logical-axis sharding rules -> mesh PartitionSpecs.

Model code annotates activations with *logical* axes via ``constrain``;
parameters get specs from path-based rules. A thread-global ``MeshRules``
context maps logical axes to mesh axes ('pod', 'data', 'model'); with no
active context everything is a no-op, so the same model code runs unsharded
on CPU tests and fully sharded under the production mesh.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_local = threading.local()


# logical axis -> mesh axis (tuple = sharded over multiple mesh axes)
DEFAULT_LOGICAL_RULES = {
    "batch": ("pod", "data"),     # DP over pod + data
    "fsdp": "data",               # param row sharding (ZeRO-3 style)
    "tensor": "model",            # TP
    "vocab": "model",
    "experts": "model",           # EP
    "kv_seq": "model",            # decode-cache sequence sharding (SP)
    "seq": None,                  # training seq unsharded by default
    "embed": None,                # residual d_model dim (activations)
    "heads": "model",
    "stack": None,                # scan-over-layers stack dim
    # optimizer per-block state: leading blocks dim tiled model-major so
    # EP-sharded expert gradients re-layout locally (EXPERIMENTS.md §Perf,
    # kimi iteration 3)
    "opt_blocks": ("model", "data"),
}


@dataclasses.dataclass
class MeshRules:
    mesh: Mesh
    rules: dict

    def axis(self, logical: Optional[str]):
        if logical is None:
            return None
        mapped = self.rules.get(logical, None)
        if mapped is None:
            return None
        axes = mapped if isinstance(mapped, tuple) else (mapped,)
        present = tuple(a for a in axes if a in self.mesh.axis_names)
        if not present:
            return None
        return present if len(present) > 1 else present[0]

    def spec(self, *logical_axes) -> P:
        return P(*(self.axis(a) for a in logical_axes))

    def sharding(self, *logical_axes) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical_axes))


def current() -> Optional[MeshRules]:
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[dict] = None):
    prev = current()
    _local.rules = MeshRules(mesh=mesh, rules={**DEFAULT_LOGICAL_RULES,
                                               **(rules or {})})
    try:
        with mesh:
            yield _local.rules
    finally:
        _local.rules = prev


def constrain(x, *logical_axes):
    """with_sharding_constraint by logical axes; no-op without a mesh.

    Shape-aware: axes whose mesh extent does not divide the dim are dropped
    (padded shardings force GSPMD into full-logits all-gathers — see
    EXPERIMENTS.md §Perf, qwen2.5-32b iteration 1)."""
    r = current()
    if r is None:
        return x
    sh = enforce_divisible(r.sharding(*logical_axes), x.shape)
    return jax.lax.with_sharding_constraint(x, sh)


def dp_axis_names(mesh: Mesh) -> tuple:
    """Mesh axes the ``batch`` logical axis maps onto — the data-parallel
    axes a gradient mean / sketch merge reduces over (train/trainer.py,
    distributed/reduce.py)."""
    mapped = DEFAULT_LOGICAL_RULES["batch"]
    axes = mapped if isinstance(mapped, tuple) else (mapped,)
    return tuple(a for a in axes if a in mesh.axis_names)


def axis_extent(logical: str) -> int:
    """Product of mesh-axis sizes a logical axis maps to (1 = unmapped)."""
    r = current()
    if r is None:
        return 1
    ax = r.axis(logical)
    if ax is None:
        return 1
    axes = ax if isinstance(ax, tuple) else (ax,)
    n = 1
    for a in axes:
        n *= r.mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# Parameter specs by path. Paths are '/'-joined pytree keys.
# Patterns are tried in order; first match wins. Leading stack dims
# (scan-over-layers, experts already named) are handled by the rule arity:
# specs are right-aligned to the param rank, left-padded with None.
PARAM_RULES: Sequence[tuple[str, tuple]] = (
    (r".*embed.*", ("vocab", "fsdp")),
    (r".*lm_head.*", ("fsdp", "vocab")),
    (r".*experts.*/w_(gate|up)", ("experts", "fsdp", None)),
    (r".*experts.*/w_down", ("experts", None, "fsdp")),
    (r".*router.*", ("fsdp", None)),
    (r".*/(wq|wk|wv|wqkv)$", ("fsdp", "tensor")),
    (r".*/(wo)$", ("tensor", "fsdp")),
    (r".*/(bq|bk|bv)$", ("tensor",)),
    (r".*/w_(gate|up)$", ("fsdp", "tensor")),
    (r".*/w_down$", ("tensor", "fsdp")),
    (r".*/in_proj$", ("fsdp", "tensor")),
    (r".*/out_proj$", ("tensor", "fsdp")),
    (r".*/conv_w$", (None, "tensor")),
    (r".*/(A_log|dt_bias|ssm_D|gate_norm)$", ("tensor",)),
    (r".*norm.*", (None,)),
    (r".*", (None,)),
)


def param_spec(path: str, rank: int, rules: MeshRules) -> P:
    for pat, logical in PARAM_RULES:
        if re.fullmatch(pat, path):
            axes = tuple(logical)
            if len(axes) < rank:          # left-pad stack dims
                axes = (None,) * (rank - len(axes)) + axes
            axes = axes[-rank:] if rank else ()
            return rules.spec(*axes)
    return P()


def enforce_divisible(sharding: NamedSharding, shape) -> NamedSharding:
    """Drop spec axes whose mesh extent does not divide the dim size.
    (pjit requires divisible input shardings; vocab sizes like 50280 are not
    multiples of 16 — production would pad, the dry-run baseline relaxes.)"""
    mesh = sharding.mesh
    spec = sharding.spec
    new = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            new.append(entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        new.append(entry if shape[i] % total == 0 else None)
    return NamedSharding(mesh, P(*new))


def tree_param_specs(params, rules: MeshRules):
    """Pytree of PartitionSpecs matching a params pytree."""
    flat = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        specs.append(param_spec(name, leaf.ndim, rules))
    return jax.tree_util.tree_unflatten(flat[1], specs)


def tree_param_shardings(params, rules: MeshRules):
    specs = tree_param_specs(params, rules)
    return jax.tree.map(
        lambda s, leaf: enforce_divisible(NamedSharding(rules.mesh, s),
                                          leaf.shape),
        specs, params, is_leaf=lambda x: isinstance(x, P))


def blocks_sharding(rules: MeshRules, leaf) -> NamedSharding:
    """Sharding for a pooled optimizer-state stack (core/pool.py): leading
    blocks dim over the model-major ``opt_blocks`` tiling (when divisible;
    falls back to data-only fsdp, then replicated).

    The pooled leading dim spans every same-shaped block in the model — not
    one parameter's tiles — so with shape-grouped pools the ('model', 'data')
    product almost always divides it and FD refresh shards over the whole
    mesh.  Model-major matches the expert-major flattening of EP-sharded
    parameters, keeping the grad->block re-layout local (EXPERIMENTS.md
    §Perf, kimi iteration 3).

    Quantized pools (core/quantize.py) route both halves of a
    ``QuantizedPool`` through here: the int8 ``values`` stack
    ``(N, bs_m, bs_n)`` and its fp32 ``scale`` stack ``(N, 1, ..., 1)``
    share the same leading ``N``, so they land on the same leading-dim
    sharding decision and every device holds the scales for exactly the
    blocks it owns (dequantize is shard-local, no gather)."""
    ndim = leaf.ndim
    if not ndim:
        return NamedSharding(rules.mesh, P())
    for axis in ("opt_blocks", "fsdp"):
        spec = rules.spec(*([axis] + [None] * (ndim - 1)))
        sh = enforce_divisible(NamedSharding(rules.mesh, spec), leaf.shape)
        if sh.spec[0] is not None:
            return sh
    return NamedSharding(rules.mesh, P(*([None] * ndim)))
