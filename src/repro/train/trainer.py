"""Training step construction + distributed state sharding.

``make_train_step`` builds the jit-able (params, opt_state, batch) ->
(params', opt_state', metrics) function: fwd+bwd (remat per layer inside the
model), optional microbatched gradient accumulation, optimizer update.

``train_state_shardings`` assigns NamedShardings to every optimizer-state
leaf by walking the ``StateMeta`` annotations (core/api.py): param-shaped
leaves (momentum, grafting, diag accumulators) inherit the owning
parameter's sharding via ``meta.param_index``; blocked leaves are the packed
shape-group pools (core/pool.py) whose leading dim spans every same-shaped
block in the model — they shard that dim over the model-major ``opt_blocks``
axes (sharding/rules.py), so FD refresh runs data-parallel over the whole
('model', 'data') mesh.  Counts/hyperparams replicate.  No
optimizer-specific types appear here — a new Preconditioner shards
correctly for free.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import api
from repro.core.transform import GradientTransformation, apply_updates
from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.sharding import rules as rules_lib

PyTree = Any


def make_train_step(cfg: ModelConfig, tx: GradientTransformation, *,
                    unroll: bool = False,
                    microbatches: Optional[int] = None,
                    data_parallel_mesh=None,
                    dp_axes: Optional[tuple] = None,
                    donate: bool = True) -> Callable:
    """Build the train step.  By default the returned function is jitted
    with ``params`` and ``opt_state`` DONATED (``donate_argnums=(0, 1)``):
    XLA reuses the input buffers for the outputs, so the step allocates no
    second copy of the model or optimizer state — in particular the async
    refresh pending slot (``EngineConfig.refresh_mode="async"``) adds zero
    steady-state copies on top of its double buffer.  Callers must not
    touch a ``params``/``opt_state`` value after passing it in (the arrays
    are deleted); pass ``donate=False`` to get the raw un-jitted callable
    (inputs preserved — reference comparisons, custom ``jax.jit`` wrappers
    with explicit shardings).

    With ``data_parallel_mesh`` the whole step runs inside the
    ``jax.shard_map`` with the batch split over
    ``dp_axes``: each shard computes gradients on its local slice, the
    chain consumes the dp-mean gradients (int-free psum — clipping,
    grafting and momentum see exactly what a replicated run sees), and the
    per-shard local gradients are handed to the engine's sharded-statistics
    path via ``distributed.reduce.local_gradients`` so
    ``stats_reduction="sharded"`` optimizers sketch their local stream and
    butterfly-merge at refresh time.  Without a mesh the behavior is the
    seed's, untouched.
    """
    def loss_of(params, batch):
        return model_lib.loss_fn(cfg, params, batch, unroll=unroll)

    def loss_and_grads(params, batch):
        if microbatches and microbatches > 1:
            def split(key, x):
                axis = 1 if key == "positions" else 0  # positions: (3, B, S)
                assert x.shape[axis] % microbatches == 0, \
                    f"batch dim {x.shape[axis]} not divisible by {microbatches}"
                if axis == 0:
                    return x.reshape(microbatches, x.shape[0] // microbatches,
                                     *x.shape[1:])
                r = x.reshape(x.shape[0], microbatches,
                              x.shape[1] // microbatches, *x.shape[2:])
                return jnp.moveaxis(r, 1, 0)

            mb = {k: split(k, v) for k, v in batch.items()}
            zero = jax.tree.map(jnp.zeros_like, params)

            def body(acc, mbatch):
                loss, grads = jax.value_and_grad(loss_of)(params, mbatch)
                acc_loss, acc_g = acc
                return (acc_loss + loss,
                        jax.tree.map(jnp.add, acc_g, grads)), None

            if unroll:  # probe mode: cost_analysis must see every microbatch
                acc = (jnp.zeros([], jnp.float32), zero)
                for i in range(microbatches):
                    acc, _ = body(acc, jax.tree.map(lambda x: x[i], mb))
                loss_sum, gsum = acc
            else:
                (loss_sum, gsum), _ = jax.lax.scan(
                    body, (jnp.zeros([], jnp.float32), zero), mb)
            inv = 1.0 / microbatches
            loss = loss_sum * inv
            grads = jax.tree.map(lambda g: g * inv, gsum)
        else:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
        return loss, grads

    def apply_and_norm(params, updates, grads):
        # the last elementwise passes, under the optimizer's opt/ scopes
        with jax.named_scope("opt/apply"):
            new_params = apply_updates(params, updates)
        with jax.named_scope("opt/grad_norm"):
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree.leaves(grads)))
        return new_params, gnorm

    def train_step(params, opt_state, batch):
        loss, grads = loss_and_grads(params, batch)
        updates, new_opt_state = tx.update(grads, opt_state, params)
        new_params, gnorm = apply_and_norm(params, updates, grads)
        return new_params, new_opt_state, {"loss": loss, "grad_norm": gnorm}

    if data_parallel_mesh is None:
        if donate:
            return jax.jit(train_step, donate_argnums=(0, 1))
        return train_step

    from repro.distributed import reduce as dreduce
    mesh = data_parallel_mesh
    axes = rules_lib.dp_axis_names(mesh) if dp_axes is None else \
        tuple(a for a in dp_axes if a in mesh.axis_names)
    if not axes:
        if donate:
            return jax.jit(train_step, donate_argnums=(0, 1))
        return train_step

    def shard_body(params, opt_state, batch):
        loss_local, grads_local = loss_and_grads(params, batch)
        loss, grads = loss_local, grads_local
        for a in axes:
            loss = dreduce.pmean(loss, a)
            grads = dreduce.pmean(grads, a)
        with dreduce.local_gradients(grads_local):
            updates, new_opt_state = tx.update(grads, opt_state, params)
        new_params, gnorm = apply_and_norm(params, updates, grads)
        return new_params, new_opt_state, {"loss": loss, "grad_norm": gnorm}

    def sharded_train_step(params, opt_state, batch):
        def batch_spec(key):
            # positions batches on axis 1 ((3, B, S)); everything else on 0
            if key == "positions":
                return P(None, axes)
            return P(axes)
        step = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(), P(), {k: batch_spec(k) for k in batch}),
            out_specs=(P(), P(), P()), check_vma=False)
        return step(params, opt_state, batch)

    if donate:
        return jax.jit(sharded_train_step, donate_argnums=(0, 1))
    return sharded_train_step


# ---------------------------------------------------------------------------
# Sharding assignment for optimizer state


def train_state_shardings(opt_state: PyTree, params: PyTree,
                          rules: rules_lib.MeshRules) -> PyTree:
    """NamedShardings for an optimizer-state pytree (works on structs).

    Pure ``StateMeta`` traversal: no isinstance checks against optimizer
    leaf types anywhere."""
    param_shardings = rules_lib.tree_param_shardings(params, rules)
    flat_param_sh = jax.tree.leaves(
        param_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    repl = NamedSharding(rules.mesh, P())

    def assign(meta: Optional[api.StateMeta], leaf) -> NamedSharding:
        if meta is None or meta.shard == "replicate" \
                or meta.role in ("count", "hyperparam"):
            return repl
        if meta.param_index is not None and meta.shard in ("auto", "param"):
            return flat_param_sh[meta.param_index]
        if meta.blocked or meta.shard == "blocks":
            return rules_lib.blocks_sharding(rules, leaf)
        return repl

    return api.map_with_meta(assign, opt_state)
