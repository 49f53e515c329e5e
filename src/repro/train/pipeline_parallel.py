"""GPipe-style pipeline parallelism over a 'pipe' mesh axis.

Layer stack is sharded across stages (leading stacked-layer dim over the
pipe axis); microbatches stream through with jax.lax.ppermute. Forward is
written with plain collectives inside shard_map, so jax.grad differentiates
it into the standard 1F1B-ish reverse schedule automatically.

This is the optional PP substrate for very deep models / cross-pod
pipelining (the default production layout for the assigned archs is
DP+FSDP+TP — see DESIGN.md §8); correctness is covered by
tests/test_pipeline.py against the sequential reference.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def gpipe_apply(mesh: Mesh, axis: str, layers, block_fn: Callable,
                x: jnp.ndarray, microbatches: int) -> jnp.ndarray:
    """Run ``block_fn`` over a layer stack pipelined across ``axis``.

    layers: pytree stacked on dim0 with size L, L % n_stages == 0;
    x: (B, ...) activations, B % microbatches == 0.
    """
    # the stages exchange activations with collectives inside shard_map;
    # Explicit mesh axes (``jax.make_mesh``'s default) would also type the
    # scalars of the backward pass as sharded, which plain ``jax.grad``
    # outside a ``jax.set_mesh`` context rejects — run over Auto axes
    mesh = Mesh(mesh.devices, mesh.axis_names)
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % microbatches == 0, (B, microbatches)
    M = microbatches
    xm = x.reshape(M, B // M, *x.shape[1:])

    layer_specs = jax.tree.map(lambda _: P(axis), layers)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(layer_specs, P()), out_specs=P(),
        check_vma=False)
    def run(local_layers, xm):
        idx = jax.lax.axis_index(axis)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def stage_apply(cur):
            def body(c, p):
                return block_fn(p, c), None

            out, _ = jax.lax.scan(body, cur, local_layers)
            return out

        state = jnp.zeros_like(xm[0])
        outs = jnp.zeros_like(xm)
        for t in range(M + n_stages - 1):
            recv = jax.lax.ppermute(state, axis, fwd_perm)
            inject = xm[min(t, M - 1)]
            first = (idx == 0) & (t < M)
            cur = jnp.where(first, inject, recv)
            cur = stage_apply(cur)
            state = cur
            m_idx = t - (n_stages - 1)
            if m_idx >= 0:
                write = (idx == n_stages - 1)
                outs = outs.at[m_idx].set(
                    jnp.where(write, cur, outs[m_idx]))
        # only the last stage holds real outputs; broadcast via psum
        outs = jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis)
        return outs

    out = run(layers, xm)
    return out.reshape(B, *x.shape[1:])
