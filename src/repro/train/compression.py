"""Gradient compression for the data-parallel all-reduce.

int8 quantize -> psum -> dequantize inside shard_map over the dp axes:
each tensor is scaled by its (all-reduduced) absmax, rounded stochastically
to int8, summed in int32, and rescaled — 4x (fp32) / 2x (bf16) reduction in
all-reduce bytes at <0.4% relative error (tests/test_compression.py).

This is the paper-adjacent distributed-optimization trick (Sketchy shrinks
optimizer *state*; this shrinks optimizer *traffic*), exposed as an optional
wrapper around the gradient computation for pure-DP (non-FSDP) runs where
gradients are all-reduced rather than reduce-scattered by GSPMD.  The
scale/round core (absmax -> int8 range, stochastic rounding) is shared with
the pool-level second-moment quantization in ``core/quantize.py``.
"""
from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import quantize

PyTree = Any


def quantized_psum(g: jnp.ndarray, axes: Sequence[str], key) -> jnp.ndarray:
    """int8 quantize -> int32 psum -> rescaled mean over bound mesh axes.

    Public: the shared int8 transport primitive — the gradient all-reduce
    here and the sketch-merge wire (distributed/sketch_merge.py) both ride
    the same ``core/quantize.py`` scale/round core.
    """
    g32 = g.astype(jnp.float32)
    absmax = jax.lax.pmax(jnp.max(jnp.abs(g32)), axes[0])
    for a in axes[1:]:
        absmax = jax.lax.pmax(absmax, a)
    # shared core (core/quantize.py): absmax -> int8 scale, stochastic
    # rounding keeps the compressed all-reduce unbiased
    scale = quantize.int8_scale(absmax)
    q = quantize.round_int8(g32 / scale, key)
    summed = q.astype(jnp.int32)
    for a in axes:
        summed = jax.lax.psum(summed, a)
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return (summed.astype(jnp.float32) * scale / n).astype(g.dtype)


def compressed_mean_grads(grads: PyTree, mesh: Mesh,
                          dp_axes: Sequence[str] = ("data",),
                          seed: int = 0) -> PyTree:
    """Average per-device gradient shards over dp axes with int8 transport.

    grads must be replicated over ``dp_axes`` *logically* (each device holds
    its local microbatch gradient); returns the dp-mean.
    """
    axes = tuple(a for a in dp_axes if a in mesh.axis_names)
    if not axes:
        return grads

    flat, treedef = jax.tree.flatten(grads)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=tuple(P() for _ in flat), out_specs=tuple(P() for _ in flat),
        check_vma=False)
    def reduce_all(*leaves):
        key = jax.random.PRNGKey(seed)
        out = []
        for i, g in enumerate(leaves):
            out.append(quantized_psum(g, axes, jax.random.fold_in(key, i)))
        return tuple(out)

    return jax.tree.unflatten(treedef, list(reduce_all(*flat)))
