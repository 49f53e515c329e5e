"""Collectives for the sharded-statistics engine path.

``butterfly_merge_fd`` is the log-depth mergeable-sketch reduction: ``P``
shards each holding a locally-updated pooled sketch stack converge to one
(identical) merged stack in ``log2(P)`` ``jax.lax.ppermute`` rounds — the
classic recursive-doubling butterfly, with ``fd_merge`` as the combiner
instead of ``+``.  Each round every shard packs its current stack into the
int8 wire form (``sketch_merge.pack_wire``), swaps it with its XOR partner,
and merges the pair in axis-index order; because the wire rounding is
deterministic and applied to both sides, all shards of a pair compute the
same merged state, so after the last round the stack is replicated across
the axis (which is exactly what the engine's out-specs assume).  Non
power-of-two axis sizes fall back to one all-gather + a single stacked
shrink (same wire bytes per shard, one wide eigh instead of log rounds).

``bound_axis_size`` detects at trace time whether a mesh axis name is bound
(we are inside ``shard_map``/``pmap``) — the engine uses it to fall back to
the replicated path bitwise when there is no data axis to reduce over.

``local_gradients`` is the trace-time side channel the trainer uses to hand
the engine per-shard *local* gradients while the update chain itself (clip,
grafting, momentum) consumes the dp-mean gradients.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.fd import FDState, fd_merge_factors_batched
from repro.distributed import sketch_merge

PyTree = Any

_local = threading.local()


def bound_axis_size(axis: str) -> Optional[int]:
    """Size of a bound mesh axis, or None when the name is unbound.

    Inside ``shard_map``/``pmap`` ``jax.lax.axis_size`` is a static Python
    int at trace time; outside, the unbound name raises ``NameError``.
    """
    try:
        return jax.lax.axis_size(axis)
    except NameError:
        return None


def pmean(x: PyTree, axis: str) -> PyTree:
    """Mean over a bound mesh axis (pytree-polymorphic)."""
    return jax.tree.map(lambda v: v / jax.lax.axis_size(axis),
                        jax.lax.psum(x, axis))


@contextlib.contextmanager
def local_gradients(grads: PyTree):
    """Expose per-shard local gradients to the engine for the duration of a
    traced update call.  ``scale_by_preconditioner`` reads them via
    ``current_local_gradients`` on its sharded-stats path; the gradients
    flowing through the transformation chain stay the dp-mean ones, so
    clipping/grafting/momentum are unchanged."""
    prev = getattr(_local, "grads", None)
    _local.grads = grads
    try:
        yield
    finally:
        _local.grads = prev


def current_local_gradients() -> Optional[PyTree]:
    return getattr(_local, "grads", None)


def _gather_shrink(state: FDState, *, axis: str, axis_size: int, ell: int,
                   kernels, wire_dtype: str) -> FDState:
    """all-gather fallback for non-power-of-two axes: one exchange, one wide
    stacked shrink over all P factors."""
    wire = sketch_merge.pack_wire(state, wire_dtype)
    gathered = jax.lax.all_gather(wire, axis)        # leaves gain leading P
    B = gathered.values.astype(jnp.float32) * gathered.scale
    # (P, N, d, r) -> (N, d, P*r)
    P_, N, d, r = B.shape
    M = jnp.transpose(B, (1, 2, 0, 3)).reshape(N, d, P_ * r)
    rho = jnp.sum(gathered.rho, axis=0)
    empty = jnp.zeros((N, d, 0), jnp.float32)
    return fd_merge_factors_batched(M, rho, empty, jnp.zeros_like(rho),
                                    ell=ell, kernels=kernels)


def butterfly_merge_fd(state: FDState, *, axis: str, axis_size: int,
                       kernels=None, wire_dtype: str = "int8") -> FDState:
    """Merge one pooled sketch stack across a bound mesh axis.

    Args:
      state: pooled FD stack (eigvecs ``(N, d, ell)``) holding this shard's
        locally-updated sketch; must be called inside ``shard_map`` with
        ``axis`` bound.
      axis: mesh axis name to reduce over.
      axis_size: static size of that axis (``bound_axis_size``).
      kernels: optional ``KernelSet`` for the merge Grams.
      wire_dtype: ``"int8"`` (default, ~4x fewer wire bytes) or ``"fp32"``
        (exact exchange — the FD merge error bound holds with no
        quantization slack; used by the property tests).

    Returns:
      The merged stack, identical on every shard of the axis.
    """
    if axis_size <= 1:
        return state
    ell = state.eigvecs.shape[-1]
    if axis_size & (axis_size - 1):
        with jax.named_scope("butterfly_merge_fd/gather_shrink"):
            merged = _gather_shrink(state, axis=axis, axis_size=axis_size,
                                    ell=ell, kernels=kernels,
                                    wire_dtype=wire_dtype)
    else:
        idx = jax.lax.axis_index(axis)
        merged = state
        dist = 1
        while dist < axis_size:
            with jax.named_scope(f"butterfly_merge_fd/round_d{dist}"):
                wire = sketch_merge.pack_wire(merged, wire_dtype)
                perm = [(i, i ^ dist) for i in range(axis_size)]
                other = jax.lax.ppermute(wire, axis, perm)
                # merge in axis-index order so both partners of a pair
                # compute the bitwise-identical result (concatenation order
                # matters to the eigh)
                is_low = (idx & dist) == 0
                lo = jax.tree.map(lambda a, b: jnp.where(is_low, a, b),
                                  wire, other)
                hi = jax.tree.map(lambda a, b: jnp.where(is_low, b, a),
                                  wire, other)
                merged = sketch_merge.merge_wire(lo, hi, ell=ell,
                                                 kernels=kernels)
            dist *= 2
    return FDState(eigvecs=merged.eigvecs.astype(state.eigvecs.dtype),
                   eigvals=merged.eigvals.astype(state.eigvals.dtype),
                   rho=merged.rho.astype(state.rho.dtype))
