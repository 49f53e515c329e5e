"""Frequent Directions sketching (paper Alg. 1 + exponentially-weighted Obs. 6).

The sketch of a PSD stream ``G_t = sum_s beta2^{t-s} A_s A_s^T`` is maintained
in *eigenpair form* ``(U, s, rho)`` with ``U: (d, ell)`` orthonormal columns,
``s: (ell,)`` descending eigenvalues (deflation keeps ``s[-1] == 0``), and
``rho`` the accumulated escaped mass used for the dynamic diagonal
compensation ``rho * I`` (the paper's key construction, Alg. 2/3 line 6).

TPU adaptation (DESIGN.md §3): instead of eigendecomposing the d x d matrix
(Alg. 1 line 3) or SVD-ing the d x (ell+r) stack (paper §6), we
eigendecompose the (ell+r) x (ell+r) Gram matrix of ``M = [sqrt(beta2)*B, A]``
— one tall-skinny MXU matmul plus a small eigh. Identical result, never
materializes d x d, and avoids large-matrix SVD which TPUs lack.  Only the
leading ``ell`` eigenpairs of the Gram are used, and on TPU only they are
computed (``core/top_eigh.py``: the QDWH recursion skips the eigenspaces
below them); elsewhere the full spectrum is solved and sliced.

Kernel injection: every function takes an optional
``kernels: repro.kernels.registry.KernelSet``.  The single-block entry
points use ``kernels.gram`` / ``kernels.lowrank_apply``; the ``*_batched``
variants — the pooled-engine hot path, operating on a whole packed
``(N, ...)`` pool stack at once — use ``kernels.batched_gram`` /
``kernels.batched_lowrank_apply`` (grid-over-N Pallas kernels on TPU).  With
``kernels=None`` everything falls back to plain jnp, and the batched jnp
expressions mirror ``jax.vmap`` of the single-block ones primitive-for-
primitive so the synchronized schedule stays bitwise-reproducible.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.top_eigh import TERMINATION_SIZE, top_eigh

# The batched paths name their Gram ``fd/gram``, and every path names its
# eigendecomposition ``fd/eigh`` (HLO op metadata, free on the device;
# ``fd/eigh`` wraps the eigensolver and nothing else, so the benchmark's
# trace readers find the solver by it).
#
# Every contraction on the FD path runs at full f32: a TPU's default f32
# dot is one bf16 pass, and the Gram feeds an eigh whose small eigenvalues
# set the preconditioner.
_HIGHEST = jax.lax.Precision.HIGHEST


class FDState(NamedTuple):
    eigvecs: jnp.ndarray  # (d, ell) approximate top eigenvectors U
    eigvals: jnp.ndarray  # (ell,) deflated eigenvalues, descending, last == 0
    rho: jnp.ndarray      # scalar: accumulated escaped mass rho_{1:t}


def _top_eigh(C: jnp.ndarray, ell: int):
    """Leading ``ell`` eigenpairs of each symmetric ``C (..., n, n)``:
    eigenvalues ``(..., ell)`` descending and clipped at 0 (round-off makes
    tiny negatives), eigenvectors ``(..., n, ell)``.

    On TPU only those are solved (``top_eigh``, the same QDWH splits the full
    solve makes for them); elsewhere, and where the TPU would solve the
    whole matrix as one Jacobi block anyway, the full spectrum is solved and
    sliced.
    """
    def full(C):
        with jax.named_scope("fd/eigh"):
            lam, V = jnp.linalg.eigh(C)          # ascending
        return (jnp.maximum(lam[..., ::-1][..., :ell], 0.0),
                V[..., ::-1][..., :ell])

    def top(C):
        with jax.named_scope("fd/eigh"):
            lam, V = top_eigh(C, ell)            # ascending
        return jnp.maximum(lam[..., ::-1], 0.0), V[..., ::-1]

    if C.shape[-1] <= TERMINATION_SIZE or ell >= C.shape[-1]:
        return full(C)
    return jax.lax.platform_dependent(C, tpu=top, default=full)


def fd_init(d: int, ell: int, dtype=jnp.float32) -> FDState:
    ell = min(ell, d)
    return FDState(
        eigvecs=jnp.zeros((d, ell), dtype),
        eigvals=jnp.zeros((ell,), dtype),
        rho=jnp.zeros((), dtype),
    )


def fd_update(state: FDState, new_factor: jnp.ndarray, beta2: float = 1.0,
              kernels=None) -> FDState:
    """One FD-update step on the PSD increment ``new_factor @ new_factor.T``.

    Args:
      state: current sketch.
      new_factor: (d, r) factor A of the new PSD term M_t = A A^T. For
        S-AdaGrad this is the gradient column g_t[:, None]; for S-Shampoo's
        left factor it is the gradient matrix G_t itself (L += G G^T), and
        G_t^T for the right factor.
      beta2: EMA decay (1.0 recovers the unweighted paper Alg. 1).
      kernels: optional ``KernelSet``; ``kernels.gram`` supplies the
        C = M^T M contraction (Pallas kernel injection point).

    Returns:
      Updated state; ``state.rho`` accumulates escaped mass with the same
      beta2 decay (DESIGN.md §6 — plain sum when beta2 == 1).
    """
    U, s, rho = state
    d, ell = U.shape
    if new_factor.ndim == 1:
        new_factor = new_factor[:, None]
    compute_dtype = jnp.promote_types(U.dtype, jnp.float32)

    # M = [sqrt(beta2) * B, A] where B = U diag(sqrt(s)).  The eigenvalue
    # ladder is non-negative by construction, so the clamp is a bitwise
    # no-op in fp32 — it only guards sqrt(negative) -> NaN when quantized
    # state storage (core/quantize.py) or a lossy checkpoint restore
    # perturbs s below zero.
    s_clamped = jnp.maximum(beta2 * s.astype(compute_dtype), 0.0)
    B = U.astype(compute_dtype) * jnp.sqrt(s_clamped)[None, :]
    M = jnp.concatenate([B, new_factor.astype(compute_dtype)], axis=1)  # (d, ell+r)

    if kernels is None:
        C = jnp.matmul(M.T, M, precision=_HIGHEST)
    else:
        C = kernels.gram(M)
    C = 0.5 * (C + C.T)  # symmetrize for eigh stability

    lam_top, V = _top_eigh(C, ell)
    # Escaped eigenvalue: lambda_ell of the un-deflated update. When the
    # stacked matrix has rank <= ell the escaped mass is genuinely 0 (it is
    # lam[ell-1] only after deflation below keeps the invariant s[-1] == 0).
    rho_t = lam_top[ell - 1]

    inv_sqrt = jnp.where(lam_top > 1e-30, jax.lax.rsqrt(jnp.maximum(lam_top, 1e-30)), 0.0)
    U_new = jnp.matmul(M, V, precision=_HIGHEST) * inv_sqrt[None, :]
    s_new = lam_top - rho_t  # deflate: last entry becomes exactly 0

    return FDState(
        eigvecs=U_new.astype(U.dtype),
        eigvals=s_new.astype(s.dtype),
        rho=(beta2 * rho + rho_t).astype(state.rho.dtype),
    )


def fd_update_batched(state: FDState, new_factor: jnp.ndarray,
                      beta2: float = 1.0, kernels=None,
                      active_k: jnp.ndarray | None = None) -> FDState:
    """``fd_update`` over a whole packed pool stack in one batched call.

    ``state`` leaves carry a leading pool dim N (eigvecs (N, d, ell), eigvals
    (N, ell), rho (N,)); ``new_factor`` is (N, d, r).  With ``kernels`` the
    Gram goes through ``kernels.batched_gram`` (grid-over-N Pallas on TPU);
    without, the jnp expressions mirror ``jax.vmap(fd_update)`` exactly.

    Masked ranks: ``active_k`` (N,) int restricts block ``b`` to its leading
    ``active_k[b]`` ladder columns — the stack keeps full ``ell`` capacity
    (shapes never change) but the FD recurrence runs at the smaller rank:
    only the active columns enter the Gram, deflation subtracts
    ``lam[active_k[b]-1]`` instead of ``lam[ell-1]``, and columns at or past
    ``active_k[b]`` come back exactly zero.  ``active_k=None`` is the
    unmasked path, bitwise-identical to before the rank-budget allocator.

    Quantized compute path: when ``state.eigvecs`` is a ``QuantizedPool``
    (int8 values + per-block scale; the engine's fused int8 mode keeps the
    storage container through the batched methods instead of dequantizing
    at the boundary), the Gram and the eigenvector write-back run through
    the fused quantized entries — the (N, d, ell) f32 eigenvector stack is
    never materialized.  The per-block dequant scale and the sqrt-
    eigenvalue ladder weights are both per-*column* of the small factor,
    so they fold into one (N, ell) weight vector exactly:

        B = dequant(Vq) sqrt(beta2 s) = Vq diag(colw),
        colw = scale * sqrt(beta2 s).

    The refreshed eigenvectors come back already re-quantized (the fused
    epilogue's round-to-nearest matches ``quantize.quantize_stack`` with
    no key), so the state returned here is a new ``QuantizedPool``.
    """
    U, s, rho = state
    if _is_quantized(U):
        return _fd_update_batched_quantized(U, s, rho, new_factor, beta2,
                                            kernels, active_k)
    _, d, ell = U.shape
    if new_factor.ndim == 2:
        new_factor = new_factor[..., None]
    compute_dtype = jnp.promote_types(U.dtype, jnp.float32)

    # non-negative clamp mirrors fd_update: free in fp32, NaN guard under
    # quantized storage
    s_clamped = jnp.maximum(beta2 * s.astype(compute_dtype), 0.0)
    kmask = _rank_mask(active_k, ell)
    if kmask is not None:
        s_clamped = jnp.where(kmask, s_clamped, 0.0)
    B = U.astype(compute_dtype) * jnp.sqrt(s_clamped)[:, None, :]
    M = jnp.concatenate([B, new_factor.astype(compute_dtype)], axis=2)

    with jax.named_scope("fd/gram"):
        if kernels is None:
            C = jnp.matmul(jnp.swapaxes(M, -1, -2), M, precision=_HIGHEST)
        else:
            C = kernels.batched_gram(M)
        C = 0.5 * (C + jnp.swapaxes(C, -1, -2))

    lam_top, V = _top_eigh(C, ell)
    rho_t = _escaped_eigval(lam_top, active_k, ell)   # (N,)

    inv_sqrt = jnp.where(lam_top > 1e-30,
                         jax.lax.rsqrt(jnp.maximum(lam_top, 1e-30)), 0.0)
    U_new = jnp.matmul(M, V, precision=_HIGHEST) * inv_sqrt[:, None, :]
    s_new = lam_top - rho_t[..., None]
    if kmask is not None:
        U_new = jnp.where(kmask[:, None, :], U_new, 0.0)
        s_new = jnp.where(kmask, s_new, 0.0)

    return FDState(
        eigvecs=U_new.astype(U.dtype),
        eigvals=s_new.astype(s.dtype),
        rho=(beta2 * rho + rho_t).astype(state.rho.dtype),
    )


def _rank_mask(active_k, ell: int):
    """(N, ell) bool mask of active ladder columns, or None when unmasked."""
    if active_k is None:
        return None
    kk = jnp.clip(active_k, 1, ell)
    return jnp.arange(ell)[None, :] < kk[:, None]


def _escaped_eigval(lam_top: jnp.ndarray, active_k, ell: int) -> jnp.ndarray:
    """Per-block deflation eigenvalue: ``lam[k-1]`` at the active rank."""
    if active_k is None:
        return lam_top[..., ell - 1]
    kk = jnp.clip(active_k, 1, ell)
    return jnp.take_along_axis(lam_top, kk[:, None] - 1, axis=-1)[..., 0]


def _is_quantized(x) -> bool:
    """True when ``x`` is a core.quantize.QuantizedPool (lazy import — fd is
    imported by modules below quantize in the package graph)."""
    from repro.core import quantize
    return isinstance(x, quantize.QuantizedPool)


def _fd_update_batched_quantized(U, s, rho, new_factor, beta2, kernels,
                                 active_k=None) -> FDState:
    """``fd_update_batched`` with the eigenvector stack in int8 pool storage
    end to end; see the caller's docstring for the scale-folding algebra."""
    from repro.core import quantize

    vq, scale = U.values, U.scale            # (N, d, ell) int8, (N, 1, 1)
    N, d, ell = vq.shape
    if new_factor.ndim == 2:
        new_factor = new_factor[..., None]
    A = new_factor.astype(jnp.float32)       # (N, d, r)

    s_clamped = jnp.maximum(beta2 * s.astype(jnp.float32), 0.0)
    kmask = _rank_mask(active_k, ell)
    if kmask is not None:
        # masking the column weights zeroes inactive columns of B exactly,
        # regardless of what the int8 values hold there
        s_clamped = jnp.where(kmask, s_clamped, 0.0)
    colw = scale.reshape(N, 1) * jnp.sqrt(s_clamped)   # (N, ell)

    with jax.named_scope("fd/gram"):
        if kernels is None:
            m = jnp.concatenate(
                [vq.astype(jnp.float32) * colw[:, None, :], A], axis=2)
            C = jnp.matmul(jnp.swapaxes(m, -1, -2), m, precision=_HIGHEST)
        else:
            C = kernels.batched_gram_mixed(vq, colw, A)
        C = 0.5 * (C + jnp.swapaxes(C, -1, -2))

    lam_top, V = _top_eigh(C, ell)
    rho_t = _escaped_eigval(lam_top, active_k, ell)   # (N,)

    inv_sqrt = jnp.where(lam_top > 1e-30,
                         jax.lax.rsqrt(jnp.maximum(lam_top, 1e-30)), 0.0)
    # U_new = M @ W with M = [Vq diag(colw), A]: split W by row block and
    # fold the column weights into the top half so the projection consumes
    # the raw int8 values directly
    W = V * inv_sqrt[:, None, :]                      # (N, ell+r, ell)
    if kmask is not None:
        # masking W's output columns keeps inactive eigenvector columns at
        # zero through the in-kernel quantization as well
        W = jnp.where(kmask[:, None, :], W, 0.0)
    w_top = colw[..., None] * W[..., :ell, :]         # (N, ell, ell)
    w_bot = W[..., ell:, :]                           # (N, r, ell)

    if kernels is None:
        un = jnp.matmul(vq.astype(jnp.float32), w_top, precision=_HIGHEST) \
            + jnp.matmul(A, w_bot, precision=_HIGHEST)
        qp = quantize.quantize_stack(un)
    else:
        values, scale_new = kernels.batched_project_quantize(
            vq, w_top, A, w_bot)
        qp = quantize.QuantizedPool(values=values, scale=scale_new)

    s_new = lam_top - rho_t[..., None]
    if kmask is not None:
        s_new = jnp.where(kmask, s_new, 0.0)
    return FDState(
        eigvecs=qp,
        eigvals=s_new.astype(s.dtype),
        rho=(beta2 * rho + rho_t).astype(rho.dtype),
    )


def fd_resize_batched(state: FDState, new_k: jnp.ndarray) -> FDState:
    """Move each block of a pooled sketch stack to a new active rank.

    Capacity (array shapes) never changes — this is the rank-*migration*
    primitive for the budget allocator.  Shrinking block ``b`` to
    ``new_k[b]`` folds the dropped eigenvalue mass into ``rho`` exactly
    (Robust-FD redistribution: ``rho += sum_{i >= new_k} s_i``) and zeroes
    the dropped ladder columns in place, so the per-block FD guarantee
    ``||G - sketch|| <= rho`` is preserved.  Growing is free: columns at or
    past the old active rank are already zero and simply become eligible
    for the next masked ``fd_update_batched``.

    Works on fp32/bf16 stacks and on ``QuantizedPool`` eigenvector storage
    (int8 values are masked in place; the per-block scale is unchanged).
    """
    U, s, rho = state
    quantized = _is_quantized(U)
    ell = (U.values if quantized else U).shape[-1]
    kmask = _rank_mask(new_k, ell)                      # (N, ell)
    s_f = s.astype(jnp.float32)
    dropped = jnp.sum(jnp.where(kmask, 0.0, s_f), axis=-1)   # (N,)
    s_new = jnp.where(kmask, s_f, 0.0).astype(s.dtype)
    rho_new = (rho.astype(jnp.float32) + dropped).astype(rho.dtype)
    if quantized:
        from repro.core import quantize
        U_new = quantize.QuantizedPool(
            values=jnp.where(kmask[:, None, :], U.values,
                             jnp.zeros((), jnp.int8)),
            scale=U.scale)
    else:
        U_new = jnp.where(kmask[:, None, :], U, 0.0).astype(U.dtype)
    return FDState(eigvecs=U_new, eigvals=s_new, rho=rho_new)


def fd_weighted_factor(state: FDState, *, drop_deflated: bool = False
                       ) -> jnp.ndarray:
    """Factor ``B = U diag(sqrt(s))`` with ``B B^T == U diag(s) U^T``.

    Works on a single state (``U (d, ell)`` -> ``(d, ell)``) or a pooled
    stack (``U (N, d, ell)`` -> ``(N, d, ell)``).  With ``drop_deflated``
    the last column is omitted: the deflation invariant ``s[-1] == 0`` makes
    it identically zero, so the merge wire format (distributed/
    sketch_merge.py) sends ``ell - 1`` columns per side without loss.
    """
    U, s, _ = state
    compute_dtype = jnp.promote_types(U.dtype, jnp.float32)
    s_clamped = jnp.maximum(s.astype(compute_dtype), 0.0)
    B = U.astype(compute_dtype) * jnp.sqrt(s_clamped)[..., None, :]
    if drop_deflated and B.shape[-1] > 1:
        B = B[..., :-1]
    return B


def fd_merge_factors_batched(Ba: jnp.ndarray, rho_a: jnp.ndarray,
                             Bb: jnp.ndarray, rho_b: jnp.ndarray, *,
                             ell: int, kernels=None) -> FDState:
    """Merge two weighted-factor stacks into one rank-``ell`` sketch stack.

    This is the mergeable-sketch primitive (Robust FD, Luo et al.): the
    union covariance ``Ba Ba^T + Bb Bb^T`` is re-sketched by stacking the
    factors, eigendecomposing the small Gram (same batched-gram kernel path
    as ``fd_update_batched``), and deflating by the escaped eigenvalue
    ``rho_t``; the carried masses add, so the merged ``rho*I`` compensation
    stays an upper bound on the total escaped mass.

    Args:
      Ba, Bb: (N, d, ra) / (N, d, rb) factor stacks (``fd_weighted_factor``).
      rho_a, rho_b: (N,) escaped masses carried by each side.
      ell: target sketch rank of the merged state.
      kernels: optional ``KernelSet`` for the batched Gram.
    """
    M = jnp.concatenate([Ba.astype(jnp.float32), Bb.astype(jnp.float32)],
                        axis=-1)                       # (N, d, ra+rb)
    if M.shape[-1] < ell:                              # skinny sides: pad so
        pad = ell - M.shape[-1]                        # U keeps (N, d, ell)
        M = jnp.pad(M, ((0, 0), (0, 0), (0, pad)))

    with jax.named_scope("fd/gram"):
        if kernels is None:
            C = jnp.matmul(jnp.swapaxes(M, -1, -2), M, precision=_HIGHEST)
        else:
            C = kernels.batched_gram(M)
        C = 0.5 * (C + jnp.swapaxes(C, -1, -2))

    lam_top, V = _top_eigh(C, ell)
    rho_t = lam_top[..., ell - 1]           # (N,) escaped eigenvalue

    inv_sqrt = jnp.where(lam_top > 1e-30,
                         jax.lax.rsqrt(jnp.maximum(lam_top, 1e-30)), 0.0)
    U_new = jnp.matmul(M, V, precision=_HIGHEST) * inv_sqrt[:, None, :]
    s_new = lam_top - rho_t[..., None]      # deflate: last entry exactly 0

    return FDState(eigvecs=U_new, eigvals=s_new,
                   rho=rho_a.astype(jnp.float32) + rho_b.astype(jnp.float32)
                   + rho_t)


def fd_merge_batched(a: FDState, b: FDState, kernels=None) -> FDState:
    """Merge two pooled sketch stacks of the same shape (leading dim N).

    ``cov(merged) ~= cov(a) + cov(b)`` within the FD bound: the operator-
    norm error of the merged sketch against the exact sum is at most
    ``merged.rho`` (escaped masses are additive through the merge)."""
    _, _, ell = a.eigvecs.shape
    out = fd_merge_factors_batched(
        fd_weighted_factor(a), a.rho, fd_weighted_factor(b), b.rho,
        ell=ell, kernels=kernels)
    return FDState(eigvecs=out.eigvecs.astype(a.eigvecs.dtype),
                   eigvals=out.eigvals.astype(a.eigvals.dtype),
                   rho=out.rho.astype(a.rho.dtype))


def fd_merge(a: FDState, b: FDState, kernels=None) -> FDState:
    """Merge two single-block sketches (``U (d, ell)``); see
    ``fd_merge_batched``.  Mergeability is what makes the sketch a
    distributed-friendly statistic: shards sketch their local streams and
    the combined sketch matches a single-stream sketch of the union within
    the FD error bound (tests/test_fd.py)."""
    stack = jax.tree.map(lambda x: x[None], a), jax.tree.map(
        lambda x: x[None], b)
    out = fd_merge_batched(stack[0], stack[1], kernels=kernels)
    return FDState(*(x[0] for x in out))


def fd_pressure(state: FDState) -> jnp.ndarray:
    """Escaped-mass ratio ``rho / (trace + rho)`` in [0, 1].

    The sketch's own estimate of how much of the stream it is failing to
    capture: near 0 the leading-``ell`` subspace holds the stream, near 1
    the mass escapes past the sketch rank.  This is the drift-pressure
    signal shared by the rank-budget allocator (``rho_greedy`` pouring) and
    the serve-time gradient monitor (serve/monitor.py).  Batch-polymorphic:
    pooled states (eigvals (N, ell), rho (N,)) return an (N,) vector.
    """
    trace = jnp.sum(state.eigvals.astype(jnp.float32), axis=-1)
    rho = state.rho.astype(jnp.float32)
    return rho / jnp.maximum(trace + rho, 1e-30)


def fd_leading_eigval(state: FDState, *, compensated: bool = True
                      ) -> jnp.ndarray:
    """Top eigenvalue of the sketched covariance.  With ``compensated``
    (default) this is the top eigenvalue of the rho-compensated estimate
    ``U diag(s) U^T + rho I`` — i.e. ``s[0] + rho`` — matching what the
    preconditioner actually applies; without, the raw deflated ladder top.
    Batch-polymorphic like ``fd_pressure``."""
    top = state.eigvals[..., 0].astype(jnp.float32)
    if compensated:
        top = top + state.rho.astype(jnp.float32)
    return top


def fd_subspace_angle(a, b, k: int = None) -> jnp.ndarray:
    """Largest principal angle (radians) between the leading-``k`` sketch
    subspaces of ``a`` and ``b`` (FDState or raw (d, ell) eigvec arrays).

    ``arccos(sigma_min(Ua^T Ub))``: 0 when the subspaces coincide, pi/2 when
    some direction of one is orthogonal to all of the other.  ``k`` defaults
    to ``ell - 1`` (the deflation invariant keeps the last ladder column
    zero, which would read as a spurious right angle).  A column that is
    still zero (un-warmed sketch, low-rank window) saturates the angle at
    pi/2 — callers should compare sketches that have both seen data.
    """
    Ua = a.eigvecs if isinstance(a, FDState) else a
    Ub = b.eigvecs if isinstance(b, FDState) else b
    if k is None:
        k = max(Ua.shape[-1] - 1, 1)
    k = min(k, Ua.shape[-1], Ub.shape[-1])
    C = jnp.matmul(Ua[..., :k].astype(jnp.float32).T,
                   Ub[..., :k].astype(jnp.float32), precision=_HIGHEST)
    sv = jnp.linalg.svd(C, compute_uv=False)
    return jnp.arccos(jnp.clip(jnp.min(sv, axis=-1), 0.0, 1.0))


def fd_covariance(state: FDState, include_rho: bool = False) -> jnp.ndarray:
    """Materialize the sketched covariance (testing/analysis only)."""
    U, s, rho = state
    cov = (U * s[None, :]) @ U.T
    if include_rho:
        cov = cov + rho * jnp.eye(U.shape[0], dtype=cov.dtype)
    return cov


def fd_inverse_root_coeffs(state: FDState, *, exponent: float, eps: float
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Coefficients for applying (U diag(s) U^T + (rho+eps) I)^{exponent}.

    Returns (base, coeffs) such that
      apply(G) = base * G + U @ diag(coeffs) @ (U^T @ G)
    Uses the eigenpair representation: eigenvalues of the compensated
    preconditioner are (s_i + rho + eps) on span(U) and (rho + eps) on the
    orthogonal complement. Elementwise — no iterative root solve needed.
    Batch-polymorphic: with a pooled state (s (N, ell), rho (N,)) it returns
    base (N,) and coeffs (N, ell).
    """
    _, s, rho = state
    damp = rho + eps
    # Moore-Penrose semantics (Alg. 2 uses the pseudoinverse): with no
    # diagonal mass, directions outside span(U) map to 0, not eps^exponent.
    tol = 1e-10
    base = jnp.where(damp > tol, jnp.power(jnp.maximum(damp, tol), exponent),
                     0.0)
    lam = s + damp[..., None]
    coeffs = jnp.where(lam > tol, jnp.power(jnp.maximum(lam, tol), exponent),
                       0.0) - base[..., None]
    return base, coeffs


def fd_apply_inverse_root(state: FDState, G: jnp.ndarray, *, exponent: float,
                          eps: float, kernels=None) -> jnp.ndarray:
    """Compute (sketch + (rho+eps) I)^{exponent} @ G without forming d x d.

    kernels: optional ``KernelSet``; ``kernels.lowrank_apply`` supplies the
    fused low-rank + diagonal apply.
    """
    base, coeffs = fd_inverse_root_coeffs(state, exponent=exponent, eps=eps)
    U = state.eigvecs
    if kernels is not None:
        return kernels.lowrank_apply(U, coeffs, base, G)
    proj = jnp.matmul(U.T, G, precision=_HIGHEST)
    return base * G + jnp.matmul(U, coeffs[:, None] * proj,
                                 precision=_HIGHEST)


def fd_apply_inverse_root_batched(state: FDState, G: jnp.ndarray, *,
                                  exponent: float, eps: float,
                                  kernels=None) -> jnp.ndarray:
    """``fd_apply_inverse_root`` over a packed pool stack (state leaves and
    G carry a leading pool dim N).  With ``kernels`` the fused apply goes
    through ``kernels.batched_lowrank_apply``; without, the jnp expressions
    mirror ``jax.vmap(fd_apply_inverse_root)`` exactly.

    A ``QuantizedPool`` eigenvector stack is consumed directly: the
    per-block scale commutes out of ``U diag(c) U^T`` as ``scale^2``, so
    the kernel path folds it into the coefficients and runs on the raw
    int8 values (``kernels.batched_lowrank_apply_quantized``)."""
    base, coeffs = fd_inverse_root_coeffs(state, exponent=exponent, eps=eps)
    U = state.eigvecs
    if _is_quantized(U):
        if kernels is not None:
            return kernels.batched_lowrank_apply_quantized(
                U.values, U.scale, coeffs, base, G)
        U = U.values.astype(jnp.float32) * U.scale
    elif kernels is not None:
        return kernels.batched_lowrank_apply(U, coeffs, base, G)
    proj = jnp.matmul(jnp.swapaxes(U, -1, -2), G, precision=_HIGHEST)
    return base[..., None, None] * G + jnp.matmul(
        U, coeffs[..., None] * proj, precision=_HIGHEST)
