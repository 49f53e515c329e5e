"""Pallas TPU kernels: Gram matrix C = A^T A with tiled reduction.

This is the tall-skinny contraction at the heart of every FD update
(DESIGN.md §3): M = [sqrt(beta2) B, G] is (d, ell+r) and we need its
(ell+r, ell+r) Gram. The reduction dim d streams through VMEM in ``bd``
tiles while each (bk x bk) output tile stays VMEM-resident and accumulates —
MXU-aligned when tiles are multiples of 128 (default ell=256 is).  Inputs of
any float dtype (bf16/fp16/f32) are upcast in-kernel so the accumulator is
always f32.

Single-block grid: (k_tiles_i, k_tiles_j, d_tiles); d is the innermost
(sequential) dimension so the output block revision is legal ("arbitrary"
semantics).

Batched grid (``batched_gram_pallas``) — the pooled-stack entry point: the
input is one packed ``(N, d, k)`` pool of same-shaped blocks (core/pool.py)
and the pool dim N joins the grid directly instead of being vmapped over:

    grid = (N / bn_stack, k_tiles_i, k_tiles_j, d_tiles)

One program instance owns ``bn_stack`` blocks' (bk x bk) output tile (default
1 — one program per block x output tile) and streams their shared d range
through VMEM exactly like the single-block kernel; d stays innermost so each
(n, i, j) accumulator is revisited sequentially.  N ragged against
``bn_stack`` is zero-padded (a zero block contributes a zero Gram) and
sliced off, as are ragged k/d tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# full-f32 MXU contraction (an f32 dot's default on a TPU is one bf16 pass)
_HIGHEST = jax.lax.Precision.HIGHEST


def _gram_kernel(a_i_ref, a_j_ref, out_ref, *, n_d_tiles: int):
    di = pl.program_id(2)

    @pl.when(di == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # upcast before the dot: bf16/fp16 inputs accumulate in f32 (MXU-style)
    a_i = a_i_ref[...].astype(jnp.float32)  # (bd, bk)
    a_j = a_j_ref[...].astype(jnp.float32)  # (bd, bk)
    out_ref[...] += jax.lax.dot_general(
        a_i, a_j, (((0,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bk", "bd", "interpret"))
def gram_pallas(a: jnp.ndarray, *, bk: int = 128, bd: int = 256,
                interpret: bool = True) -> jnp.ndarray:
    """C = A^T A for A of shape (d, k). Pads to tile multiples."""
    d, k = a.shape
    bk = min(bk, max(k, 1))
    bd = min(bd, max(d, 1))
    pk = (-k) % bk
    pd = (-d) % bd
    if pk or pd:
        a = jnp.pad(a, ((0, pd), (0, pk)))
    dp, kp = a.shape
    n_d_tiles = dp // bd
    grid = (kp // bk, kp // bk, n_d_tiles)

    out = pl.pallas_call(
        functools.partial(_gram_kernel, n_d_tiles=n_d_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bd, bk), lambda i, j, di: (di, i)),
            pl.BlockSpec((bd, bk), lambda i, j, di: (di, j)),
        ],
        out_specs=pl.BlockSpec((bk, bk), lambda i, j, di: (i, j)),
        # accumulate in f32 regardless of input dtype (MXU-style)
        out_shape=jax.ShapeDtypeStruct((kp, kp), jnp.float32),
        name="gram",
        interpret=interpret,
    )(a, a)
    return out[:k, :k]  # f32 accumulator result (FD consumes f32)


def _batched_gram_kernel(a_i_ref, a_j_ref, out_ref):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a_i = a_i_ref[...].astype(jnp.float32)  # (bn_stack, bd, bk)
    a_j = a_j_ref[...].astype(jnp.float32)
    # per-block A^T A: contract the streamed d tile, batch the pool dim
    out_ref[...] += jax.lax.dot_general(
        a_i, a_j, (((1,), (1,)), ((0,), (0,))),
        precision=_HIGHEST, preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("bk", "bd", "bn_stack", "interpret"))
def batched_gram_pallas(a: jnp.ndarray, *, bk: int = 128, bd: int = 256,
                        bn_stack: int = 1,
                        interpret: bool = True) -> jnp.ndarray:
    """C[n] = A[n]^T A[n] for a packed pool stack A of shape (N, d, k).

    The pool dim N lives on the Pallas grid (``bn_stack`` blocks per program,
    default one program per block x output tile) — no vmap over the
    single-block kernel.  Ragged N/d/k are zero-padded and sliced off.
    """
    N, d, k = a.shape
    if N == 0:
        # empty pool group: a 0-sized grid dim is undefined behaviour in
        # some lowerings, and the result is shape-determined anyway
        return jnp.zeros((0, k, k), jnp.float32)
    bk = min(bk, max(k, 1))
    bd = min(bd, max(d, 1))
    bn_stack = min(bn_stack, max(N, 1))
    pN = (-N) % bn_stack
    pk = (-k) % bk
    pd = (-d) % bd
    if pN or pk or pd:
        a = jnp.pad(a, ((0, pN), (0, pd), (0, pk)))
    Np, dp, kp = a.shape
    grid = (Np // bn_stack, kp // bk, kp // bk, dp // bd)

    out = pl.pallas_call(
        _batched_gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn_stack, bd, bk), lambda n, i, j, di: (n, di, i)),
            pl.BlockSpec((bn_stack, bd, bk), lambda n, i, j, di: (n, di, j)),
        ],
        out_specs=pl.BlockSpec((bn_stack, bk, bk),
                               lambda n, i, j, di: (n, i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, kp, kp), jnp.float32),
        name="batched_gram",
        interpret=interpret,
    )(a, a)
    return out[:N, :k, :k]


def _batched_gram_mixed_kernel(vq_ref, a_ref, out_ref):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # the int8 eigenvector stack dequantizes in-registers: the astype IS the
    # dequantize (per-block scale + per-column ladder weights are folded in
    # outside the kernel, on the small (N, k+r, k+r) output)
    b = vq_ref[...].astype(jnp.float32)       # (bn_stack, bd, k)
    a = a_ref[...].astype(jnp.float32)        # (bn_stack, bd, r)
    m = jnp.concatenate([b, a], axis=2)       # in-register, never HBM
    out_ref[...] += jax.lax.dot_general(
        m, m, (((1,), (1,)), ((0,), (0,))),
        precision=_HIGHEST, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bd", "bn_stack", "interpret"))
def batched_gram_mixed_pallas(vq: jnp.ndarray, colw: jnp.ndarray,
                              a: jnp.ndarray, *, bd: int = 256,
                              bn_stack: int = 1,
                              interpret: bool = True) -> jnp.ndarray:
    """Gram of the mixed FD stack ``M = [dequant(vq) * colw, A]`` without
    ever materializing the dequantized ``(N, d, k)`` f32 eigenvector stack.

    vq: (N, d, k) int8 quantized eigenvectors, colw: (N, k) f32 per-column
    weights (per-block quantization scale x sqrt(beta2 * s) folded
    together), a: (N, d, r) f32 new factors.  Returns (N, k+r, k+r) f32.

    The kernel accumulates ``C0 = [V, A]^T [V, A]`` with the int8 upcast
    happening in-registers (grid = (N/bn_stack, d/bd); the whole (k+r)^2
    output tile stays VMEM-resident per block — fine for the pool shapes
    the engine produces, where k+r <= block_size + rank).  The exact column
    weighting ``C = D C0 D`` with ``D = diag([colw, 1])`` is applied
    outside on the small output: elementwise f32, no d-sized traffic.
    """
    N, d, k = vq.shape
    Na, da, r = a.shape
    assert (N, d) == (Na, da), (vq.shape, a.shape)
    K = k + r
    if N == 0:
        return jnp.zeros((0, K, K), jnp.float32)
    bd = min(bd, max(d, 1))
    bn_stack = min(bn_stack, max(N, 1))
    pN = (-N) % bn_stack
    pd = (-d) % bd
    if pN or pd:
        vq = jnp.pad(vq, ((0, pN), (0, pd), (0, 0)))
        a = jnp.pad(a, ((0, pN), (0, pd), (0, 0)))
    Np, dp, _ = vq.shape

    out = pl.pallas_call(
        _batched_gram_mixed_kernel,
        grid=(Np // bn_stack, dp // bd),
        in_specs=[
            pl.BlockSpec((bn_stack, bd, k), lambda n, di: (n, di, 0)),
            pl.BlockSpec((bn_stack, bd, r), lambda n, di: (n, di, 0)),
        ],
        out_specs=pl.BlockSpec((bn_stack, K, K), lambda n, di: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, K, K), jnp.float32),
        name="batched_gram_mixed",
        interpret=interpret,
    )(vq, a)
    out = out[:N]
    w = jnp.concatenate(
        [colw.astype(jnp.float32), jnp.ones((N, r), jnp.float32)], axis=1)
    return out * w[:, :, None] * w[:, None, :]
