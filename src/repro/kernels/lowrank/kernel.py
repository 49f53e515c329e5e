"""Pallas TPU kernels: fused low-rank + diagonal inverse-root apply.

The Sketchy preconditioner application (DESIGN.md §3):

    Y = base * G + U @ diag(coeffs) @ (U^T @ G)

U is (d, ell) with ell <= 256 by default, so U (1024 x 256 fp32 = 1 MiB) and
one (d, bn) tile of G stay VMEM-resident together; both matmuls and the
diagonal scale fuse into a single pass over G — HBM traffic is exactly
read(G) + read(U) + write(Y) instead of three round trips for the unfused
projection / scale / expand chain.  bf16/fp16 operands are upcast in-kernel
so both matmuls accumulate in f32.

Single-block grid: 1-D over column tiles of G.

Batched grid (``batched_lowrank_apply_pallas``) — the pooled-stack entry
point: every operand gains a leading pool dim N (U: (N, d, ell), coeffs:
(N, ell), base: (N,), G: (N, d, n)) and N joins the grid directly:

    grid = (N / bn_stack, n_tiles)

One program fuses the full low-rank apply for ``bn_stack`` blocks' (d, bn)
column tile of G (default 1 — one program per block x column tile), keeping
those blocks' U factors VMEM-resident.  N ragged against ``bn_stack`` is
zero-padded (zero U/base produce a zero output block) and sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# f32 operands take the full-f32 MXU contraction: the default precision
# of an f32 dot on a TPU is a single bf16 pass, which would round U and G
_HIGHEST = jax.lax.Precision.HIGHEST


def _apply_block(u, coeffs, base, g):
    """``base * g + u diag(coeffs) u^T g`` for one block: u (d, ell) and
    g (d, bn) f32, coeffs (1, ell) f32, base (1, 1) f32."""
    # P = U^T G : (ell, bn)
    proj = jax.lax.dot_general(u, g, (((0,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)
    proj = proj * coeffs.reshape(-1, 1)
    expand = jax.lax.dot_general(u, proj, (((1,), (0,)), ((), ())),
                                 precision=_HIGHEST,
                                 preferred_element_type=jnp.float32)
    return base * g + expand


def _lowrank_kernel(u_ref, coeffs_ref, base_ref, g_ref, out_ref):
    out_ref[...] = _apply_block(
        u_ref[...].astype(jnp.float32), coeffs_ref[...], base_ref[...],
        g_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def lowrank_apply_pallas(u: jnp.ndarray, coeffs: jnp.ndarray, base: jnp.ndarray,
                         g: jnp.ndarray, *, bn: int = 256,
                         interpret: bool = True) -> jnp.ndarray:
    """Y = base*G + U diag(coeffs) U^T G.  u: (d, ell), g: (d, n)."""
    d, ell = u.shape
    dg, n = g.shape
    assert d == dg, (u.shape, g.shape)
    bn = min(bn, max(n, 1))
    pn = (-n) % bn
    if pn:
        g = jnp.pad(g, ((0, 0), (0, pn)))
    np_ = g.shape[1]
    coeffs2d = coeffs.reshape(1, ell).astype(jnp.float32)
    base2d = jnp.asarray(base, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        _lowrank_kernel,
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec((d, ell), lambda j: (0, 0)),
            pl.BlockSpec((1, ell), lambda j: (0, 0)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
            pl.BlockSpec((d, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((d, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((d, np_), g.dtype),
        name="lowrank_apply",
        interpret=interpret,
    )(u, coeffs2d, base2d, g)
    return out[:, :n]


def _batched_lowrank_kernel(u_ref, coeffs_ref, base_ref, g_ref, out_ref):
    # each of the program's bn_stack blocks runs the single-block math, so
    # a batched call is bitwise the single-block kernel on every block
    for i in range(u_ref.shape[0]):
        out_ref[i] = _apply_block(
            u_ref[i].astype(jnp.float32), coeffs_ref[i], base_ref[i],
            g_ref[i].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bn_stack", "interpret"))
def batched_lowrank_apply_pallas(u: jnp.ndarray, coeffs: jnp.ndarray,
                                 base: jnp.ndarray, g: jnp.ndarray, *,
                                 bn: int = 256, bn_stack: int = 1,
                                 interpret: bool = True) -> jnp.ndarray:
    """Y[n] = base[n]*G[n] + U[n] diag(coeffs[n]) U[n]^T G[n] over a pool.

    u: (N, d, ell), coeffs: (N, ell), base: (N,), g: (N, d, n).  The pool dim
    N lives on the Pallas grid — no vmap over the single-block kernel.
    """
    N, d, ell = u.shape
    Ng, dg, n = g.shape
    assert (N, d) == (Ng, dg), (u.shape, g.shape)
    if N == 0:
        # empty pool group: nothing to apply (0-sized grid dims are
        # undefined behaviour in some lowerings)
        return jnp.zeros((0, d, n), g.dtype)
    bn = min(bn, max(n, 1))
    bn_stack = min(bn_stack, max(N, 1))
    pN = (-N) % bn_stack
    pn = (-n) % bn
    # coeffs/base ride as (N, 1, ell)/(N, 1, 1): with the pool dim leading,
    # every block's last two dims equal the array's, which Mosaic accepts
    # for any bn_stack (an (N, ell) layout would put bn_stack in the
    # sublane dim, illegal unless it is a multiple of 8 or N itself)
    coeffs3d = coeffs.reshape(N, 1, ell).astype(jnp.float32)
    base3d = jnp.asarray(base, jnp.float32).reshape(N, 1, 1)
    if pN or pn:
        u = jnp.pad(u, ((0, pN), (0, 0), (0, 0)))
        g = jnp.pad(g, ((0, pN), (0, 0), (0, pn)))
        coeffs3d = jnp.pad(coeffs3d, ((0, pN), (0, 0), (0, 0)))
        base3d = jnp.pad(base3d, ((0, pN), (0, 0), (0, 0)))
    Np, _, np_ = g.shape

    out = pl.pallas_call(
        _batched_lowrank_kernel,
        grid=(Np // bn_stack, np_ // bn),
        in_specs=[
            pl.BlockSpec((bn_stack, d, ell), lambda nb, j: (nb, 0, 0)),
            pl.BlockSpec((bn_stack, 1, ell), lambda nb, j: (nb, 0, 0)),
            pl.BlockSpec((bn_stack, 1, 1), lambda nb, j: (nb, 0, 0)),
            pl.BlockSpec((bn_stack, d, bn), lambda nb, j: (nb, 0, j)),
        ],
        out_specs=pl.BlockSpec((bn_stack, d, bn), lambda nb, j: (nb, 0, j)),
        out_shape=jax.ShapeDtypeStruct((Np, d, np_), g.dtype),
        name="batched_lowrank_apply",
        interpret=interpret,
    )(u, coeffs3d, base3d, g)
    return out[:N, :, :n]


# The write-back epilogue holds whole (d, ell) and (d, r) blocks per grid
# step; with their double buffers, d=1024, ell=256, r=768 takes 21.5 MiB,
# past the 16 MiB default scoped VMEM (a v5e core has 128 MiB).
_PROJECT_VMEM_LIMIT = 64 * 2**20

# int8 range of the quantized-pool storage format; must mirror
# core/quantize.py (_INT8_MAX) so the fused requantize epilogue below is
# interchangeable with quantize.quantize_stack's round-to-nearest path.
_INT8_MAX = 127.0


def _batched_project_quantize_kernel(vq_ref, wtop_ref, a_ref, wbot_ref,
                                     values_ref, scale_ref):
    # U_new = dequant(Vq) @ W_top + A @ W_bot, with the per-block dequant
    # scale and the eigenvalue-ladder column weights pre-folded into W_top
    # (both are per-column of the SMALL factor, so folding is exact); the
    # int8 upcast happens in-registers, and the freshly projected factor is
    # re-quantized before it ever leaves the kernel — the f32 (d, ell)
    # stack exists only in VMEM scratch, never in HBM.
    v = vq_ref[...].astype(jnp.float32)       # (bn_stack, d, k)
    un = jax.lax.dot_general(v, wtop_ref[...],
                             (((2,), (1,)), ((0,), (0,))),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)
    un += jax.lax.dot_general(a_ref[...].astype(jnp.float32), wbot_ref[...],
                              (((2,), (1,)), ((0,), (0,))),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)
    absmax = jnp.max(jnp.abs(un), axis=(1, 2), keepdims=True)
    scale = jnp.where(absmax > 0, absmax / _INT8_MAX, 1.0)
    scale_ref[...] = scale
    values_ref[...] = jnp.clip(jnp.round(un / scale),
                               -_INT8_MAX, _INT8_MAX).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bn_stack", "interpret"))
def batched_project_quantize_pallas(vq: jnp.ndarray, w_top: jnp.ndarray,
                                    a: jnp.ndarray, w_bot: jnp.ndarray, *,
                                    bn_stack: int = 1,
                                    interpret: bool = True
                                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused FD write-back epilogue for int8 pool storage.

    Computes ``U_new = dequant(vq) @ w_top + a @ w_bot`` per pool block and
    re-quantizes it in the same kernel: returns ``(values int8 (N, d, e),
    scale f32 (N, 1, 1))`` matching the ``QuantizedPool`` storage layout.

    vq: (N, d, k) int8, w_top: (N, k, e) f32 (quantization scale + ladder
    weights folded in by the caller), a: (N, d, r) f32, w_bot: (N, r, e)
    f32.  One grid step owns ``bn_stack`` whole blocks (the per-block
    absmax needs the full (d, e) factor resident — d x e stays comfortably
    in VMEM for the engine's block sizes; round-to-nearest is used because
    the eigenvector factor is fully recomputed each refresh, not EMA-
    accumulated, so stochastic rounding buys nothing here).
    """
    N, d, k = vq.shape
    e = w_top.shape[-1]
    r = a.shape[-1]
    assert w_top.shape == (N, k, e), (vq.shape, w_top.shape)
    assert a.shape[:2] == (N, d) and w_bot.shape == (N, r, e), \
        (a.shape, w_bot.shape)
    if N == 0:
        return (jnp.zeros((0, d, e), jnp.int8),
                jnp.ones((0, 1, 1), jnp.float32))
    bn_stack = min(bn_stack, max(N, 1))
    pN = (-N) % bn_stack
    if pN:
        vq = jnp.pad(vq, ((0, pN), (0, 0), (0, 0)))
        w_top = jnp.pad(w_top, ((0, pN), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, pN), (0, 0), (0, 0)))
        w_bot = jnp.pad(w_bot, ((0, pN), (0, 0), (0, 0)))
    Np = vq.shape[0]

    values, scale = pl.pallas_call(
        _batched_project_quantize_kernel,
        grid=(Np // bn_stack,),
        in_specs=[
            pl.BlockSpec((bn_stack, d, k), lambda nb: (nb, 0, 0)),
            pl.BlockSpec((bn_stack, k, e), lambda nb: (nb, 0, 0)),
            pl.BlockSpec((bn_stack, d, r), lambda nb: (nb, 0, 0)),
            pl.BlockSpec((bn_stack, r, e), lambda nb: (nb, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn_stack, d, e), lambda nb: (nb, 0, 0)),
            pl.BlockSpec((bn_stack, 1, 1), lambda nb: (nb, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, d, e), jnp.int8),
            jax.ShapeDtypeStruct((Np, 1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PROJECT_VMEM_LIMIT),
        name="batched_project_quantize",
        interpret=interpret,
    )(vq, w_top, a, w_bot)
    return values[:N], scale[:N]
