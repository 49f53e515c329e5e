"""Pallas TPU kernel: causal flash attention forward (GQA-aware).

Training-forward hotspot. Online-softmax tiling: each (q-block, kv-block)
pair streams K/V tiles through VMEM while the (bq, hd) output accumulator,
running max m and normalizer l live in VMEM scratch across the kv dimension
(sequential innermost grid axis). Never materializes the (S, S) logits.

Causal blocks entirely above the diagonal are skipped via pl.when.
GQA: the kv-head index map divides the query-head grid index by the group
size, so no KV repetition is materialized in HBM.

Grid: (batch, q_heads, q_tiles, kv_tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, sm_scale: float, bq: int, bk: int, n_kv_tiles: int,
                  causal: bool, kv_valid: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # Skip fully-masked blocks (strictly above the causal diagonal).
    run = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                     # (bq, hd)
        k = k_ref[0, 0]                     # (bk, hd)
        v = v_ref[0, 0]                     # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                    # (bq, bk)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if kv_valid % bk:                   # mask padded KV tail
            s = jnp.where(cols < kv_valid, s, NEG_INF)

        m_prev = m_ref[...]                 # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)              # (bq, bk)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_kv_tiles - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, bq: int = 128, bk: int = 128,
                           interpret: bool = True) -> jnp.ndarray:
    """q: (B, Hq, S, hd); k, v: (B, Hkv, S, hd) with Hq % Hkv == 0."""
    B, Hq, S, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    bq = min(bq, S)
    bk = min(bk, Sk)
    pq = (-S) % bq
    pk = (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Sp, Skp = q.shape[2], k.shape[2]
    n_q = Sp // bq
    n_kv = Skp // bk
    sm_scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_flash_kernel, sm_scale=sm_scale, bq=bq, bk=bk,
                               n_kv_tiles=n_kv, causal=causal, kv_valid=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S, :]
