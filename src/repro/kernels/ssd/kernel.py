"""Pallas TPU kernel: Mamba2 SSD chunk scan (single head-group, n_groups=1).

The sequential inter-chunk recurrence becomes the innermost grid dimension;
the (H-tile, P, N) running state lives in VMEM scratch across chunk steps, so
HBM traffic per chunk is exactly read(u, dlog, B, C tiles) + write(y tile) —
the decay matrices L and the per-chunk states never hit HBM (the pure-jnp
path materializes both).

Grid: (batch, head_tiles, n_chunks) — chunks sequential ("arbitrary"), batch
and head tiles parallel. Head tiles keep the VMEM working set
(Q x P x N + Q x Q decay) bounded; P and N are MXU-lane sized (64/128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(u_ref, dlog_ref, b_ref, c_ref, y_ref, state_ref,
                *, Q: int, HT: int, P: int, N: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0].astype(jnp.float32)          # (Q, HT, P)
    dlog = dlog_ref[0].astype(jnp.float32)    # (Q, HT)
    Bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)         # (Q, N)

    A_cs = jnp.cumsum(dlog, axis=0)           # (Q, HT)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = rows >= cols

    state = state_ref[...]                    # (HT, P, N)
    dec_q = jnp.exp(A_cs)                     # (Q, HT)
    y = jnp.zeros((Q, HT, P), jnp.float32)
    # per-head-in-tile loop: HT is small (<= 8); keeps everything 2-D/MXU
    for h in range(HT):
        dec = A_cs[:, None, h] - A_cs[None, :, h]      # (Q, Q)
        L = jnp.where(causal, jnp.exp(dec), 0.0)
        intra = jax.lax.dot_general(scores * L, u[:, h, :],
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        inter = jax.lax.dot_general(Cm * dec_q[:, h:h + 1], state[h].T,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        y = y.at[:, h, :].set(intra + inter)
        dec_end = jnp.exp(A_cs[-1, h] - A_cs[:, h])    # (Q,)
        new_s = jax.lax.dot_general(u[:, h, :] * dec_end[:, None], Bm,
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        state = state.at[h].set(jnp.exp(A_cs[-1, h]) * state[h] + new_s)

    state_ref[...] = state
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "head_tile", "interpret"))
def ssd_pallas(u: jnp.ndarray, dlog: jnp.ndarray, Bm: jnp.ndarray,
               Cm: jnp.ndarray, *, chunk: int = 128, head_tile: int = 4,
               interpret: bool = True) -> jnp.ndarray:
    """u: (B, S, H, P); dlog: (B, S, H); Bm/Cm: (B, S, N) -> y like u.
    S must be a multiple of ``chunk`` and H of ``head_tile`` (callers pad)."""
    B, S, H, P = u.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0 and H % head_tile == 0, (S, Q, H, head_tile)
    n_chunks = S // Q
    HT = head_tile
    n_ht = H // HT

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, Q=Q, HT=HT, P=P, N=N),
        grid=(B, n_ht, n_chunks),
        in_specs=[
            pl.BlockSpec((1, Q, HT, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, Q, HT), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, HT, P), lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, P), u.dtype),
        scratch_shapes=[pltpu.VMEM((HT, P, N), jnp.float32)],
        name="ssd",
        interpret=interpret,
    )(u, dlog, Bm, Cm)
    return out
