"""Attention: GQA with q-chunked causal softmax (no S^2 materialization),
RoPE / M-RoPE / qk-norm / qkv-bias variants, KV-cache decode path.

Training/prefill attention iterates over query chunks; each chunk attends to
the full prefix with an online-safe fp32 softmax. ``unroll=True`` (probe mode,
DESIGN.md §4) replaces the lax.scan with a Python loop so
``compiled.cost_analysis()`` sees every chunk.

GQA is computed grouped — queries reshaped to (B, S, KV, G, hd) — so KV is
never repeated in memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, rms_norm
from repro.sharding import rules as rules_lib
from repro.sharding.rules import axis_extent, constrain

NEG_INF = -1e30


def attn_params_shape(cfg: ModelConfig) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    shapes = {
        "wq": (D, H * hd),
        "wk": (D, KV * hd),
        "wv": (D, KV * hd),
        "wo": (H * hd, D),
    }
    if cfg.qkv_bias:
        shapes.update({"bq": (H * hd,), "bk": (KV * hd,), "bv": (KV * hd,)})
    if cfg.qk_norm:
        shapes.update({"q_norm": (hd,), "k_norm": (hd,)})
    return shapes


def _project_qkv(cfg: ModelConfig, p: dict, x: jnp.ndarray, positions):
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "heads", None)
    v = constrain(v, "batch", "seq", "heads", None)
    return q, k, v


def _head_sharding_mode(KV: int, G: int, cq: int) -> str:
    """TP policy for attention internals.

    'heads' when the (padded-free) head dims divide the model axis;
    otherwise 'qchunk': shard the query-chunk dim instead (sequence-parallel
    softmax — always divisible since cq is a power of two). Non-divisible
    head sharding makes GSPMD all-gather the full fp32 logits
    (EXPERIMENTS.md §Perf)."""
    n = axis_extent("heads")
    if n == 1:
        return "none"
    if KV % n == 0:
        return "heads"
    if cq % n == 0:   # GQA with KV < model axis: shard query positions
        return "qchunk"
    return "none"


def _attend_math(q_chunk, k, v, q_start, kv_len=None,
                 logits_dtype=jnp.float32):
    """Pure attention math for one q chunk (no sharding annotations).

    ``q_start``/``kv_len`` are scalars for the aligned train/prefill path,
    or (B,) vectors for continuous-batching decode where every lane sits at
    its own position (serve/engine.py slot reuse).

    ``logits_dtype`` controls the MATERIALIZED logits dtype (HBM traffic in
    the jnp fallback); the row max is always tracked in f32 and subtracted
    before the cast, so bf16 only quantizes already-centered values."""
    B, cq, KV, G, hd = q_chunk.shape
    S = k.shape[1]
    scale = hd ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", q_chunk.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    k_pos = jnp.arange(S)
    if jnp.ndim(q_start):                       # per-lane decode positions
        q_pos = q_start[:, None] + jnp.arange(cq)          # (B, cq)
        mask = q_pos[:, :, None] >= k_pos[None, None, :]   # (B, cq, S)
        if kv_len is not None:
            mask = mask & (k_pos[None, None, :] < kv_len[:, None, None])
        s = jnp.where(mask[:, None, None], s, NEG_INF)
    else:
        q_pos = q_start + jnp.arange(cq)
        mask = q_pos[:, None] >= k_pos[None, :]
        if kv_len is not None:
            mask = mask & (k_pos[None, :] < kv_len)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    if logits_dtype != jnp.float32:
        m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
        s = (s - m).astype(logits_dtype)
        p = jnp.exp(s)
        p = p / jnp.sum(p.astype(jnp.float32), axis=-1,
                        keepdims=True).astype(logits_dtype)
    else:
        p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)


def _chunk_attend(q_chunk: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  q_start, kv_len=None, logits_dtype="float32") -> jnp.ndarray:
    """q_chunk: (B, cq, KV, G, hd); k, v: (B, S, KV, hd). Causal vs absolute
    positions; kv_len masks cache tail when decoding.

    Sharding policy (EXPERIMENTS.md §Perf): when KV heads divide the model
    axis, annotate head sharding and let GSPMD place the softmax; otherwise
    shard QUERY POSITIONS explicitly with shard_map — forward and backward
    are then local by construction (GSPMD's transpose of the q-sharded
    softmax otherwise all-gathers the full fp32 cotangent)."""
    B, cq, KV, G, hd = q_chunk.shape
    mode = _head_sharding_mode(KV, G, cq)
    rules = rules_lib.current()
    ldt = jnp.dtype(logits_dtype)

    if mode == "qchunk" and rules is not None and kv_len is None:
        model_ax = rules.axis("tensor")
        batch_ax = rules.axis("batch")
        n = axis_extent("tensor")
        if isinstance(model_ax, str) and cq % n == 0 and \
                (batch_ax is None or B % axis_extent("batch") == 0):
            cq_local = cq // n
            qs = jnp.asarray(q_start, jnp.int32)

            @functools.partial(
                jax.shard_map, mesh=rules.mesh,
                in_specs=(P(batch_ax, model_ax, None, None, None),
                          P(batch_ax, None, None, None),
                          P(batch_ax, None, None, None), P()),
                out_specs=P(batch_ax, model_ax, None, None, None),
                check_vma=False)
            def inner(qc, k_, v_, qs_):
                idx = jax.lax.axis_index(model_ax)
                return _attend_math(qc, k_, v_, qs_ + idx * cq_local,
                                    logits_dtype=ldt)

            return inner(q_chunk, k, v, qs)

    out = _attend_math(q_chunk, k, v, q_start, kv_len, logits_dtype=ldt)
    if mode == "heads":
        out = constrain(out, "batch", None, "heads", None, None)
    return out


def causal_attention(cfg: ModelConfig, q, k, v, *, unroll: bool) -> jnp.ndarray:
    """q: (B, S, H, hd); k, v: (B, S, KV, hd) -> (B, S, H, hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    cq = min(cfg.q_chunk, S)
    n_chunks = (S + cq - 1) // cq
    if n_chunks * cq != S:  # pad seq to chunk multiple (rare)
        pad = n_chunks * cq - S
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qg = qg.reshape(B, n_chunks, cq, KV, G, hd)

    ldt = cfg.attn_logits_dtype
    if unroll or n_chunks == 1:
        outs = [_chunk_attend(qg[:, i], k, v, i * cq, logits_dtype=ldt)
                for i in range(n_chunks)]
        out = jnp.stack(outs, axis=1)
    else:
        def body(_, qc_i):
            qc, i = qc_i
            return None, _chunk_attend(qc, k, v, i * cq, logits_dtype=ldt)

        _, out = jax.lax.scan(body, None,
                              (jnp.moveaxis(qg, 1, 0), jnp.arange(n_chunks)))
        out = jnp.moveaxis(out, 0, 1)
    out = out.reshape(B, n_chunks * cq, KV, G, hd)[:, :S]
    return out.reshape(B, S, H, hd)


def attention_block(cfg: ModelConfig, p: dict, x: jnp.ndarray, positions,
                    *, unroll: bool) -> jnp.ndarray:
    """Full-sequence (train / prefill) attention sublayer (no residual/norm)."""
    B, S, D = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions)
    out = causal_attention(cfg, q, k, v, unroll=unroll)
    out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, -1), p["wo"])
    return constrain(out, "batch", "seq", "embed")


def attention_decode(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray, pos
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode. x: (B, 1, D); cache_k/v: (B, Smax, KV, hd);
    pos: scalar current position shared by all lanes (static batch), or a
    (B,) vector of per-lane positions (continuous batching: each lane's
    cache write, RoPE phase, and causal mask follow its own position).
    Returns (out, new_k, new_v)."""
    B, _, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    per_lane = jnp.ndim(pos) == 1
    if per_lane:
        pos = jnp.asarray(pos, jnp.int32)
        positions = pos[:, None] if not cfg.mrope else \
            jnp.broadcast_to(pos[None, :, None], (3, B, 1))
    else:
        positions = jnp.full((B, 1), pos, jnp.int32) if not cfg.mrope else \
            jnp.full((3, B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, positions)
    if per_lane:
        write = jax.vmap(
            lambda c, u, pb: jax.lax.dynamic_update_slice(c, u, (pb, 0, 0)))
        cache_k = write(cache_k, k.astype(cache_k.dtype), pos)
        cache_v = write(cache_v, v.astype(cache_v.dtype), pos)
    else:
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
    qg = q.reshape(B, 1, KV, H // KV, hd)
    out = _chunk_attend(qg, cache_k, cache_v, pos, kv_len=pos + 1,
                        logits_dtype=cfg.attn_logits_dtype)
    out = out.reshape(B, 1, H * hd)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return constrain(out, "batch", None, "embed"), cache_k, cache_v
