"""Model composition: embeddings -> block stack -> head, for every family.

Two execution modes over the same stacked parameter pytree:
  - scan mode (production): lax.scan over layers (+remat) — fast compiles,
    low HLO size, realistic memory picture;
  - probe/unrolled mode: Python loops everywhere so compiled.cost_analysis()
    counts every layer/chunk (roofline probes, DESIGN.md §4).

Decode carries KV caches (attention), SSM+conv states (mamba), and for the
hybrid family a *sites-only* attention cache (zamba2's shared attention
appears every `attn_every` layers; caching only those sites divides cache
memory by ~attn_every).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ModelConfig
from repro.models.layers import gated_mlp, rms_norm
from repro.sharding.rules import constrain

PyTree = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _remat(cfg: ModelConfig, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Parameter shape declarations


def _dense_layer_shapes(cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d_ff = d_ff or cfg.d_ff
    return {
        "attn": attn_lib.attn_params_shape(cfg),
        "mlp": {"w_gate": (cfg.d_model, d_ff), "w_up": (cfg.d_model, d_ff),
                "w_down": (d_ff, cfg.d_model)},
        "norm1": (cfg.d_model,),
        "norm2": (cfg.d_model,),
    }


def _moe_layer_shapes(cfg: ModelConfig) -> dict:
    return {
        "attn": attn_lib.attn_params_shape(cfg),
        "moe": moe_lib.moe_params_shape(cfg),
        "norm1": (cfg.d_model,),
        "norm2": (cfg.d_model,),
    }


def _mamba_layer_shapes(cfg: ModelConfig) -> dict:
    return {"mixer": ssm_lib.ssm_params_shape(cfg), "norm": (cfg.d_model,)}


def _stack(shapes: dict, n: int) -> dict:
    return jax.tree.map(lambda s: (n,) + tuple(s), shapes,
                        is_leaf=lambda x: isinstance(x, tuple))


def param_shapes(cfg: ModelConfig) -> PyTree:
    """Pytree of shape tuples for all parameters."""
    D, V = cfg.d_model, cfg.vocab_size
    K = max(cfg.num_codebooks, 1)
    shapes: Dict[str, Any] = {}
    if cfg.embed_inputs:
        shapes["embed"] = (K, V, D) if cfg.num_codebooks else (V, D)
    if not cfg.tie_embeddings:
        shapes["lm_head"] = (K, D, V) if cfg.num_codebooks else (D, V)
    elif not cfg.embed_inputs:
        shapes["lm_head"] = (D, V)
    shapes["final_norm"] = (D,)

    fam = cfg.family
    L = cfg.num_layers
    if fam in ("dense", "vlm", "audio"):
        shapes["layers"] = _stack(_dense_layer_shapes(cfg), L)
    elif fam == "moe":
        fd = cfg.first_dense_layers
        if fd:
            shapes["dense_layers"] = _stack(
                _dense_layer_shapes(cfg, cfg.dense_ff or cfg.d_ff), fd)
        shapes["moe_layers"] = _stack(_moe_layer_shapes(cfg), L - fd)
    elif fam == "ssm":
        shapes["layers"] = _stack(_mamba_layer_shapes(cfg), L)
    elif fam == "hybrid":
        shapes["layers"] = _stack(_mamba_layer_shapes(cfg), L)
        # zamba2: ONE shared transformer block (attention + MLP) reused at
        # every site — parameters counted once, applied n_sites times.
        shapes["shared_attn"] = _dense_layer_shapes(cfg)
    else:
        raise ValueError(fam)
    return shapes


def param_struct(cfg: ModelConfig) -> PyTree:
    """ShapeDtypeStructs (no allocation) — dry-run input."""
    dt = _dtype(cfg)

    def leaf(s):
        return jax.ShapeDtypeStruct(tuple(s), dt)

    return jax.tree.map(leaf, param_shapes(cfg),
                        is_leaf=lambda x: isinstance(x, tuple))


def init_params(cfg: ModelConfig, key) -> PyTree:
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    dt = _dtype(cfg)

    leaves = []
    for s, k in zip(flat, keys):
        s = tuple(s)
        if len(s) == 1:
            leaves.append(jnp.zeros(s, dt))  # norms/bias -> 0 (scale adds 1)
        else:
            fan_in = s[-2] if len(s) >= 2 else s[-1]
            leaves.append((jax.random.normal(k, s, jnp.float32)
                           * (fan_in ** -0.5)).astype(dt))
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Blocks (train / prefill)


# Name scopes (HLO op metadata, free on the device): model/embed (tokens
# and their positions), model/stack around the layer scan, inside it
# model/attention, model/mlp, model/moe and model/ssm, then model/head.  A
# forward scope stays on the operations of its backward and of its
# recomputed (remat) forward, so each scope holds its layer's whole cost;
# the benchmark's trace readers (bench/metrics) match them.


def _attention_half(cfg: ModelConfig, p, x, positions, unroll):
    with jax.named_scope("model/attention"):
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        return x + attn_lib.attention_block(cfg, p["attn"], h, positions,
                                            unroll=unroll)


def _dense_block(cfg: ModelConfig, p, x, positions, unroll):
    x = _attention_half(cfg, p, x, positions, unroll)
    with jax.named_scope("model/mlp"):
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        return x + gated_mlp(cfg, p["mlp"], h)


def _moe_block(cfg: ModelConfig, p, x, positions, unroll):
    x = _attention_half(cfg, p, x, positions, unroll)
    with jax.named_scope("model/moe"):
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        return x + moe_lib.moe_block(cfg, p["moe"], h)


def _mamba_layer(cfg: ModelConfig, p, x, unroll):
    with jax.named_scope("model/ssm"):
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        return x + ssm_lib.mamba_block(cfg, p["mixer"], h, unroll=unroll)


def _shared_attn_apply(cfg: ModelConfig, p, x, positions, unroll):
    return _dense_block(cfg, p, x, positions, unroll)


def _run_stack(cfg, stacked, x, positions, block_fn, unroll, n_override=None):
    n = jax.tree.leaves(stacked)[0].shape[0] if n_override is None else n_override
    if unroll:
        fn = _remat(cfg, block_fn)
        for i in range(n):
            p_i = jax.tree.map(lambda a: a[i], stacked)
            x = fn(p_i, x)
        return x

    def body(carry, p_i):
        fn = _remat(cfg, block_fn)
        return fn(p_i, carry), None

    x, _ = jax.lax.scan(body, x, stacked)
    return x


def _hybrid_stack(cfg, params, x, positions, unroll):
    layers = params["layers"]
    shared = params["shared_attn"]
    L = cfg.num_layers
    sites = cfg.shared_attn_layers()
    is_site = jnp.array([i in sites for i in range(L)])

    def block(p_i, site_flag, x):
        def with_attn(x):
            return _shared_attn_apply(cfg, shared, x, positions, unroll)

        if unroll:
            x = with_attn(x) if bool(site_flag) else x
        else:
            x = jax.lax.cond(site_flag, with_attn, lambda v: v, x)
        return _mamba_layer(cfg, p_i, x, unroll)

    if unroll:
        for i in range(L):
            p_i = jax.tree.map(lambda a: a[i], layers)
            fn = _remat(cfg, functools.partial(block, p_i, bool(i in sites)))
            x = fn(x)
        return x

    def body(carry, xs):
        p_i, flag = xs
        fn = _remat(cfg, functools.partial(block, p_i, flag))
        return fn(carry), None

    x, _ = jax.lax.scan(body, x, (layers, is_site))
    return x


# ---------------------------------------------------------------------------
# Forward / loss


def embed_tokens(cfg: ModelConfig, params, batch):
    with jax.named_scope("model/embed"):
        dt = _dtype(cfg)
        if not cfg.embed_inputs:
            x = batch["embeds"].astype(dt)          # modality-frontend stub
        elif cfg.num_codebooks:
            toks = batch["tokens"]                   # (B, S, K)
            emb = params["embed"]                    # (K, V, D)
            x = sum(emb[i][toks[..., i]] for i in range(cfg.num_codebooks))
            x = x.astype(dt)
        else:
            x = params["embed"][batch["tokens"]].astype(dt)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
        return constrain(x, "batch", "seq", None)


def _positions(cfg: ModelConfig, batch, B, S):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.mrope:
        return jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def forward(cfg: ModelConfig, params, batch, *, unroll: bool = False):
    """Returns logits: (B, S, V) or (B, S, K, V) for codebook models."""
    x = embed_tokens(cfg, params, batch)
    B, S, _ = x.shape
    with jax.named_scope("model/embed"):
        positions = _positions(cfg, batch, B, S)

    def dense(p, v):
        return _dense_block(cfg, p, v, positions, unroll)

    # model/stack: the layer scan's own work (each layer's parameter
    # slice, its gradient's write into the stacked gradient) around the
    # blocks' scopes
    with jax.named_scope("model/stack"):
        fam = cfg.family
        if fam in ("dense", "vlm", "audio"):
            x = _run_stack(cfg, params["layers"], x, positions, dense, unroll)
        elif fam == "moe":
            if cfg.first_dense_layers:
                x = _run_stack(cfg, params["dense_layers"], x, positions,
                               dense, unroll)
            x = _run_stack(cfg, params["moe_layers"], x, positions,
                           lambda p, v: _moe_block(cfg, p, v, positions,
                                                   unroll),
                           unroll)
        elif fam == "ssm":
            x = _run_stack(cfg, params["layers"], x, positions,
                           lambda p, v: _mamba_layer(cfg, p, v, unroll),
                           unroll)
        elif fam == "hybrid":
            x = _hybrid_stack(cfg, params, x, positions, unroll)
        else:
            raise ValueError(fam)

    with jax.named_scope("model/head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return project_logits(cfg, params, x)


def project_logits(cfg: ModelConfig, params, x):
    if cfg.num_codebooks:
        head = params["lm_head"]                    # (K, D, V)
        logits = jnp.einsum("bsd,kdv->bskv", x, head)
    elif cfg.tie_embeddings and cfg.embed_inputs:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return constrain(logits, "batch", "seq", "vocab")


def loss_fn(cfg: ModelConfig, params, batch, *, unroll: bool = False):
    logits = forward(cfg, params, batch, unroll=unroll)
    with jax.named_scope("model/head"):
        logits = logits.astype(jnp.float32)
        labels = batch["labels"]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = logz - gold
        mask = batch.get("mask")
        if mask is not None:
            while mask.ndim < nll.ndim:
                mask = mask[..., None]
            nll = nll * mask
            return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.mean(nll)
