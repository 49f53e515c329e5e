"""Mixture-of-Experts block: shared + routed top-k experts with sort-based
capacity dispatch (DeepSeekMoE / Kimi-K2 style fine-grained experts).

Dispatch is O(T·k·log) gather/scatter — no dense (T, E) one-hot einsum, so
FLOPs and memory scale with *active* experts (capacity = cf·T·k/E per
expert). Under the production mesh the expert dim is EP-sharded over
'model'; GSPMD inserts the all-to-all-equivalent collectives around the
per-expert einsums.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.sharding import rules as rules_lib
from repro.sharding.rules import axis_extent, constrain


def moe_params_shape(cfg: ModelConfig) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    shapes = {
        "router": (D, E),
        "experts": {
            "w_gate": (E, D, F),
            "w_up": (E, D, F),
            "w_down": (E, F, D),
        },
    }
    if cfg.num_shared_experts:
        Fs = cfg.num_shared_experts * cfg.d_ff
        shapes["shared"] = {"w_gate": (D, Fs), "w_up": (D, Fs),
                            "w_down": (Fs, D)}
    return shapes


def _route(cfg: ModelConfig, router, xt):
    """Top-k routing tables. xt: (T, D). Returns (gate_w, gate_idx)."""
    logits = jnp.einsum("td,de->te", xt, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gate_w = gate_w / (jnp.sum(gate_w, axis=-1, keepdims=True) + 1e-9)
    return gate_w, gate_idx


def _slot_tables(E, k, capacity, gate_w, gate_idx, T):
    """Slot-indexed routing tables (D-free)."""
    flat_expert = gate_idx.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T), k)
    flat_w = gate_w.reshape(-1)
    order = jnp.argsort(flat_expert)
    se, stok, sw = flat_expert[order], flat_token[order], flat_w[order]
    counts = jnp.bincount(flat_expert, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * k) - starts[se]
    keep = pos_in_e < capacity
    slot = jnp.where(keep, se * capacity + pos_in_e, E * capacity)
    slot_tok = jnp.full((E * capacity + 1,), T, jnp.int32).at[slot].set(stok)
    slot_w = jnp.zeros((E * capacity + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, sw, 0.0))
    return slot_tok[:-1], slot_w[:-1]


def _experts_ffn(cfg, we, buf):
    act = jax.nn.silu if cfg.mlp_act == "swiglu" else \
        (lambda v: jax.nn.gelu(v, approximate=True))
    h = act(jnp.einsum("ecd,edf->ecf", buf, we["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, we["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, we["w_down"])


def _moe_routed_shard_map(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                          rules) -> jnp.ndarray:
    """Expert-parallel MoE with explicit locality (EXPERIMENTS.md §Perf,
    kimi iteration 4): tokens are replicated across the model axis, so each
    model shard gathers its own experts' tokens LOCALLY; the only collectives
    are the FSDP weight all-gathers and one psum of the (T_local, D) partial
    combine — GSPMD's generic lowering of the same graph moves the full
    (E*C, D) buffers instead."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    model_ax = rules.axis("experts")
    fsdp_ax = rules.axis("fsdp")
    batch_ax = rules.axis("batch")
    n_model = axis_extent("experts")
    E_loc = E // n_model

    in_specs = (
        P(batch_ax, None, None),                      # x
        P(fsdp_ax, None),                             # router (D, E)
        {"w_gate": P(model_ax, fsdp_ax, None),        # experts
         "w_up": P(model_ax, fsdp_ax, None),
         "w_down": P(model_ax, None, fsdp_ax)},
    )

    @functools.partial(jax.shard_map, mesh=rules.mesh,
                       in_specs=in_specs,
                       out_specs=P(batch_ax, None, None),
                       check_vma=False)
    def body(x_loc, router_loc, we_loc):
        Bl, Sl, _ = x_loc.shape
        T = Bl * Sl
        xt = x_loc.reshape(T, D)
        if fsdp_ax is not None:
            router_full = jax.lax.all_gather(router_loc, fsdp_ax, axis=0,
                                             tiled=True)
            we_full = {
                "w_gate": jax.lax.all_gather(we_loc["w_gate"], fsdp_ax,
                                             axis=1, tiled=True),
                "w_up": jax.lax.all_gather(we_loc["w_up"], fsdp_ax,
                                           axis=1, tiled=True),
                "w_down": jax.lax.all_gather(we_loc["w_down"], fsdp_ax,
                                             axis=2, tiled=True),
            }
        else:
            router_full, we_full = router_loc, we_loc

        capacity = int(cfg.capacity_factor * T * k / E) + 1
        gate_w, gate_idx = _route(cfg, router_full, xt)
        slot_tok, slot_w = _slot_tables(E, k, capacity, gate_w, gate_idx, T)
        # local expert range (shard_map already gave us our E_loc weights)
        eidx = jax.lax.axis_index(model_ax) if model_ax else 0
        lo = eidx * E_loc * capacity
        slot_tok_loc = jax.lax.dynamic_slice_in_dim(slot_tok, lo,
                                                    E_loc * capacity)
        slot_w_loc = jax.lax.dynamic_slice_in_dim(slot_w, lo,
                                                  E_loc * capacity)

        xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), x.dtype)], axis=0)
        buf = xt_pad[slot_tok_loc].reshape(E_loc, capacity, D)
        out_buf = _experts_ffn(cfg, we_full, buf)
        contrib = out_buf.reshape(E_loc * capacity, D) * \
            slot_w_loc[:, None].astype(x.dtype)
        routed = jnp.zeros((T + 1, D), x.dtype).at[slot_tok_loc].add(
            contrib)[:T]
        if model_ax is not None:
            routed = jax.lax.psum(routed, model_ax)
        return routed.reshape(Bl, Sl, D)

    return body(x, p["router"], p["experts"])


def _shard_map_ok(cfg: ModelConfig, B: int) -> bool:
    rules = rules_lib.current()
    if rules is None or cfg.moe_impl == "gspmd":
        return False
    n_model = axis_extent("experts")
    n_batch = axis_extent("batch")
    model_ax = rules.axis("experts")
    return (isinstance(model_ax, str) and n_model > 1
            and cfg.num_experts % n_model == 0 and B % n_batch == 0)


def moe_block(cfg: ModelConfig, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D)."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, D)

    if _shard_map_ok(cfg, B):
        routed = _moe_routed_shard_map(cfg, p, x, rules_lib.current())
        routed = routed.reshape(T, D)
        return _finish_moe(cfg, p, xt, routed, B, S, D)

    gate_w, gate_idx = _route(cfg, p["router"], xt)

    capacity = int(cfg.capacity_factor * T * k / E) + 1
    # slot-indexed routing tables: all (E*C,)-shaped, D-free. The naive
    # formulation gathers/scatters (T*k, D) tensors, which GSPMD replicates
    # and all-reduces at ~1TB/device/layer for kimi-scale MoE
    # (EXPERIMENTS.md §Perf, kimi iteration 1).
    slot_tok, slot_w = _slot_tables(E, k, capacity, gate_w, gate_idx, T)

    # dispatch: one (E*C, D) gather from the padded token table
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), x.dtype)], axis=0)
    buf = xt_pad[slot_tok].reshape(E, capacity, D)
    buf = constrain(buf, "experts", None, None)
    out_buf = _experts_ffn(cfg, p["experts"], buf)
    out_buf = constrain(out_buf, "experts", None, None)

    # combine: weight in expert-sharded layout, one scatter-add to tokens
    contrib = out_buf.reshape(E * capacity, D) * slot_w[:, None].astype(x.dtype)
    routed = jnp.zeros((T + 1, D), x.dtype).at[slot_tok].add(contrib)[:T]
    return _finish_moe(cfg, p, xt, routed, B, S, D)


def _finish_moe(cfg, p, xt, routed, B, S, D):
    act = jax.nn.silu if cfg.mlp_act == "swiglu" else \
        (lambda v: jax.nn.gelu(v, approximate=True))
    out = routed
    if cfg.num_shared_experts:
        sh = p["shared"]
        hs = act(jnp.einsum("td,df->tf", xt, sh["w_gate"])) * \
            jnp.einsum("td,df->tf", xt, sh["w_up"])
        out = out + jnp.einsum("tf,fd->td", hs, sh["w_down"])
    out = out.reshape(B, S, D)
    return constrain(out, "batch", "seq", "embed")
