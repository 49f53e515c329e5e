"""Plain float32 reference of paper-lm-100m: a pre-norm decoder with
RMSNorm (scale stored as an offset from 1), rotary position embeddings on
the two halves of each head, causal softmax attention, a SwiGLU MLP and an
untied output head.  Written from the description in
``paper-lm-100m.json``; it imports nothing of the program.

``loss_sum`` returns the summed next-token loss of the given rows, each
token weighted by its row's weight; ``flops_per_token`` counts the training
step's matmul work.
"""
import jax
import jax.numpy as jnp

from bench import reflib


def param_shapes(model: dict) -> dict:
    D, V = model["hidden_size"], model["vocab_size"]
    H, KV, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    F, L = model["intermediate_size"], model["num_hidden_layers"]
    layer = {
        "attn": {"wq": (D, H * hd), "wk": (D, KV * hd), "wv": (D, KV * hd),
                 "wo": (H * hd, D)},
        "mlp": {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)},
        "norm1": (D,), "norm2": (D,),
    }
    return {"embed": (V, D), "lm_head": (D, V), "final_norm": (D,),
            "layers": reflib.stack(layer, L)}


def flops_per_token(model: dict, seq: int) -> float:
    """Training FLOPs per token: 6 per parameter of every matrix the tokens
    are multiplied by (the input embedding gather is a lookup and is not
    counted), plus 3 x the forward's causal attention contractions;
    recomputation is not counted."""
    D, V, L = model["hidden_size"], model["vocab_size"], \
        model["num_hidden_layers"]
    H, KV, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    per_layer = D * (H + 2 * KV) * hd + H * hd * D \
        + 3 * D * model["intermediate_size"]
    matmul_params = L * per_layer + D * V          # head; gather skipped
    # causal attention: q.k and p.v, 2 * hd * (S / 2) each, per head
    attn = L * 2 * 2 * H * hd * (seq / 2)
    return 6 * matmul_params + 3 * attn


def _rope(x, theta):
    """x: (B, S, H, hd); rotate the first half of each head against the
    second by angle position * theta ** (-2i / hd)."""
    B, S, H, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss_sum(params, tokens, labels, row_w, model, mm):
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    H, KV, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    B, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(p, x):
        h = reflib.rms_norm(x, p["norm1"], eps)
        q = mm("bsd,dk->bsk", h, p["attn"]["wq"]).reshape(B, S, H, hd)
        k = mm("bsd,dk->bsk", h, p["attn"]["wk"]).reshape(B, S, KV, hd)
        v = mm("bsd,dk->bsk", h, p["attn"]["wv"]).reshape(B, S, KV, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = mm("bqhd,bkhd->bhqk", q * hd ** -0.5, k)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + mm("bsk,kd->bsd", o.reshape(B, S, H * hd), p["attn"]["wo"])
        h = reflib.rms_norm(x, p["norm2"], eps)
        a = jax.nn.silu(mm("bsd,df->bsf", h, p["mlp"]["w_gate"])) \
            * mm("bsd,df->bsf", h, p["mlp"]["w_up"])
        return x + mm("bsf,fd->bsd", a, p["mlp"]["w_down"])

    x = params["embed"][tokens]
    x = reflib.remat_scan(layer, x, params["layers"])
    x = reflib.rms_norm(x, params["final_norm"], eps)
    return reflib.nll_sum(mm("bsd,dv->bsv", x, params["lm_head"]), labels,
                          row_w)
