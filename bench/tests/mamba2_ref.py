"""Plain float32 reference of Mamba2 (Mamba-2, arXiv:2405.21060): pre-norm
residual blocks of the Mamba2 mixer, a final RMSNorm and a head tied to the
embedding.  Written from the published description; it imports nothing of
the program.

One mixer: ``in_proj`` splits into ``z``, ``x``, ``B``, ``C`` and ``dt``
(one group of ``B`` and ``C``); a depthwise causal convolution of width
``d_conv`` with SiLU over ``xBC``; ``dt = softplus(dt_raw + dt_bias)`` and
``A = -exp(A_log)`` per head; the state-space model run as the sequential
recurrence over time

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t,   y_t = C_t h_t + D x_t

(so it shares nothing with a chunked SSD); then ``RMSNorm(y * silu(z))``
and ``out_proj``.  Every RMSNorm stores its scale as an offset from 1.
The leaves are named so that they flatten in one fixed order, the one the
harness's weight rule (``reflib.init_params``) draws them in.

``loss_sum`` returns the summed next-token loss of the given rows, each
token weighted by its row's weight; ``flops_per_token`` counts the training
step's matmul work.
"""
import math

import jax
import jax.numpy as jnp

from bench import reflib

# time steps between the recurrence's saved states in the backward pass
TIME_BLOCK = 64


def _sizes(model: dict) -> tuple:
    if model["ngroups"] != 1 or not model["tie_embeddings"]:
        raise ValueError("one group of B and C, and a tied head, only")
    D, N, P = model["d_model"], model["d_state"], model["headdim"]
    din = model["expand"] * D
    return D, N, P, din, din // P


def param_shapes(model: dict) -> dict:
    D, N, P, din, H = _sizes(model)
    conv_dim = din + 2 * N
    layer = {
        "mixer": {"in_proj": (D, 2 * din + 2 * N + H),
                  "conv_w": (model["d_conv"], conv_dim),
                  "conv_b": (conv_dim,), "A_log": (H,), "dt_bias": (H,),
                  "ssm_D": (H,), "gate_norm": (din,),
                  "out_proj": (din, D)},
        "norm": (D,),
    }
    return {"embed": (model["vocab_size"], D), "final_norm": (D,),
            "layers": reflib.stack(layer, model["n_layer"])}


def flops_per_token(model: dict, seq: int) -> float:
    """Training FLOPs per token: 6 per parameter of every matrix the tokens
    are multiplied by (``in_proj``, ``out_proj`` and the tied head, counted
    once; the embedding gather is a lookup), plus 3 x the forward's
    token-mixing contractions as the chunked SSD computes them at chunk
    ``Q``: ``C B^T`` and the weighted sum over a chunk at the causal
    average ``Q / 2`` (``Q N + Q H P``), the state read-out and update in
    full (``4 H P N``).  The depthwise convolution and recomputation are
    not counted."""
    D, N, P, din, H = _sizes(model)
    L, V = model["n_layer"], model["vocab_size"]
    Q = min(model["chunk_size"], seq)
    matmul_params = L * (D * (2 * din + 2 * N + H) + din * D) + D * V
    mixing = L * (Q * N + Q * H * P + 4 * H * P * N)
    return 6 * matmul_params + 3 * mixing


def _causal_conv(x, w, b, mm):
    """Depthwise causal convolution: x (B, S, C), w (W, C); position t sees
    t - W + 1 .. t."""
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    win = jnp.stack([xp[:, i:i + S] for i in range(W)], axis=2)
    return mm("bswc,wc->bsc", win, w) + b


def _recurrence(x, dt, A, Bm, Cm, mm):
    """x (B, S, H, P), dt (B, S, H), A (H,), Bm and Cm (B, S, N) ->
    y (B, S, H, P) with y_t = C_t h_t, one time step after another."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]

    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs
        h = jnp.exp(dt_t * A)[:, :, None, None] * h \
            + mm("bhp,bn->bhpn", dt_t[:, :, None] * x_t, B_t)
        return h, mm("bhpn,bn->bhp", h, C_t)

    # outer steps over blocks of time, each recomputed in the backward pass,
    # so that only one block's states are held at a time
    T = math.gcd(S, TIME_BLOCK)

    def block(h, xs):
        return jax.lax.scan(step, h, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((S // T, T) + a.shape[:1]
                                             + a.shape[2:])
               for a in (x, dt, Bm, Cm))
    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    _, y = jax.lax.scan(jax.checkpoint(block), h0, xs)
    return jnp.moveaxis(y.reshape((S, Bsz, H, P)), 0, 1)


def loss_sum(params, tokens, labels, row_w, model, mm):
    D, N, P, din, H = _sizes(model)
    eps = model["norm_epsilon"]
    B, S = tokens.shape

    def mixer(p, u):
        zxbcdt = mm("bsd,de->bse", u, p["in_proj"])
        z = zxbcdt[..., :din]
        xbc = zxbcdt[..., din:2 * din + 2 * N]
        dt_raw = zxbcdt[..., 2 * din + 2 * N:]
        xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"], mm))
        x = xbc[..., :din].reshape(B, S, H, P)
        Bm, Cm = xbc[..., din:din + N], xbc[..., din + N:]
        dt = jax.nn.softplus(dt_raw + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        y = _recurrence(x, dt, A, Bm, Cm, mm) + p["ssm_D"][:, None] * x
        y = reflib.rms_norm(y.reshape(B, S, din) * jax.nn.silu(z),
                            p["gate_norm"], eps)
        return mm("bse,ed->bsd", y, p["out_proj"])

    def layer(p, x):
        return x + mixer(p["mixer"], reflib.rms_norm(x, p["norm"], eps))

    x = params["embed"][tokens]
    x = reflib.remat_scan(layer, x, params["layers"])
    x = reflib.rms_norm(x, params["final_norm"], eps)
    return reflib.nll_sum(mm("bsd,vd->bsv", x, params["embed"]), labels,
                          row_w)
