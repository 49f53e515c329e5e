"""Pure-jnp oracles for the gram kernels (also the "xla" backend entries).

``batched_gram_ref`` is written as the single ``dot_general`` that
``jax.vmap(gram_ref)`` lowers to, so the pooled engine's XLA path stays
bitwise-identical to the per-leaf vmap dispatch it replaced.
"""
import jax
import jax.numpy as jnp

# full-f32 contraction on every backend (a TPU's default f32 dot is one
# bf16 pass)
_HIGHEST = jax.lax.Precision.HIGHEST


def gram_ref(a: jnp.ndarray) -> jnp.ndarray:
    a32 = a.astype(jnp.float32)
    # contract d in place (no transposed copy), the kernel's contraction
    return jax.lax.dot_general(a32, a32, (((0,), (0,)), ((), ())),
                               precision=_HIGHEST)


def batched_gram_ref(a: jnp.ndarray) -> jnp.ndarray:
    """C[n] = A[n]^T A[n] for a (N, d, k) stack; f32 accumulation."""
    a32 = a.astype(jnp.float32)
    return jax.lax.dot_general(a32, a32, (((1,), (1,)), ((0,), (0,))),
                               precision=_HIGHEST)


def batched_gram_mixed_ref(vq: jnp.ndarray, colw: jnp.ndarray,
                           a: jnp.ndarray) -> jnp.ndarray:
    """Oracle for the fused mixed Gram: vq (N, d, k) int8 eigenvectors,
    colw (N, k) f32 per-column weights (block scale x sqrt(beta2*s)),
    a (N, d, r) f32 new factors -> (N, k+r, k+r) f32 Gram of [vq*colw, a].

    Mirrors the kernel's math exactly: the unweighted Gram of [V, A] first,
    column weights applied on the small output (not on the d-sized stack).
    """
    N, _, k = vq.shape
    r = a.shape[-1]
    m = jnp.concatenate([vq.astype(jnp.float32), a.astype(jnp.float32)],
                        axis=2)
    c0 = jax.lax.dot_general(m, m, (((1,), (1,)), ((0,), (0,))),
                             precision=_HIGHEST)
    w = jnp.concatenate(
        [colw.astype(jnp.float32), jnp.ones((N, r), jnp.float32)], axis=1)
    return c0 * w[:, :, None] * w[:, None, :]
