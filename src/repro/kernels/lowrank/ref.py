"""Pure-jnp oracles for the fused low-rank + diagonal apply (also the "xla"
backend entries).

``batched_lowrank_apply_ref`` mirrors ``jax.vmap(lowrank_apply_ref)``
primitive-for-primitive (batched dot_generals, broadcast scale) so the
pooled engine's XLA path stays bitwise-identical to the per-leaf vmap
dispatch it replaced.
"""
import jax
import jax.numpy as jnp

# full-f32 contraction on every backend (a TPU's default f32 dot is one
# bf16 pass)
_HIGHEST = jax.lax.Precision.HIGHEST


def lowrank_apply_ref(u: jnp.ndarray, coeffs: jnp.ndarray, base,
                      g: jnp.ndarray) -> jnp.ndarray:
    proj = jax.lax.dot_general(u, g, (((0,), (0,)), ((), ())),
                               precision=_HIGHEST)
    return base * g + jnp.matmul(u, coeffs[:, None] * proj,
                                 precision=_HIGHEST)


def batched_lowrank_apply_ref(u: jnp.ndarray, coeffs: jnp.ndarray, base,
                              g: jnp.ndarray) -> jnp.ndarray:
    """Per-pool-block apply: u (N, d, ell), coeffs (N, ell), base (N,),
    g (N, d, n) -> (N, d, n)."""
    proj = jax.lax.dot_general(u, g, (((1,), (1,)), ((0,), (0,))),
                               precision=_HIGHEST)
    scaled = coeffs[:, :, None] * proj
    expand = jax.lax.dot_general(u, scaled, (((2,), (1,)), ((0,), (0,))),
                                 precision=_HIGHEST)
    return base[:, None, None] * g + expand


def batched_lowrank_apply_quantized_ref(values: jnp.ndarray,
                                        scale: jnp.ndarray,
                                        coeffs: jnp.ndarray, base,
                                        g: jnp.ndarray) -> jnp.ndarray:
    """Oracle for the quantized-eigenvector apply: the per-block scale of
    the int8 factor commutes out of ``U diag(c) U^T`` as ``scale^2``, so
    the apply runs on the raw int8 values (upcast only) with the scale
    folded into the coefficients — the same algebra the pallas path uses.

    values (N, d, ell) int8, scale (N, 1, 1) f32, coeffs (N, ell),
    base (N,), g (N, d, n)."""
    s2 = jnp.square(scale.reshape(scale.shape[0], 1).astype(jnp.float32))
    return batched_lowrank_apply_ref(values.astype(jnp.float32),
                                     coeffs * s2, base, g)


def batched_project_quantize_ref(vq: jnp.ndarray, w_top: jnp.ndarray,
                                 a: jnp.ndarray, w_bot: jnp.ndarray
                                 ) -> tuple:
    """Oracle for the fused FD write-back epilogue: project the new factor
    and re-quantize per block (round-to-nearest, same rule as
    core/quantize.quantize_stack with no key)."""
    from repro.core import quantize
    un = jnp.matmul(vq.astype(jnp.float32), w_top, precision=_HIGHEST) \
        + jnp.matmul(a.astype(jnp.float32), w_bot, precision=_HIGHEST)
    qp = quantize.quantize_stack(un)
    return qp.values, qp.scale
