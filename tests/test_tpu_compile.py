"""Compile the optimizer's Pallas kernels for a described TPU v5e chip.

Every other kernel test runs in interpret mode, which cannot see what the
chip's compiler refuses: block shapes off the (8, 128) tiling, more fast
memory than a kernel may use.  These tests compile each entry of the
registry's pallas ``KernelSet`` with ``interpret=False`` at the widths the
paper-lm-100m engine produces — blocks of d=1024 against 768-wide
gradient blocks, sketch rank ell 64 and 256, N=12 pooled blocks, f32 and
int8 stacks, f32 and bf16 gradients — and check that the compiled program
holds the kernel (``tpu_custom_call``).  Nothing runs, so no chip is needed.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, registry

N, D, R = 12, 1024, 768
F32, BF16, I8 = jnp.float32, jnp.bfloat16, jnp.int8


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 topology.  The persistent
    compilation cache stays off meanwhile: what is compiled for a described
    chip cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def kernels(one_chip):
    """The pallas KernelSet as the registry builds it on a TPU host
    (``interpret=False``, tiles resolved through the tune cache)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(registry, "on_tpu", lambda: True)
        registry._kernel_set.cache_clear()
        ks = registry.get_kernels("pallas")
    # later tests in this process must get the interpret-mode set back
    registry._kernel_set.cache_clear()
    return ks


def _assert_compiles_to_kernel(fn, one_chip, *specs):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_gram_single_block(kernels, one_chip):
    _assert_compiles_to_kernel(kernels.gram, one_chip, ((D, 64 + R), F32))


@pytest.mark.parametrize("ell", [64, 256])
def test_batched_gram(kernels, one_chip, ell):
    _assert_compiles_to_kernel(kernels.batched_gram, one_chip,
                               ((N, D, ell + R), F32))


@pytest.mark.parametrize("ell", [64, 256])
def test_batched_gram_mixed_int8(kernels, one_chip, ell):
    _assert_compiles_to_kernel(kernels.batched_gram_mixed, one_chip,
                               ((N, D, ell), I8), ((N, ell), F32),
                               ((N, D, R), F32))


def test_lowrank_apply_single_block(kernels, one_chip):
    _assert_compiles_to_kernel(kernels.lowrank_apply, one_chip,
                               ((D, 64), F32), ((64,), F32), ((), F32),
                               ((D, R), F32))


@pytest.mark.parametrize("g_dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("ell", [64, 256])
def test_batched_lowrank_apply(kernels, one_chip, ell, g_dtype):
    _assert_compiles_to_kernel(kernels.batched_lowrank_apply, one_chip,
                               ((N, D, ell), F32), ((N, ell), F32),
                               ((N,), F32), ((N, D, R), g_dtype))


@pytest.mark.parametrize("ell", [64, 256])
def test_batched_lowrank_apply_quantized(kernels, one_chip, ell):
    _assert_compiles_to_kernel(kernels.batched_lowrank_apply_quantized,
                               one_chip, ((N, D, ell), I8),
                               ((N, 1, 1), F32), ((N, ell), F32),
                               ((N,), F32), ((N, D, R), F32))


@pytest.mark.parametrize("ell", [64, 256])
def test_batched_project_quantize(kernels, one_chip, ell):
    _assert_compiles_to_kernel(kernels.batched_project_quantize, one_chip,
                               ((N, D, ell), I8), ((N, ell, ell), F32),
                               ((N, D, R), F32), ((N, R, ell), F32))


@pytest.mark.parametrize("bn_stack", [2, 4])
def test_batched_lowrank_apply_stacked_blocks(kernels, one_chip, bn_stack):
    """Several pool blocks per grid step (a tile the autotuner may pick):
    the per-block coefficient and scale blocks stay legal for any
    ``bn_stack``, not only for multiples of 8."""
    config = autotune.TileConfig(bn_stack=bn_stack, bn=128)
    _assert_compiles_to_kernel(
        lambda u, co, b, g: kernels.batched_lowrank_apply(
            u, co, b, g, config=config),
        one_chip, ((N, D, 64), F32), ((N, 64), F32), ((N,), F32),
        ((N, D, R), F32))
