"""Every Pallas kernel of ``repro.kernels``, compiled (``interpret=False``)
for one chip of a described TPU v5e 2x2 topology at the FMNIST model's
leaf sizes and at an I=60 update stack.

Nothing runs: this catches what the chip's compiler refuses (tiling,
layouts, VMEM) and interpret mode does not.  The topology is described
in a module-scoped fixture, never at import, so that only the worker
that runs these tests loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import aio_agg, fused_compress, quantize, sparsify

# flattened leaf sizes of fmnist-cnn: conv1 w/b, conv2 w/b, dense w/b,
# out w/b
FMNIST_LEAVES = [800, 32, 51200, 64, 1605632, 512, 5120, 10]
# (kernels, ksize) views of the weight leaves: conv kernels are
# (Cout, 5*5*Cin), dense columns (out, in)
FMNIST_ROWS = [(32, 25), (64, 800), (512, 3136), (10, 512)]
I_STACK = 60            # the paper's fleet, stacked for the batched AIO


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(os.environ, "TPU_LOG_DIR",
                   os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to a persistent
        # cache but not read back without one
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def _s(sharding, shape=(), dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n", FMNIST_LEAVES)
def test_aio_aggregate_compiles(one_chip, n):
    s = lambda *shape: _s(one_chip, shape)
    _compile(lambda u, m, w: aio_agg.aio_aggregate(u, m, w,
                                                   interpret=False),
             s(I_STACK, n), s(I_STACK, n), s(I_STACK))


@pytest.mark.parametrize("n", FMNIST_LEAVES)
def test_aio_absorb_compiles(one_chip, n):
    s = lambda *shape: _s(one_chip, shape)
    _compile(lambda a, b, u, m, w: aio_agg.aio_absorb(a, b, u, m, w,
                                                      interpret=False),
             s(n), s(n), s(n), s(n), s())


@pytest.mark.parametrize("n", FMNIST_LEAVES)
def test_aio_merge_compiles(one_chip, n):
    s = lambda *shape: _s(one_chip, shape)
    _compile(lambda a, b, c, d: aio_agg.aio_merge(a, b, c, d,
                                                  interpret=False),
             s(n), s(n), s(n), s(n))


@pytest.mark.parametrize("n", FMNIST_LEAVES)
def test_prob_quantize_compiles(one_chip, n):
    s = lambda *shape: _s(one_chip, shape)
    _compile(lambda v, m, lo, hi, lv, r: quantize.prob_quantize(
        v, m, lo, hi, lv, r, interpret=False),
        s(n), s(n), s(), s(), s(), s(n))


@pytest.mark.parametrize("k,c", FMNIST_ROWS)
def test_kernel_sumsq_compiles(one_chip, k, c):
    _compile(lambda x: sparsify.kernel_sumsq(x, interpret=False),
             _s(one_chip, (k, c)))


@pytest.mark.parametrize("k,c", FMNIST_ROWS)
def test_threshold_apply_compiles(one_chip, k, c):
    s = lambda *shape: _s(one_chip, shape)
    _compile(lambda x, nrm, t: sparsify.threshold_apply(x, nrm, t,
                                                        interpret=False),
             s(k, c), s(k), s())


@pytest.mark.parametrize("k,c", FMNIST_ROWS)
def test_fused_sparsify_quantize_compiles(one_chip, k, c):
    s = lambda *shape: _s(one_chip, shape)
    _compile(lambda x, nrm, t, lo, hi, lv, r:
             fused_compress.fused_sparsify_quantize(
                 x, nrm, t, lo, hi, lv, r, interpret=False),
             s(k, c), s(k), s(), s(), s(), s(), s(k, c))
