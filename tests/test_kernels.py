"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
swept over shapes and dtypes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import aio_agg, quantize, ref, sparsify

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("I,N", [(2, 512), (7, 3000), (16, 1024),
                                 (3, 17), (60, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aio_aggregate(I, N, dtype):
    ks = jax.random.split(KEY, 3)
    u = jax.random.normal(ks[0], (I, N), dtype)
    m = (jax.random.uniform(ks[1], (I, N)) > 0.5).astype(dtype)
    w = jax.random.uniform(ks[2], (I,), jnp.float32)
    out = aio_agg.aio_aggregate(u, m, w, interpret=True, block_n=512)
    expect = ref.aio_aggregate_ref(u, m, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=tol)


@pytest.mark.parametrize("N", [512, 3000, 17])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aio_absorb_matches_ref(N, dtype):
    ks = jax.random.split(KEY, 4)
    num = jax.random.normal(ks[0], (N,))
    den = jax.random.uniform(ks[1], (N,))
    u = jax.random.normal(ks[2], (N,), dtype)
    m = (jax.random.uniform(ks[3], (N,)) > 0.5).astype(dtype)
    want = ref.aio_absorb_ref(num, den, u, m, 0.37)
    # ref first: the kernel *donates* its accumulator operands
    got = aio_agg.aio_absorb(num, den, u, m, 0.37, interpret=True,
                             block_n=512)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=tol)


@pytest.mark.parametrize("N", [512, 3000, 17])
def test_aio_merge_matches_ref(N):
    ks = jax.random.split(KEY, 4)
    args = [jax.random.normal(ks[i], (N,)) for i in range(4)]
    want = ref.aio_merge_ref(*args)
    # ref first: the kernel *donates* the a-side accumulator pair
    got = aio_agg.aio_merge(*args, interpret=True, block_n=512)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)


def test_chained_absorb_matches_batched_kernel():
    """Streaming I kernel absorbs + the finalize ratio == the batched
    (I, N) aio_aggregate kernel — the O(N)-memory path is exact."""
    I, N = 5, 700
    ks = jax.random.split(KEY, 3)
    u = jax.random.normal(ks[0], (I, N))
    m = (jax.random.uniform(ks[1], (I, N)) > 0.5).astype(jnp.float32)
    w = jax.random.uniform(ks[2], (I,), jnp.float32)
    num = jnp.zeros((N,), jnp.float32)
    den = jnp.zeros((N,), jnp.float32)
    for i in range(I):
        num, den = aio_agg.aio_absorb(num, den, u[i], m[i], w[i],
                                      interpret=True, block_n=512)
    got = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)
    want = aio_agg.aio_aggregate(u, m, w, interpret=True, block_n=512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("K,C", [(8, 128), (100, 700), (256, 512),
                                 (33, 1000), (1000, 9)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_sumsq(K, C, dtype):
    x = jax.random.normal(KEY, (K, C), dtype)
    rtol = 3e-3 if dtype == jnp.bfloat16 else 1e-5
    ss = sparsify.kernel_sumsq(x, interpret=True)
    np.testing.assert_allclose(np.asarray(ss),
                               np.asarray(ref.kernel_sumsq_ref(x)),
                               rtol=rtol, atol=1e-4)
    out = sparsify.kernel_l2(x, interpret=True)
    expect = ref.kernel_l2_ref(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=rtol, atol=1e-4)


@pytest.mark.parametrize("K,C", [(64, 256), (37, 129)])
def test_threshold_apply(K, C):
    x = jax.random.normal(KEY, (K, C))
    norms = ref.kernel_l2_ref(x)
    thr = jnp.float32(np.median(np.asarray(norms)))
    xo, mo = sparsify.threshold_apply(x, norms, thr, interpret=True)
    xr, mr = ref.threshold_mask_ref(x, norms, thr)
    np.testing.assert_allclose(np.asarray(xo), np.asarray(xr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(mr), atol=0)


@pytest.mark.parametrize("N", [512, 5000, 2048])
@pytest.mark.parametrize("levels", [2, 16, 255])
def test_prob_quantize(N, levels):
    ks = jax.random.split(KEY, 3)
    v = jax.random.normal(ks[0], (N,))
    mask = (jax.random.uniform(ks[1], (N,)) > 0.3).astype(jnp.float32)
    rand = jax.random.uniform(ks[2], (N,))
    av = jnp.abs(v) * mask
    u_min = jnp.min(jnp.where((mask > 0) & (av > 0), av, jnp.inf))
    u_max = jnp.max(jnp.where(mask > 0, av, -jnp.inf))
    q, lvl = quantize.prob_quantize(v, mask, u_min, u_max,
                                    jnp.float32(levels), rand,
                                    interpret=True, block_n=512)
    qr, lr = ref.quantize_ref(v, mask, u_min, u_max, jnp.float32(levels),
                              rand)
    np.testing.assert_allclose(np.asarray(q), np.asarray(qr), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(lvl), np.asarray(lr))


def test_ops_dispatch_matches_ref():
    """aggregation's kernel route (flatten each leaf, run the Pallas
    kernel, restore the shape) against its jnp route."""
    from repro.core import aggregation
    ks = jax.random.split(KEY, 3)
    u = jax.random.normal(ks[0], (4, 30, 10))
    m = (jax.random.uniform(ks[1], (4, 30, 10)) > 0.5).astype(jnp.float32)
    w = jax.random.uniform(ks[2], (4,))
    ups = [{"k": u[i]} for i in range(4)]
    mks = [{"k": m[i]} for i in range(4)]
    a = aggregation.aio_aggregate(ups, mks, w)
    b = aggregation.aio_aggregate(ups, mks, w, use_kernel=True,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(a["k"]), np.asarray(b["k"]),
                               atol=1e-5)


def test_ops_absorb_merge_dispatch_matches_ref():
    from repro.core import aggregation
    ks = jax.random.split(KEY, 4)
    num = {"k": jax.random.normal(ks[0], (30, 10))}
    den = {"k": jax.random.uniform(ks[1], (30, 10))}
    u = {"k": jax.random.normal(ks[2], (30, 10))}
    m = {"k": (jax.random.uniform(ks[3], (30, 10)) > 0.5
               ).astype(jnp.float32)}
    copy = functools.partial(jax.tree.map, jnp.copy)
    # the kernel routes donate their accumulator operands: every call
    # gets copies, so num/den stay live for the next one
    a = aggregation.absorb_trees(copy(num), copy(den), u, m, 0.6)
    b = aggregation.absorb_trees(copy(num), copy(den), u, m, 0.6,
                                 use_kernel=True, interpret=True)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x["k"]), np.asarray(y["k"]),
                                   atol=1e-5)
    a2 = aggregation.merge_trees(copy(num), copy(den), u, m)
    b2 = aggregation.merge_trees(copy(num), copy(den), u, m,
                                 use_kernel=True, interpret=True)
    for x, y in zip(a2, b2):
        np.testing.assert_allclose(np.asarray(x["k"]), np.asarray(y["k"]),
                                   atol=1e-5)
