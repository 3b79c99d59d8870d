"""Kernel micro-benchmarks: Pallas (interpret on CPU / compiled on TPU) vs
the pure-jnp oracle. Prints ``name,us_per_call,derived`` CSV.

On this CPU container the *oracle* timing is the meaningful number (it is
what the FL loop runs); interpret-mode timings are recorded for reference
only — on TPU the runner's hier route takes the compiled aio_absorb /
aio_merge (``use_kernel`` in core/aggregation.py, chosen by the caller).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import aggregation as A  # noqa: E402
from repro.kernels import ref  # noqa: E402


def _bench(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _bench_absorb(n: int, reps: int = 50) -> float:
    """us/call of the donated streaming absorb (the EdgeAggregator hot
    path).  Donation invalidates the inputs, so the accumulator pair is
    threaded through the loop instead of re-fed."""
    donated = jax.jit(A.absorb_trees, donate_argnums=(0, 1))
    u = jax.random.normal(jax.random.PRNGKey(7), (n,), jnp.float32)
    m = (jax.random.uniform(jax.random.PRNGKey(8), (n,)) > 0.5
         ).astype(jnp.float32)
    num = jnp.zeros((n,), jnp.float32)
    den = jnp.zeros((n,), jnp.float32)
    num, den = donated(num, den, u, m, jnp.float32(0.5))   # warm compile
    jax.block_until_ready((num, den))
    t0 = time.perf_counter()
    for _ in range(reps):
        num, den = donated(num, den, u, m, jnp.float32(0.5))
    jax.block_until_ready((num, den))
    return (time.perf_counter() - t0) / reps * 1e6


def main() -> dict:
    key = jax.random.PRNGKey(0)
    I, N = 16, 1 << 20
    metrics = {}
    u = jax.random.normal(key, (I, N))
    m = (jax.random.uniform(jax.random.PRNGKey(1), (I, N)) > 0.5
         ).astype(jnp.float32)
    w = jax.random.uniform(jax.random.PRNGKey(2), (I,))
    us = _bench(jax.jit(ref.aio_aggregate_ref), u, m, w)
    gbps = (I * N * 2 * 4) / (us / 1e6) / 1e9
    metrics["aio_aggregate_us"] = us
    metrics["aio_aggregate_gbps"] = gbps
    print(f"aio_aggregate_ref_{I}x{N},{us:.1f},{gbps:.2f}GB/s")

    us = _bench_absorb(N)
    metrics["aio_absorb_us"] = us
    print(f"aio_absorb_donated_{N},{us:.1f},in-place")

    x = jax.random.normal(key, (4096, 1152))
    us = _bench(jax.jit(ref.kernel_l2_ref), x)
    gbps = x.size * 4 / (us / 1e6) / 1e9
    metrics["kernel_l2_us"] = us
    print(f"kernel_l2_ref_4096x1152,{us:.1f},{gbps:.2f}GB/s")

    v = jax.random.normal(key, (N,))
    mask = jnp.ones((N,))
    rand = jax.random.uniform(jax.random.PRNGKey(3), (N,))
    us = _bench(jax.jit(lambda a, b, c: ref.quantize_ref(
        a, b, jnp.float32(1e-3), jnp.float32(3.0), jnp.float32(256), c)),
        v, mask, rand)
    metrics["quantize_us"] = us
    print(f"quantize_ref_{N},{us:.1f},-")

    # pallas interpret-mode sanity timing on a small size (NOT a perf claim)
    from repro.kernels import aio_agg
    small_u, small_m = u[:, :4096], m[:, :4096]
    us = _bench(lambda a, b, c: aio_agg.aio_aggregate(a, b, c,
                                                      interpret=True),
                small_u, small_m, w, reps=3)
    metrics["aio_pallas_interpret_us"] = us
    print(f"aio_aggregate_pallas_interpret_{I}x4096,{us:.1f},interpret-mode")
    return metrics


if __name__ == "__main__":
    main()
