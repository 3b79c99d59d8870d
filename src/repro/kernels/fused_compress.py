"""Pallas TPU kernel: fused sparsify + probabilistic quantize (FGC one-pass).

The separate sparsify -> quantize pipeline reads the gradient twice and
writes the masked intermediate once (3 passes over hundreds of MB). This
kernel fuses Eq. 2's thresholding with Eq. 3-4's stochastic rounding into a
single pass: one read of (values, norms-row-map, randoms), one write of
(dequantized values, level indices) — for the memory-bound compression
stage, a ~2.5x HBM-traffic reduction by construction.

Layout: x is the (K, ksize) kernel-major view of one leaf; per-row norms
ride as a (K, 1) column in (BK, 1) blocks (a 1-D (BK,) block does not
match the TPU's 1-D tiling of 1024 lanes), and the global
threshold/scalars in a small side input.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BK = 128
BC = 512


def _fused_kernel(s_ref, n_ref, x_ref, r_ref, q_ref, l_ref):
    thr, u_min, u_max, L = s_ref[0], s_ref[1], s_ref[2], s_ref[3]
    keep = (n_ref[...] >= thr).astype(jnp.float32)     # (BK, 1)
    v = x_ref[...].astype(jnp.float32) * keep
    av = jnp.abs(v)
    span = jnp.maximum(u_max - u_min, 1e-20)
    step = span / L
    t = jnp.clip((av - u_min) / step, 0.0, L)
    lo = jnp.floor(t)
    lvl = lo + (r_ref[...] < (t - lo)).astype(jnp.float32)
    lvl = jnp.clip(lvl, 0.0, L)
    q = (u_min + lvl * step) * jnp.sign(v)
    nz = av > 0
    q_ref[...] = jnp.where(nz, q, 0.0).astype(q_ref.dtype)
    l_ref[...] = jnp.where(nz, lvl, 0.0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "bk", "bc"))
def fused_sparsify_quantize(x: jax.Array, norms: jax.Array, thr: jax.Array,
                            u_min: jax.Array, u_max: jax.Array,
                            n_levels: jax.Array, rand: jax.Array, *,
                            interpret: bool = False, bk: int = BK,
                            bc: int = BC) -> tuple[jax.Array, jax.Array]:
    """x, rand: (K, ksize); norms: (K,). Returns (dequantized, levels)."""
    K, C = x.shape
    bk = min(bk, max(8, K))
    bc = min(bc, max(128, C))
    kp = (-K) % bk
    cp = (-C) % bc
    if kp or cp:
        x = jnp.pad(x, ((0, kp), (0, cp)))
        rand = jnp.pad(rand, ((0, kp), (0, cp)))
        norms = jnp.pad(norms, (0, kp))
    Kp, Cp = x.shape
    scalars = jnp.stack([thr.astype(jnp.float32), u_min.astype(jnp.float32),
                         u_max.astype(jnp.float32),
                         jnp.asarray(n_levels, jnp.float32)])
    q, lvl = pl.pallas_call(
        _fused_kernel,
        grid=(Kp // bk, Cp // bc),
        in_specs=[
            pl.BlockSpec((4,), lambda i, j: (0,)),
            pl.BlockSpec((bk, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bk, bc), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bk, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bk, bc), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, Cp), x.dtype),
            jax.ShapeDtypeStruct((Kp, Cp), jnp.int32),
        ],
        interpret=interpret,
    )(scalars, norms.astype(jnp.float32).reshape(Kp, 1), x, rand)
    return q[:K, :C], lvl[:K, :C]


def fused_ref(x, norms, thr, u_min, u_max, n_levels, rand):
    """Composition oracle — single home is kernels/ref.py (ORACLES)."""
    from repro.kernels import ref
    return ref.fused_sparsify_quantize_ref(x, norms, thr, u_min, u_max,
                                           n_levels, rand)
