"""Pallas TPU kernels: kernel-wise L2 norms + threshold masking (Eq. 2).

FGC's sparsification pass touches every gradient element twice (norms, then
masking) — memory-bound over hundreds of MB. Two kernels:

* ``kernel_sumsq`` — row-wise sum-of-squares with a 2-D grid (row tiles x
  column tiles); the column grid dim accumulates into the output tile, so
  arbitrarily long rows stream through a fixed (BK, BC) VMEM window.
* ``threshold_apply`` — elementwise ``x * (norm[row] >= thr)`` over the same
  tiling, fused mask materialization.

Per-row vectors (sums of squares, norms, keep masks) travel as ``(K, 1)``
columns in ``(BK, 1)`` blocks: a 1-D ``(BK,)`` block with BK < 1024 does
not match the TPU's 1-D tiling of 1024 lanes, and Mosaic refuses it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BK = 256    # rows per tile
BC = 512    # columns per tile


def _sumsq_kernel(x_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.sum(x * x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "bk", "bc"))
def kernel_sumsq(x: jax.Array, *, interpret: bool = False, bk: int = BK,
                 bc: int = BC) -> jax.Array:
    """x: (K, ksize) -> row sum-of-squares (K,) f32."""
    K, C = x.shape
    bk = min(bk, max(8, K))
    bc = min(bc, max(128, C))
    kp = (-K) % bk
    cp = (-C) % bc
    if kp or cp:
        x = jnp.pad(x, ((0, kp), (0, cp)))
    Kp, Cp = x.shape
    out = pl.pallas_call(
        _sumsq_kernel,
        grid=(Kp // bk, Cp // bc),
        in_specs=[pl.BlockSpec((bk, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bk, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Kp, 1), jnp.float32),
        interpret=interpret,
    )(x)
    return out[:K, 0]


def kernel_l2(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    return jnp.sqrt(kernel_sumsq(x, interpret=interpret))


def _threshold_kernel(thr_ref, x_ref, n_ref, xo_ref, mo_ref):
    keep = (n_ref[...] >= thr_ref[0]).astype(jnp.float32)     # (BK, 1)
    xo_ref[...] = (x_ref[...].astype(jnp.float32)
                   * keep).astype(xo_ref.dtype)
    mo_ref[...] = keep


@functools.partial(jax.jit, static_argnames=("interpret", "bk", "bc"))
def threshold_apply(x: jax.Array, norms: jax.Array, thr: jax.Array, *,
                    interpret: bool = False, bk: int = BK, bc: int = BC
                    ) -> tuple[jax.Array, jax.Array]:
    """Eq. 2: returns (masked x, per-row keep mask (K,) f32)."""
    K, C = x.shape
    bk = min(bk, max(8, K))
    bc = min(bc, max(128, C))
    kp = (-K) % bk
    cp = (-C) % bc
    if kp or cp:
        x = jnp.pad(x, ((0, kp), (0, cp)))
        norms = jnp.pad(norms, (0, kp))
    Kp, Cp = x.shape
    xo, mo = pl.pallas_call(
        _threshold_kernel,
        grid=(Kp // bk, Cp // bc),
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (0,)),
            pl.BlockSpec((bk, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bk, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bk, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bk, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, Cp), x.dtype),
            jax.ShapeDtypeStruct((Kp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(thr.reshape(1).astype(jnp.float32), x,
      norms.astype(jnp.float32).reshape(Kp, 1))
    return xo[:K, :C], mo[:K, 0]
