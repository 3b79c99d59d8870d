"""AnycostFL on the pod: compressed cross-pod gradient synchronization.

The paper compresses each device's uplink before server aggregation. On a
multi-pod TPU mesh the analogue (DESIGN.md §3) treats each *pod* as a
device: per-pod gradients are FGC-compressed — magnitude-threshold
sparsification + int8 probabilistic quantization — exchanged with
``all_gather`` over the "pod" axis, and combined with the AIO masked mean.
The wire payload per leaf drops from the baseline psum's 2*(G-1)/G * N * 2
bytes (bf16 all-reduce) to (G-1)/G * N * 1 byte: ~4x.

Partitioner constraints (measured, not hypothetical): inside a
partial-manual shard_map (manual "pod", auto "data"/"model"), gathers and
scatter-adds on auto-sharded operands abort XLA's SPMD partitioner
(``PartitionGather`` CHECK — the class of issues its warnings defer to the
Shardy rewrite). The implementation therefore avoids index-based top-k
entirely: sparsification uses a *moment-based magnitude threshold* (the
keep_frac quantile of a half-normal fitted to the leaf — the same
keep-the-largest semantics as FGC's kernel norms, Eq. 2, at elementwise
grain), and the compressed exchange stays value-dense int8. On hardware, a
packed sparse representation would buy the remaining keep_frac factor;
XLA cannot express it through this path today (EXPERIMENTS.md §Perf P3
documents the gap).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.scipy.special import erfinv

PyTree = Any


def magnitude_threshold(g: jax.Array, keep_frac: float) -> jax.Array:
    """Approximate keep_frac-quantile of |g| via a half-normal moment fit
    (elementwise + scalar reductions only — partitioner-safe)."""
    if keep_frac >= 1.0:
        return jnp.zeros((), jnp.float32)
    std = jnp.sqrt(jnp.mean(jnp.square(g.astype(jnp.float32))) + 1e-30)
    # |g| ~ HalfNormal(std): P(|g| > t) = keep -> t = std*sqrt(2)*erfinv(1-keep)
    return std * jnp.sqrt(2.0) * erfinv(1.0 - keep_frac)


def _local_compress(gf: jax.Array, keep_frac: float, quantize: bool):
    """The local FGC stage shared by the sync collective and its EF
    residual: magnitude threshold -> explicit keep mask -> optional int8
    amax quantization.

    Returns ``(sparse, keep, q, scale)``; ``q``/``scale`` are None when
    ``quantize`` is off.  The *dequantized* contribution this pod puts on
    the wire is ``q * scale`` (or ``sparse`` unquantized) — EF residuals
    must subtract that, not the pre-quantization value, or the int8
    rounding error is never fed back.
    """
    thr = magnitude_threshold(gf, keep_frac)
    keep = (jnp.abs(gf) >= thr).astype(jnp.float32)
    sparse = jnp.where(keep > 0, gf, 0.0)
    if not quantize:
        return sparse, keep, None, None
    amax = jnp.max(jnp.abs(sparse))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(sparse / scale), -127, 127).astype(jnp.int8)
    return sparse, keep, q, scale


def anycost_sync_leaf(g: jax.Array, axis_name: str, keep_frac: float,
                      quantize: bool = True, axes=None) -> jax.Array:
    """Compressed AIO all-reduce of one gradient leaf over ``axis_name``.

    ``axes``: the leaf's logical axes (models.layers.LogicalAxes). Inside
    the partial-manual region XLA's sharding propagation loses the grad's
    data/model sharding through the int8 ops and replicates the exchange
    buffers per device; re-constraining to the parameter's own sharding
    keeps the compression *shard-wise* (each device compresses and
    exchanges only its ZeRO shard over the pod axis — measured 30x wire
    difference, EXPERIMENTS.md §Perf P3).

    The AIO denominator is built from the *explicit* keep mask, exchanged
    alongside the values (1 bit/coordinate on a real wire — negligible
    next to the int8 payload).  Inferring transmission from ``val != 0``
    would mis-count a pod whose kept coordinate quantized (or genuinely
    landed) on zero as absent and bias the mean.
    """
    from repro import sharding as shd

    def _pin(x, lead=0):
        if axes is None or not shd.active():
            return x
        names = ((None,) * lead) + tuple(axes.names)
        return jax.lax.with_sharding_constraint(
            x, shd.sharding_for(x.shape, names))

    gf = _pin(g.astype(jnp.float32))
    sparse, keep, q, scale = _local_compress(gf, keep_frac, quantize)
    sparse = _pin(sparse)
    if quantize:
        q_all = _pin(jax.lax.all_gather(_pin(q), axis_name), lead=1)
        s_all = jax.lax.all_gather(scale, axis_name)            # (P,)
        vals = q_all.astype(jnp.float32) \
            * s_all.reshape((-1,) + (1,) * g.ndim)
    else:
        vals = _pin(jax.lax.all_gather(sparse, axis_name), lead=1)
    # AIO (Eq. 5) at uniform p (pods see equal local batches): element-wise
    # masked mean over the pods that transmitted the coordinate. At
    # keep_frac >= 1 every coordinate is transmitted (plain mean).
    num = jnp.sum(vals, axis=0)
    if keep_frac >= 1.0:
        return (num / vals.shape[0]).astype(g.dtype)
    # exchange the mask at int8 ({0,1} is exact) so its wire cost stays
    # a fraction of the payload's, not 4x it; cast back after the gather
    m_all = _pin(jax.lax.all_gather(_pin(keep.astype(jnp.int8)),
                                    axis_name), lead=1)
    den = jnp.sum(m_all.astype(jnp.float32), axis=0)
    out = jnp.where(den > 0, num / jnp.maximum(den, 1.0), 0.0)
    return out.astype(g.dtype)


def anycost_gradient_sync(grads: PyTree, axis_name: str = "pod", *,
                          keep_frac: float = 1.0 / 16.0,
                          quantize: bool = True,
                          axes_tree: PyTree = None,
                          key: jax.Array | None = None) -> PyTree:
    """FGC+AIO compressed mean of per-pod gradients (vs plain psum)."""
    del key
    if axes_tree is None:
        return jax.tree.map(
            lambda g: anycost_sync_leaf(g, axis_name, keep_frac, quantize),
            grads)
    from repro.models.layers import LogicalAxes
    return jax.tree.map(
        lambda g, ax: anycost_sync_leaf(g, axis_name, keep_frac, quantize,
                                        axes=ax),
        grads, axes_tree)


def mean_gradient_sync(grads: PyTree, axis_name: str = "pod") -> PyTree:
    """The uncompressed baseline: plain psum mean over the pod axis."""
    size = jax.lax.psum(1, axis_name)
    return jax.tree.map(lambda g: jax.lax.psum(g, axis_name) / size, grads)


# ------------------------------------------------------------ error feedback

def init_error_feedback(params: PyTree) -> PyTree:
    """Residual accumulators for EF compressed sync (Seide et al. / EF-SGD).

    The paper's FL clients retransmit fresh gradients every round; for
    *repeated* pod-sync steps the compression error compounds unless the
    dropped mass is fed back — a beyond-paper addition that makes the
    compressed sync usable at training length.
    """
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def anycost_gradient_sync_ef(grads: PyTree, residual: PyTree,
                             axis_name: str = "pod", *,
                             keep_frac: float = 1.0 / 16.0,
                             quantize: bool = True,
                             axes_tree: PyTree = None
                             ) -> tuple[PyTree, PyTree]:
    """EF variant: compress (grad + residual); residual' = input - sent."""
    def one(g, r, ax=None):
        corrected = g.astype(jnp.float32) + r
        synced = anycost_sync_leaf(corrected.astype(g.dtype), axis_name,
                                   keep_frac, quantize, axes=ax)
        # what this pod actually contributed: recompute the local compress
        # stage on the same dtype-round-tripped view the collective saw.
        # ``sent`` is the *dequantized* wire value — with quantize on, the
        # int8 rounding error stays in the residual (EF's whole point).
        gf = corrected.astype(g.dtype).astype(jnp.float32)
        sparse, _, qv, scale = _local_compress(gf, keep_frac, quantize)
        sent = qv.astype(jnp.float32) * scale if quantize else sparse
        return synced, corrected - sent

    if axes_tree is None:
        pairs = jax.tree.map(one, grads, residual)
    else:
        pairs = jax.tree.map(lambda g, r, ax: one(g, r, ax), grads,
                             residual, axes_tree)
    synced = jax.tree.map(lambda t: t[0], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
    new_res = jax.tree.map(lambda t: t[1], pairs,
                           is_leaf=lambda x: isinstance(x, tuple))
    return synced, new_res


# ------------------------------------------------- mesh-mapped edge cells

def mesh_cell_aggregate(u: jax.Array, m: jax.Array, w: jax.Array, mesh, *,
                        axis_name: str = "cell", finalize: bool = True):
    """Pod-scale hierarchical AIO: edge cells mapped onto a mesh axis.

    ``u``/``m``: ``(I, N)`` stacked updates/masks, ``w``: ``(I,)``
    unnormalized coefficients, with the client dim ``I`` partitioned over
    the ``axis_name`` mesh axis — each shard is one edge cell's roster.
    Inside the manual region every cell folds its local clients into an
    O(N) ``(num, den)`` partial with the streaming absorb (never holding
    its ``(I_c, N)`` block as weighted copies), then the partials are
    cloud-merged with the monoid over the axis: ``merge`` is element-wise
    addition, so ``psum`` *is* the merge.  ``finalize=True`` applies the
    Eq.-5 ratio once and returns the replicated ``(N,)`` aggregate;
    ``finalize=False`` returns the merged ``(num, den)`` pair (for a
    caller that wants to keep folding — e.g. across rounds or pods).

    Equals the flat ``aio_aggregate_stacked`` oracle up to float
    reordering, for any cell partitioning (the monoid is commutative).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.kernels.ref import aio_absorb_ref

    def per_cell(u_c, m_c, w_c):
        # shard-local streaming absorb: one pass over the cell's clients,
        # O(N) accumulator state (the EdgeAggregator semantics, vectorized
        # onto the mesh)
        num = jnp.zeros(u_c.shape[1:], jnp.float32)
        den = jnp.zeros_like(num)

        def absorb(carry, upd):
            ui, mi, wi = upd
            return aio_absorb_ref(carry[0], carry[1], ui, mi, wi), None

        (num, den), _ = jax.lax.scan(absorb, (num, den), (u_c, m_c, w_c))
        num = jax.lax.psum(num, axis_name)      # monoid merge over cells
        den = jax.lax.psum(den, axis_name)
        if not finalize:
            return num, den
        from repro.core.aggregation import finalize_trees
        return finalize_trees(num, den)

    spec = P(axis_name)
    out_specs = P() if finalize else (P(), P())
    return shard_map(per_cell, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=out_specs, check_vma=False)(u, m, w)
