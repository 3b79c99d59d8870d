"""FGC — Flexible Gradient Compression (paper §III-C).

Pipeline over a local update pytree ``u``:

1. *Kernel-wise sparsification* (Eq. 2): per-kernel L2 norms (a kernel = one
   output unit's fan-in slice: conv filters, linear columns; 1-D leaves are
   one kernel), global threshold = the ``ceil((1-rho)*K)``-th largest norm
   (the appendix semantics: ``rho`` is the *removed* fraction), kernels below
   the threshold are zeroed.
2. *Probabilistic quantization* (Eq. 3-4): uniform magnitude grid with L
   intervals on [u_min, u_max] of the surviving non-zero magnitudes,
   unbiased stochastic rounding, sign preserved.
3. *Lossless coding size model*: empirical-entropy bits for the level
   indices (entropy coding, [14,37]) + Golomb bits for the sparsity mask
   ([11,38]) + header. We model the exact bit count (the thing every paper
   claim depends on) and provide byte packing for transport simulation.

The analytic planner of Appendix A sets ``rho = 1 - sqrt(beta)`` and
``L = 2**(32*sqrt(beta))``; :class:`BetaPlanner` additionally fits the
piecewise-linear (beta -> rho, L) map from a small probe update, exactly as
the server does offline in §III-C.3.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry import profiler
from repro.utils.pytree import flatten_to_vector, tree_size

PyTree = Any


# ----------------------------------------------------------- kernel structure

def leaf_kernel_shape(shape: tuple) -> tuple[int, int]:
    """(K, ksize): kernels = output units (last axis); 1-D leaves = 1 kernel."""
    if len(shape) >= 2:
        k = shape[-1]
        return k, int(np.prod(shape[:-1]))
    return 1, int(np.prod(shape)) if shape else 1


def kernel_segments(tree: PyTree) -> tuple[np.ndarray, int]:
    """Element -> kernel-id map for the flattened update vector.

    Returns (segment_ids (N,), total kernel count K). Static (numpy) — shapes
    only, safe to close over in jit.
    """
    seg = []
    kid = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        k, ksize = leaf_kernel_shape(leaf.shape)
        if len(leaf.shape) >= 2:
            # C-order flattening: the last axis varies fastest, so element i
            # belongs to kernel i % k
            seg.append(np.tile(np.arange(k, dtype=np.int32), ksize) + kid)
        else:
            seg.append(np.full(int(np.prod(leaf.shape)), kid, np.int32))
        kid += k
    if not seg:
        return np.zeros((0,), np.int32), 0
    return np.concatenate(seg), kid


# ------------------------------------------------------------- sparsification

def kernel_norms(v: jax.Array, seg_ids: np.ndarray, n_kernels: int
                 ) -> jax.Array:
    """Per-kernel L2 norms of the flat update vector."""
    sq = jax.ops.segment_sum(jnp.square(v), profiler.put(seg_ids),
                             num_segments=n_kernels)
    return jnp.sqrt(sq)


def sparsify_threshold(norms: jax.Array, rho) -> jax.Array:
    """Eq. 2's threshold: the exact ``ceil((1-rho)*K)``-th largest norm.

    ``jnp.quantile``'s linear interpolation lands *between* adjacent
    order statistics and can shift the kept-kernel count by one at small
    K; the appendix semantics are an exact order statistic, so we sort
    and gather.  ``rho`` may be a traced scalar.  At ``rho == 1`` the
    index clips to the largest norm, so the top kernel (and its ties)
    always survives.
    """
    K = norms.shape[0]
    rho = jnp.clip(jnp.asarray(rho, jnp.float32), 0.0, 1.0)
    kept = jnp.ceil((1.0 - rho) * K)              # kernels to keep
    idx = jnp.clip(K - kept, 0, K - 1).astype(jnp.int32)
    return jnp.sort(norms)[idx]


def sparsify_mask(v: jax.Array, seg_ids: np.ndarray, n_kernels: int,
                  rho: jax.Array) -> jax.Array:
    """Eq. 2 — keep the top ``ceil((1-rho)*K)`` kernels by L2 norm.

    Returns the elementwise {0,1} mask. ``rho`` may be a traced scalar.
    """
    norms = kernel_norms(v, seg_ids, n_kernels)
    thr = sparsify_threshold(norms, rho)
    keep = norms >= thr                       # (K,)
    return keep[profiler.put(seg_ids)].astype(v.dtype)


# -------------------------------------------------------------- quantization

class Quantized(NamedTuple):
    values: jax.Array        # dequantized values (same shape as input)
    levels: jax.Array        # int32 level index per element (0 where masked)
    u_min: jax.Array
    u_max: jax.Array


def prob_quantize(v: jax.Array, mask: jax.Array, n_levels,
                  key: jax.Array) -> Quantized:
    """Eq. 3-4 — probabilistic quantization of the surviving elements.

    Grid: L+1 points u_min + l*(u_max-u_min)/L, l=0..L, on |v|; stochastic
    rounding to the two neighbours with probability proportional to
    proximity (unbiased: E[q] = v).
    """
    L = jnp.asarray(n_levels, jnp.float32)
    av = jnp.abs(v) * mask
    nz = mask > 0
    big = jnp.float32(jnp.inf)
    u_min = jnp.min(jnp.where(nz & (av > 0), av, big))
    u_min = jnp.where(jnp.isfinite(u_min), u_min, 0.0)
    u_max = jnp.max(jnp.where(nz, av, -big))
    u_max = jnp.where(jnp.isfinite(u_max), u_max, 0.0)
    span = jnp.maximum(u_max - u_min, 1e-20)
    step = span / L
    # continuous level position in [0, L]
    t = jnp.clip((av - u_min) / step, 0.0, L)
    lo = jnp.floor(t)
    frac = t - lo
    u = jax.random.uniform(key, v.shape)
    lvl = lo + (u < frac)                       # stochastic rounding
    lvl = jnp.clip(lvl, 0.0, L)
    q = (u_min + lvl * step) * jnp.sign(v)
    q = jnp.where(nz, q, 0.0)
    lvl = jnp.where(nz, lvl, 0.0).astype(jnp.int32)
    return Quantized(q.astype(v.dtype), lvl, u_min, u_max)


# ---------------------------------------------------------------- size model

def entropy_bits(levels: jax.Array, mask: jax.Array, n_levels: int
                 ) -> jax.Array:
    """Empirical-entropy coded size (bits) of the level indices (+signs)."""
    nnz = jnp.maximum(jnp.sum(mask), 1.0)
    hist = jax.ops.segment_sum(mask, levels, num_segments=int(n_levels) + 1)
    p = hist / nnz
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-12)), 0.0))
    return nnz * (h + 1.0)     # +1 sign bit per surviving element


def golomb_bits(mask: jax.Array) -> jax.Array:
    """Golomb-coded size (bits) of the sparsity mask ([11], [38]).

    Run-length Golomb coding with the optimal parameter for density p:
    m = ceil(-1/log2(1-p)); average ~ H2(p) per element at small p. We use
    the standard expected-length formula on the empirical density.
    """
    n = mask.size
    p = jnp.clip(jnp.sum(mask) / n, 1e-9, 1 - 1e-9)
    # expected Golomb code length per *one* (kept) element encoding the gap:
    # log2(m) + 1/(1-(1-p)^m) with m = 2^ceil(log2(-1/log2(1-p))) (power of 2)
    m_star = -1.0 / jnp.log2(1.0 - p)
    b = jnp.ceil(jnp.log2(jnp.maximum(m_star, 1.0)))
    m = jnp.exp2(b)
    exp_len = b + 1.0 / (1.0 - jnp.power(1.0 - p, m))
    return jnp.sum(mask) * exp_len


HEADER_BITS = 2 * 32 + 16      # u_min, u_max float32 + L uint16


def compressed_bits(q: Quantized, mask: jax.Array, n_levels: int
                    ) -> jax.Array:
    return entropy_bits(q.levels, mask, n_levels) + golomb_bits(mask) \
        + HEADER_BITS


# -------------------------------------------------------- compression driver

class CompressedUpdate(NamedTuple):
    """A compressed local update, full-coordinate (server view, decoded)."""
    values: PyTree           # dequantized update (zeros where dropped)
    mask: PyTree             # {0,1} elementwise mask of transmitted elements
    bits: jax.Array          # modelled wire size
    rho: jax.Array
    n_levels: jax.Array


def analytic_rho(beta) -> jax.Array:
    """Appendix A: sparsity rho = 1 - sqrt(beta)."""
    return 1.0 - jnp.sqrt(jnp.asarray(beta, jnp.float32))


def analytic_levels(beta, bit_width: int = 32, cap: int = 65535):
    """Appendix A: L = 2**(bit_width*sqrt(beta)), capped for sanity."""
    L = jnp.exp2(bit_width * jnp.sqrt(jnp.asarray(beta, jnp.float32)))
    return jnp.clip(L, 2.0, float(cap))


def compress_update(update: PyTree, beta, key,
                    rho: Optional[jax.Array] = None,
                    n_levels: Optional[jax.Array] = None,
                    max_levels: int = 65535) -> CompressedUpdate:
    """FGC end-to-end on an update pytree with target rate ``beta``.

    If (rho, n_levels) are not given, uses the analytic Appendix-A split.
    """
    rho = analytic_rho(beta) if rho is None else jnp.asarray(rho)
    n_levels = analytic_levels(beta) if n_levels is None \
        else jnp.asarray(n_levels)
    vec, unflatten = flatten_to_vector(update)
    seg, K = kernel_segments(update)
    mask = sparsify_mask(vec, seg, K, rho)
    q = prob_quantize(vec, mask, n_levels, key)
    bits = compressed_bits(q, mask, max_levels)
    return CompressedUpdate(values=unflatten(q.values),
                            mask=unflatten(mask),
                            bits=bits, rho=rho, n_levels=n_levels)


# ------------------------------------------------------ error decomposition

class StageErrors(NamedTuple):
    """Single-pass energies of one device's compression pipeline.

    With ``u`` the full-coordinate update, ``w`` the {0,1} EMS width mask,
    ``m`` the final transmitted mask (``w * sparsity``, so ``m <= w``) and
    ``u_hat`` the decoded wire values (zeros outside ``m``), the three
    stage supports ``(1-w)``, ``(w-m)``, ``m`` partition the coordinates,
    so in exact arithmetic

        e_shrink + e_sparsify + e_quantize == ||u - u_hat||^2

    coordinate-exactly — not as a bound.  ``e_shrink`` is structurally 0
    under the expand-update convention (``u`` is the *zero-padded*
    sub-update, so nothing outside ``w`` carries mass); the axis keeps
    the term the way the cost-attribution axis keeps its zero phases, so
    a cost model that estimates the untrained coordinates can populate
    it without a schema change.
    """
    update_norm_sq: jax.Array    # ||u||^2
    e_shrink: jax.Array          # ||u * (1 - w)||^2
    e_sparsify: jax.Array        # ||u * (w - m)||^2  (kernels dropped)
    e_quantize: jax.Array        # ||u * m - u_hat||^2 (grid rounding)
    e_total: jax.Array           # ||u - u_hat||^2 (single-reduction ref)


def stage_error_energies(full_update: PyTree, width_mask: PyTree,
                         mask: PyTree, decoded: PyTree) -> StageErrors:
    """Per-stage error energies of the EMS->FGC pipeline (jit-friendly).

    One pass over the update: every energy is a fused square-and-reduce
    per leaf, summed across leaves — five scalars out, no intermediate
    the size of the model materialized beyond the masked products XLA
    fuses away.  ``decoded`` is the server-view wire values (already
    masked); ``mask`` is the final transmitted mask.
    """
    def leaf(u, w, m, q):
        u = u.astype(jnp.float32)
        w = w.astype(jnp.float32)
        m = m.astype(jnp.float32)
        q = q.astype(jnp.float32)
        return (jnp.sum(jnp.square(u)),
                jnp.sum(jnp.square(u * (1.0 - w))),
                jnp.sum(jnp.square(u * (w - m))),
                jnp.sum(jnp.square(u * m - q)),
                jnp.sum(jnp.square(u - q)))

    parts = [leaf(u, w, m, q) for u, w, m, q in zip(
        jax.tree_util.tree_leaves(full_update),
        jax.tree_util.tree_leaves(width_mask),
        jax.tree_util.tree_leaves(mask),
        jax.tree_util.tree_leaves(decoded))]
    if not parts:
        z = jnp.float32(0.0)
        return StageErrors(z, z, z, z, z)
    sums = [functools.reduce(jnp.add, comp) for comp in zip(*parts)]
    return StageErrors(*sums)


# -------------------------------------------------------------- beta planner

@dataclasses.dataclass
class BetaPlanner:
    """Server-side piecewise-linear (beta -> rho, L) map (§III-C.3).

    Fit offline from a probe update (the paper: "a rather small amount of
    public training data, e.g. 16 samples"): sweep (rho, L) combinations,
    record achieved rate, and keep for each target rate the
    divergence-minimizing pair, linearly interpolated at runtime.
    """
    betas: np.ndarray
    rhos: np.ndarray
    levels: np.ndarray

    @staticmethod
    def fit(probe_update: PyTree, key,
            rho_grid=(0.0, 0.3, 0.5, 0.7, 0.8, 0.9, 0.95, 0.99),
            level_grid=(2, 4, 8, 16, 32, 64, 128, 256, 1024, 4096)
            ) -> "BetaPlanner":
        vec, _ = flatten_to_vector(probe_update)
        seg, K = kernel_segments(probe_update)
        n = vec.size
        records = []
        for rho in rho_grid:
            mask = sparsify_mask(vec, seg, K, jnp.float32(rho))
            for L in level_grid:
                q = prob_quantize(vec, mask, L, key)
                bits = compressed_bits(q, mask, 65535)
                beta = float(profiler.read(bits)) / (32.0 * n)
                err = float(profiler.read(
                    jnp.linalg.norm(q.values * mask - vec)))
                records.append((beta, rho, L, err))
        # pareto: for ascending beta keep min-err
        records.sort()
        betas, rhos, levels = [], [], []
        best = np.inf
        for beta, rho, L, err in records:
            if err < best:
                best = err
                betas.append(beta)
                rhos.append(rho)
                levels.append(L)
        return BetaPlanner(np.asarray(betas), np.asarray(rhos, np.float64),
                           np.asarray(levels, np.float64))

    def plan(self, beta: float) -> tuple[float, int]:
        """Target rate -> (rho, L) by piecewise-linear interpolation."""
        b = float(np.clip(beta, self.betas[0], self.betas[-1]))
        rho = float(np.interp(b, self.betas, self.rhos))
        lvl = int(round(float(np.interp(b, self.betas, self.levels))))
        return rho, max(lvl, 2)
