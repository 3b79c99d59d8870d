"""AIO — All-in-One aggregation (paper §III-D, Theorem 1).

Element-wise masked weighted averaging of heterogeneous local updates
(different sub-model widths, different sparsity patterns):

    u[j] = sum_i p_i m_i[j] u_i[j] / sum_i p_i m_i[j]     (Eq. 5)
           0 where no device covers j

with optimal coefficients (Theorem 1):

    p_i* ∝ 1 / (1 - alpha_i (2 - alpha_i) sqrt(beta_i))^2  (Eq. 13)

Updates arrive zero-padded to full coordinates (see shrinking.expand_update)
with their {0,1} masks; stacking them gives the (I, ...) arrays the Pallas
``aio_aggregate`` kernel consumes on TPU (kernels/aio_agg.py; the pure-jnp
path below is the oracle).

Streaming form — the :class:`PartialAgg` monoid
-----------------------------------------------

Eq. 5 is a normalized ratio, so its unnormalized running sums

    num = sum_i p_i m_i u_i        den = sum_i p_i m_i

form a commutative monoid under element-wise addition:

    init                           identity (all-zero partial)
    absorb(part, u_i, m_i, p_i)    fold one device update in, O(N) memory
    merge(a, b)                    fuse two partials (edge -> cloud)
    finalize(part)                 num / den where covered, else 0

Any absorb/merge order yields the same aggregate (up to float rounding),
and because the ratio cancels a common weight scale, ``absorb`` takes
*unnormalized* coefficients — a streaming consumer never needs to know the
full participant set up front.  This is what lets a server (or an edge
aggregator in a client->edge->cloud topology) fold arrivals into one
O(N) accumulator instead of materializing the ``(I, N)`` stack that the
batched ``aio_aggregate`` consumes; the batched path stays as the oracle.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp

PyTree = Any


def divergence_factor(alpha, beta) -> jax.Array:
    """(1 - alpha(2-alpha)sqrt(beta)) — the Lemma-1 contraction factor."""
    alpha = jnp.asarray(alpha, jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)
    return 1.0 - alpha * (2.0 - alpha) * jnp.sqrt(beta)


def optimal_coefficients(alphas, betas) -> jax.Array:
    """Theorem 1 (Eq. 13): p* minimizing the global divergence bound."""
    d = divergence_factor(jnp.asarray(alphas), jnp.asarray(betas))
    inv = 1.0 / jnp.maximum(jnp.square(d), 1e-12)
    return inv / jnp.sum(inv)


def fedavg_coefficients(data_sizes) -> jax.Array:
    """Conventional FedAvg weights |D_i|/|D| (the w/o-AIO ablation)."""
    d = jnp.asarray(data_sizes, jnp.float32)
    return d / jnp.sum(d)


def aio_aggregate(updates: Sequence[PyTree], masks: Sequence[PyTree],
                  weights: jax.Array, *, use_kernel: bool = False,
                  interpret: bool = False) -> PyTree:
    """Eq. 5 over pytrees. updates/masks: per-device, same treedef.

    ``use_kernel`` runs the compiled Pallas kernel (``interpret`` runs it
    in the Pallas interpreter instead, for tests off the chip)."""
    stacked_u = jax.tree.map(lambda *xs: jnp.stack(xs), *updates)
    stacked_m = jax.tree.map(lambda *xs: jnp.stack(xs), *masks)

    def agg(u, m):
        if use_kernel:
            from repro.kernels import aio_agg
            shape = u.shape[1:]
            flat = aio_agg.aio_aggregate(u.reshape(u.shape[0], -1),
                                         m.reshape(m.shape[0], -1), weights,
                                         interpret=interpret)
            return flat.reshape(shape)
        w = weights.reshape((-1,) + (1,) * (u.ndim - 1))
        num = jnp.sum(w * m * u, axis=0)
        den = jnp.sum(w * m, axis=0)
        return jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)

    return jax.tree.map(agg, stacked_u, stacked_m)


def aio_aggregate_stacked(u: jax.Array, m: jax.Array, weights: jax.Array
                          ) -> jax.Array:
    """Vector form used by tests/benchmarks. u,m: (I, N); weights: (I,)."""
    w = weights[:, None]
    num = jnp.sum(w * m * u, axis=0)
    den = jnp.sum(w * m, axis=0)
    return jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)


# --------------------------------------------------------------- PartialAgg


@dataclasses.dataclass
class PartialAgg:
    """Unnormalized AIO running sums over a pytree of coordinates.

    ``num``/``den`` share the model treedef; ``count`` tracks how many
    device updates have been folded in (bookkeeping only — it does not
    enter the math, so ``merge`` stays a pure monoid op).
    """
    num: PyTree
    den: PyTree
    count: int = 0


def partial_init(template: PyTree) -> PartialAgg:
    """The monoid identity: an all-zero partial shaped like ``template``."""
    zeros = jax.tree.map(
        lambda x: jnp.zeros(jnp.shape(x), jnp.float32), template)
    return PartialAgg(num=zeros,
                      den=jax.tree.map(jnp.zeros_like, zeros), count=0)


def _absorb_leaves(num, den, u, m, w, *, use_kernel: bool,
                   interpret: bool):
    if use_kernel:
        from repro.kernels import aio_agg
        shape = u.shape
        n2, d2 = aio_agg.aio_absorb(num.reshape(-1), den.reshape(-1),
                                    u.reshape(-1), m.reshape(-1), w,
                                    interpret=interpret)
        return n2.reshape(shape), d2.reshape(shape)
    wm = w * m.astype(jnp.float32)
    return num + wm * u.astype(jnp.float32), den + wm


def absorb_trees(num: PyTree, den: PyTree, values: PyTree, mask: PyTree,
                 weight, *, use_kernel: bool = False,
                 interpret: bool = False) -> tuple[PyTree, PyTree]:
    """The absorb update rule over (num, den) pytrees — jit-compatible.

    Single home of the ``num += w*m*u, den += w*m`` math; both
    :func:`partial_absorb` and the runner's jit'd edge absorb route
    through here so the rule cannot drift between call sites.
    ``use_kernel`` runs the compiled Pallas ``aio_absorb``, which donates
    (num, den); ``interpret`` runs it in the Pallas interpreter (tests).
    """
    w = jnp.asarray(weight, jnp.float32)
    pairs = jax.tree.map(
        lambda n, d, u, m: _absorb_leaves(n, d, u, m, w,
                                          use_kernel=use_kernel,
                                          interpret=interpret),
        num, den, values, mask)
    treedef = jax.tree.structure(num)
    flat = treedef.flatten_up_to(pairs)
    return (jax.tree.unflatten(treedef, [p[0] for p in flat]),
            jax.tree.unflatten(treedef, [p[1] for p in flat]))


def partial_absorb(part: PartialAgg, values: PyTree, mask: PyTree,
                   weight, *, use_kernel: bool = False) -> PartialAgg:
    """Fold one device update in: num += w*m*u, den += w*m.

    ``weight`` is the device's *unnormalized* coefficient (e.g. the
    Theorem-1 inverse divergence, or |D_i| for FedAvg) — Eq. 5's ratio
    cancels any common normalization, see the module docstring.
    """
    num, den = absorb_trees(part.num, part.den, values, mask, weight,
                            use_kernel=use_kernel)
    return PartialAgg(num=num, den=den, count=part.count + 1)


def merge_trees(num_a: PyTree, den_a: PyTree, num_b: PyTree, den_b: PyTree,
                *, use_kernel: bool = False, interpret: bool = False
                ) -> tuple[PyTree, PyTree]:
    """The merge update rule over (num, den) pytrees — jit-compatible.

    Single home of the element-wise pair addition; :func:`partial_merge`
    and the runner's donated cloud-merge hot path both route through
    here.  Under ``jax.jit(..., donate_argnums=(0, 1))`` the ``a``-side
    accumulator is updated in place instead of reallocated per arrival
    (the Pallas kernel route aliases its outputs onto the same operands
    via ``input_output_aliases``); ``interpret`` runs the kernel in the
    Pallas interpreter (tests).
    """
    if use_kernel:
        from repro.kernels import aio_agg

        def leaf(na, da, nb, db):
            shape = na.shape
            n, d = aio_agg.aio_merge(na.reshape(-1), da.reshape(-1),
                                     nb.reshape(-1), db.reshape(-1),
                                     interpret=interpret)
            return n.reshape(shape), d.reshape(shape)

        pairs = jax.tree.map(leaf, num_a, den_a, num_b, den_b)
        treedef = jax.tree.structure(num_a)
        flat = treedef.flatten_up_to(pairs)
        return (jax.tree.unflatten(treedef, [p[0] for p in flat]),
                jax.tree.unflatten(treedef, [p[1] for p in flat]))
    return (jax.tree.map(jnp.add, num_a, num_b),
            jax.tree.map(jnp.add, den_a, den_b))


def partial_merge(a: PartialAgg, b: PartialAgg, *,
                  use_kernel: bool = False) -> PartialAgg:
    """Fuse two partials (commutative, associative up to float rounding)."""
    num, den = merge_trees(a.num, a.den, b.num, b.den,
                           use_kernel=use_kernel)
    return PartialAgg(num=num, den=den, count=a.count + b.count)


def finalize_trees(num: PyTree, den: PyTree) -> PyTree:
    """Eq. 5's ratio over (num, den) pytrees — the single home of the
    zero-coverage floor, like :func:`absorb_trees`/:func:`merge_trees`
    for their rules (the mesh route and benchmarks call this directly)."""
    return jax.tree.map(
        lambda n, d: jnp.where(d > 0, n / jnp.maximum(d, 1e-12), 0.0),
        num, den)


def partial_finalize(part: PartialAgg) -> PyTree:
    """Eq. 5's ratio: num/den where any device covered, else 0."""
    return finalize_trees(part.num, part.den)


def alignment_stats(a: PyTree, b: PyTree) -> tuple:
    """(cosine, relative L2 distance) between two update pytrees.

    The learning-dynamics diagnostics use this both for per-device
    alignment (device update vs. the round aggregate) and per-cell
    divergence (a cell's finalized partial vs. the global aggregate).
    Cosine is 0 when either side is all-zero; the relative distance is
    ``||a - b|| / ||b||`` with the same zero guard, so a cell that
    exactly matches the global aggregate reads (1.0, 0.0).  Pure jnp —
    jit-friendly, consumes no RNG.
    """
    def sq(t):
        parts = jax.tree.map(
            lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), t)
        return functools.reduce(jnp.add,
                                jax.tree_util.tree_leaves(parts))

    dots = jax.tree.map(
        lambda x, y: jnp.vdot(x.astype(jnp.float32),
                              y.astype(jnp.float32)), a, b)
    dot = functools.reduce(jnp.add, jax.tree_util.tree_leaves(dots))
    na = jnp.sqrt(sq(a))
    nb = jnp.sqrt(sq(b))
    diff = jnp.sqrt(sq(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    cos = jnp.where((na > 0) & (nb > 0),
                    dot / jnp.maximum(na * nb, 1e-30), 0.0)
    rel = jnp.where(nb > 0, diff / jnp.maximum(nb, 1e-30), 0.0)
    return cos, rel
