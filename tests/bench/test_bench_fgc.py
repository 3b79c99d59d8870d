"""The reference's FGC, leaf by leaf, against the whole-vector FGC it
replaced, kept here as the oracle: the same values and the same mask, bit
for bit, on trees of every leaf rank, with all-zero kernels, ties at the
threshold, ``rho`` at 0 and 1, and several keys."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference


def fgc_whole_vector(update, beta, key):
    """The FGC of the reference before it went leaf by leaf: the update as
    one vector, the leaves in JAX's order, kernel norms by segment sums
    over segment ids."""
    leaves, tree = jax.tree.flatten(update)
    vec = jnp.concatenate([x.reshape(-1) for x in leaves])
    seg, kid = [], 0
    for x in leaves:
        if x.ndim >= 2:
            k = x.shape[-1]
            seg.append(np.tile(np.arange(k), x.size // k) + kid)
        else:
            k = 1
            seg.append(np.full(x.size, kid))
        kid += k
    seg = jnp.asarray(np.concatenate(seg).astype(np.int32))
    sb = jnp.sqrt(jnp.asarray(beta, jnp.float32))
    rho = 1.0 - sb
    n_levels = jnp.clip(jnp.exp2(32.0 * sb), 2.0, 65535.0)
    norms = jnp.sqrt(jax.ops.segment_sum(vec * vec, seg, num_segments=kid))
    kept = jnp.ceil((1.0 - rho) * kid)
    thr = jnp.sort(norms)[jnp.clip(kid - kept, 0, kid - 1).astype(jnp.int32)]
    mask = (norms >= thr)[seg].astype(jnp.float32)
    mag = jnp.abs(vec) * mask
    live = mask > 0
    lo = jnp.min(jnp.where(live & (mag > 0), mag, jnp.inf))
    lo = jnp.where(jnp.isfinite(lo), lo, 0.0)
    hi = jnp.max(jnp.where(live, mag, -jnp.inf))
    hi = jnp.where(jnp.isfinite(hi), hi, 0.0)
    step = jnp.maximum(hi - lo, 1e-20) / n_levels
    pos = jnp.clip((mag - lo) / step, 0.0, n_levels)
    base = jnp.floor(pos)
    level = jnp.clip(base + (jax.random.uniform(key, vec.shape)
                             < pos - base), 0.0, n_levels)
    q = jnp.where(live, (lo + level * step) * jnp.sign(vec), 0.0)
    ends = np.cumsum([x.size for x in leaves])[:-1]
    return tuple(tree.unflatten([part.reshape(x.shape) for part, x in zip(
        jnp.split(a, ends), leaves)]) for a in (q, mask))


SHAPES = {"scalar": (), "bias": (7,), "dense": (5, 9), "stack": (3, 4, 6),
          "conv": (3, 3, 2, 8), "experts": (2, 3, 5, 4)}


def _random(seed):
    """Every leaf rank, normal values; some kernels all zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), len(SHAPES))
    tree = {name: jax.random.normal(k, s) for k, (name, s) in
            zip(ks, SHAPES.items())}
    tree["dense"] = tree["dense"].at[:, 2].set(0.0)
    tree["conv"] = tree["conv"].at[..., 5].set(0.0)
    tree["zero"] = {"w": jnp.zeros((4, 3)), "b": jnp.zeros((3,))}
    return tree


def _ties(seed):
    """Integer values, so every norm is exact: kernels of equal norm in one
    leaf and across leaves, the threshold among them."""
    rng = np.random.default_rng(seed)
    col = rng.integers(-3, 4, size=6).astype(np.float32)
    dense = np.repeat(col[:, None], 8, axis=1)
    dense[:, ::3] *= 2.0
    return {"dense": jnp.asarray(dense),
            "copy": jnp.asarray(col),
            "stack": jnp.asarray(np.stack([dense[:3], -dense[3:]])),
            "scalar": jnp.asarray(np.float32(np.linalg.norm(col)))}


@pytest.mark.parametrize("beta", [0.0, 1 / 15, 0.2, 0.5, 0.81, 1.0])
@pytest.mark.parametrize("build,seed", [(_random, 1), (_random, 2),
                                        (_ties, 3), (_ties, 4)])
def test_fgc_per_leaf_equals_the_whole_vector_fgc(build, seed, beta):
    tree = build(seed)
    for k in range(3):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), k)
        got = jax.jit(reference.fgc)(tree, beta, key)
        want = jax.jit(fgc_whole_vector)(tree, beta, key)
        for g, w in zip(got, want):
            assert jax.tree.structure(g) == jax.tree.structure(w)
            for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fgc_keeps_the_kernels_it_should():
    """rho 0 keeps every kernel, rho 1 only the largest; a tie at the
    threshold keeps every kernel of that norm."""
    tree = _ties(3)
    norms = np.concatenate([np.asarray(reference._kernel_norms(x))
                            for x in jax.tree.leaves(tree)])
    for beta, kept in [(1.0, norms.size),
                       (0.0, int(np.sum(norms == norms.max())))]:
        _, mask = reference.fgc(tree, beta, jax.random.PRNGKey(0))
        counted = sum(int(np.sum(np.asarray(m).reshape(-1, 1 if m.ndim < 2
                                                        else m.shape[-1])
                                 .max(axis=0)))
                      for m in jax.tree.leaves(mask))
        assert counted == kept


@pytest.mark.parametrize("k", [1, 2, 5, 9, 13])
def test_kth_largest_is_the_order_statistic(k):
    x = jnp.asarray([0.0, 3.5, 1e-30, 3.5, 7.0, 0.0, 2.0, 1e30, 0.25,
                     5.0, 3.5, 6.0, 1.0], jnp.float32)
    assert float(reference._kth_largest(x, k)) == float(
        jnp.sort(x)[x.size - k])
