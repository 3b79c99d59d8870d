"""The ``jax.shard_map`` surface this repo relies on (keyword specs,
``check_vma``, partial-manual ``axis_names`` over an ``Auto`` mesh), plus
the fast in-process coverage of the mesh-mapped edge-cell aggregation
route."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.core.aggregation import aio_aggregate_stacked
from repro.core.distributed import mesh_cell_aggregate
from jax import shard_map


def test_shard_map_full_manual_psum():
    """A full-manual psum over a one-device axis is the identity."""
    mesh = jax.make_mesh((1,), ("pod",))
    out = shard_map(lambda x: jax.lax.psum(x, "pod"), mesh=mesh,
                    in_specs=(P(),), out_specs=P(),
                    check_vma=False)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


def test_shard_map_check_vma_both_values():
    mesh = jax.make_mesh((1,), ("x",))
    for check in (True, False):
        out = shard_map(lambda a: a * 2.0, mesh=mesh, in_specs=(P("x"),),
                        out_specs=P("x"), check_vma=check)(jnp.ones(2))
        np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(2))


def test_shim_axis_names_subset():
    """Partial-manual spelling: ``axis_names`` names the manual axes and
    the rest stay under the compiler, which needs them ``Auto`` (the
    anycost pod sync in ``launch/steps.py`` runs this way, inside its
    jitted step: eager dispatch of a partial-manual region with
    ``check_vma=False`` is refused by JAX 0.9)."""
    mesh = jax.make_mesh((1, 1), ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2)
    mapped = shard_map(lambda x: jax.lax.psum(x, "pod"), mesh=mesh,
                       axis_names=frozenset({"pod"}),
                       in_specs=(P("pod"),), out_specs=P(),
                       check_vma=False)
    out = jax.jit(mapped)(jnp.ones((1, 3)))
    assert out.shape == (1, 3)


def test_mesh_cell_aggregate_matches_oracle():
    """Shard-local absorb + psum monoid merge == flat stacked Eq. 5 (the
    1-device mesh runs the whole fleet as one cell; the 2-device split is
    covered by the slow subprocess test)."""
    key = jax.random.PRNGKey(1)
    ku, km, kw = jax.random.split(key, 3)
    I, N = 6, 384
    u = jax.random.normal(ku, (I, N), jnp.float32)
    m = (jax.random.uniform(km, (I, N)) > 0.5).astype(jnp.float32)
    w = jax.random.uniform(kw, (I,), jnp.float32, 0.5, 1.5)
    mesh = jax.make_mesh((1,), ("cell",))
    out = mesh_cell_aggregate(u, m, w, mesh)
    ref = aio_aggregate_stacked(u, m, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)
    num, den = mesh_cell_aggregate(u, m, w, mesh, finalize=False)
    fin = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(ref), atol=1e-5)
