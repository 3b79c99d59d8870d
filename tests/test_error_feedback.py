"""Error-feedback compressed sync: residual bookkeeping + convergence on a
quadratic (single-device semantics; the collective path is covered by
test_distributed)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed as D
from jax import shard_map


P = jax.sharding.PartitionSpec


def _fake_axis(fn, args, out_like):
    """Run an axis_name-using function under a 1-device 'pod' axis.

    ``out_like``: a pytree prototype of the output (specs are P() for every
    leaf — eval_shape can't trace unbound axis names outside the map).
    """
    return shard_map(
        fn, mesh=jax.make_mesh((1,), ("pod",)),
        in_specs=tuple(jax.tree.map(lambda _: P(), a) for a in args),
        out_specs=jax.tree.map(lambda _: P(), out_like),
        check_vma=False)(*args)


def test_residual_tracks_dropped_mass():
    g = {"w": jnp.asarray([10.0, 0.1, -8.0, 0.05])}
    res = D.init_error_feedback(g)

    def run(g, r):
        return D.anycost_gradient_sync_ef(g, r, "pod", keep_frac=0.5,
                                          quantize=False)

    out_like = (g, res)
    synced, new_res = _fake_axis(run, (g, res), out_like)
    # large coords transmitted -> residual ~0 there; small coords kept back
    assert abs(float(new_res["w"][0])) < 1e-6
    assert abs(float(new_res["w"][1]) - 0.1) < 1e-6
    # next round the residual is added back
    synced2, new_res2 = _fake_axis(run, ({"w": jnp.zeros(4)}, new_res),
                                   out_like)
    assert float(jnp.abs(synced2["w"][1])) >= 0.0


def test_residual_feeds_back_quantization_error():
    """With quantize=True, ``sent`` is the *dequantized* int8 wire value,
    so sent + residual == corrected exactly — the rounding error stays in
    the residual instead of being silently dropped."""
    g = {"w": jnp.asarray([10.0, 0.37, -8.13, 0.05, 3.1415, -0.61])}
    res = D.init_error_feedback(g)

    def run(gg, rr):
        return D.anycost_gradient_sync_ef(gg, rr, "pod", keep_frac=1.0,
                                          quantize=True)

    synced, new_res = _fake_axis(run, (g, res), (g, res))
    # reconstruct this pod's dequantized contribution the same way the
    # collective computed it
    _, _, q, scale = D._local_compress(g["w"], 1.0, True)
    sent = np.asarray(q, np.float32) * float(scale)
    np.testing.assert_allclose(np.asarray(new_res["w"]),
                               np.asarray(g["w"]) - sent, atol=1e-6)
    # the rounding error is genuinely nonzero at this amax spread — the
    # pre-fix residual (corrected - pre-quantization sparse) was all-zero
    assert float(np.abs(np.asarray(new_res["w"])).max()) > 1e-4


def test_ef_converges_where_plain_compression_stalls():
    """Minimize ||w - b||^2 with heavy compression: EF reaches the optimum,
    plain (no-feedback) compression leaves persistent bias."""
    b = jnp.asarray(np.random.default_rng(0).normal(0, 1, 64))

    mesh = jax.make_mesh((1,), ("pod",))
    proto = {"w": jnp.zeros(64)}

    @jax.jit
    def run_ef(w, res):
        def body(wr, _):
            w, res = wr
            g = {"w": 2 * (w - b)}
            synced, res = shard_map(
                lambda gg, rr: D.anycost_gradient_sync_ef(
                    gg, rr, "pod", keep_frac=0.1, quantize=False),
                mesh=mesh, in_specs=(jax.tree.map(lambda _: P(), g),
                                     jax.tree.map(lambda _: P(), res)),
                out_specs=(jax.tree.map(lambda _: P(), g),
                           jax.tree.map(lambda _: P(), res)),
                check_vma=False)(g, res)
            return (w - 0.1 * synced["w"], res), None

        (w, res), _ = jax.lax.scan(body, (w, res), None, length=300)
        return w

    @jax.jit
    def run_plain(w):
        def body(w, _):
            g = {"w": 2 * (w - b)}
            synced = shard_map(
                lambda gg: D.anycost_gradient_sync(gg, "pod",
                                                   keep_frac=0.1,
                                                   quantize=False),
                mesh=mesh, in_specs=(jax.tree.map(lambda _: P(), g),),
                out_specs=jax.tree.map(lambda _: P(), g),
                check_vma=False)(g)
            return w - 0.1 * synced["w"], None

        w, _ = jax.lax.scan(body, w, None, length=300)
        return w

    res0 = D.init_error_feedback(proto)
    w_ef = run_ef(jnp.zeros(64), res0)
    w_plain = run_plain(jnp.zeros(64))
    assert float(jnp.linalg.norm(w_ef - b)) < 0.05
    # top-10% never revisits small coordinates without feedback
    assert float(jnp.linalg.norm(w_plain - b)) > 0.05
