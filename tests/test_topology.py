"""Hierarchical multi-cell topology: cell assignment, backhaul model,
edge-tier streaming aggregation, and the flat-equivalence guarantees."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import aggregation as A
from repro.orchestrator import OrchestratorConfig, run_orchestrated
from repro.sysmodel.population import FleetConfig
from repro.sysmodel.wireless import WirelessConfig
from repro.topology import (BackhaulConfig, TopologyConfig, assign_cells,
                            decode_partial, encode_partial, payload_factor)
from repro.train.fl_loop import FLRunConfig

TINY = dict(rounds=2, n_train=128, n_test=64, eval_every=1, lr=0.1,
            batch_size=32, seed=3, use_planner=False)


def _run(topology=None, n=4, policy="sync", **kw):
    cfg = FLRunConfig(method="anycostfl", **TINY)
    fleet = FleetConfig(n_devices=n, topology=topology)
    return run_orchestrated(cfg, fleet,
                            OrchestratorConfig(policy=policy,
                                               use_pool=False, **kw))


# ------------------------------------------------------------ config / cells

def test_assign_cells_contiguous_and_round_robin():
    t = TopologyConfig(kind="hier", n_cells=3)
    c = assign_cells(7, t)
    assert sorted(set(c.tolist())) == [0, 1, 2]
    assert all(np.diff(c) >= 0)          # contiguous blocks
    rr = assign_cells(7, TopologyConfig(kind="hier", n_cells=3,
                                        assignment="round_robin"))
    assert rr.tolist()[:3] == [0, 1, 2]  # striped
    for k in range(3):                   # every cell non-empty
        assert (c == k).sum() >= 2
        assert (rr == k).sum() >= 2


def test_topology_validation():
    with pytest.raises(ValueError):
        TopologyConfig(kind="mesh")
    with pytest.raises(ValueError):
        TopologyConfig(kind="flat", n_cells=2)
    with pytest.raises(ValueError):
        TopologyConfig(kind="hier", n_cells=0)
    with pytest.raises(ValueError):
        assign_cells(2, TopologyConfig(kind="hier", n_cells=3))
    with pytest.raises(ValueError):
        BackhaulConfig(rate_bps=0.0)
    with pytest.raises(ValueError):
        BackhaulConfig(latency_s=-1.0)


def test_backhaul_costs():
    assert BackhaulConfig.zero_cost().ship_cost(1e6) == (0.0, 0.0)
    b = BackhaulConfig(rate_bps=1e6, latency_s=0.5, energy_per_bit=1e-9,
                       payload_factor=2.0)
    t, e = b.ship_cost(1e6)
    assert t == pytest.approx(0.5 + 2.0)     # 2e6 bits at 1e6 bit/s
    assert e == pytest.approx(2e6 * 1e-9)
    assert b.payload_bits(1e6) == 2e6        # constant in client count


# ------------------------------------------------------------ backhaul codec

def _partial(key, n=4096, count=3):
    ku, kd = jax.random.split(key)
    num = {"w": jax.random.normal(ku, (n,)) * 5.0,
           "b": jax.random.normal(kd, (n // 8,))}
    den = jax.tree.map(lambda x: jnp.abs(x) * 0.5, num)
    return A.PartialAgg(num=num, den=den, count=count)


def test_codec_f32_is_identity_passthrough():
    part = _partial(jax.random.PRNGKey(0))
    enc = encode_partial(part, "f32")
    dec = decode_partial(enc)
    # bitwise AND zero-copy: the very same arrays ride the wire
    assert dec.num["w"] is part.num["w"]
    assert dec.den["b"] is part.den["b"]
    assert dec.count == part.count
    n = 4096 + 512
    assert enc.bits == 2 * 32 * n


def test_codec_roundtrip_tolerances():
    part = _partial(jax.random.PRNGKey(1))
    n = 4096 + 512
    for codec, factor, headers in (("bf16", 1.0, 0),
                                   ("int8", 0.5, 2 * 2 * 32)):
        enc = encode_partial(part, codec)
        # payload_factor is wire size / S_bits with S_bits = 32*n
        assert enc.bits == factor * 32 * n + headers
        dec = decode_partial(enc)
        for plane_in, plane_out in ((part.num, dec.num),
                                    (part.den, dec.den)):
            for k in plane_in:
                x = np.asarray(plane_in[k], np.float32)
                y = np.asarray(plane_out[k], np.float32)
                amax = np.abs(x).max()
                tol = amax / 254 + 1e-7 if codec == "int8" \
                    else amax * 2.0 ** -8
                assert np.abs(x - y).max() <= tol, (codec, k)


def test_codec_int8_finalize_within_quantization_tolerance():
    """The acceptance bound: finalize(decode(int8)) tracks the
    uncompressed finalize within the amax/127 grid of the planes."""
    part = _partial(jax.random.PRNGKey(2))
    ref = A.partial_finalize(part)
    got = A.partial_finalize(decode_partial(encode_partial(part, "int8")))
    for k in ref:
        x, y = np.asarray(ref[k]), np.asarray(got[k])
        num_amax = float(np.abs(np.asarray(part.num[k])).max())
        den = np.asarray(part.den[k])
        # |Δ(n/d)| <= (Δn + |n/d| Δd) / d; bound with the floor den
        dmin = np.maximum(den, 1e-12)
        bound = (num_amax / 127 + np.abs(x) * den.max() / 127) / dmin
        assert (np.abs(x - y) <= bound + 1e-5).all(), k


def test_codec_validation_and_derived_payload_factor():
    with pytest.raises(ValueError):
        encode_partial(_partial(jax.random.PRNGKey(3)), "fp4")
    with pytest.raises(ValueError):
        BackhaulConfig(codec="fp4")
    assert payload_factor("f32") == 2.0
    assert payload_factor("bf16") == 1.0
    assert payload_factor("int8") == 0.5
    # derived unless explicitly overridden
    assert BackhaulConfig(codec="int8").wire_factor == 0.5
    assert BackhaulConfig(codec="int8",
                          payload_factor=3.0).wire_factor == 3.0
    b = BackhaulConfig(rate_bps=1e6, codec="bf16", latency_s=0.0)
    assert b.ship_cost(1e6)[0] == pytest.approx(1.0)   # 1e6 bits @ 1e6 bps


def test_hier_int8_codec_shrinks_backhaul_and_tracks_f32():
    """An int8 backhaul pays ~4x fewer bits than f32 (modulo the scale
    headers) and the learning trajectory stays close."""
    bh32 = BackhaulConfig(rate_bps=1e9, latency_s=0.01)
    bh8 = BackhaulConfig(rate_bps=1e9, latency_s=0.01, codec="int8")
    h32 = _run(topology=TopologyConfig(kind="hier", n_cells=2,
                                       backhaul=bh32), n=4)
    h8 = _run(topology=TopologyConfig(kind="hier", n_cells=2,
                                      backhaul=bh8), n=4)
    b32 = h32.rounds[0].backhaul_bits
    b8 = h8.rounds[0].backhaul_bits
    assert b32 / b8 == pytest.approx(4.0, rel=0.01)
    assert h8.rounds[0].test_acc == pytest.approx(
        h32.rounds[0].test_acc, abs=0.1)


def test_radius_scale_defaults_to_area_tiling():
    base = WirelessConfig()
    t4 = TopologyConfig(kind="hier", n_cells=4)
    assert t4.radius_scale == pytest.approx(0.5)
    ws = t4.cell_wireless(base)
    assert len(ws) == 4
    assert ws[0].cell_radius_m == pytest.approx(base.cell_radius_m * 0.5)
    # 1 cell keeps the macro geometry object identity (flat equivalence)
    assert TopologyConfig(kind="hier", n_cells=1).cell_wireless(base)[0] \
        is base


# -------------------------------------------------------- flat equivalences

def test_hier_one_cell_zero_backhaul_reproduces_flat_sync():
    """Acceptance: --topology hier --cells 1 with a zero-cost backhaul
    reproduces the flat sync trajectory (costs bitwise, learning metrics
    to float tolerance — the streaming fold reorders the Eq.-5 sums)."""
    h_flat = _run()
    topo = TopologyConfig(kind="hier", n_cells=1,
                          backhaul=BackhaulConfig.zero_cost())
    h_hier = _run(topology=topo)
    assert len(h_flat.rounds) == len(h_hier.rounds)
    # round 0 sees identical params, so every realized cost is bitwise
    # equal; later rounds inherit the streaming fold's float reordering
    # through the model (compression bits depend on the update values),
    # so costs track to float tolerance
    a0, b0 = h_flat.rounds[0], h_hier.rounds[0]
    assert (a0.latency_s, a0.energy_j, a0.comm_bits, a0.mean_alpha,
            a0.mean_beta) == (b0.latency_s, b0.energy_j, b0.comm_bits,
                              b0.mean_alpha, b0.mean_beta)
    for a, b in zip(h_flat.rounds, h_hier.rounds):
        assert a.latency_s == pytest.approx(b.latency_s, rel=1e-6)
        assert a.energy_j == pytest.approx(b.energy_j, rel=1e-6)
        assert a.comm_bits == pytest.approx(b.comm_bits, rel=1e-6)
        assert a.mean_alpha == b.mean_alpha
        assert a.test_loss == pytest.approx(b.test_loss, rel=1e-4)
    assert h_hier.rounds[0].n_cells_reporting == 1
    assert h_hier.rounds[0].backhaul_bits > 0
    assert h_flat.rounds[0].backhaul_bits == 0.0


# ---------------------------------------------------------- multi-cell runs

def test_hier_multicell_ships_per_cell_and_pays_backhaul():
    bh = BackhaulConfig(rate_bps=1e8, latency_s=0.2, energy_per_bit=1e-10)
    topo = TopologyConfig(kind="hier", n_cells=3, backhaul=bh)
    h = _run(topology=topo, n=6)
    r = h.rounds[0]
    assert r.n_cells_reporting == 3
    assert r.n_clients == 6
    # each reporting cell ships one constant-size (num, den) partial
    import jax
    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.utils.pytree import tree_size
    n_params = tree_size(build_model(get_config("fmnist-cnn")).init(
        jax.random.PRNGKey(0)))
    assert r.backhaul_bits == pytest.approx(
        3 * bh.payload_bits(32.0 * n_params))
    # backhaul latency sits on the critical path of every round
    assert all(x.latency_s >= 0.2 for x in h.rounds)
    # EDGE_MERGE events are on the recorded timeline
    assert any(kind == "edge_merge" for _, _, kind, _ in h.trace)


def test_hier_seeded_determinism():
    topo = TopologyConfig(kind="hier", n_cells=2)
    h1, h2 = _run(topology=topo), _run(topology=topo)
    assert h1.trace == h2.trace
    assert [r.energy_j for r in h1.rounds] == \
        [r.energy_j for r in h2.rounds]
    assert h1.best_acc == h2.best_acc


def test_hier_cell_deadline_binds_at_the_edge():
    """A tight per-cell deadline caps every cell barrier (plus zero-cost
    shipping, the whole round) and drops the stragglers."""
    topo = TopologyConfig(kind="hier", n_cells=2, cell_deadline_s=0.5,
                          backhaul=BackhaulConfig.zero_cost())
    h = _run(topology=topo, n=6)
    assert all(r.latency_s <= 0.5 + 1e-9 for r in h.rounds)
    assert sum(r.n_dropped for r in h.rounds) > 0


def test_hier_rejects_stream_policies():
    with pytest.raises(ValueError):
        _run(topology=TopologyConfig(kind="hier", n_cells=2),
             policy="fedbuff", max_wallclock_s=5.0)


# ------------------------------------------------- mobility flat-equivalence

def test_static_mobility_one_cell_bitwise_identical_to_hier():
    """Acceptance guard: ``--mobility static`` attaches nothing — a
    1-cell hierarchy with the static mobility config is *bitwise*
    identical to the same hierarchy with no mobility field at all."""
    from repro.mobility import MobilityConfig
    topo = TopologyConfig(kind="hier", n_cells=1,
                          backhaul=BackhaulConfig.zero_cost())
    cfg = FLRunConfig(method="anycostfl", **TINY)
    base = run_orchestrated(
        cfg, FleetConfig(n_devices=4, topology=topo),
        OrchestratorConfig(policy="sync", use_pool=False))
    static = run_orchestrated(
        cfg, FleetConfig(n_devices=4, topology=topo,
                         mobility=MobilityConfig(kind="static")),
        OrchestratorConfig(policy="sync", use_pool=False))
    assert base.trace == static.trace
    for a, b in zip(base.rounds, static.rounds):
        assert (a.latency_s, a.energy_j, a.comm_bits, a.mean_alpha,
                a.mean_beta, a.test_acc, a.test_loss) == \
            (b.latency_s, b.energy_j, b.comm_bits, b.mean_alpha,
             b.mean_beta, b.test_acc, b.test_loss)
    assert base.best_acc == static.best_acc


# -------------------------------------------------- backhaul error feedback

def test_codec_error_feedback_stream_tracks_f32():
    """Satellite acceptance: with the per-cell EF residual, the lossy
    shipped stream telescopes — after T rounds the cumulative decoded
    planes equal the cumulative f32 planes up to ONE quantization step
    (the final residual), instead of T accumulated rounding errors."""
    from repro.topology import CodecErrorFeedback

    key = jax.random.PRNGKey(0)
    ef = CodecErrorFeedback()
    cum_f32 = cum_ef = cum_raw = 0.0
    worst_step = 0.0
    for t in range(12):
        key, k = jax.random.split(key)
        part = _partial(k, n=2048, count=2)
        cum_f32 = cum_f32 + np.asarray(part.num["w"], np.float64)
        enc_ef = ef.encode_ship(0, part, "int8")
        cum_ef = cum_ef + np.asarray(
            decode_partial(enc_ef).num["w"], np.float64)
        cum_raw = cum_raw + np.asarray(
            decode_partial(encode_partial(part, "int8")).num["w"],
            np.float64)
        worst_step = max(worst_step,
                         float(np.abs(np.asarray(part.num["w"])).max())
                         / 127.0)
    err_ef = np.abs(cum_ef - cum_f32).max()
    err_raw = np.abs(cum_raw - cum_f32).max()
    # EF: bounded by a single step (+ float slack); raw drifts well past
    assert err_ef <= 2.0 * worst_step + 1e-4, (err_ef, worst_step)
    assert err_ef < 0.5 * err_raw, (err_ef, err_raw)


def test_codec_error_feedback_frame_change_drops_residual():
    """A residual stored under one EMS sort frame must never be added
    into a differently-permuted frame — it is dropped instead (the
    encode then equals the raw codec's)."""
    from repro.topology import CodecErrorFeedback
    part = _partial(jax.random.PRNGKey(4))
    ef = CodecErrorFeedback()
    ef.encode_ship(0, part, "int8", frame=("a",))
    enc_moved = ef.encode_ship(0, part, "int8", frame=("b",))
    raw = encode_partial(part, "int8")
    np.testing.assert_array_equal(np.asarray(enc_moved.num["w"]),
                                  np.asarray(raw.num["w"]))
    # same frame: the residual IS applied (differs from raw)
    enc_same = ef.encode_ship(0, part, "int8", frame=("b",))
    assert not np.array_equal(np.asarray(enc_same.num["w"]),
                              np.asarray(raw.num["w"]))


def test_codec_error_feedback_f32_is_free():
    """The exact f32 passthrough keeps no residual (flat-equivalence is
    preserved when EF is enabled with the default codec)."""
    from repro.topology import CodecErrorFeedback
    ef = CodecErrorFeedback()
    part = _partial(jax.random.PRNGKey(1))
    enc = ef.encode_ship(0, part, "f32")
    assert enc.num["w"] is part.num["w"]       # zero-copy passthrough
    assert ef._res == {}


def test_hier_backhaul_ef_runs_and_keeps_costs():
    bh = BackhaulConfig(rate_bps=1e9, latency_s=0.01, codec="int8",
                        error_feedback=True)
    h = _run(topology=TopologyConfig(kind="hier", n_cells=2,
                                     backhaul=bh), n=4)
    h_raw = _run(topology=TopologyConfig(kind="hier", n_cells=2,
                                         backhaul=dataclasses.replace(
                                             bh, error_feedback=False)),
                 n=4)
    # EF changes wire numerics, never the bit accounting
    assert h.rounds[0].backhaul_bits == h_raw.rounds[0].backhaul_bits
    assert h.best_acc == pytest.approx(h_raw.best_acc, abs=0.15)


# ------------------------------------------------------ aggregation routes

def test_agg_route_validation():
    with pytest.raises(ValueError):
        OrchestratorConfig(agg_route="edge")


def test_agg_route_batched_matches_streaming():
    topo = TopologyConfig(kind="hier", n_cells=2)
    hs = _run(topology=topo, n=4)
    hb = _run(topology=topo, n=4, agg_route="batched")
    # same wire accounting, same learning trajectory to float tolerance
    for a, b in zip(hs.rounds, hb.rounds):
        assert a.backhaul_bits == b.backhaul_bits
        assert a.test_loss == pytest.approx(b.test_loss, rel=1e-5)
        assert a.n_cells_reporting == b.n_cells_reporting


def test_agg_route_mesh_refuses_one_device():
    """With one visible device the mesh route has no axis to shard cells
    over: the run is refused, never quietly routed through the streaming
    fold."""
    if len(jax.devices()) >= 2:
        pytest.skip("multi-device host: the mesh route is available")
    topo = TopologyConfig(kind="hier", n_cells=2)
    with pytest.raises(ValueError, match="needs >= 2 devices"):
        _run(topology=topo, n=4, agg_route="mesh")
