"""Discrete-event orchestrator: determinism, policy equivalences, staleness
weighting, and the vmapped client pool."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.orchestrator import OrchestratorConfig, run_orchestrated
from repro.orchestrator.events import EventQueue
from repro.orchestrator.policies import (SemiSyncPolicy,
                                         staleness_scaled_weights)
from repro.sysmodel.population import FleetConfig
from repro.train.fl_loop import FLRunConfig, run_fl

TINY = dict(rounds=2, n_train=128, n_test=64, eval_every=1, lr=0.1,
            batch_size=32, seed=3, use_planner=False)


def _fleet(n=3):
    return FleetConfig(n_devices=n)


# ------------------------------------------------------------- event engine

def test_event_queue_orders_by_time_then_seq():
    q = EventQueue()
    q.push(2.0, "complete", client=1)
    q.push(1.0, "complete", client=2)
    q.push(1.0, "retry", client=3)     # same time: insertion order wins
    kinds = [(q.pop().client, ) for _ in range(3)]
    assert kinds == [(2,), (3,), (1,)]
    assert [c for _, _, _, c in q.trace] == [2, 3, 1]


def test_event_queue_trace_signature_deterministic():
    def build():
        q = EventQueue()
        for i, t in enumerate([3.5, 0.25, 0.25, 7.0]):
            q.push(t, "complete", client=i)
        while len(q):
            q.pop()
        return q.trace_signature()

    assert build() == build()


# --------------------------------------------------------- staleness weights

def test_staleness_weights_sum_to_one():
    w = staleness_scaled_weights(jnp.asarray([0.2, 0.3, 0.5]),
                                 [0, 3, 7], gamma=0.5)
    assert abs(float(jnp.sum(w)) - 1.0) < 1e-6
    assert bool(jnp.all(w > 0))


def test_fully_stale_update_cannot_dominate():
    # equal base coefficients, one update maximally stale
    base = jnp.full((4,), 0.25)
    w = staleness_scaled_weights(base, [0, 0, 0, 50], gamma=0.5)
    w = np.asarray(w)
    assert abs(w.sum() - 1.0) < 1e-6
    # the stale update's share is below every fresh update's and below the
    # uniform share — it can contribute but never dominate the merge
    assert w[3] < w[:3].min()
    assert w[3] < 1.0 / 4.0


def test_zero_staleness_keeps_base_weights_bitwise():
    base = jnp.asarray([0.125, 0.375, 0.5])
    w = staleness_scaled_weights(base, [0, 0, 0], gamma=0.5)
    assert bool(jnp.all(w == base))    # scales of 1.0 short-circuit


def test_unnormalized_weights_stay_in_lockstep_with_base_weights():
    """Guard: the streaming path's per-update coefficients, normalized
    over the cohort, must match base_weights for every method — a change
    to one formula (Theorem-1 floor, FedHQ noise term, FedAvg counts)
    that misses the other breaks hier/fedbuff vs flat silently."""
    from repro.orchestrator.policies import base_weights, \
        unnormalized_weight

    class U:
        def __init__(self, alpha, beta, n):
            self.alpha, self.beta_target, self.n_samples = alpha, beta, n

    ups = [U(0.25, 1e-3, 96), U(0.7, 0.02, 128), U(1.0, 1.0 / 15, 64)]
    fedhq_L = [2, 16, 256]
    for method, use_aio in (("anycostfl", True), ("anycostfl", False),
                            ("fedhq", False), ("fedavg", False)):
        base = np.asarray(base_weights(method, use_aio, ups, fedhq_L))
        raw = np.array([unnormalized_weight(method, use_aio, u, L)
                        for u, L in zip(ups, fedhq_L)])
        np.testing.assert_allclose(raw / raw.sum(), base, rtol=1e-6)


def test_semisync_deadline_partition():
    class P:
        def __init__(self, d):
            self.duration = d

    pol = SemiSyncPolicy(OrchestratorConfig(policy="semisync",
                                            deadline_s=5.0,
                                            straggler_mode="drop"),
                         fleet_T_max=10.0)
    accepted, scales, lat = pol.accept([P(3.0), P(6.0), P(4.0)], 0.0)
    assert [p.duration for p in accepted] == [3.0, 4.0]
    assert lat == 5.0

    pol2 = SemiSyncPolicy(OrchestratorConfig(policy="semisync",
                                             deadline_s=5.0,
                                             straggler_mode="downweight",
                                             straggler_weight=0.1),
                          fleet_T_max=10.0)
    accepted, scales, lat = pol2.accept([P(3.0), P(6.0)], 0.0)
    assert len(accepted) == 2 and scales == [1.0, 0.1]


# ------------------------------------------------------- policy equivalences

def test_semisync_nonbinding_deadline_equals_sync_exactly():
    h_sync = run_fl(FLRunConfig(method="anycostfl", **TINY), _fleet())
    h_semi = run_orchestrated(
        FLRunConfig(method="anycostfl", **TINY), _fleet(),
        OrchestratorConfig(policy="semisync", deadline_s=1e9,
                           use_pool=False))
    assert h_sync.best_acc == h_semi.best_acc
    for a, b in zip(h_sync.rounds, h_semi.rounds):
        assert (a.latency_s, a.energy_j, a.comm_bits, a.test_acc,
                a.test_loss) == \
               (b.latency_s, b.energy_j, b.comm_bits, b.test_acc,
                b.test_loss)


def test_pool_matches_sequential_clients():
    cfg = FLRunConfig(method="anycostfl", **TINY)
    h_seq = run_orchestrated(cfg, _fleet(),
                             OrchestratorConfig(policy="sync",
                                                use_pool=False))
    h_pool = run_orchestrated(cfg, _fleet(),
                              OrchestratorConfig(policy="sync",
                                                 use_pool=True))
    for a, b in zip(h_seq.rounds, h_pool.rounds):
        assert a.energy_j == pytest.approx(b.energy_j, rel=1e-4)
        assert a.comm_bits == pytest.approx(b.comm_bits, rel=1e-4)
        if a.test_loss is not None:
            assert a.test_loss == pytest.approx(b.test_loss, rel=1e-4)


def test_sync_matches_pre_refactor_golden():
    """Pins the sync policy's trajectory on its default (unpooled) route,
    whose client finish is the width bucket's compiled program: any change
    to the round's numerics shows here (regenerate with
    ``scripts/regen_golden.py`` only when it is meant)."""
    path = os.path.join(os.path.dirname(__file__), "goldens",
                        "fl_sync_golden.json")
    g = json.load(open(path))
    c = g["config"]
    for method, want in g["results"].items():
        hist = run_fl(
            FLRunConfig(method=method, rounds=c["rounds"],
                        n_train=c["n_train"], n_test=c["n_test"],
                        eval_every=c["eval_every"], lr=c["lr"],
                        batch_size=c["batch_size"], seed=c["seed"],
                        use_planner=c["use_planner"]),
            FleetConfig(n_devices=c["n_devices"]))
        assert hist.best_acc == want["best_acc"]
        for r, wr in zip(hist.rounds, want["rounds"]):
            for k, v in wr.items():
                assert getattr(r, k) == v, (method, r.round, k)


# ----------------------------------------------------------------- fedbuff

def _fedbuff(seed=3, **kw):
    cfg = FLRunConfig(method="anycostfl", **{**TINY, "seed": seed})
    orch = OrchestratorConfig(policy="fedbuff", buffer_size=2,
                              **{"max_wallclock_s": 30.0, **kw})
    return run_orchestrated(cfg, _fleet(), orch)


def test_fedbuff_same_seed_identical_event_trace():
    h1, h2 = _fedbuff(), _fedbuff()
    assert h1.trace is not None and len(h1.trace) > 0
    assert h1.trace == h2.trace
    assert [r.energy_j for r in h1.rounds] == \
        [r.energy_j for r in h2.rounds]


def test_fedbuff_different_seed_different_trace():
    assert _fedbuff(seed=3).trace != _fedbuff(seed=4).trace


def test_fedbuff_advances_wallclock_and_tracks_staleness():
    h = _fedbuff()
    assert len(h.rounds) >= 2
    walls = [r.t_wall for r in h.rounds]
    assert all(b >= a for a, b in zip(walls, walls[1:]))
    assert h.wallclock() <= 30.0
    assert all(np.isfinite(r.energy_j) and r.energy_j > 0
               for r in h.rounds)
    assert all(r.mean_staleness >= 0.0 for r in h.rounds)
    # at least one merge should see a non-fresh update under a tiny buffer
    assert any(r.mean_staleness > 0 for r in h.rounds)
    assert all(r.test_acc is not None for r in h.rounds)  # eval_every=1


def test_fedbuff_staleness_cap_bounds_aggregated_staleness():
    """Admission control: a capped run never aggregates an update staler
    than the cap, and the cap actually binds (an uncapped run sees
    staler updates and the capped run reports rejected arrivals)."""
    h_free = _fedbuff(max_wallclock_s=60.0)
    assert max(r.max_staleness for r in h_free.rounds) > 1
    for cap in (0, 1):
        h = _fedbuff(max_wallclock_s=60.0, staleness_cap=cap)
        assert all(r.max_staleness <= cap for r in h.rounds)
        assert sum(r.n_stale_dropped for r in h.rounds) > 0
        assert all(r.mean_staleness <= cap for r in h.rounds)


def test_fedbuff_staleness_requeue_mode_runs_and_bounds():
    h = _fedbuff(max_wallclock_s=60.0, staleness_cap=1,
                 staleness_mode="requeue")
    assert len(h.rounds) >= 2
    assert all(r.max_staleness <= 1 for r in h.rounds)
    assert all(np.isfinite(r.energy_j) for r in h.rounds)


def test_staleness_config_validation():
    with pytest.raises(ValueError):
        OrchestratorConfig(policy="fedbuff", staleness_cap=-1)
    with pytest.raises(ValueError):
        OrchestratorConfig(policy="fedbuff", staleness_mode="defer")
    with pytest.raises(ValueError):
        OrchestratorConfig(policy="fedbuff", max_inflight=0)


def test_fedbuff_max_inflight_throttles_concurrency():
    """--max-inflight caps concurrent dispatched flights: an uncapped
    3-device run has all 3 in flight at t=0; a cap of 2 is never
    exceeded, waiters drain FIFO, and the run still makes progress."""
    h_free = _fedbuff()
    assert h_free.peak_inflight == 3
    h_cap = _fedbuff(max_inflight=2)
    assert 1 <= h_cap.peak_inflight <= 2
    assert len(h_cap.rounds) >= 1
    assert all(np.isfinite(r.energy_j) and r.energy_j > 0
               for r in h_cap.rounds)
    # seeded determinism under the throttle
    assert h_cap.trace == _fedbuff(max_inflight=2).trace


@pytest.mark.slow
def test_fedbuff_unpooled_matches_pooled_closely():
    h_pool = _fedbuff()
    h_seq = _fedbuff(use_pool=False)
    assert h_pool.trace == h_seq.trace   # timeline is training-independent
    for a, b in zip(h_pool.rounds, h_seq.rounds):
        assert a.test_loss == pytest.approx(b.test_loss, rel=1e-3)
