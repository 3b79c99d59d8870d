"""Arrival/aggregation policies: ``sync``, ``semisync``, ``fedbuff``.

One interface, three server behaviours:

* :class:`SyncPolicy` — the paper's lock-step round: the server barriers on
  every dispatched client, the round lasts ``max_i (T_cmp_i + T_com_i)``.
  Bit-equivalent to the pre-orchestrator ``train/fl_loop.py`` loop.
* :class:`SemiSyncPolicy` — the server aggregates at a hard deadline
  (default: the fleet's shared ``T_max``); clients that finish late are
  either dropped or down-weighted.  With a non-binding deadline this is
  exactly ``sync``.
* :class:`FedBuffPolicy` — fully asynchronous buffered aggregation
  (FedBuff-style): updates stream in, the server merges every ``K`` arrivals
  with the element-wise AIO rule, scaling each update's Theorem-1
  coefficient by a staleness discount ``(1 + s)^-gamma``.

All three use the same per-update aggregation coefficients as the
synchronous loop (Theorem-1 optimal for AnycostFL, FedHQ / FedAvg for the
baselines) — round-based merges via the normalized :func:`base_weights`,
fedbuff's streaming accumulator via :func:`unnormalized_weight` times the
staleness discount (Eq. 5's ratio cancels the normalization; a guard test
asserts the two stay in lock-step).  A policy only decides *which* updates
enter the merge, *at what simulated time*, and with *what scale factors*.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import aggregation
from repro.telemetry import profiler
from repro.train.baselines import fedhq_weights

POLICIES = ("sync", "semisync", "fedbuff")

# straggler handling for semisync
DROP = "drop"
DOWNWEIGHT = "downweight"

# staleness-cap handling for fedbuff
STALE_DROP = "drop"          # discard the update; the client's automatic
                             # re-dispatch trains fresh data on the new model
STALE_REQUEUE = "requeue"    # retrain the *same* minibatch draw against the
                             # current model version before dispatching fresh

# aggregation route for hierarchical round merges
AGG_ROUTES = ("streaming", "batched", "mesh")


@dataclasses.dataclass
class OrchestratorConfig:
    """Knobs of the discrete-event server (see module docstring)."""
    policy: str = "sync"
    # --- semisync
    deadline_s: Optional[float] = None     # None -> fleet T_max
    straggler_mode: str = DROP             # drop | downweight
    straggler_weight: float = 0.25         # scale in downweight mode
    # --- fedbuff
    buffer_size: int = 8                   # K updates per server merge
    staleness_exponent: float = 0.5        # w_i *= (1 + s_i)^-gamma
    staleness_cap: Optional[int] = None    # admission: reject staler updates
    staleness_mode: str = STALE_DROP       # drop | requeue
    retry_interval_s: Optional[float] = None   # infeasible-draw backoff
    max_inflight: Optional[int] = None     # cap concurrent dispatched
                                           # clients (fedbuff throttle)
    # --- hierarchical aggregation route
    # streaming: host-side per-cell edge fold -> cloud monoid merge (the
    #            default; O(N) memory, codec-aware wire numerics);
    # batched:   the flat (I, N) Eq.-5 oracle over all accepted updates
    #            (backhaul costs still modeled per cell);
    # mesh:      core/distributed.mesh_cell_aggregate — cells mapped onto
    #            a "cell" mesh axis (falls back to streaming with a
    #            warning when only one device is visible)
    agg_route: str = "streaming"
    # --- stopping / execution
    max_wallclock_s: Optional[float] = None    # simulated seconds
    use_pool: Optional[bool] = None        # None -> policy default
    # --- telemetry / event-trace retention
    # None (default) retains the full pop trace — the pre-telemetry
    # behaviour; N bounds the in-memory trace to the newest N records on
    # long (million-event) runs, with evicted records folded into a
    # rolling hash so History.trace stays a usable replay signature
    event_trace_limit: Optional[int] = None

    def __post_init__(self):
        if self.policy not in POLICIES:
            raise ValueError(f"unknown policy {self.policy!r}; "
                             f"expected one of {POLICIES}")
        if self.straggler_mode not in (DROP, DOWNWEIGHT):
            raise ValueError(
                f"unknown straggler_mode {self.straggler_mode!r}; "
                f"expected {DROP!r} or {DOWNWEIGHT!r}")
        if self.staleness_mode not in (STALE_DROP, STALE_REQUEUE):
            raise ValueError(
                f"unknown staleness_mode {self.staleness_mode!r}; "
                f"expected {STALE_DROP!r} or {STALE_REQUEUE!r}")
        if self.staleness_cap is not None and self.staleness_cap < 0:
            raise ValueError("staleness_cap must be >= 0")
        if self.max_inflight is not None and self.max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        if self.event_trace_limit is not None \
                and self.event_trace_limit < 1:
            raise ValueError("event_trace_limit must be >= 1 (or None "
                             "for unbounded retention)")
        if self.agg_route not in AGG_ROUTES:
            raise ValueError(f"unknown agg_route {self.agg_route!r}; "
                             f"expected one of {AGG_ROUTES}")


def base_weights(method: str, use_aio: bool, updates: Sequence,
                 fedhq_L: Sequence[int]) -> jax.Array:
    """The synchronous loop's aggregation coefficients, factored out."""
    if method == "anycostfl" and use_aio:
        return aggregation.optimal_coefficients(
            [u.alpha for u in updates],
            [max(u.beta_target, 1e-6) for u in updates])
    if method == "fedhq":
        return fedhq_weights(list(fedhq_L))
    return aggregation.fedavg_coefficients([u.n_samples for u in updates])


def unnormalized_weight(method: str, use_aio: bool, update,
                        fedhq_level: Optional[int] = None) -> float:
    """One update's aggregation coefficient WITHOUT the cohort sum.

    The streaming-AIO monoid needs this: Eq. 5's num/den ratio cancels any
    common normalization, so an edge aggregator (or the fedbuff
    accumulator) can absorb an arrival the moment it lands without knowing
    who else participates.  Normalizing these per-cohort reproduces
    exactly :func:`base_weights` — the ratio of either is the same
    aggregate up to float rounding.
    """
    if method == "anycostfl" and use_aio:
        d = float(profiler.read(aggregation.divergence_factor(
            update.alpha, max(update.beta_target, 1e-6))))
        return 1.0 / max(d * d, 1e-12)
    if method == "fedhq":
        L = int(fedhq_level)
        return 1.0 / (1.0 + 1.0 / (4.0 * L * L))
    return float(update.n_samples)


def apply_scales(weights: jax.Array, scales: Sequence[float]) -> jax.Array:
    """Rescale + renormalize — identity (bitwise) when every scale is 1."""
    if all(s == 1.0 for s in scales):
        return weights
    w = weights * jnp.asarray(scales, jnp.float32)
    return w / jnp.sum(w)


def staleness_scales(staleness: Sequence[int], gamma: float) -> list[float]:
    """FedBuff-style discount ``(1 + s)^-gamma`` per buffered update."""
    return [float((1.0 + float(s)) ** (-gamma)) for s in staleness]


def staleness_scaled_weights(base: jax.Array, staleness: Sequence[int],
                             gamma: float) -> jax.Array:
    """Staleness-discounted AIO coefficients, renormalized to sum to 1.

    A fully-stale update keeps a strictly positive (AIO coverage) but
    strictly discounted share: with equal base weights its coefficient is
    below every fresher update's, so it cannot dominate the merge.
    """
    return apply_scales(base, staleness_scales(staleness, gamma))


class SyncPolicy:
    """Barrier on all dispatched clients (the paper's synchronous round)."""

    name = "sync"
    round_based = True
    pool_default = False      # one client at a time, as the old loop

    def __init__(self, cfg: OrchestratorConfig):
        self.cfg = cfg

    def accept(self, completions, round_start: float):
        """All updates accepted; the round lasts until the last arrival.

        Works on per-client *durations* (relative to the round start) so a
        late round's latency is the same float as round 0's would be —
        keeping multi-round runs bitwise identical to the old loop.
        """
        lat = max((c.duration for c in completions), default=0.0)
        return list(completions), [1.0] * len(completions), lat


class SemiSyncPolicy:
    """Hard deadline cutoff; stragglers dropped or down-weighted.

    ``downweight`` is a modeling simplification, not a causal timeline: a
    late update is merged *at the deadline* with a discounted weight, as a
    proxy for the server folding it in when it eventually lands. Time-to-
    accuracy under ``downweight`` is therefore optimistic by up to one
    straggler flight; use ``drop`` when strict causality matters.
    """

    name = "semisync"
    round_based = True
    pool_default = True

    def __init__(self, cfg: OrchestratorConfig, *, fleet_T_max: float):
        self.cfg = cfg
        self.deadline = cfg.deadline_s if cfg.deadline_s is not None \
            else fleet_T_max

    def accept(self, completions, round_start: float):
        on_time = [c for c in completions if c.duration <= self.deadline]
        late = [c for c in completions if c.duration > self.deadline]
        if not late:
            # non-binding deadline: exactly the sync barrier
            lat = max((c.duration for c in completions), default=0.0)
            return list(completions), [1.0] * len(completions), lat
        if self.cfg.straggler_mode == DROP:
            return on_time, [1.0] * len(on_time), self.deadline
        accepted = on_time + late
        scales = [1.0] * len(on_time) + \
            [self.cfg.straggler_weight] * len(late)
        return accepted, scales, self.deadline


class FedBuffPolicy:
    """Buffered fully-async aggregation with staleness-discounted weights."""

    name = "fedbuff"
    round_based = False
    pool_default = True

    def __init__(self, cfg: OrchestratorConfig):
        self.cfg = cfg

    def should_aggregate(self, buffer) -> bool:
        return len(buffer) >= self.cfg.buffer_size

    def admit(self, staleness: int) -> bool:
        """Staleness-cap admission control: an arriving update whose model
        version lags the server by more than the cap never enters the
        buffer (ROADMAP item; guards against divergence under deep
        asynchrony).  The runner then either lets the client's automatic
        re-dispatch replace the work (``drop``) or retrains the rejected
        round's exact minibatches against the current version
        (``requeue``)."""
        return self.cfg.staleness_cap is None \
            or staleness <= self.cfg.staleness_cap


def make_policy(cfg: OrchestratorConfig, *, fleet_T_max: float):
    if cfg.policy == "sync":
        return SyncPolicy(cfg)
    if cfg.policy == "semisync":
        return SemiSyncPolicy(cfg, fleet_T_max=fleet_T_max)
    return FedBuffPolicy(cfg)
