"""Unified FL driver over the discrete-event engine.

``run_orchestrated(run_cfg, fleet_cfg, orch_cfg)`` executes any method
(anycostfl / baselines) under any arrival policy (sync / semisync /
fedbuff).  ``train/fl_loop.run_fl`` delegates here with the sync policy,
which keeps the pre-orchestrator loop's order: the per-device sequence of
numpy-RNG draws, JAX key splits, and cost-accumulation float ops is kept
identical (see ``Simulation.prepare`` / ``materialize``).

Timeline semantics:

* **sync / semisync** (round-based): every device is dispatched at the
  round start; per-device completion offsets are ``T_cmp + T_com`` from the
  realized strategy (Eq. 6-9, identical formulas to the old loop); the
  policy decides the round barrier and which arrivals aggregate.
* **fedbuff** (stream-based): devices run free; each completion enqueues
  the update into the server buffer with staleness = (server version now) -
  (version at dispatch) and the device immediately re-dispatches on a fresh
  channel draw.  Every ``K`` arrivals the server applies the AIO merge with
  staleness-discounted Theorem-1 weights.  Local training is *deferred* to
  aggregation time so buffered clients train as one vmapped batch; the
  event timestamps use the device's planned wire size (its uplink
  reservation) while energy/comm accounting uses realized bits, exactly as
  in the synchronous loop.  EMS channel sorting is frozen at t=0 in this
  mode: cross-version element-wise merges require a fixed coordinate frame.
  The merge itself streams: each materialized update is folded into one
  ``(num, den)`` accumulator (the AIO monoid) and its decoded pytrees are
  dropped on the spot — the server never stacks the buffer into an
  ``(I, N)`` array, and ``--max-inflight`` can additionally cap how many
  clients hold a dispatched flight at once (waiters join a FIFO).

**Hierarchical topologies** (``FleetConfig.topology``, round-based
policies only): devices are partitioned into cells, each with its own
wireless environment and per-cell availability/selection; an edge
aggregator per cell streams its local arrivals into an O(N) partial
(``topology/edge.py``), applies the arrival policy *per cell* (the
semisync deadline — or ``TopologyConfig.cell_deadline_s`` — binds at the
edge), and ships the constant-size partial over the modeled backhaul.
The cloud merges cell partials (EDGE_MERGE events) and finalizes Eq. 5
once.  Weights are the per-update *unnormalized* coefficients
(``policies.unnormalized_weight``) — Eq. 5's ratio cancels the cohort
normalization, which is what makes the fold order-free.

**Mobility** (``FleetConfig.mobility``): with a motion model attached,
positions evolve along true trajectories and Eq. 8 sees the distance to
the serving cell site; at each round boundary the handover engine
re-homes devices to cells (HANDOVER events, ``--handover-policy``), and
every flight carries the cell that dispatched it so edge merges never
mis-home an in-flight update.  Per-cell backhauls can be heterogeneous
(seeded draw) and time-varying (scenario trace), and
``OrchestratorConfig.agg_route`` picks the numeric aggregation route
(streaming edge fold / batched oracle / mesh-mapped cells).
"""
from __future__ import annotations

import dataclasses
import math
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import aggregation, compression, schedule, shrinking
from repro.core.anycost import (AnycostClient, AnycostServer, ClientUpdate,
                                bucket_alpha)
from repro.data.partition import partition_dirichlet, partition_iid
from repro.data.synthetic import make_image_task
from repro.fleet import AlwaysOn, FleetDynamicsConfig, make_selection
from repro.mobility import HandoverEngine, ScenarioTrace
from repro.models import cnn as cnn_mod
from repro.models.registry import build_model
from repro.orchestrator import events as ev_mod
from repro.orchestrator.client_pool import ClientPool, TrainJob
from repro.orchestrator.policies import (STALE_REQUEUE, OrchestratorConfig,
                                         apply_scales, base_weights,
                                         make_policy, staleness_scales,
                                         unnormalized_weight)
from repro.sysmodel.population import FleetConfig, make_fleet
from repro.telemetry import NULL_TELEMETRY, MetricsRegistry, profiler
from repro.topology.codec import decode_partial, encode_partial
from repro.topology.edge import (CodecErrorFeedback, EdgeAggregator,
                                 cloud_merge, finalize_apply)
from repro.train.baselines import BaselinePolicy
from repro.train.fl_loop import (FLRunConfig, History, RoundLog,
                                 _device_batches, _make_eval,
                                 flops_per_sample)
from repro.utils.pytree import tree_size, tree_sub

PyTree = Any


@dataclasses.dataclass
class PendingUpdate:
    """A dispatched client round travelling through the event queue."""
    client_id: int
    env: schedule.DeviceEnv
    strat: schedule.Strategy
    alpha: float                 # bucketed width actually trained
    batches: PyTree
    key: jax.Array               # the round's compression key (k2)
    n_steps: int
    version: int = 0             # server version at dispatch (fedbuff)
    cell: int = 0                # serving cell at dispatch: an in-flight
                                 # update always merges at the edge that
                                 # dispatched it, whatever handover does
    dispatched_at: float = 0.0
    completes_at: float = 0.0
    staleness: int = 0
    # filled by Simulation.materialize
    update: Optional[ClientUpdate] = None
    fedhq_level: Optional[int] = None
    t_cmp: float = 0.0
    t_com: float = 0.0
    energy: float = 0.0
    # per-phase split of ``energy`` for cost attribution: compute (train)
    # vs radio (uplink).  e_cmp + e_com == energy on every path, including
    # the pro-rated churn charge.
    e_cmp: float = 0.0
    e_com: float = 0.0

    @property
    def duration(self) -> float:
        return self.t_cmp + self.t_com


class Simulation:
    """Shared state + the per-device round body of the old fl_loop."""

    def __init__(self, run_cfg: FLRunConfig,
                 fleet_cfg: Optional[FleetConfig] = None,
                 telemetry=None):
        # setup order mirrors the pre-orchestrator run_fl exactly — the rng
        # stream position after setup must match for bit-equivalence.
        self.run_cfg = run_cfg
        # telemetry: the registry is ALWAYS live (it is RoundLog's backing
        # store — pure-Python dicts, no RNG/JAX contact, bitwise-invisible
        # by construction); the trace sink + per-device emission only run
        # behind ``if self.tel.enabled`` guards.
        self.tel = telemetry if telemetry is not None \
            and telemetry.enabled else NULL_TELEMETRY
        self.registry = self.tel.registry if self.tel.enabled \
            else MetricsRegistry()
        rng = self.rng = np.random.default_rng(run_cfg.seed)
        arch_cfg = self.arch_cfg = get_config(run_cfg.arch)
        self.model = build_model(arch_cfg)
        self.spec = shrinking.cnn_shrink_spec(arch_cfg)

        shape = cnn_mod.image_shape(arch_cfg)
        self.train, self.test = make_image_task(
            rng, run_cfg.n_train, run_cfg.n_test, shape=shape)
        self.test_x = jnp.asarray(self.test.x)
        self.test_y = jnp.asarray(self.test.y)

        fleet_cfg = self.fleet_cfg = fleet_cfg or FleetConfig()
        # fleet-size report engages the registry's rollup policy (if one
        # was configured via --telemetry-rollup) past its threshold;
        # pure bookkeeping, records nothing, so no guard is needed
        self.registry.set_fleet_size(fleet_cfg.n_devices)
        if run_cfg.iid:
            self.parts = partition_iid(rng, run_cfg.n_train,
                                       fleet_cfg.n_devices)
        else:
            self.parts = partition_dirichlet(rng, self.train.y,
                                             fleet_cfg.n_devices,
                                             run_cfg.dirichlet_alpha)
        self.fleet = make_fleet(
            rng, fleet_cfg, np.array([len(p) for p in self.parts]))

        self.W = flops_per_sample(arch_cfg)
        self.params = self.model.init(jax.random.PRNGKey(run_cfg.seed))
        self._n_params = tree_size(self.params)
        self.S_bits = 32.0 * self._n_params

        self.client = AnycostClient(self.model, self.spec, lr=run_cfg.lr,
                                    batch_size=run_cfg.batch_size,
                                    alpha_buckets=run_cfg.alpha_buckets)
        self.server = AnycostServer(self.model, self.spec)
        self.baseline = None
        if run_cfg.method not in ("anycostfl",):
            self.baseline = BaselinePolicy(run_cfg.method)
        self.tiers = np.argsort(np.argsort(-self.fleet.eps_hw)) * 3 \
            // fleet_cfg.n_devices
        self.planner = None
        self.ev = _make_eval(self.model, self.test_x, self.test_y)
        self.key = jax.random.PRNGKey(run_cfg.seed + 1)
        self.pool = ClientPool(self.client)
        self._agg_fast = None
        # the round (fedbuff: the merge) in progress, for the fl.* spans
        self.round_idx = 0

        # ---- fleet-dynamics control plane.  Selection randomness lives in
        # its own generator so who-trains-when ablations never perturb the
        # model-init / data / channel streams; --selection-seed decouples it
        # from the run seed entirely.
        dyn = self.dyn = fleet_cfg.dynamics or FleetDynamicsConfig()
        sel_seed = dyn.selection_seed if dyn.selection_seed is not None \
            else run_cfg.seed
        self.selection = make_selection(
            dyn.selection, np.random.default_rng([0x5E1EC7, sel_seed]))
        self.dispatch_log: list[tuple] = []
        self.fleet_dynamic = (
            (self.fleet.trace is not None
             and not isinstance(self.fleet.trace, AlwaysOn))
            or self.fleet.battery is not None)

        # ---- hierarchical topology (None -> the paper's flat single cell,
        # which keeps every code path below bit-identical to the pre-
        # topology loop)
        topo = fleet_cfg.topology
        self.topo = topo if topo is not None and topo.kind == "hier" \
            else None
        self.edge_kernel = jax.default_backend() == "tpu"

        # ---- mobility & handover.  A motion model makes the device->cell
        # binding dynamic: the handover engine re-homes devices at round
        # boundaries (HANDOVER events), per-cell backhauls may differ (and
        # vary over time under a scenario trace), and a lossy backhaul
        # codec can carry an EF residual per edge site across rounds.
        self.handover = None
        if self.topo is not None and self.fleet.mobility is not None \
                and self.topo.handover is not None \
                and self.fleet.n_cells > 1:
            self.handover = HandoverEngine(self.topo.handover,
                                           self.fleet.sites)
        self.cell_backhauls = self.topo.cell_backhauls() \
            if self.topo is not None else None
        self.codec_ef = None
        self._ef_frame = None
        if self.topo is not None and self.topo.backhaul.error_feedback:
            self.codec_ef = CodecErrorFeedback()
        # the scenario was already parsed by make_fleet (replay
        # mobility); reuse the Fleet's copy rather than re-reading it
        self.scenario = self.fleet.scenario
        # aggregation route for hierarchical merges (run_orchestrated
        # overrides from OrchestratorConfig.agg_route)
        self.agg_route = "streaming"

        # ---- learning-dynamics diagnostics.  Only an enabled session
        # gets a recorder, and the import is deferred to that branch so
        # the disabled path never loads the module (the CI memory guard
        # attributes zero allocations to telemetry files on the
        # streaming path).  The recorder's statistics run in their own
        # jit'd passes — the training path's compiled programs are the
        # same with or without it (bitwise-invisibility).
        self.learn = None
        if self.tel.enabled:
            from repro.telemetry.learning import LearningRecorder
            self.learn = LearningRecorder(self.spec,
                                          self.fleet_cfg.n_devices)

    # ------------------------------------------------------- fleet dynamics

    def effective_T_max(self, t_wall: float) -> float:
        """Battery-aware deadline adaptation: when the fleet's mean state
        of charge sinks below ``soc_deadline_threshold``, the round
        deadline handed to the Problem-(P4) solver shrinks by
        ``soc_deadline_scale`` — a drained fleet solves for shorter,
        cheaper rounds instead of spending its reserve on long ones.
        Identity (the fleet's ``T_max``) when unconfigured or batteryless.
        """
        scale = getattr(self.dyn, "soc_deadline_scale", None)
        if scale is None or self.fleet.battery is None:
            return self.fleet_cfg.T_max
        if self.fleet.battery.mean_soc_frac(t_wall) \
                < self.dyn.soc_deadline_threshold:
            return self.fleet_cfg.T_max * scale
        return self.fleet_cfg.T_max

    def gate_round(self, t_wall: float, envs: list[schedule.DeviceEnv]):
        """Availability/battery/selection gating for a round-based dispatch.

        Static-fleet identity: an always-on trace with no battery and
        uniform selection under a non-binding cap selects every device in
        order, consumes no randomness, and hands back the caller's env
        objects untouched — bit-identical to the ungated loop.
        """
        n = self.fleet_cfg.n_devices
        cand = [i for i in range(n) if self.fleet.available(i, t_wall)]
        envs_eff = {i: self.fleet.dynamic_env(i, envs[i], t_wall)
                    for i in cand}
        t_max_eff = self.effective_T_max(t_wall)
        if t_max_eff != self.fleet_cfg.T_max:
            envs_eff = {i: dataclasses.replace(e, T_max=t_max_eff)
                        for i, e in envs_eff.items()}
        headroom = {i: (self.fleet.battery.headroom(i, t_wall)
                        if self.fleet.battery is not None
                        else envs_eff[i].E_max) for i in cand}
        if not cand:
            return [], envs_eff, n, headroom
        if self.topo is not None and self.fleet.n_cells > 1:
            # per-cell selection: each edge runs the policy over its own
            # roster with its own participation cap (ascending cell order
            # keeps seeded runs replayable)
            selected = []
            for k in range(self.fleet.n_cells):
                ck = [i for i in cand if self.fleet.cell_of(i) == k]
                if not ck:
                    continue
                cap = len(ck) if self.dyn.participation >= 1.0 \
                    else max(1, math.ceil(self.dyn.participation * len(ck)))
                selected.extend(self.selection.select(ck, envs_eff,
                                                      headroom, cap))
            return sorted(selected), envs_eff, n - len(cand), headroom
        cap = len(cand) if self.dyn.participation >= 1.0 \
            else max(1, math.ceil(self.dyn.participation * len(cand)))
        selected = self.selection.select(cand, envs_eff, headroom, cap)
        return selected, envs_eff, n - len(cand), headroom

    # ------------------------------------------------------------ round body

    def sort_params(self, params: PyTree) -> PyTree:
        with profiler.span("fl.sort", round=self.round_idx):
            if not self.run_cfg.use_ems:
                return shrinking._deepcopy_dicts(params)
            if self.codec_ef is None:
                return self.server.sort(params)
            # EF residuals live in the sorted coordinate frame; capture
            # the round's sort permutations so a frame move invalidates
            # the stale residual instead of feeding it into the wrong
            # channels (see topology.edge.CodecErrorFeedback)
            sorted_p, perms = shrinking.sort_channels(
                params, self.spec, return_perms=True)
            self._ef_frame = tuple(
                tuple(p.tolist()) for p in profiler.read(perms))
            return sorted_p

    def ensure_planner(self, sorted_params: PyTree) -> None:
        """Fit the server-side beta planner on a probe update (§III-C.3)."""
        rc = self.run_cfg
        if self.planner is None and rc.method == "anycostfl" \
                and rc.use_planner:
            self.key, k1 = jax.random.split(self.key)
            probe_idx = self.rng.permutation(rc.n_train)[:16]
            probe_batches = {
                "images": profiler.put(self.train.x[probe_idx][None]),
                "labels": profiler.put(self.train.y[probe_idx][None])}
            trained = self.client._local_steps(1.0, 1)(sorted_params,
                                                       probe_batches)
            probe_update = tree_sub(sorted_params, trained)
            self.planner = compression.BetaPlanner.fit(probe_update, k1)

    def prepare(self, i: int, env: schedule.DeviceEnv
                ) -> Optional[PendingUpdate]:
        """Strategy + minibatch draw for device i (consumes rng/keys in the
        old loop's order). Returns None when no (alpha, beta, f) satisfies
        the budgets (the device sits this dispatch out)."""
        rc = self.run_cfg
        with profiler.span("fl.schedule", round=self.round_idx, client=i):
            if rc.method == "anycostfl":
                strat = schedule.solve(env)
                if not strat.feasible:
                    return None
                if not rc.use_ems:
                    strat = dataclasses.replace(strat, alpha=1.0)
                if not rc.use_fgc:
                    strat = dataclasses.replace(strat, beta=1.0)
                alpha = bucket_alpha(strat.alpha, rc.alpha_buckets)
            else:
                strat = self.baseline.strategy(env, tier=int(self.tiers[i]))
                alpha = bucket_alpha(strat.alpha, rc.alpha_buckets) \
                    if rc.method == "heterofl" else 1.0
        self.key, k1, k2 = jax.random.split(self.key, 3)
        with profiler.span("fl.batches", round=self.round_idx, client=i):
            batches = _device_batches(self.rng, self.train.x, self.train.y,
                                      self.parts[i], rc.batch_size, rc.tau)
        n_steps = int(jax.tree_util.tree_leaves(batches)[0].shape[0])
        return PendingUpdate(client_id=i, env=env, strat=strat, alpha=alpha,
                             batches=batches, key=k2, n_steps=n_steps,
                             cell=self.fleet.cell_of(i))

    def train_one(self, p: PendingUpdate, sorted_params: PyTree) -> PyTree:
        sub = shrinking.shrink(sorted_params, p.alpha, self.spec)
        return self.client._local_steps(p.alpha, p.n_steps)(sub, p.batches)

    def materialize(self, p: PendingUpdate, trained: PyTree,
                    sorted_params: PyTree, *,
                    sub: Optional[PyTree] = None) -> PendingUpdate:
        """Decode the trained sub-model into a ClientUpdate + realized costs
        (Eq. 6-9). AnycostFL's finish is the width bucket's compiled
        program (``AnycostClient.finish_round``); ``sub``, the sub-model
        the client started from, is sliced from ``sorted_params`` if not
        given."""
        with profiler.span("fl.finish", round=self.round_idx,
                           client=p.client_id):
            rc = self.run_cfg
            env, strat = p.env, p.strat
            if rc.method == "anycostfl":
                upd = self.client.finish_round(
                    sorted_params, p.alpha, trained, strat, p.n_steps,
                    p.key, planner=self.planner if rc.use_fgc else None,
                    w_per_sample=self.W, sub=sub)
                if not rc.use_fgc:
                    # transmit the raw (width-masked) update
                    upd = dataclasses.replace(
                        upd, bits=32.0 * strat.alpha * self._n_params,
                        beta_realized=1.0)
            else:
                sub = shrinking.shrink(sorted_params, p.alpha, self.spec)
                update_sub = tree_sub(sub, trained)
                full_update, wmask = shrinking.expand_update(
                    update_sub, sorted_params, p.alpha, self.spec)
                comp = self.baseline.compress(full_update, env, p.key)
                mask = jax.tree.map(lambda a, b: a * b, wmask, comp.mask)
                vals = jax.tree.map(lambda v, m: v * m, comp.values, mask)
                n_samp = p.n_steps * rc.batch_size
                bits = float(profiler.read(comp.bits))
                upd = ClientUpdate(
                    values=vals, mask=mask, alpha=p.alpha,
                    beta_target=strat.beta,
                    beta_realized=bits / self.S_bits,
                    bits=bits, n_samples=n_samp,
                    flops=p.alpha * self.W * n_samp)
                if rc.method == "fedhq":
                    p.fedhq_level = self.baseline.fedhq_levels(env)
            p.update = upd
            # realized costs (Eq. 6-9) with the *realized* wire size
            t_com = upd.bits / env.rate
            e_com = t_com * env.P_com
            t_cmp = upd.alpha * env.tau * env.D * env.W / strat.freq
            e_cmp = env.eps_hw * strat.freq ** 2 * upd.alpha \
                * env.tau * env.D * env.W
            p.t_com, p.t_cmp = t_com, t_cmp
            p.e_cmp, p.e_com = e_cmp, e_com
            p.energy = e_cmp + e_com
            return p

    def shrink_fast(self, sorted_params: PyTree, alpha: float) -> PyTree:
        """jit'd EMS slice (one compile per width bucket) for hot paths:
        the client's own, which its finish also uses."""
        return self.client.shrink(sorted_params, alpha)

    def aggregate(self, sorted_params: PyTree, accepted: list[PendingUpdate],
                  weights: jax.Array, *, fast: bool = False) -> PyTree:
        with profiler.span("fl.aggregate", round=self.round_idx,
                           n_clients=len(accepted)):
            if not fast:
                return self.server.aggregate(sorted_params,
                                             [p.update for p in accepted],
                                             weights=weights)
            # jit'd wrapper over the canonical Eq.-5 merge + server step
            # (jit retraces per update count — the input lists are pytrees)
            if self._agg_fast is None:
                server = self.server

                @jax.jit
                def agg(params, values, masks, w):
                    return server.apply_update(
                        params, aggregation.aio_aggregate(values, masks, w))

                self._agg_fast = agg
            return self._agg_fast(sorted_params,
                                  [p.update.values for p in accepted],
                                  [p.update.mask for p in accepted], weights)

    def evaluate(self, params: PyTree) -> tuple[float, float]:
        with profiler.span("fl.eval", round=self.round_idx):
            acc, loss = self.ev(params)
            return float(profiler.read(acc)), float(profiler.read(loss))

    # --------------------------------------------------- hierarchical glue

    def cell_backhaul(self, k: int, t_wall: float):
        """Cell k's backhaul at time t: the (possibly heterogeneous)
        per-cell draw, overlaid with any time-varying rate the scenario
        trace carries for this cell."""
        bh = self.cell_backhauls[k]
        if self.scenario is not None:
            rate = self.scenario.backhaul_rate(k, t_wall)
            if rate is not None:
                bh = dataclasses.replace(bh, rate_bps=rate)
        return bh

    def encode_ship(self, k: int, part):
        """Wire-encode cell k's partial, through the per-cell EF residual
        when the backhaul codec runs with error feedback."""
        codec = self.topo.backhaul.codec
        if self.codec_ef is not None:
            return self.codec_ef.encode_ship(k, part, codec,
                                             frame=self._ef_frame)
        return encode_partial(part, codec)

    def resolve_agg_route(self, route: str) -> str:
        """The mesh route shards cells over a mesh axis; with a single
        visible device there is nothing to shard over, and the run is
        refused rather than quietly taking another route."""
        if route == "mesh" and len(jax.devices()) < 2:
            raise ValueError(
                f"--agg-route mesh maps cells onto a mesh axis and needs "
                f">= 2 devices; JAX sees {len(jax.devices())} "
                f"({jax.devices()[0].platform})")
        if route != "streaming" and self.topo is not None \
                and (self.topo.backhaul.codec != "f32"
                     or self.codec_ef is not None):
            # the batched/mesh routes aggregate in exact f32 — only the
            # streaming edge fold passes numerics through the wire codec
            # (bits are still charged at the codec's size on all routes)
            print(f"[topology] warning: --agg-route {route} models the "
                  f"backhaul codec's cost but not its numerics (and "
                  f"ignores --backhaul-ef); use the streaming route to "
                  f"study codec/EF effects")
        return route


# ---------------------------------------------------------------- round mode

def _mesh_route_params(sim: Simulation, pairs, sorted_params) -> PyTree:
    """Aggregate via ``core.distributed.mesh_cell_aggregate``: flatten
    every accepted update/mask to one vector, stack, shard the client dim
    over a "cell" mesh axis, and let the monoid psum do the cloud merge.
    The AIO monoid is commutative, so any partitioning of clients across
    shards (and zero-weight padding rows) yields the batched oracle's
    aggregate up to float reordering."""
    from jax.sharding import Mesh

    from repro.core.distributed import mesh_cell_aggregate

    leaves, treedef = jax.tree_util.tree_flatten(sorted_params)
    shapes = [jnp.shape(x) for x in leaves]
    sizes = [int(np.prod(s)) for s in shapes]

    def flat(tree):
        ls = treedef.flatten_up_to(tree)
        return jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                                for x in ls])

    u = jnp.stack([flat(p.update.values) for p, _ in pairs])
    m = jnp.stack([flat(p.update.mask) for p, _ in pairs])
    w = jnp.asarray([wv for _, wv in pairs], jnp.float32)
    devs = jax.devices()
    n_shards = min(len(devs), u.shape[0])
    pad = (-u.shape[0]) % n_shards
    if pad:                        # zero-weight rows are the monoid identity
        u = jnp.concatenate([u, jnp.zeros((pad, u.shape[1]), u.dtype)])
        m = jnp.concatenate([m, jnp.zeros((pad, m.shape[1]), m.dtype)])
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])
    mesh = Mesh(np.array(devs[:n_shards]), ("cell",))
    num_f, den_f = mesh_cell_aggregate(u, m, w, mesh, finalize=False)

    def unflat(vec):
        out, off = [], 0
        for shape, size in zip(shapes, sizes):
            out.append(jnp.reshape(vec[off:off + size], shape))
            off += size
        return jax.tree_util.tree_unflatten(treedef, out)

    return finalize_apply(sorted_params, unflat(num_f), unflat(den_f),
                          sim.server.server_lr)


def _hier_round_merge(sim: Simulation, policy, live, aborted,
                      sorted_params, queue, t_wall: float,
                      round_idx: int = 0):
    """One hierarchical round tail: per-cell accept -> edge absorb ->
    backhaul ship -> cloud merge.

    Each cell applies the arrival policy over its own arrivals (per-cell
    deadline semantics), folds the admitted updates into an O(N)
    streaming partial with *unnormalized* AIO coefficients, and ships the
    constant-size partial over the backhaul; the round's latency is the
    slowest cell's barrier plus its shipping time.  Membership is the
    cell recorded on each flight *at dispatch* (``PendingUpdate.cell``)
    — handover re-homes devices between rounds, never an update already
    in the air.  Per-cell backhauls may be heterogeneous and
    time-varying (``Simulation.cell_backhaul``), and the shipped partial
    can ride a per-site EF residual (``--backhaul-ef``).

    ``sim.agg_route`` selects the numeric route: ``streaming`` (the
    default edge fold + cloud monoid merge, codec on the wire),
    ``batched`` (the flat Eq.-5 oracle over all accepted updates), or
    ``mesh`` (cells over a mesh axis).  The backhaul *cost* model is
    route-independent: one constant-size partial per reporting cell.

    Returns ``(accepted, new_params|None, lat, ship_energy,
    backhaul_bits, n_cells_reporting, lat_parts)`` where ``lat_parts``
    is the ``(train, uplink, backhaul)`` decomposition of ``lat`` along
    the critical cell's path (the cell maximizing barrier + shipping).
    """
    from repro.topology.codec import payload_bits as codec_payload_bits
    from repro.utils.pytree import tree_size as _tree_size

    topo, fleet, rc = sim.topo, sim.fleet, sim.run_cfg
    tel = sim.tel
    cell_dl = topo.cell_deadline_s
    route = sim.agg_route
    accepted_all, parts, ships, route_pairs = [], [], [], []
    lat = e_ship = bh_bits = 0.0
    n_rep = 0
    # (total, barrier, ship, max accepted t_cmp) per reporting cell — the
    # critical path for the round's latency attribution
    crit: list[tuple[float, float, float, float]] = []
    for k in range(fleet.n_cells):
        cell_live = [p for p in live if p.cell == k]
        cell_ab = [p for p in aborted if p.cell == k]
        if not cell_live and not cell_ab:
            continue
        acc_k, scales_k, lat_k = policy.accept(cell_live, 0.0)
        if cell_dl is not None:
            # the edge never waits past its own deadline, whatever the
            # global policy's barrier would have been
            pairs = [(p, s) for p, s in zip(acc_k, scales_k)
                     if p.duration <= cell_dl]
            if len(pairs) < len(acc_k):
                acc_k = [p for p, _ in pairs]
                scales_k = [s for _, s in pairs]
                lat_k = cell_dl
            else:
                lat_k = min(lat_k, cell_dl)
        if cell_ab:
            # the edge learns of a dropout at the departure moment, but
            # never waits past its barrier (mirrors the flat loop)
            barrier = cell_dl if cell_dl is not None \
                else getattr(policy, "deadline", math.inf)
            lat_k = max(lat_k, min(barrier,
                                   max(p.completes_at - t_wall
                                       for p in cell_ab)))
        if acc_k:
            w_uns = [unnormalized_weight(rc.method, rc.use_aio, p.update,
                                         p.fedhq_level) * s
                     for p, s in zip(acc_k, scales_k)]
            if route == "streaming":
                edge = EdgeAggregator(k, sorted_params,
                                      use_kernel=sim.edge_kernel)
                for p, w_un in zip(acc_k, w_uns):
                    edge.absorb(p.update.values, p.update.mask, w_un)
                # encode the partial at the configured wire dtype; the
                # exact encoded bit count (planes + int8 scale headers)
                # is what the link serializes and the tariff charges
                enc = sim.encode_ship(k, edge.ship())
                parts.append((k, enc))
                bits = enc.bits
                if tel.enabled and sim.codec_ef is not None:
                    sim.learn.record_ef_residual(tel, k, round_idx,
                                                 sim.codec_ef)
            else:
                route_pairs.extend(zip(acc_k, w_uns))
                bits = codec_payload_bits(
                    _tree_size(sorted_params),
                    len(jax.tree_util.tree_leaves(sorted_params)),
                    topo.backhaul.codec)
            bh = sim.cell_backhaul(k, t_wall)
            t_ship, e_k = bh.ship_bits(bits)
            bh_bits += bits
            e_ship += e_k
            ships.append((t_wall + lat_k + t_ship, k))
            lat = max(lat, lat_k + t_ship)
            n_rep += 1
            crit.append((lat_k + t_ship, lat_k, t_ship,
                         max(p.t_cmp for p in acc_k)))
            if tel.enabled:
                tel.span(f"cell/{k}", "backhaul_ship", t_wall + lat_k,
                         t_wall + lat_k + t_ship, round=round_idx,
                         bits=float(bits), codec=topo.backhaul.codec,
                         energy_j=e_k, n_updates=len(acc_k))
                tel.counter("cost.energy_j", e_k, cell=k,
                            phase="backhaul", round=round_idx)
                tel.counter("cost.comm_bits", float(bits), cell=k,
                            phase="backhaul", round=round_idx)
                tel.counter("backhaul.ships", 1.0, cell=k,
                            codec=topo.backhaul.codec, round=round_idx)
            if tel.enabled:
                for p, w_un in zip(acc_k, w_uns):
                    sim.learn.note_contribution(p.client_id, w_un)
        else:
            lat = max(lat, lat_k)
            crit.append((lat_k, lat_k, 0.0,
                         max((p.t_cmp for p in acc_k), default=0.0)))
        accepted_all.extend(acc_k)
    for t_arr, k in ships:      # record cloud arrival order
        queue.push(t_arr, ev_mod.EDGE_MERGE, k)
        if tel.enabled:
            tel.instant("server", "EDGE_MERGE", t_arr, cell=k,
                        round=round_idx)
    for _ in ships:
        queue.pop()
    new_params = None
    if parts:
        decoded = [(k, decode_partial(e)) for k, e in parts]
        cell_aggs = []
        if tel.enabled:
            # finalize each cell's aggregate while its buffers are still
            # alive — the donated cloud merge below consumes them
            cell_aggs = [(k, aggregation.finalize_trees(d.num, d.den))
                         for k, d in decoded]
        merged = cloud_merge([d for _, d in decoded],
                             use_kernel=sim.edge_kernel)
        new_params = finalize_apply(sorted_params, merged.num, merged.den,
                                    sim.server.server_lr)
        if tel.enabled:
            delta = tree_sub(sorted_params, new_params)
            for k, cell_agg in cell_aggs:
                sim.learn.record_cell(tel, k, round_idx, cell_agg, delta)
    elif route_pairs:
        if route == "mesh":
            new_params = _mesh_route_params(sim, route_pairs, sorted_params)
        else:                      # batched: the flat (I, N) Eq.-5 oracle
            agg = aggregation.aio_aggregate(
                [p.update.values for p, _ in route_pairs],
                [p.update.mask for p, _ in route_pairs],
                jnp.asarray([w for _, w in route_pairs], jnp.float32))
            new_params = sim.server.apply_update(sorted_params, agg)
    # latency attribution along the critical cell: its barrier splits
    # into compute (until the slowest accepted T_cmp elapses) and uplink
    # (the rest — wire time plus any deadline/dropout wait); shipping is
    # the backhaul share.  The three sum to ``lat`` exactly.
    lat_parts = (0.0, 0.0, 0.0)
    if crit:
        _, bar, t_ship_c, max_tcmp = max(crit, key=lambda c: c[0])
        lt = min(bar, max_tcmp)
        lat_parts = (lt, bar - lt, t_ship_c)
    return (accepted_all, new_params, lat, e_ship, bh_bits, n_rep,
            lat_parts)


def _run_round_based(sim: Simulation, policy, orch: OrchestratorConfig,
                     verbose: bool) -> History:
    rc = sim.run_cfg
    use_pool = orch.use_pool if orch.use_pool is not None \
        else policy.pool_default
    tel = sim.tel
    queue = ev_mod.EventQueue(trace_limit=orch.event_trace_limit)
    hist = History(rc, [], registry=sim.registry)
    params = sim.params
    t_wall = 0.0

    for t in range(rc.rounds):
        sim.round_idx = t
        with profiler.span("fl.round", round=t) as round_span:
            # round-boundary handover: re-home mobile devices to their
            # serving cell *before* dispatch, so this round's channels,
            # selection, and edge merges all see the new binding.  One
            # HANDOVER event per move lands on the recorded timeline.
            n_handover = 0
            if sim.handover is not None:
                new_cells, moves = sim.handover.reassign(
                    sim.fleet.positions(t_wall), sim.fleet.cells)
                for i, old, new in moves:
                    queue.push(t_wall, ev_mod.HANDOVER, i, (old, new))
                    if tel.enabled:
                        tel.instant(f"device/{i}", "HANDOVER", t_wall,
                                    round=t, src_cell=old, dst_cell=new)
                        tel.counter("mobility.handovers", 1.0, device=i,
                                    round=t)
                for _ in moves:
                    queue.pop()
                sim.fleet.cells = new_cells
                n_handover = len(moves)
            with profiler.span("fl.channels", round=t):
                envs = sim.fleet.round_envs(sim.rng, sim.W, sim.S_bits,
                                            t=t_wall)
            sorted_params = sim.sort_params(params)
            sim.ensure_planner(sorted_params)

            selected, envs_eff, n_unavail, headroom = sim.gate_round(t_wall,
                                                                     envs)
            t_max_eff = sim.effective_T_max(t_wall)
            occupancy = int(np.bincount(sim.fleet.cells).max()) \
                if sim.fleet.cells is not None else 0
            pendings = [p for p in (sim.prepare(i, envs_eff[i])
                                    for i in selected)
                        if p is not None]
            for p in pendings:
                sim.dispatch_log.append((t_wall, p.client_id,
                                         headroom[p.client_id]))
            if tel.enabled:
                tel.counter("fleet.unavailable", float(n_unavail), round=t)
                tel.counter("fleet.selected", float(len(selected)), round=t)
                tel.counter("fleet.infeasible",
                            float(len(selected) - len(pendings)), round=t)

            # mid-round churn: a device that leaves the cell before its
            # *planned* T_cmp + T_com elapses aborts — its update never
            # arrives, training is skipped, and the compute/energy burned up
            # to the departure is charged (pro-rated over the planned flight)
            live, aborted = [], []
            for p in pendings:
                t_off = sim.fleet.next_departure(p.client_id, t_wall)
                planned = p.strat.T_cmp + p.strat.T_com
                if t_off < t_wall + planned:
                    p.dispatched_at = t_wall
                    p.completes_at = t_off
                    frac = min(1.0, (t_off - t_wall) / planned) \
                        if planned > 0 else 1.0
                    p.energy = frac * (p.strat.E_cmp + p.strat.E_com)
                    p.e_cmp = frac * p.strat.E_cmp
                    p.e_com = frac * p.strat.E_com
                    aborted.append(p)
                else:
                    live.append(p)
            round_span.set_metadata(n_clients=len(live))

            subs: dict = {}
            if use_pool and rc.method == "anycostfl":
                for p in live:
                    if p.alpha not in subs:
                        subs[p.alpha] = sim.shrink_fast(sorted_params, p.alpha)
            if use_pool:
                trained = sim.pool.train_shared(
                    sorted_params,
                    [TrainJob(p.client_id, p.alpha, p.batches)
                     for p in live], subs)
            else:
                trained = []
                for p in live:
                    with profiler.span("fl.local_train", round=t,
                                       client=p.client_id):
                        trained.append(sim.train_one(p, sorted_params))

            en, fl, cb = 0.0, 0.0, 0.0
            en_cmp = en_com = 0.0
            for p, tr in zip(live, trained):
                sim.materialize(p, tr, sorted_params, sub=subs.get(p.alpha))
                p.dispatched_at = t_wall
                p.completes_at = t_wall + p.duration
                # dispatch->arrival flight time goes to the always-live
                # registry (like the round.* gauges), so p95 dispatch
                # latency is queryable/gateable without a telemetry session
                # repro: ignore[unguarded-telemetry] — always-live by design
                sim.registry.observe("dispatch.latency_s", p.duration,
                                     device=p.client_id, cell=p.cell,
                                     round=t)
                queue.push(p.completes_at, ev_mod.COMPLETE, p.client_id, p)
                en += p.energy
                en_cmp += p.e_cmp
                en_com += p.e_com
                fl += p.update.flops
                cb += p.update.bits
                if tel.enabled:
                    sub_s = subs.get(p.alpha)
                    if sub_s is None:
                        sub_s = shrinking.shrink(sorted_params, p.alpha,
                                                 sim.spec)
                    sim.learn.record_device(
                        tel, p.client_id, t,
                        sim.learn.device_stats(p.alpha, sub_s, tr,
                                               p.update.values,
                                               p.update.mask))
                    tel.span(f"device/{p.client_id}", "train", t_wall,
                             t_wall + p.t_cmp, round=t, cell=p.cell,
                             alpha=p.update.alpha, energy_j=p.e_cmp,
                             flops=p.update.flops)
                    tel.span(f"device/{p.client_id}", "uplink",
                             t_wall + p.t_cmp, t_wall + p.duration, round=t,
                             cell=p.cell, bits=p.update.bits,
                             beta=p.update.beta_realized, energy_j=p.e_com)
                    tel.counter("cost.energy_j", p.e_cmp,
                                device=p.client_id, cell=p.cell,
                                phase="train", round=t)
                    tel.counter("cost.energy_j", p.e_com,
                                device=p.client_id, cell=p.cell,
                                phase="uplink", round=t)
                    tel.counter("cost.comm_bits", p.update.bits,
                                device=p.client_id, cell=p.cell,
                                phase="uplink", round=t)
            for p in aborted:
                queue.push(p.completes_at, ev_mod.CHURN, p.client_id, p)
                en += p.energy
                en_cmp += p.e_cmp
                en_com += p.e_com
                if tel.enabled:
                    tel.instant(f"device/{p.client_id}", "CHURN",
                                p.completes_at, round=t, cell=p.cell)
                    tel.counter("cost.energy_j", p.e_cmp,
                                device=p.client_id, cell=p.cell,
                                phase="train", round=t)
                    tel.counter("cost.energy_j", p.e_com,
                                device=p.client_id, cell=p.cell,
                                phase="uplink", round=t)
            for _ in range(len(live) + len(aborted)):  # record arrival order
                queue.pop()

            if not live:               # every device faded out this round
                for p in aborted:
                    sim.fleet.debit(p.client_id, p.energy, p.completes_at)
                hist.log_round(
                    t, latency_s=0.0, energy_j=en, flops=0.0,
                    comm_bits=0.0, mean_alpha=0.0, mean_beta=0.0,
                    mean_gain=0.0, t_wall=t_wall, n_unavailable=n_unavail,
                    n_aborted=len(aborted),
                    mean_soc=(sim.fleet.battery.mean_soc_frac(t_wall)
                              if sim.fleet.battery is not None else 1.0),
                    n_handovers=n_handover, max_cell_occupancy=occupancy,
                    t_max_effective=t_max_eff,
                    energy_train_j=en_cmp, energy_uplink_j=en_com)
                if sim.fleet_dynamic:
                    # idle server deadline: let traces/batteries evolve so the
                    # fleet can come back (a static fleet must not drift)
                    t_wall += sim.fleet_cfg.T_max
                continue

            bh_bits, n_cells_rep, e_ship = 0.0, 0, 0.0
            agg_delta = None
            if sim.topo is not None:
                with profiler.span("fl.aggregate", round=t,
                                   n_clients=len(live)):
                    (accepted, new_params, lat, e_ship, bh_bits, n_cells_rep,
                     lat_parts) = _hier_round_merge(
                         sim, policy, live, aborted, sorted_params, queue,
                         t_wall, round_idx=t)
                en += e_ship
                t_wall += lat
                for p in live + aborted:
                    sim.fleet.debit(p.client_id, p.energy, t_wall)
                if new_params is not None:
                    params = new_params
                    if tel.enabled:
                        agg_delta = tree_sub(sorted_params, new_params)
            else:
                accepted, scales, lat = policy.accept(live, 0.0)
                if aborted:
                    # the server learns of a dropout at the departure moment,
                    # but never waits past its own deadline barrier (semisync)
                    barrier = getattr(policy, "deadline", math.inf)
                    lat = max(lat, min(barrier,
                                       max(p.completes_at - t_wall
                                           for p in aborted)))
                # critical-path split: compute until the slowest accepted
                # client's T_cmp elapses, uplink/barrier wait for the rest
                lt = min(lat, max((p.t_cmp for p in accepted), default=0.0))
                lat_parts = (lt, lat - lt, 0.0)
                t_wall += lat
                for p in live + aborted:
                    sim.fleet.debit(p.client_id, p.energy, t_wall)
                if accepted:
                    fedhq_L = [p.fedhq_level for p in accepted] \
                        if rc.method == "fedhq" else []
                    w = base_weights(rc.method, rc.use_aio,
                                     [p.update for p in accepted], fedhq_L)
                    w = apply_scales(w, scales)
                    params = sim.aggregate(sorted_params, accepted, w,
                                           fast=use_pool)
                    if tel.enabled:
                        agg_delta = tree_sub(sorted_params, params)
                        for p, wv in zip(accepted, np.asarray(w)):
                            sim.learn.note_contribution(p.client_id,
                                                        float(wv))

            log = hist.log_round(
                t, latency_s=lat, energy_j=en, flops=fl, comm_bits=cb,
                mean_alpha=float(np.mean([p.update.alpha for p in live])),
                mean_beta=float(np.mean([p.update.beta_realized
                                         for p in live])),
                mean_gain=float(np.mean([p.strat.gain for p in live])),
                t_wall=t_wall, n_clients=len(accepted),
                n_dropped=len(live) - len(accepted),
                n_unavailable=n_unavail, n_aborted=len(aborted),
                mean_soc=(sim.fleet.battery.mean_soc_frac(t_wall)
                          if sim.fleet.battery is not None else 1.0),
                n_cells_reporting=n_cells_rep, backhaul_bits=bh_bits,
                n_handovers=n_handover, max_cell_occupancy=occupancy,
                t_max_effective=t_max_eff,
                energy_train_j=en_cmp, energy_uplink_j=en_com,
                energy_backhaul_j=e_ship,
                latency_train_s=lat_parts[0],
                latency_uplink_s=lat_parts[1],
                latency_backhaul_s=lat_parts[2])
            if tel.enabled:
                if agg_delta is not None:
                    for p in accepted:
                        sim.learn.record_alignment(tel, p.client_id, t,
                                                   p.update.values, agg_delta)
                sim.learn.record_round(tel, t, agg_delta)
                tel.span("server", "round", t_wall - lat, t_wall, round=t,
                         n_clients=len(accepted), n_cells=n_cells_rep,
                         energy_j=en)
                if tel.health is not None:
                    tel.health.evaluate(t, t_wall, sim.registry, tel)
            if t % rc.eval_every == 0 or t == rc.rounds - 1:
                acc, loss = sim.evaluate(params)
                hist.log_eval(log, acc, loss)
                if verbose:
                    print(f"[{rc.method}/{policy.name}] round {t:3d} "
                          f"acc={acc:.3f} loss={loss:.3f} lat={lat:.2f}s "
                          f"E={en:.2f}J t={t_wall:.1f}s "
                          f"alpha={log.mean_alpha:.2f} "
                          f"beta={log.mean_beta:.4f}")
            if orch.max_wallclock_s is not None \
                    and t_wall >= orch.max_wallclock_s:
                break
    hist.trace = queue.trace_signature()
    hist.dispatch_log = sim.dispatch_log
    hist.params = params
    return hist


# --------------------------------------------------------------- fedbuff mode

def _run_fedbuff(sim: Simulation, policy, orch: OrchestratorConfig,
                 verbose: bool) -> History:
    rc = sim.run_cfg
    use_pool = orch.use_pool if orch.use_pool is not None \
        else policy.pool_default
    retry_dt = orch.retry_interval_s if orch.retry_interval_s is not None \
        else sim.fleet_cfg.T_max
    if sim.dyn.selection != "uniform" or sim.dyn.participation < 1.0:
        print("[fedbuff] warning: selection policies and participation "
              "caps are round-based controls; fedbuff devices free-run "
              "(availability/battery gating still applies)")
    tel = sim.tel
    queue = ev_mod.EventQueue(trace_limit=orch.event_trace_limit)
    hist = History(rc, [], registry=sim.registry)

    # frozen sorted coordinate frame (cross-version merges need one frame)
    current = sim.sort_params(sim.params)
    sim.ensure_planner(current)
    version = 0
    version_params: dict[int, PyTree] = {0: current}
    inflight_version: dict[int, int] = {}
    buffer: list[PendingUpdate] = []
    n_agg = 0
    last_agg_t = 0.0
    en, fl, cb = 0.0, 0.0, 0.0
    en_cmp = en_com = 0.0
    # --max-inflight participation throttle: clients beyond the cap of
    # concurrent dispatched flights wait in FIFO order for a free slot
    cap = orch.max_inflight
    waiting: deque = deque()
    peak_inflight = 0

    def enqueue_flight(p: PendingUpdate, now: float) -> None:
        """COMPLETE at the planned arrival — unless the availability trace
        says the device churns out of the cell first."""
        nonlocal peak_inflight
        i = p.client_id
        inflight_version[i] = p.version
        peak_inflight = max(peak_inflight, len(inflight_version))
        # always-live registry write (host-side, never touches device
        # state) so async dispatch latency is queryable without a
        # telemetry session
        # repro: ignore[unguarded-telemetry] — always-live by design
        sim.registry.observe("dispatch.latency_s", p.completes_at - now,
                             device=p.client_id, version=p.version)
        t_off = sim.fleet.next_departure(i, now)
        if t_off < p.completes_at:
            queue.push(t_off, ev_mod.CHURN, i, p)
        else:
            queue.push(p.completes_at, ev_mod.COMPLETE, i, p)

    def dispatch(i: int, env: schedule.DeviceEnv, now: float) -> None:
        # availability / battery gating: an off-cell device re-enters the
        # queue when its trace flips back on; a drained one when the
        # trickle restores its reserve headroom (never, with no recharge)
        fleet = sim.fleet
        if fleet.trace is not None and not fleet.trace.available(i, now):
            inflight_version.pop(i, None)
            t_on = fleet.trace.next_change(i, now)
            if math.isfinite(t_on):
                queue.push(t_on, ev_mod.RETRY, i)
            return
        if fleet.battery is not None and not fleet.battery.available(i, now):
            inflight_version.pop(i, None)
            t_rdy = fleet.battery.ready_time(i, now)
            if math.isfinite(t_rdy):
                queue.push(max(t_rdy, now + 1e-9), ev_mod.RETRY, i)
            return
        env = fleet.dynamic_env(i, env, now)
        t_max_eff = sim.effective_T_max(now)
        if t_max_eff != sim.fleet_cfg.T_max:
            env = dataclasses.replace(env, T_max=t_max_eff)
        p = sim.prepare(i, env)
        if p is None:
            queue.push(now + retry_dt, ev_mod.RETRY, i)
            inflight_version.pop(i, None)
            return
        p.version = version
        p.dispatched_at = now
        # planned timeline: the device reserves compute + uplink by its plan
        t_cmp = p.alpha * env.tau * env.D * env.W / p.strat.freq
        t_com = p.alpha * p.strat.beta * env.S_bits / env.rate
        p.completes_at = now + t_cmp + t_com
        sim.dispatch_log.append((now, i,
                                 fleet.battery.headroom(i, now)
                                 if fleet.battery is not None
                                 else env.E_max))
        enqueue_flight(p, now)

    def pump(now: float) -> None:
        """Fill free flight slots from the waiting FIFO (fresh channel
        draw per dispatch, as in the unthrottled runner)."""
        while waiting and (cap is None or len(inflight_version) < cap):
            j = waiting.popleft()
            dispatch(j, sim.fleet.device_env(sim.rng, j, sim.W,
                                             sim.S_bits, t=now), now)

    def redispatch(i: int, now: float) -> None:
        """Throttle-aware re-dispatch: join the FIFO behind any earlier
        waiters, then fill whatever slots are free.  With no cap the
        queue is always empty, so this is the unthrottled runner's
        immediate dispatch with the identical env-draw order."""
        waiting.append(i)
        pump(now)

    def requeue(p: PendingUpdate, now: float) -> None:
        """Staleness-cap ``requeue`` mode: retrain the rejected round's
        exact minibatch draw against the *current* model version (same
        env/strategy, fresh flight) instead of discarding the work.
        Subject to the same availability/battery gates as a dispatch —
        a device that just spent itself below reserve falls back to the
        gated dispatch path (which schedules its recharge RETRY).
        Deliberately bypasses the --max-inflight FIFO: the replay takes
        back the slot its own rejected flight just freed (routing it
        through the queue would drop the retained minibatches and
        degrade requeue to a plain re-dispatch)."""
        fleet = sim.fleet
        i = p.client_id
        if (fleet.trace is not None
                and not fleet.trace.available(i, now)) \
                or (fleet.battery is not None
                    and not fleet.battery.available(i, now)):
            redispatch(i, now)
            return
        q = dataclasses.replace(p, version=version, dispatched_at=now,
                                staleness=0, update=None)
        q.completes_at = now + (p.completes_at - p.dispatched_at)
        sim.dispatch_log.append((now, i,
                                 fleet.battery.headroom(i, now)
                                 if fleet.battery is not None
                                 else p.env.E_max))
        enqueue_flight(q, now)

    with profiler.span("fl.channels", round=0):
        envs = sim.fleet.round_envs(sim.rng, sim.W, sim.S_bits)
    for i, env in enumerate(envs):
        if cap is not None and len(inflight_version) >= cap:
            waiting.append(i)
        else:
            dispatch(i, env, 0.0)

    # Progress guard: without a wall-clock budget the run targets rc.rounds
    # merges, but an all-infeasible fleet (deep fade draws on every retry)
    # would spin on RETRY events forever. Budget enough simulated time for
    # every merge even if only one device is ever feasible, then stop.
    wall_limit = orch.max_wallclock_s
    if wall_limit is None:
        cycle = max(sim.fleet_cfg.T_max, retry_dt)
        wall_limit = rc.rounds * orch.buffer_size * cycle * 4.0

    now = 0.0
    n_stale = n_aborted = 0
    while len(queue):
        ev = queue.pop()
        if ev.time > wall_limit:
            break
        now = ev.time
        if ev.kind == ev_mod.RETRY:
            if tel.enabled:
                tel.instant(f"device/{ev.client}", "RETRY", now)
                tel.counter("fedbuff.retries", 1.0, device=ev.client)
            redispatch(ev.client, now)
            continue
        if ev.kind == ev_mod.CHURN:
            # the device left the cell mid-flight: abort, charge the
            # pro-rated planned energy, and come back when the trace does
            p = ev.payload
            planned = p.completes_at - p.dispatched_at
            frac = min(1.0, (now - p.dispatched_at) / planned) \
                if planned > 0 else 1.0
            waste = frac * (p.strat.E_cmp + p.strat.E_com)
            en += waste
            en_cmp += frac * p.strat.E_cmp
            en_com += frac * p.strat.E_com
            if tel.enabled:
                tel.instant(f"device/{p.client_id}", "CHURN", now,
                            version=p.version)
                tel.counter("cost.energy_j", frac * p.strat.E_cmp,
                            device=p.client_id, phase="train")
                tel.counter("cost.energy_j", frac * p.strat.E_com,
                            device=p.client_id, phase="uplink")
            sim.fleet.debit(p.client_id, waste, now)
            n_aborted += 1
            inflight_version.pop(p.client_id, None)
            t_on = sim.fleet.trace.next_change(p.client_id, now)
            if math.isfinite(t_on):
                queue.push(t_on, ev_mod.RETRY, p.client_id)
            pump(now)      # the aborted flight freed a throttle slot
            continue

        p = ev.payload
        inflight_version.pop(p.client_id, None)   # flight landed
        p.staleness = version - p.version
        # the device spent its planned round energy whether or not the
        # server admits the update (battery model; the energy *log* keeps
        # realized costs from materialization, as in the sync loop)
        sim.fleet.debit(p.client_id, p.strat.E_cmp + p.strat.E_com, now)
        if not policy.admit(p.staleness):
            n_stale += 1
            en += p.strat.E_cmp + p.strat.E_com   # spent, never aggregated
            en_cmp += p.strat.E_cmp
            en_com += p.strat.E_com
            if tel.enabled:
                tel.instant(f"device/{p.client_id}", "STALE_REJECT",
                            now, staleness=p.staleness)
                tel.counter("cost.energy_j", p.strat.E_cmp,
                            device=p.client_id, phase="train")
                tel.counter("cost.energy_j", p.strat.E_com,
                            device=p.client_id, phase="uplink")
            if orch.staleness_mode == STALE_REQUEUE:
                requeue(p, now)
            else:
                redispatch(p.client_id, now)
            continue
        buffer.append(p)
        redispatch(p.client_id, now)

        if not policy.should_aggregate(buffer):
            continue

        # ---- materialize the buffered rounds (deferred, batched training)
        shrunk: dict = {}
        jobs = []
        for b in buffer:
            vk = (b.version, b.alpha)
            if vk not in shrunk:
                shrunk[vk] = (sim.shrink_fast(version_params[b.version],
                                              b.alpha) if use_pool
                              else shrinking.shrink(
                                  version_params[b.version], b.alpha,
                                  sim.spec))
            jobs.append(TrainJob(b.client_id, b.alpha, b.batches,
                                 sub_params=shrunk[vk]))
        if use_pool:
            trained = sim.pool.train_stacked(jobs)
        else:
            trained = [sim.client._local_steps(j.alpha, int(
                jax.tree_util.tree_leaves(j.batches)[0].shape[0]))(
                    j.sub_params, j.batches) for j in jobs]
        # stream each decoded update into one O(N) AIO accumulator and
        # drop its pytrees on the spot — the server never materializes
        # the (I, N) buffer stack.  Unnormalized weights x the FedBuff
        # staleness discount; Eq. 5's ratio cancels the cohort
        # normalization the round-based base_weights would have applied.
        stream_acc = EdgeAggregator(-1, current,
                                    use_kernel=sim.edge_kernel)
        gamma = orch.staleness_exponent
        for b, j, tr in zip(buffer, jobs, trained):
            sim.materialize(b, tr, version_params[b.version],
                            sub=j.sub_params)
            en += b.energy
            en_cmp += b.e_cmp
            en_com += b.e_com
            fl += b.update.flops
            cb += b.update.bits
            if tel.enabled:
                sim.learn.record_device(
                    tel, b.client_id, n_agg,
                    sim.learn.device_stats(b.alpha, j.sub_params, tr,
                                           b.update.values,
                                           b.update.mask))
                tel.span(f"device/{b.client_id}", "train",
                         b.dispatched_at, b.dispatched_at + b.t_cmp,
                         version=b.version, staleness=b.staleness,
                         alpha=b.update.alpha, energy_j=b.e_cmp)
                tel.span(f"device/{b.client_id}", "uplink",
                         b.dispatched_at + b.t_cmp,
                         b.dispatched_at + b.duration,
                         version=b.version, bits=b.update.bits,
                         energy_j=b.e_com)
                tel.counter("cost.energy_j", b.e_cmp,
                            device=b.client_id, phase="train")
                tel.counter("cost.energy_j", b.e_com,
                            device=b.client_id, phase="uplink")
                tel.counter("cost.comm_bits", b.update.bits,
                            device=b.client_id, phase="uplink")
            w_b = unnormalized_weight(rc.method, rc.use_aio, b.update,
                                      b.fedhq_level) \
                * staleness_scales([b.staleness], gamma)[0]
            stream_acc.absorb(b.update.values, b.update.mask, w_b)
            if tel.enabled:
                # keep the decoded pytrees alive until the post-merge
                # alignment pass below — a telemetry-only memory cost of
                # one buffer's worth of updates (the uninstrumented
                # stream still drops them here)
                sim.learn.note_contribution(b.client_id, float(w_b))
            else:
                b.update = dataclasses.replace(b.update, values=None,
                                               mask=None)
        part = stream_acc.ship()
        prev_current = current
        current = finalize_apply(current, part.num, part.den,
                                 sim.server.server_lr)
        if tel.enabled:
            agg_delta = tree_sub(prev_current, current)
            for b in buffer:
                sim.learn.record_alignment(tel, b.client_id, n_agg,
                                           b.update.values, agg_delta)
                b.update = dataclasses.replace(b.update, values=None,
                                               mask=None)
            sim.learn.record_round(tel, n_agg, agg_delta)
        version += 1
        version_params[version] = current
        # retain only versions still referenced by an in-flight client (a
        # straggler pins just its own dispatch version, not every version
        # since)
        keep = set(inflight_version.values()) | {version}
        for v in [v for v in version_params if v not in keep]:
            del version_params[v]
        n_agg += 1
        sim.round_idx = n_agg
        if tel.enabled:
            tel.instant("server", "BUFFER_MERGE", now, version=version,
                        n_updates=len(buffer))

        # Inter-merge latency attribution: the merge fires the instant
        # its K-th update lands, so the triggering arrival (buffer[-1],
        # whose COMPLETE is this event) is the interval's critical path.
        # Its training time inside [last_agg_t, now] is the compute
        # share; the remainder — its wire time plus the window's wait on
        # the earlier K-1 arrivals — is the uplink share (the same
        # convention the round-based split uses for barrier wait).
        # fedbuff has no backhaul tier, so that component is 0; the
        # three components sum to latency_s exactly (pinned by
        # tests/test_telemetry.py).
        lat = now - last_agg_t
        trig = buffer[-1]
        lo = max(trig.dispatched_at, last_agg_t)
        compute_end = min(trig.dispatched_at + trig.t_cmp, now)
        lat_train = max(0.0, compute_end - lo)
        log = hist.log_round(
            n_agg - 1, latency_s=lat, energy_j=en,
            flops=fl, comm_bits=cb,
            mean_alpha=float(np.mean([b.update.alpha for b in buffer])),
            mean_beta=float(np.mean([b.update.beta_realized
                                     for b in buffer])),
            mean_gain=float(np.mean([b.strat.gain for b in buffer])),
            t_wall=now, n_clients=len(buffer),
            mean_staleness=float(np.mean([b.staleness for b in buffer])),
            max_staleness=int(max(b.staleness for b in buffer)),
            n_stale_dropped=n_stale, n_aborted=n_aborted,
            mean_soc=(sim.fleet.battery.mean_soc_frac(now)
                      if sim.fleet.battery is not None else 1.0),
            t_max_effective=sim.effective_T_max(now),
            energy_train_j=en_cmp, energy_uplink_j=en_com,
            latency_train_s=lat_train,
            latency_uplink_s=lat - lat_train)
        if tel.enabled and tel.health is not None:
            tel.health.evaluate(n_agg - 1, now, sim.registry, tel)
        done = (orch.max_wallclock_s is None and n_agg >= rc.rounds)
        if (n_agg - 1) % rc.eval_every == 0 or done:
            acc, loss = sim.evaluate(current)
            hist.log_eval(log, acc, loss)
            if verbose:
                print(f"[{rc.method}/fedbuff] merge {n_agg:3d} "
                      f"t={now:7.1f}s acc={acc:.3f} loss={loss:.3f} "
                      f"stale={log.mean_staleness:.1f} "
                      f"alpha={log.mean_alpha:.2f}")
        buffer = []
        en, fl, cb = 0.0, 0.0, 0.0
        en_cmp = en_com = 0.0
        n_stale = n_aborted = 0
        last_agg_t = now
        if done:
            break

    # final eval so best_acc reflects the last merged model
    if hist.rounds and hist.rounds[-1].test_acc is None:
        acc_, loss = sim.evaluate(current)
        hist.log_eval(hist.rounds[-1], acc_, loss)
    hist.trace = queue.trace_signature()
    hist.dispatch_log = sim.dispatch_log
    hist.peak_inflight = peak_inflight
    hist.params = current
    return hist


# ----------------------------------------------------------------- entrypoint

def run_orchestrated(run_cfg: FLRunConfig,
                     fleet_cfg: Optional[FleetConfig] = None,
                     orch: Optional[OrchestratorConfig] = None,
                     verbose: bool = False,
                     telemetry=None,
                     jax_profile: Optional[str] = None) -> History:
    """Run federated training under an arrival/aggregation policy.

    ``telemetry`` is an optional :class:`repro.telemetry.Telemetry`
    session; when absent (or NULL) the run is bitwise-identical to the
    uninstrumented runner and allocates nothing on the event path.
    ``jax_profile`` is a directory: the round loop runs under
    ``jax.profiler`` and its trace (with the ``fl.*`` host spans) is
    written there.
    """
    orch = orch or OrchestratorConfig()
    sim = Simulation(run_cfg, fleet_cfg, telemetry=telemetry)
    sim.agg_route = sim.resolve_agg_route(orch.agg_route)
    policy = make_policy(orch, fleet_T_max=sim.fleet_cfg.T_max)
    if not policy.round_based and sim.topo is not None:
        raise ValueError(
            "hierarchical topology needs a round-based policy "
            "(sync/semisync): fedbuff's cross-version stream has no "
            "per-cell round barrier to ship partials at")
    runner = _run_round_based if policy.round_based else _run_fedbuff
    with profiler.profile_trace(jax_profile):
        return runner(sim, policy, orch, verbose)
