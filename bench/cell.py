"""Drives the program's synchronous FL round loop for one benchmark cell.

The cell builds one ``repro.orchestrator.runner.Simulation`` from the
configuration and workload files, with the benchmark's own data and
weights made from the seed, and runs it one round at a time through the
runner's round loop (``_run_round_based``, one round per call, the global
model carried from call to call).  The program has no per-round hook and
no wall-clock stop, so the cell reaches into it here:

* the program's task function that the model's task file names
  (``PROGRAM_TASK``) is swapped for the task file's generator while the
  Simulation is built, and ``Simulation.params`` is set to the
  benchmark's weights;
* the Simulation's ``prepare``, ``sort_params``, ``materialize``,
  ``aggregate``, ``evaluate``, ``pool.train_shared`` and
  ``fleet.round_envs`` are wrapped on the instance, to count work, capture
  the checked rounds and, when tracing, to mark host phases in the trace;
* warm-up calls ``pool.train_shared``, ``client.finish_round_fast``,
  ``Simulation.shrink_fast`` and ``Simulation.aggregate`` with inputs of
  every shape a round can give them.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import math
import sys
import time
import types
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.paths import BENCH, ROOT

sys.path.insert(0, str(ROOT / "src"))

# the profiler's host spans written around the program's calls
SPAN = "bench:"
# every cell runs the paper's synchronous round, flat; the workload's
# ``use_pool`` picks the pooled route (one vmapped local-step program per
# alpha bucket and padded group, the jitted finish and aggregate) or the
# sync policy's own unpooled one (one client at a time, eager finish)
POLICY, ROUTE = "sync", "streaming"
# rounds of channel draws that set-up samples for the shapes to warm
WARM_DRAWS = 200
# rounds at the start of set-up that the output check follows
CHECKED_ROUNDS = 3


class Round(NamedTuple):
    seconds: float
    n_clients: int
    jobs: tuple          # (alpha, samples) of every client that trained
    test_loss: float
    compiles: int

    @property
    def samples(self) -> int:
        return sum(s for _, s in self.jobs)

    @property
    def ok(self) -> bool:
        return self.n_clients > 0 and math.isfinite(self.test_loss)


@dataclasses.dataclass
class Checked:
    """One checked round: its inputs and what the program made of them."""
    clients: list                   # reference.Client, in dispatch order
    delta_norms: dict               # leaf path -> |new - sorted|
    test_loss: float
    change_sq: float = 0.0          # sum of |trained - start|^2 of clients
    updates: Optional[list] = None  # start - trained of each client, flat
    change: Optional[dict] = None   # leaf path -> new - sorted


class CompileCounter:
    """Counts lowerings to XLA (each is a compile or a cache load), and the
    persistent cache's hits and misses."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.count = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        jax.monitoring.register_event_listener(self._event)

    def _seen(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1

    def _event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._seen)
        jax.monitoring.unregister_event_listener(self._event)


class Cell:
    def __init__(self, workload: dict, config: dict, seed: int,
                 compiles: CompileCounter, *, trace: bool = False,
                 bench_dir=BENCH):
        from repro.orchestrator import runner
        from repro.orchestrator.policies import (OrchestratorConfig,
                                                 make_policy)
        from repro.sysmodel.population import FleetConfig
        from repro.train.fl_loop import FLRunConfig

        self.workload, self.config, self.seed = workload, config, seed
        self.model = reference.load_model(config["arch"], bench_dir)
        self.task = reference.load_task(self.model.TASK, bench_dir)
        self.compiles = compiles
        self.trace = trace
        self._runner = runner
        key = reference.seed_key(seed)
        k_data, k_init = jax.random.split(key)
        made = self.task.make(k_data, config)
        # the test split as the reference reads it
        self.test = self.task.rows(made[1])

        rc = FLRunConfig(
            arch=config["arch"], method="anycostfl", rounds=1,
            lr=config["lr"], batch_size=config["batch_size"],
            tau=config["tau"], seed=seed, iid=config["iid"],
            n_train=config["n_train"], n_test=config["n_test"],
            eval_every=workload["eval_every"],
            alpha_buckets=tuple(config["alpha_buckets"]),
            use_planner=config["use_planner"])
        fleet = FleetConfig(n_devices=config["n_devices"],
                            T_max=config["T_max"],
                            E_max_range=tuple(config["E_max_range"]),
                            tau=config["tau"])
        self.use_pool = bool(workload["use_pool"])
        self.orch = OrchestratorConfig(policy=POLICY, agg_route=ROUTE,
                                       use_pool=self.use_pool)
        name = self.task.PROGRAM_TASK
        saved = getattr(runner, name)
        setattr(runner, name, lambda *_a, **_k: made)
        try:
            sim = runner.Simulation(rc, fleet)
        finally:
            setattr(runner, name, saved)
        sim.agg_route = sim.resolve_agg_route(self.orch.agg_route)
        self.policy = make_policy(self.orch, fleet_T_max=fleet.T_max)
        sim.params = jax.jit(self.model.init)(k_init)
        self.sim = sim
        self.warm_alphas, self.warm_cohorts = self._warm_plan()
        self._jobs: list = []
        self._capture: Optional[Checked] = None
        self._install_hooks()

    def _warm_plan(self) -> tuple[list, range]:
        """The alpha buckets and cohort sizes this cell's rounds give: P4
        solved for every device, as the program's ``prepare`` does, over
        ``WARM_DRAWS`` rounds of channel draws from copies of the
        Simulation's generator and fleet (the rounds themselves draw from
        the originals)."""
        from repro.core import schedule
        from repro.core.anycost import bucket_alpha
        sim = self.sim
        rng, fleet = copy.deepcopy(sim.rng), copy.deepcopy(sim.fleet)
        alphas, cohorts = set(), []
        for _ in range(WARM_DRAWS):
            ok = [s for s in map(schedule.solve, fleet.round_envs(
                rng, sim.W, sim.S_bits, t=0.0)) if s.feasible]
            alphas.update(bucket_alpha(s.alpha, sim.run_cfg.alpha_buckets)
                          for s in ok)
            if ok:       # a round that trains nobody aggregates nothing
                cohorts.append(len(ok))
        if not cohorts:
            raise ValueError("P4 is infeasible for every device in every "
                             "draw: the cell's rounds would train nobody")
        return sorted(alphas), range(min(cohorts), max(cohorts) + 1)

    # ------------------------------------------------------------ hooks

    def _span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(SPAN + name)

    def _wrap(self, obj: Any, attr: str, span: str, before=None,
              after=None) -> None:
        orig = getattr(obj, attr)

        def hooked(*args, **kwargs):
            if before is not None:
                before(*args, **kwargs)
            with self._span(span):
                out = orig(*args, **kwargs)
            if after is not None:
                after(out, *args, **kwargs)
            return out

        setattr(obj, f"_bench_orig_{attr}", orig)
        setattr(obj, attr, hooked)

    def _install_hooks(self) -> None:
        sim = self.sim

        def capture_client(p, trained, sorted_params, *, sub=None, **_kw):
            steps, b = jax.tree_util.tree_leaves(p.batches)[0].shape[:2]
            self._jobs.append((float(p.alpha), int(steps) * int(b)))
            if self._capture is not None:
                if sub is None:       # the unpooled route shrinks inside
                    sub = sim.shrink_fast(sorted_params, p.alpha)
                self._capture.clients.append(reference.Client(
                    alpha=float(p.alpha), beta=float(p.strat.beta),
                    key=p.key, batches=jax.tree.map(np.asarray,
                                                    p.batches)))
                u = _flat_update(sub, trained)
                self._capture.change_sq += float(np.sum(np.square(
                    u.astype(np.float64))))
                if self._capture.updates is not None:
                    self._capture.updates.append(u)

        def capture_delta(out, sorted_params, *_a, **_kw):
            if self._capture is not None:
                self._capture.delta_norms = {
                    k: float(v) for k, v in
                    _delta_norms(out, sorted_params).items()}
                if self._capture.updates is not None:
                    self._capture.change = _change(out, sorted_params)

        self._wrap(sim.fleet, "round_envs", "round_envs")
        self._wrap(sim, "sort_params", "sort_params")
        self._wrap(sim, "prepare", "prepare")
        self._wrap(sim.pool, "train_shared", "local_train")
        self._wrap(sim, "train_one", "local_train")
        self._wrap(sim, "materialize", "finish", before=capture_client)
        self._wrap(sim, "aggregate", "aggregate", after=capture_delta)
        self._wrap(sim, "evaluate", "eval")

    # ----------------------------------------------------------- rounds

    def round(self) -> Round:
        """One whole round; it ends when its eval is back on the host."""
        self._jobs = []
        c0 = self.compiles.count
        t0 = time.perf_counter()
        with self._span("round"):
            hist = self._runner._run_round_based(self.sim, self.policy,
                                                 self.orch, False)
        dt = time.perf_counter() - t0
        self.sim.params = hist.params
        log = hist.rounds[-1]
        loss = log.test_loss if log.test_loss is not None else math.nan
        return Round(dt, int(log.n_clients), tuple(self._jobs),
                     float(loss), self.compiles.count - c0)

    def checked_round(self, keep: bool = False) -> tuple[Round, Checked]:
        """A round whose inputs and results are captured for the output
        check; ``keep`` also keeps every client's local change and the
        server's change of the parameters."""
        self._capture = Checked(clients=[], delta_norms={},
                                test_loss=math.nan,
                                updates=[] if keep else None)
        try:
            r = self.round()
            cap = self._capture
        finally:
            self._capture = None
        cap.test_loss = r.test_loss
        return r, cap

    # ----------------------------------------------------------- warm-up

    def _shapes(self) -> tuple[int, int, list]:
        """(local steps, batch, padded group sizes) of this cell's rounds."""
        from repro.orchestrator import client_pool
        cfg = self.config
        n_dev = cfg["n_devices"]
        n = cfg["n_train"] // n_dev
        bs = min(cfg["batch_size"], n)
        steps = max(int(round(cfg["tau"] * n / bs)), 1)
        sizes = sorted({1} | {client_pool._pad_size(k)
                              for k in range(2, n_dev + 1)})
        return steps, bs, sizes

    def _batches(self, lanes: int):
        from repro.orchestrator import client_pool
        steps, bs, _ = self._shapes()
        one = jax.tree.map(jnp.asarray,
                           self.task.zero_batch(self.config, steps, bs))
        return one if lanes == 1 else client_pool._tree_stack([one] * lanes)

    def compile_all(self) -> None:
        """Compile, in parallel threads, the programs that dominate compile
        time: the local steps of each of the cell's ``warm_alphas`` (pooled:
        at every padded group size) and the per-width finish programs.
        Runs nothing."""
        sim = self.sim
        steps, _, sizes = self._shapes()
        sorted_params = sim.sort_params(sim.params)
        if not self.use_pool:
            one = self._batches(1)
            _compile_parallel([
                (sim.client._local_steps(alpha, steps),
                 (sim.shrink_fast(sorted_params, alpha), one))
                for alpha in self.warm_alphas])
            return
        key = jax.random.split(jax.random.PRNGKey(0), 3)[2]
        rho, levels = sim.client.finish_plan(0.05, None)
        batches = {k: self._batches(k) for k in sizes}
        jobs = []
        for alpha in self.warm_alphas:
            sub = sim.shrink_fast(sorted_params, alpha)
            trained = jax.tree.map(np.asarray, sub)
            single = sim.client._local_steps_fast(alpha, steps)
            jobs.append((sim.client._finish_core(alpha),
                         (sub, trained, rho, levels, key)))
            for k in sizes:
                fn = single if k == 1 else sim.pool._vmapped(
                    alpha, steps, k, True)
                jobs.append((fn, (sub, batches[k])))
        _compile_parallel(jobs)

    def warm_shapes(self) -> None:
        """Run once every program a round of this cell can call, so that
        the window finds each traced and compiled: the local steps of each
        of ``warm_alphas`` (pooled: at each padded group size), the shrink
        and finish of each width, and the aggregate at each of
        ``warm_cohorts`` (pooled: compiled in parallel first)."""
        from repro.core.schedule import Strategy
        from repro.orchestrator.client_pool import TrainJob
        from repro.orchestrator.policies import base_weights

        sim = self.sim
        steps, _, sizes = self._shapes()
        sorted_params = sim.sort_params(sim.params)
        strat = Strategy(alpha=1.0, beta=0.05, freq=1e9, phi=0.5,
                         varphi=0.5, gain=0.05, T_cmp=1.0, T_com=1.0,
                         E_cmp=1.0, E_com=1.0, feasible=True)
        train = sim.pool._bench_orig_train_shared
        one = self._batches(1)
        finished = None
        for alpha in self.warm_alphas:
            if not self.use_pool:
                trained = sim._bench_orig_train_one(types.SimpleNamespace(
                    alpha=alpha, n_steps=steps, batches=one), sorted_params)
                finished = sim.client.finish_round(
                    sorted_params, alpha, trained, strat, steps,
                    jax.random.PRNGKey(0), planner=None, w_per_sample=sim.W)
                continue
            sub = sim.shrink_fast(sorted_params, alpha)
            for k in sizes:
                jobs = [TrainJob(i, alpha, one) for i in range(k)]
                trained = train(sorted_params, jobs, {alpha: sub})
            finished = sim.client.finish_round_fast(
                alpha, trained[0], strat, steps, jax.random.PRNGKey(0),
                sub=sub, planner=None, w_per_sample=sim.W)
        upd = types.SimpleNamespace(update=finished)
        aggregate = sim._bench_orig_aggregate
        cohorts = self.warm_cohorts
        weights = {k: base_weights("anycostfl", True, [finished] * k, [])
                   for k in cohorts}
        if sim._agg_fast is not None:
            _compile_parallel([
                (sim._agg_fast, (sorted_params, [finished.values] * k,
                                 [finished.mask] * k, weights[k]))
                for k in cohorts])
        for k in cohorts:
            out = aggregate(sorted_params, [upd] * k, weights[k],
                            fast=self.use_pool)
        jax.block_until_ready(out)

    def close(self) -> None:
        """Drop the program's state so that its device buffers are freed."""
        self.sim = None
        self._runner = None


def _compile_parallel(jobs) -> None:
    """``fn.lower(*args).compile()`` for each job, on a pool of threads
    (XLA compiles outside the interpreter lock).  A later call of ``fn``
    with arguments like these finds the compiled program."""
    import concurrent.futures
    import os
    workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 1))
    with concurrent.futures.ThreadPoolExecutor(workers) as ex:
        for f in [ex.submit(lambda j: j[0].lower(*j[1]).compile(), j)
                  for j in jobs]:
            f.result()


def _flat_update(start, trained) -> np.ndarray:
    """``start - trained`` of one client's sub-model, its leaves in tree
    order, as one float32 vector on the host."""
    return np.concatenate([
        (np.asarray(s, np.float32) - np.asarray(t, np.float32)).reshape(-1)
        for s, t in zip(jax.tree.leaves(start), jax.tree.leaves(trained))])


def _change(new_params, old_params) -> dict:
    old = reference.flat(old_params)
    return jax.device_get({k: x - old[k] for k, x in
                           reference.flat(new_params).items()})


def _delta_norms(new_params, old_params) -> dict:
    old = reference.flat(old_params)
    return jax.device_get({k: jnp.linalg.norm((x - old[k]).reshape(-1))
                           for k, x in reference.flat(new_params).items()})
