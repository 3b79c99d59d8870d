"""End-to-end training driver.

Two modes:
  * ``--mode pod``  — the production-style LM trainer: builds the mesh that
    fits the available devices, shards params/optimizer with the logical
    rules, and runs real steps on synthetic token data (CPU: reduced
    configs; TPU: full configs).
  * ``--mode fl``   — the paper's federated simulation (train/fl_loop.py)
    with AnycostFL or any baseline.

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode fl --method anycostfl \
      --rounds 40 --devices 12
  PYTHONPATH=src python -m repro.launch.train --mode pod --arch qwen2-7b \
      --reduced --steps 20
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.configs import get_config, TRAIN_4K
from repro.configs.base import InputShape
from repro.data.synthetic import make_token_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step, param_shardings, \
    opt_state_shardings, batch_shardings, input_specs
from repro.models.registry import build_model
from repro.train.checkpoint import save_checkpoint
from repro.train.optimizer import adamw


def run_pod(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    opt = adamw(args.lr, warmup=10)
    mesh = make_host_mesh()
    shape = InputShape("cli", args.seq_len, args.batch, "train")
    rng = np.random.default_rng(args.seed)
    docs = make_token_dataset(rng, max(args.batch * 4, 16), args.seq_len,
                              cfg.vocab_size)

    with shd.use_sharding(mesh):
        params = model.init(jax.random.PRNGKey(args.seed))
        opt_state = opt.init(params)
        step = make_train_step(model, opt, remat=args.remat)
        with mesh:
            jstep = jax.jit(step)
            losses = []
            # repro: ignore[unseeded-randomness] — operator progress
            # timing only; never feeds model or simulation state.
            t0 = time.time()
            for i in range(args.steps):
                idx = rng.integers(0, docs.shape[0], args.batch)
                batch = {"tokens": jnp.asarray(docs[idx])}
                extras = _modality_extras(cfg, args.batch, args.seq_len)
                batch.update(extras)
                params, opt_state, loss = jstep(params, opt_state, batch)
                losses.append(float(loss))
                if i % max(args.steps // 10, 1) == 0:
                    print(f"step {i:4d} loss {float(loss):.4f} "
                          # repro: ignore[unseeded-randomness] — progress
                          f"({time.time() - t0:.1f}s)")
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, params, step=args.steps)
        print(f"checkpoint -> {args.checkpoint}")
    return losses


def _modality_extras(cfg, batch, seq_len):
    key = jax.random.PRNGKey(7)
    if cfg.family == "vlm":
        v = cfg.vlm
        n_p = min(v.n_patches, seq_len)
        return {"patch_embeds": jax.random.normal(
            key, (batch, n_p, v.patch_embed_dim), cfg.param_dtype)}
    if cfg.family == "encdec":
        e = cfg.encdec
        return {"frames": jax.random.normal(
            key, (batch, e.n_frames, cfg.d_model), cfg.param_dtype)}
    return {}


def _dynamics_config(args):
    """Fleet-dynamics control plane from CLI flags.  The defaults
    (``--availability always --battery off --selection uniform``) build a
    trivial config that reproduces the static fleet bit-for-bit."""
    from repro.fleet import (AvailabilityConfig, BatteryConfig,
                             FleetDynamicsConfig)
    avail = AvailabilityConfig(
        kind=args.availability,
        seed=args.availability_seed
        if args.availability_seed is not None else args.seed,
        # one scenario file can drive positions AND availability: replay
        # availability composes with --scenario-trace when no dedicated
        # --trace-file is given
        trace_file=args.trace_file or args.scenario_trace)
    battery = None
    if args.battery == "on":
        battery = BatteryConfig(capacity_j=args.battery_capacity,
                                recharge_w=args.battery_recharge,
                                seed=args.seed)
    return FleetDynamicsConfig(
        availability=avail, battery=battery, selection=args.selection,
        participation=args.participation,
        selection_seed=args.selection_seed,
        soc_deadline_scale=args.soc_deadline_scale,
        soc_deadline_threshold=args.soc_deadline_threshold)


def _topology_config(args):
    """Multi-cell topology from CLI flags.  ``--topology flat`` (the
    default) returns None — the paper's single macro cell, bit-identical
    to the pre-topology loop."""
    if args.topology == "flat":
        return None
    from repro.mobility import HandoverConfig
    from repro.topology import BackhaulConfig, TopologyConfig
    handover = None
    if args.mobility != "static" and args.handover_policy != "none":
        handover = HandoverConfig(policy=args.handover_policy,
                                  margin_m=args.handover_margin)
    return TopologyConfig(
        kind="hier", n_cells=args.cells,
        assignment=args.cell_assignment,
        cell_radius_scale=args.cell_radius_scale,
        cell_deadline_s=args.cell_deadline,
        handover=handover,
        backhaul_rate_range=(tuple(args.backhaul_rate_range)
                             if args.backhaul_rate_range else None),
        backhaul_het_seed=args.seed,
        backhaul=BackhaulConfig(
            rate_bps=args.backhaul_rate,
            latency_s=args.backhaul_latency,
            energy_per_bit=args.backhaul_energy,
            codec=args.backhaul_codec,
            error_feedback=args.backhaul_ef))


def _mobility_config(args):
    """Device motion from CLI flags.  ``--mobility static`` (the
    default) returns None — the paper's per-round position re-drop,
    bit-identical to the pre-mobility loop."""
    if args.mobility == "static":
        return None
    from repro.mobility import MobilityConfig
    speed = args.speed
    return MobilityConfig(
        kind=args.mobility,
        seed=args.mobility_seed if args.mobility_seed is not None
        else args.seed,
        speed_range=(0.5 * speed, 1.5 * speed),
        mean_speed=speed,
        scenario_file=args.scenario_trace)


def run_fl(args):
    from repro.orchestrator import OrchestratorConfig, run_orchestrated
    from repro.sysmodel.population import FleetConfig
    from repro.telemetry import NULL_TELEMETRY, Telemetry, build_manifest
    from repro.train.fl_loop import FLRunConfig, PHASES
    family = get_config(args.arch).family
    if family != "cnn":
        raise SystemExit(f"--mode fl trains the image CNNs (fmnist-cnn, "
                         f"vgg9-cifar); --arch {args.arch} is a {family} "
                         f"model")
    run_cfg = FLRunConfig(
        arch=args.arch, method=args.method, rounds=args.rounds, lr=args.lr,
        seed=args.seed, iid=not args.non_iid, n_train=args.n_train,
        n_test=args.n_test, eval_every=args.eval_every)
    fleet = FleetConfig(n_devices=args.devices,
                        dynamics=_dynamics_config(args),
                        topology=_topology_config(args),
                        mobility=_mobility_config(args))
    orch = OrchestratorConfig(
        policy=args.async_mode, max_wallclock_s=args.max_wallclock,
        deadline_s=args.deadline, buffer_size=args.buffer_size,
        staleness_exponent=args.staleness_exp,
        staleness_cap=args.staleness_cap,
        staleness_mode=args.staleness_mode,
        straggler_mode=args.straggler_mode,
        max_inflight=args.max_inflight,
        agg_route=args.agg_route,
        use_pool=False if args.no_pool else None,
        event_trace_limit=args.event_trace_limit)
    if args.telemetry_dir:
        rollup = None
        if args.telemetry_rollup is not None:
            from repro.telemetry import RollupPolicy
            rollup = RollupPolicy(device_threshold=args.telemetry_rollup,
                                  seed=args.seed)
        tel = Telemetry(args.telemetry_dir,
                        rollup=rollup,
                        trace_sample=args.trace_sample,
                        trace_seed=args.seed)
    else:
        tel = NULL_TELEMETRY
    if args.health:
        if not tel.enabled:
            raise SystemExit("--health needs --telemetry-dir: the health "
                             "engine evaluates the learning.* series a "
                             "telemetry session records")
        from repro.telemetry import DEFAULT_RULES, HealthEngine, load_rules
        rules = load_rules(args.health_rules) if args.health_rules \
            else DEFAULT_RULES
        tel.health = HealthEngine(rules)
    hist = run_orchestrated(run_cfg, fleet, orch, verbose=True,
                            telemetry=tel, jax_profile=args.jax_profile)
    # time-to-accuracy: simulated wall-clock at fixed accuracy milestones
    tta = {f"acc>={th:.2f}": hist.time_to_acc(th)
           for th in (0.3, 0.5, 0.7, 0.9) if hist.best_acc >= th}
    print(json.dumps({"method": args.method, "policy": args.async_mode,
                      "availability": args.availability,
                      "selection": args.selection,
                      "topology": args.topology,
                      "cells": args.cells if args.topology == "hier" else 1,
                      "mobility": args.mobility,
                      "handover_policy": args.handover_policy,
                      "n_handovers": hist.total_handovers(),
                      "best_acc": hist.best_acc,
                      "sim_wallclock_s": hist.wallclock(),
                      "backhaul_mb": float(sum(r.backhaul_bits
                                               for r in hist.rounds) / 8e6),
                      "time_to_acc_s": tta,
                      "rows": hist.to_rows()[-1]}, indent=1))
    # per-phase cost attribution (always available: the registry backs
    # every RoundLog whether or not a telemetry dir was given)
    totals = hist.phase_totals()
    print("[cost attribution]")
    print(f"  {'phase':>9s} {'energy_j':>12s} {'latency_s':>12s} "
          f"{'comm_mb':>12s}")
    for phase in PHASES:
        print(f"  {phase:>9s} {totals['energy_j'][phase]:12.3f} "
              f"{totals['latency_s'][phase]:12.3f} "
              f"{totals['comm_bits'][phase] / 8e6:12.3f}")
    if tel.enabled:
        if tel.health is not None:
            for line in tel.health.summary_table():
                print(line)
        manifest = build_manifest(run_cfg, fleet, orch,
                                  trace_signature=hist.trace,
                                  extra={"phase_totals": totals,
                                         "best_acc": hist.best_acc,
                                         "n_alerts":
                                         (len(tel.health.alerts())
                                          if tel.health is not None
                                          else None)})
        paths = tel.flush(manifest=manifest)
        for kind, path in sorted(paths.items()):
            print(f"[telemetry] {kind}: {path}")
    return hist


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="fl", choices=["fl", "pod"])
    ap.add_argument("--arch", default="fmnist-cnn")
    ap.add_argument("--method", default="anycostfl")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--devices", type=int, default=12)
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--n-train", type=int, default=1536)
    ap.add_argument("--n-test", type=int, default=384)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--async-mode", default="sync",
                    choices=["sync", "semisync", "fedbuff"])
    ap.add_argument("--max-wallclock", type=float, default=None,
                    help="stop after this many *simulated* seconds "
                         "(fedbuff: overrides --rounds)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="semisync cutoff in seconds (default: fleet T_max)")
    ap.add_argument("--buffer-size", type=int, default=8,
                    help="fedbuff: updates per server merge")
    ap.add_argument("--staleness-exp", type=float, default=0.5,
                    help="fedbuff: weight *= (1+staleness)^-exp")
    ap.add_argument("--straggler-mode", default="drop",
                    choices=["drop", "downweight"])
    ap.add_argument("--staleness-cap", type=int, default=None,
                    help="fedbuff admission: reject updates staler than "
                         "this many server versions")
    ap.add_argument("--staleness-mode", default="drop",
                    choices=["drop", "requeue"],
                    help="what to do with a cap-rejected update: discard "
                         "it, or retrain its minibatches on the current "
                         "model")
    ap.add_argument("--max-inflight", type=int, default=None,
                    help="fedbuff: cap concurrent dispatched clients "
                         "(participation throttle; waiters join a FIFO)")
    ap.add_argument("--no-pool", action="store_true",
                    help="disable vmapped client batching")
    # ---- hierarchical multi-cell topology
    ap.add_argument("--topology", default="flat", choices=["flat", "hier"],
                    help="flat = the paper's single cell; hier = "
                         "client->edge->cloud with per-cell wireless, "
                         "streaming edge aggregation, and a modeled "
                         "backhaul (round-based policies only)")
    ap.add_argument("--cells", type=int, default=4,
                    help="number of edge cells under --topology hier")
    ap.add_argument("--cell-assignment", default="contiguous",
                    choices=["contiguous", "round_robin"],
                    help="device->cell mapping")
    ap.add_argument("--cell-radius-scale", type=float, default=None,
                    help="per-cell radius as a fraction of the macro "
                         "cell's (default: 1/sqrt(cells), area tiling)")
    ap.add_argument("--cell-deadline", type=float, default=None,
                    help="per-cell edge deadline in seconds (the edge "
                         "ships its partial then; late arrivals drop)")
    ap.add_argument("--backhaul-rate", type=float, default=1e9,
                    help="edge->cloud backhaul throughput in bit/s")
    ap.add_argument("--backhaul-latency", type=float, default=0.01,
                    help="edge->cloud one-way latency in seconds")
    ap.add_argument("--backhaul-energy", type=float, default=0.0,
                    help="edge->cloud energy tariff in J/bit")
    ap.add_argument("--backhaul-codec", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="wire dtype of the shipped (num, den) partial: "
                         "f32 = bitwise passthrough (flat-equivalent), "
                         "bf16 = 2x smaller, int8 = 4x smaller with "
                         "per-leaf amax scaling")
    ap.add_argument("--backhaul-ef", action="store_true",
                    help="feed each round's bf16/int8 backhaul "
                         "quantization error back into the next round's "
                         "shipped partial (per-cell EF residual)")
    ap.add_argument("--backhaul-rate-range", type=float, nargs=2,
                    default=None, metavar=("LO", "HI"),
                    help="heterogeneous backhaul: draw each cell's rate "
                         "log-uniformly from [LO, HI] bit/s (seeded per "
                         "cell id; overrides --backhaul-rate)")
    ap.add_argument("--agg-route", default="streaming",
                    choices=["streaming", "batched", "mesh"],
                    help="hierarchical aggregation route: streaming "
                         "edge fold (default), the batched (I,N) Eq.-5 "
                         "oracle, or core/distributed.mesh_cell_aggregate"
                         " over a 'cell' mesh axis (needs >= 2 "
                         "devices)")
    # ---- mobility & handover
    ap.add_argument("--mobility", default="static",
                    choices=["static", "random_waypoint", "gauss_markov",
                             "replay"],
                    help="device motion model (static = the paper's "
                         "per-round position re-drop, bit-identical to "
                         "the pre-mobility loop)")
    ap.add_argument("--speed", type=float, default=5.0,
                    help="mean device speed in m/s (random_waypoint "
                         "draws U[0.5x, 1.5x]; gauss_markov reverts to "
                         "this mean)")
    ap.add_argument("--mobility-seed", type=int, default=None,
                    help="motion-model seed (default: --seed)")
    ap.add_argument("--handover-policy", default="nearest",
                    choices=["none", "nearest", "load_balanced"],
                    help="round-boundary device->cell re-assignment for "
                         "mobile hierarchical fleets (none = stale-cell: "
                         "devices keep their initial cell)")
    ap.add_argument("--handover-margin", type=float, default=25.0,
                    help="handover hysteresis margin in metres")
    ap.add_argument("--scenario-trace", default=None,
                    help="unified JSON scenario for --mobility replay: "
                         "device waypoints + availability intervals + "
                         "per-cell backhaul rates over time (also feeds "
                         "--availability replay when no --trace-file is "
                         "given)")
    # ---- fleet dynamics control plane
    ap.add_argument("--availability", default="always",
                    choices=["always", "markov", "diurnal", "replay"],
                    help="device availability trace (always = the static "
                         "fleet of the paper)")
    ap.add_argument("--availability-seed", type=int, default=None,
                    help="trace seed (default: --seed)")
    ap.add_argument("--trace-file", default=None,
                    help="JSON on-intervals for --availability replay")
    ap.add_argument("--battery", default="off", choices=["off", "on"],
                    help="per-device state-of-charge model: dispatches "
                         "drain E_cmp+E_com, headroom clamps E_max")
    ap.add_argument("--battery-capacity", type=float, default=60.0,
                    help="battery capacity in joules")
    ap.add_argument("--battery-recharge", type=float, default=0.05,
                    help="trickle recharge in joules per simulated second")
    ap.add_argument("--soc-deadline-scale", type=float, default=None,
                    help="battery-aware deadline adaptation: shrink the "
                         "effective T_max handed to the P4 solver by "
                         "this factor while fleet mean SoC is below "
                         "--soc-deadline-threshold (no-op by default)")
    ap.add_argument("--soc-deadline-threshold", type=float, default=0.5,
                    help="mean-SoC fraction below which the deadline "
                         "adaptation kicks in")
    ap.add_argument("--selection", default="uniform",
                    choices=["uniform", "energy", "gain", "oort"],
                    help="client-selection policy (oort = gain x speed "
                         "utility with an exploration reserve)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="per-round cap as a fraction of available devices")
    ap.add_argument("--selection-seed", type=int, default=None,
                    help="independent seed for who-trains-when (default: "
                         "derived from --seed via a decorrelated stream, "
                         "so selection ablations never perturb model-init "
                         "or data draws)")
    # ---- telemetry / observability
    ap.add_argument("--telemetry-dir", default=None,
                    help="write the observability bundle here: "
                         "trace.perfetto.json (load in ui.perfetto.dev), "
                         "trace.jsonl, metrics.jsonl, manifest.json. "
                         "Off by default — disabled telemetry is "
                         "bitwise-invisible to the seeded run")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="run the round loop under jax.profiler and write "
                         "its trace (device planes and the fl.* host "
                         "spans; load in TensorBoard or Perfetto) to DIR. "
                         "Needs no telemetry session")
    ap.add_argument("--health", action="store_true",
                    help="attach the streaming health engine (needs "
                         "--telemetry-dir): rule-based detectors over "
                         "the learning.* / round.* series emit ALERT "
                         "trace instants, an alerts.jsonl in the "
                         "bundle, and a [health] end-of-run table")
    ap.add_argument("--health-rules", default=None,
                    help="JSON rule file overriding the default health "
                         "detectors (see telemetry/health.py for the "
                         "schema)")
    ap.add_argument("--telemetry-rollup", type=int, default=None,
                    metavar="N",
                    help="fleet-size threshold at which device-labeled "
                         "metrics fold into bounded per-cell quantile "
                         "sketches + top-K straggler/energy-hog "
                         "trackers (memory O(cells), not O(devices)); "
                         "below N — or without this flag — telemetry "
                         "keeps the exact per-device cells, "
                         "bitwise-identical to before")
    ap.add_argument("--trace-sample", type=float, default=None,
                    metavar="RATE",
                    help="keep only this fraction of device/<id> trace "
                         "rows, chosen by the deterministic hash "
                         "blake2b(seed, device_id) < RATE — never an "
                         "RNG stream — so replays of a seeded run "
                         "trace the same devices and sampled traces "
                         "stay comparable across runs")
    ap.add_argument("--event-trace-limit", type=int, default=None,
                    help="bound the in-memory event pop trace to the "
                         "newest N records (evicted records fold into a "
                         "rolling hash; the replay signature stays "
                         "deterministic). Default: retain everything")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=None,
                    help="learning rate (default: 0.05 for fl SGD, "
                         "3e-3 for pod AdamW)")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    # mode-dependent lr default: a None sentinel (not value equality, which
    # would also clobber an explicit --lr equal to the other mode's default)
    if args.lr is None:
        args.lr = 3e-3 if args.mode == "pod" else 0.05
        print(f"[train] using the {args.mode}-mode default lr {args.lr:g} "
              f"(pass --lr to override)")
    if args.mode == "pod":
        run_pod(args)
    else:
        run_fl(args)


if __name__ == "__main__":
    main()
