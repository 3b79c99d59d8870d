"""Host-side check of a configuration's budgets, without the chip.

    JAX_PLATFORMS=cpu python3 bench/feasibility.py fmnist-cnn [--draws 6] [--rounds 5]

For each of ``--draws`` seeded fleet draws it solves Problem P4 with the
program's own solver for every device over ``--rounds`` rounds of channel
draws, and prints how many devices are feasible in each round and how the
feasible devices fall into the EMS alpha buckets, with the quartiles of
their compression rate beta.  A budget that leaves
much of the fleet infeasible gives a cell that trains too few clients.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))
sys.path.insert(0, str(BENCH.parent / "src"))

from bench import reference  # noqa: E402


def fleet_config(cfg: dict):
    from repro.sysmodel.population import FleetConfig
    return FleetConfig(n_devices=cfg["n_devices"], T_max=cfg["T_max"],
                       E_max_range=tuple(cfg["E_max_range"]),
                       tau=cfg["tau"])


def feasibility(cfg: dict, draws: int, rounds: int) -> list[dict]:
    from repro.configs import get_config
    from repro.core import schedule
    from repro.core.anycost import bucket_alpha
    from repro.sysmodel.population import make_fleet
    from repro.train.fl_loop import flops_per_sample

    arch = get_config(cfg["arch"])
    W = flops_per_sample(arch)
    S_bits = 32.0 * reference.n_params(reference.load_model(cfg["arch"]))
    sizes = np.array([len(p) for p in np.array_split(
        np.arange(cfg["n_train"]), cfg["n_devices"])])
    buckets = tuple(cfg["alpha_buckets"])
    out = []
    for seed in range(draws):
        rng = np.random.default_rng(seed)
        fleet = make_fleet(rng, fleet_config(cfg), sizes)
        feasible, hist, betas = [], collections.Counter(), []
        for _ in range(rounds):
            strats = [schedule.solve(e)
                      for e in fleet.round_envs(rng, W, S_bits)]
            ok = [s for s in strats if s.feasible]
            feasible.append(len(ok))
            hist.update(bucket_alpha(s.alpha, buckets) for s in ok)
            betas.extend(s.beta for s in ok)
        out.append({"seed": seed, "feasible": feasible,
                    "alpha_hist": {str(b): hist.get(b, 0)
                                   for b in buckets},
                    "beta_quartiles": [float(q) for q in np.quantile(
                        betas, [0.25, 0.5, 0.75])] if betas else []})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--draws", type=int, default=6)
    ap.add_argument("--rounds", type=int, default=5)
    args = ap.parse_args(argv)
    cfg = json.loads((BENCH / "configs" / f"{args.config}.json").read_text())
    for row in feasibility(cfg, args.draws, args.rounds):
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
