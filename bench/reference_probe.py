#!/usr/bin/env python3
"""The plain reference's memory and time on one device, at a model's size.

    python3 bench/reference_probe.py --dir tests/bench/fixtures/toy \
        --config toy-lm-300m --seed 7

The memory check for a new configuration: before its cell goes into
``BENCHMARK.json``, run this on the chip at the cell's own rows, row length
and alpha buckets, and keep ``memory_peak_bytes`` well under the chip's
memory, since the harness runs the reference after the program's window.
Where it does not fit, the model file's ``PASS_ROWS`` takes fewer rows in
one pass.

Loads the configuration ``<dir>/configs/<config>.json``, its model file and
task file (``reference.load_model``), makes the data from ``--seed``, and
follows ``--rounds`` synchronous rounds of ``--clients`` clients of
``--steps`` local steps through ``reference.follow`` in float32 at
``Precision.HIGHEST``, each round's clients cycling through the
configuration's ``alpha_buckets``.  Prints one JSON line: the device, its
peak memory (``memory_peak_bytes``, null where the backend reports none),
the seconds the data took and the seconds ``follow`` took, compiling
included, and the test loss after each round.
"""
import argparse
import json
import pathlib
import sys
import time

T_START = time.perf_counter()

BETAS = (1 / 15, 0.2, 0.5, 0.3)     # FGC's beta, client by client


def run(bench_dir, config_name: str, seed: int, rounds: int, clients: int,
        steps: int) -> dict:
    import jax

    from bench import harness, reference
    bench_dir = pathlib.Path(bench_dir)
    config = harness.load_json(bench_dir / "configs" / f"{config_name}.json")
    model = reference.load_model(config["arch"], bench_dir)
    task = reference.load_task(model.TASK, bench_dir)
    batch = config["batch_size"]
    need = rounds * clients * steps * batch
    if config["n_train"] < need:
        raise ValueError(f"{config_name}: {rounds} rounds of {clients} "
                         f"clients of {steps} steps of {batch} rows need "
                         f"{need} train rows, n_train is {config['n_train']}")
    t0 = time.perf_counter()
    k_data, k_clients = jax.random.split(reference.seed_key(seed))
    train, test = (task.rows(s) for s in task.make(k_data, config))
    buckets = config["alpha_buckets"]
    plan = []
    for r in range(rounds):
        plan.append([])
        for i in range(clients):
            at = (r * clients + i) * steps * batch
            plan[-1].append(reference.Client(
                alpha=buckets[(r * clients + i) % len(buckets)],
                beta=BETAS[i % len(BETAS)],
                key=jax.random.fold_in(k_clients, r * clients + i),
                batches={k: x[at:at + steps * batch].reshape(
                    (steps, batch) + x.shape[1:]) for k, x in train.items()}))
    t1 = time.perf_counter()
    out = reference.follow(model, seed, plan, config["lr"], test)
    t2 = time.perf_counter()
    d = jax.devices()[0]
    return {"config": config_name, "n_params": reference.n_params(model),
            "rounds": rounds, "clients": clients, "steps": steps,
            "batch": batch, "platform": d.platform, "kind": d.device_kind,
            "memory_peak_bytes": harness.memory_peak(),
            "data_s": t1 - t0, "follow_s": t2 - t1,
            "process_s": t2 - T_START, "losses": out.losses,
            "change_sq": out.change_sq}


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/reference_probe.py")
    ap.add_argument("--dir", required=True,
                    help="directory holding configs/, models/, tasks/")
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--steps", type=int, default=2)
    a = ap.parse_args(argv)
    print(json.dumps(run(a.dir, a.config, a.seed, a.rounds, a.clients,
                         a.steps)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    sys.exit(main(sys.argv[1:]))
