"""Readings that the output check's limits are set from (run on the chip).

    python3 bench/calibrate.py --workload fmnist.sync.unpooled --seeds 11 12 13 ...

For each seed it builds the cell and runs its checked rounds through the
program, as a benchmark run does, then follows the same rounds with

* the float32 reference (``program`` row: the program against it);
* the reference in bfloat16 in the program's place (``control`` row);
* the reference fed half of each minibatch, the mean taken over the rest
  (``half_batch`` row, a planted fault);
* the float32 reference with its products at the default precision
  (``default_precision`` row: what the TPU's default precision alone
  moves).

It prints one JSON line per seed with the compared numbers of each row.
A program that returns its state unchanged reads ``train_gap`` and
``update_gap`` 1 by construction and needs no run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402
from bench.paths import BENCH  # noqa: E402


def readings(workload: dict, config: dict, seed: int) -> dict:
    from bench import cell as cell_mod, compare, reference
    t0 = time.perf_counter()
    counter = cell_mod.CompileCounter()
    cell = cell_mod.Cell(workload, config, seed, counter)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    checked = []
    for i in range(cell_mod.CHECKED_ROUNDS):
        r, cap = cell.checked_round(keep=i == 0)
        checked.append(cap)
    t_prog = time.perf_counter() - t0
    model, test = cell.model, cell.test
    cell.close()
    counter.close()
    out = {"seed": seed, "build_s": t_build, "program_s": t_prog,
           "clients": [len(c.clients) for c in checked],
           "lowerings": counter.count, "cache_hits": counter.hits,
           "cache_misses": counter.misses}
    t0 = time.perf_counter()
    rounds = [c.clients for c in checked]

    def follow(**kw):
        return reference.follow(model, seed, rounds, config["lr"], test,
                                keep=True, **kw)

    ref = follow()
    out["reference_s"] = time.perf_counter() - t0
    rows = {"program": harness.program_rounds(checked),
            "control": follow(dtype="bfloat16"),
            "half_batch": follow(half_batch=True),
            "default_precision": follow(highest=False)}
    for name, side in rows.items():
        out[name] = compare.numbers(side, ref)
        out[name].update(compare.diffs(side, ref))
        out[name]["worst_leaf_gap"] = compare.worst_leaf_gap(
            side.deltas[0], ref.deltas[0])
        out[name]["losses"] = side.losses
        out[name]["round0"] = side.deltas[0]
    out["reference"] = {"losses": ref.losses, "round0": ref.deltas[0]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--lr", type=float,
                    help="train at this rate, not the configuration's")
    args = ap.parse_args(argv)
    workload, config = harness.load_cell(args.workload, BENCH)
    if args.lr is not None:
        config = dict(config, lr=args.lr)
    try:
        harness.check_devices(workload["chips"])
    except harness.DeviceError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    harness.enable_cache()
    for seed in args.seeds:
        print(json.dumps(readings(workload, config, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
