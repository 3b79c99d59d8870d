#!/usr/bin/env python3
"""The program's host spans in one traced window of a cell (run on the chip).

    python3 bench/host_spans.py --workload fmnist.sync.unpooled --seed 7 --seconds 51

Sets the cell up as a benchmark run does (compile, as many plain rounds
as a run checks, warm-up of every shape), runs whole rounds for
``--seconds`` under the profiler, and prints one JSON line: the per-round
host metrics of ``bench/span_reduce.py`` (``METRICS``), every ``fl.*``
span's count, total, self and idle seconds and stat sums, the share of
the device's idle time that lies inside ``fl.round``, and the bytes put
on the device by shape arithmetic beside what ``fl.h2d`` counted.  Runs
no output check.  Without a TPU it exits 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402


def h2d_by_shapes(cell, rounds) -> float:
    """Bytes a window's rounds put on the device: each trained client's
    minibatches, at the task file's bytes per sample (the compiled FGC
    finish holds its segment ids as constants on both routes)."""
    per_sample = cell.task.bytes_per_sample(cell.config)
    return float(sum(samples * per_sample
                     for r in rounds for _, samples in r.jobs))


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/host_spans.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    workload, config = harness.load_cell(args.workload)
    try:
        harness.check_devices(workload["chips"])
    except harness.DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.enable_cache()
    import jax

    from bench import cell as cell_mod, span_reduce, trace_reduce

    compiles = cell_mod.CompileCounter()
    cell = cell_mod.Cell(workload, config, args.seed, compiles, trace=True)
    cell.compile_all()
    for _ in range(cell_mod.CHECKED_ROUNDS):
        cell.round()
    cell.warm_shapes()
    setup_s = time.perf_counter() - T_START

    log_dir = tempfile.mkdtemp(prefix="bench_spans_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    rounds = []
    t0 = time.perf_counter()
    try:
        while True:
            rounds.append(cell.round())
            if time.perf_counter() - t0 >= args.seconds:
                break
    finally:
        window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
    try:
        events, spans = span_reduce.read(trace_reduce.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    summary = trace_reduce.reduce(events)
    window = summary.window if summary else span_reduce.round_window(spans)
    busy = span_reduce.device_busy(events, window)
    totals = span_reduce.reduce(spans, window, busy)
    n = len(rounds)
    idle = (window[1] - window[0]) - trace_reduce.length(busy)
    in_round = totals.get(span_reduce.ROUND)
    expected = h2d_by_shapes(cell, rounds) / n
    counted = span_reduce.per_round(totals, n).get("h2d.bytes")
    line = {
        "workload": args.workload, "seed": args.seed, "rounds": n,
        "setup_s": setup_s, "round_s_traced": window_s / n,
        "clients": [r.n_clients for r in rounds],
        "compiles_window": sum(r.compiles for r in rounds),
        "metrics": {k: {"value": v, "unit": span_reduce.METRICS[k][2]}
                    for k, v in span_reduce.per_round(totals, n).items()},
        "device_idle_s_per_round": idle / n,
        "idle_inside_round_share": (in_round.idle_s / idle
                                    if in_round and idle > 0 else None),
        "h2d_bytes_by_shapes": expected,
        "h2d_counted_over_shapes": (counted / expected
                                    if counted is not None else None),
        "spans": {k: t._asdict() for k, t in sorted(totals.items())},
        "idle_gaps": summary.gaps if summary else None,
    }
    cell.close()
    compiles.close()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
