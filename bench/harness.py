"""One run of one benchmark cell: set-up, measured window, output check.

Everything that belongs to a cell, a configuration or a per-layer metric
is a file found by name: ``bench/workloads/<cell>.json``,
``bench/configs/<config>.json``, the reference model
``bench/models/<arch>.py`` and its data ``bench/tasks/<task>.py`` (the
interface is in ``bench/reference.py``), and ``bench/metrics/<metric>.py``.
``BENCHMARK.json`` says which metrics a cell reports.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import NamedTuple, Optional

from bench.paths import BENCH, ROOT


class DeviceError(RuntimeError):
    """No accelerator, the wrong one, or fewer chips than the cell needs."""


# ------------------------------------------------------------------ files

def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_dir=BENCH) -> tuple[dict, dict]:
    """The workload file of cell ``name`` and its configuration file."""
    workload = load_json(bench_dir / "workloads" / f"{name}.json")
    config = load_json(bench_dir / "configs" / f"{workload['config']}.json")
    return workload, config


def load_metric(name: str, bench_dir=BENCH):
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(benchmark: dict, cell: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries that ``cell`` reports."""
    def applies(m):
        return "workloads" not in m or cell in m["workloads"]
    return ([m for m in benchmark["end_to_end"] if applies(m)],
            [m for m in benchmark["per_layer"] if applies(m)])


# ----------------------------------------------------------------- device

def check_devices(chips: int) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise DeviceError(f"no TPU: JAX found {len(devices)} "
                          f"{d.platform} device(s) ({d.device_kind})")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devices)}")
    peaks(d.device_kind)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise DeviceError(f"no peaks for device kind {device_kind!r} in "
                          f"bench/peaks.json")
    return table[device_kind]


def memory_peak() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


CACHE_DIR = ROOT / ".jax_cache"


def enable_cache() -> str:
    """JAX's persistent compile cache in ``<checkout>/.jax_cache``, a fixed
    path inside the checkout, handed to the program's own helper through
    the variable it reads.  No size cap: a capped cache evicts this cell's
    large programs and takes a file lock on every read and write."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    # every program goes into the cache, however small or quick to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# -------------------------------------------------------------------- run

class Readings(NamedTuple):
    """What a per-layer metric's reader gets."""
    rounds: int
    compiles: int
    model_flops: float          # forward + backward + eval, whole window
    peak_flops_per_s: float
    trace: Optional[object]     # trace_reduce.Summary, None if untraced

    def program_seconds(self, names) -> Optional[float]:
        if self.trace is None:
            return None
        found = [self.trace.programs[n] for n in names
                 if n in self.trace.programs]
        return sum(found) if found else None


class Result(NamedTuple):
    line: dict
    check: dict
    notes: list


def run_cell(workload: dict, config: dict, seed: int, seconds: float,
             trace: bool, metrics: tuple[list, list], t_start: float,
             device: dict, *, peak_flops: float = math.nan,
             bench_dir=BENCH, break_program=None) -> Result:
    """Set up, measure, check.  ``break_program(cell)``, for tests only,
    plants a fault in the program before its first round."""
    import jax

    from bench import cell as cell_mod, compare, flops, reference
    from bench import trace_reduce

    compiles = cell_mod.CompileCounter()
    phases = {"start": time.perf_counter() - t_start}
    cell = cell_mod.Cell(workload, config, seed, compiles, trace=trace,
                         bench_dir=bench_dir)
    if break_program is not None:
        break_program(cell)
    phases["build"] = time.perf_counter() - t_start
    cell.compile_all()
    phases["compile_all"] = time.perf_counter() - t_start
    checked = [cell.checked_round()
               for _ in range(cell_mod.CHECKED_ROUNDS)]
    phases["checked_rounds"] = time.perf_counter() - t_start
    cell.warm_shapes()
    setup_s = time.perf_counter() - t_start
    phases["warm_shapes"] = setup_s
    phases["cache_hits"], phases["cache_misses"] = (compiles.hits,
                                                    compiles.misses)

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    rounds = []
    t0 = time.perf_counter()
    try:
        while True:
            rounds.append(cell.round())
            if time.perf_counter() - t0 >= seconds:
                break
    finally:
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
    mem_peak = memory_peak()
    compiles.close()
    model = cell.model
    test = cell.test
    cell.close()
    del cell
    gc.collect()

    summary = None
    if trace:
        try:
            summary = trace_reduce.reduce(trace_reduce.read_events(
                trace_reduce.find_xplane(log_dir)))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    # ---- the output check: the reference follows the checked rounds
    t_ref = time.perf_counter()
    ref = reference.follow(model, seed, [cap.clients for _, cap in checked],
                           config["lr"], test)
    values = compare.numbers(program_rounds([cap for _, cap in checked]),
                             ref)
    phases["reference_s"] = time.perf_counter() - t_ref
    limits = workload["limits"]
    setup_ok = all(r.ok for r, _ in checked)
    correct = setup_ok and compare.verdict(values, limits)
    check = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    check["checked_rounds_trained"] = {
        "value": min(r.n_clients for r, _ in checked), "limit": 1}

    # ---- metrics
    e2e, per_layer = metrics
    n = len(rounds)
    values_e2e = {
        "round_s": window_s / n,
        "client_samples_per_s": sum(r.samples for r in rounds) / window_s,
        "setup_s": setup_s,
    }
    out_metrics = {}
    if not trace:
        for m in e2e:
            out_metrics[m["name"]] = {"value": values_e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        model_flops = sum(flops.train(model, a) * s
                          for r in rounds for a, s in r.jobs) \
            + n * flops.forward(model) * config["n_test"]
        readings = Readings(rounds=n, compiles=sum(r.compiles
                                                   for r in rounds),
                            model_flops=float(model_flops),
                            peak_flops_per_s=peak_flops, trace=summary)
        for m in per_layer:
            v = load_metric(m["name"], bench_dir).read(readings)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(device)
    device["memory_peak_bytes"] = mem_peak
    if trace:
        device["busy_s"] = summary.busy_s if summary else 0.0
        device["window_s"] = summary.window_s if summary else window_s
    line = {"correct": correct, "attempted": n,
            "failed": sum(not r.ok for r in rounds),
            "metrics": out_metrics, "device": device}
    if summary is not None:
        line["breakdown"] = {"device_ops": [list(x) for x in summary.ops],
                             "idle_gaps": [list(x) for x in summary.gaps]}
    line["check"] = check
    notes = [
        {"setup_s": setup_s, "window_s": window_s, "rounds": n,
         "memory_peak_bytes": mem_peak, "setup_phases_s": phases,
         "numbers": values},
        {"checked_rounds": [{"seconds": r.seconds, "clients": r.n_clients,
                             "test_loss": r.test_loss,
                             "compiles": r.compiles} for r, _ in checked],
         "reference_test_loss": ref.losses},
        {"window_rounds": [{"seconds": r.seconds, "clients": r.n_clients,
                            "samples": r.samples, "compiles": r.compiles,
                            "test_loss": r.test_loss} for r in rounds]},
    ]
    if summary is not None:
        notes.append({"programs_device_s": summary.programs})
    return Result(line, check, notes)


def program_rounds(checked) -> "reference.Rounds":
    """The program's checked rounds, as the reference reports its own."""
    from bench import reference
    return reference.Rounds([c.delta_norms for c in checked],
                            [c.test_loss for c in checked],
                            [c.change_sq for c in checked],
                            checked[0].updates or [],
                            checked[0].change or {})


# ------------------------------------------------------------------- main

def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    benchmark = load_json(ROOT / "BENCHMARK.json")
    workload, config = load_cell(args.workload)
    try:
        device = check_devices(workload["chips"])
    except DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    enable_cache()
    res = run_cell(workload, config, args.seed, args.seconds,
                   bool(args.trace), cell_metrics(benchmark, args.workload),
                   t_start, device,
                   peak_flops=peaks(device["kind"])["bf16_flops_per_s"])
    for note in res.notes:
        print(json.dumps(note))
    for k, v in res.check.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res.line), flush=True)
    return 0
