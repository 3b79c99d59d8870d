"""Compile a cell's local-step programs for a described TPU, without one.

    JAX_PLATFORMS=cpu python3 bench/compile_probe.py fmnist.sync [--alphas 1.0 0.25] [--pads 1 64]

Builds the program's ``AnycostClient``/``ClientPool`` for the cell's
configuration and compiles the vmapped local-step program (``n_pad`` lanes,
or the single-client program for 1) for one chip of a described
``v5e:2x2``, printing the compile seconds and the compiler's memory
analysis of each.  Nothing runs, so it gives compile times and memory,
never run times.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import harness, reference  # noqa: E402
from bench.paths import ROOT  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    ap.add_argument("--alphas", type=float, nargs="+")
    ap.add_argument("--pads", type=int, nargs="+", default=[1, 64])
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.core import shrinking
    from repro.core.anycost import AnycostClient
    from repro.models.registry import build_model
    from repro.orchestrator.client_pool import ClientPool

    jax.config.update("jax_enable_compilation_cache", False)
    workload, cfg = harness.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    arch = get_config(cfg["arch"])
    model = build_model(arch)
    spec = shrinking.cnn_shrink_spec(arch)
    client = AnycostClient(model, spec, lr=cfg["lr"],
                           batch_size=cfg["batch_size"])
    pool = ClientPool(client)
    ref_model = reference.load_model(cfg["arch"])
    task = reference.load_task(ref_model.TASK)
    full = jax.eval_shape(ref_model.init, jax.random.PRNGKey(0))
    n = cfg["n_train"] // cfg["n_devices"]
    bs = min(cfg["batch_size"], n)
    steps = max(int(round(cfg["tau"] * n / bs)), 1)
    for alpha in args.alphas or cfg["alpha_buckets"]:
        sub = jax.eval_shape(lambda p: shrinking.shrink(p, alpha, spec),
                             full)
        sub = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), sub)
        for k in args.pads:
            lead = () if k == 1 else (k,)
            batches = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                lead + x.shape, x.dtype, sharding=chip),
                task.zero_batch(cfg, steps, bs))
            fn = client._local_steps_fast(alpha, steps) if k == 1 \
                else pool._vmapped(alpha, steps, k, True)
            t0 = time.perf_counter()
            compiled = fn.lower(sub, batches).compile()
            dt = time.perf_counter() - t0
            mem = compiled.memory_analysis()
            print(json.dumps({
                "workload": args.workload, "alpha": alpha, "lanes": k,
                "steps": steps, "compile_s": dt,
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "argument_bytes": getattr(mem, "argument_size_in_bytes",
                                          None)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
