import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).
_DOC = """Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

The lines above MUST run before any jax import — jax locks the device
count at first init, and the host devices are the CPU's (on a machine
with a chip JAX would otherwise take the TPU). 512 host devices back the
production meshes:
16x16 (single pod) and 2x16x16 (two pods).

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all              # every assigned pair
  python -m repro.launch.dryrun --all --mesh multi # the 512-chip pass

Results (memory analysis, cost analysis, collective stats, roofline terms)
are cached as JSON under experiments/dryrun/.
"""

import argparse
import json
import time
import traceback

import jax

from repro import sharding as shd
from repro.configs import ASSIGNED_ARCHS, get_config, get_shape, INPUT_SHAPES
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh, describe
from repro.launch.steps import make_step_and_args, rules_for
from repro.models.registry import build_model
from repro.train.optimizer import adamw

OUT_DIR = "experiments/dryrun"

# long_500k needs sub-quadratic attention (assignment): native for ssm /
# hybrid; dense/moe/vlm run their sliding-window variant; encdec skips.
SLIDING_WINDOW_FOR_LONG = 4096


def plan_entry(arch: str, shape_name: str):
    """Returns (cfg, shape, note) or None if the pair is skipped."""
    import dataclasses
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    note = ""
    if shape_name == "long_500k":
        if cfg.family == "encdec":
            return None  # full cross+self attention; out of domain (DESIGN.md)
        if cfg.family in ("dense", "moe", "vlm"):
            cfg = dataclasses.replace(cfg,
                                      sliding_window=SLIDING_WINDOW_FOR_LONG)
            note = f"sliding_window={SLIDING_WINDOW_FOR_LONG} variant"
    return cfg, shape, note


def run_one(arch: str, shape_name: str, mesh_kind: str, *,
            remat: str = "full", causal_skip: bool = False,
            grad_sync: str = "auto", keep_frac: float = 1.0 / 16.0,
            logits_bf16: bool = False, moe_gather: bool = False,
            expert_zero_decode: bool = False, data_par: int = 16,
            tag: str = "baseline", out_dir: str = OUT_DIR) -> dict:
    entry = plan_entry(arch, shape_name)
    if entry is None:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "skipped": True,
                "reason": "long_500k unsupported for this family (DESIGN.md)"}
    cfg, shape, note = entry
    import dataclasses
    if logits_bf16:
        cfg = dataclasses.replace(cfg, logits_bf16=True)
    if moe_gather:
        cfg = dataclasses.replace(cfg, moe_decode="gather")
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"),
                                data_par=data_par)
    model = build_model(cfg)
    opt = adamw(3e-4)
    # repro: ignore[unseeded-randomness] — wall-clock here *measures*
    # lowering/compile latency (the benchmark output); it never feeds
    # model or simulation state.
    t0 = time.time()
    rules = dict(rules_for(shape, grad_sync))
    if moe_gather or expert_zero_decode:
        # keep the train-style ZeRO expert sharding at decode (P1 ablation)
        rules.pop("expert_in", None)
        rules.pop("expert_ff", None)
    with shd.use_sharding(mesh, rules):
        step, args, in_sh, out_sh = make_step_and_args(
            model, opt, shape, remat=remat, causal_skip=causal_skip,
            grad_sync=grad_sync, keep_frac=keep_frac, mesh=mesh)
        with mesh:
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            lowered = jitted.lower(*args)
            # repro: ignore[unseeded-randomness] — compile-time probe
            t_lower = time.time() - t0
            compiled = lowered.compile()
            # repro: ignore[unseeded-randomness] — compile-time probe
            t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = rl.parse_collectives(compiled.as_text(),
                                bf16_model=(cfg.dtype == "bfloat16"))
    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v
    analytic = rl.analytic_cost(
        cfg, shape, remat=remat if shape.kind == "train" else "none",
        causal_skip=causal_skip, n_chips=n_chips,
        data_shards=mesh.shape.get("data", 1) * mesh.shape.get("pod", 1))
    roof = rl.derive(cost, coll, n_chips=n_chips,
                     model_flops_total=rl.model_flops(cfg, shape),
                     analytic=analytic)
    mem_d = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
        mem_d[field] = getattr(mem, field, None)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_desc": describe(mesh), "note": note, "tag": tag,
        "skipped": False,
        "remat": remat, "causal_skip": causal_skip, "grad_sync": grad_sync,
        "logits_bf16": logits_bf16, "keep_frac": keep_frac,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_d,
        "cost_analysis": {"flops": cost.get("flops"),
                          "bytes_accessed": cost.get("bytes accessed")},
        "collectives": coll.to_dict(),
        "roofline": roof.to_dict(),
    }
    return result


def save(result: dict, out_dir: str = OUT_DIR):
    os.makedirs(out_dir, exist_ok=True)
    name = (f"{result['arch']}__{result['shape']}__{result['mesh']}"
            f"__{result.get('tag', 'baseline')}.json")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f, indent=1)
    return os.path.join(out_dir, name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "none"])
    ap.add_argument("--causal-skip", action="store_true")
    ap.add_argument("--grad-sync", default="auto",
                    choices=["auto", "anycost"])
    ap.add_argument("--keep-frac", type=float, default=1.0 / 16.0)
    ap.add_argument("--logits-bf16", action="store_true")
    ap.add_argument("--moe-gather", action="store_true")
    ap.add_argument("--expert-zero-decode", action="store_true")
    ap.add_argument("--data-par", type=int, default=16)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    pairs = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in INPUT_SHAPES:
                pairs.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs.append((args.arch, args.shape))

    failures = 0
    for arch, shape in pairs:
        name = f"{arch}__{shape}__{args.mesh}__{args.tag}.json"
        path = os.path.join(args.out, name)
        if args.skip_existing and os.path.exists(path):
            print(f"[skip-existing] {name}")
            continue
        # repro: ignore[unseeded-randomness] — operator progress timing
        t0 = time.time()
        try:
            res = run_one(arch, shape, args.mesh, remat=args.remat,
                          causal_skip=args.causal_skip,
                          grad_sync=args.grad_sync,
                          keep_frac=args.keep_frac,
                          logits_bf16=args.logits_bf16,
                          moe_gather=args.moe_gather,
                          expert_zero_decode=args.expert_zero_decode,
                          data_par=args.data_par,
                          tag=args.tag, out_dir=args.out)
            p = save(res, args.out)
            if res.get("skipped"):
                print(f"[SKIP] {arch} x {shape} ({args.mesh}): "
                      f"{res['reason']}")
            else:
                r = res["roofline"]
                print(f"[OK] {arch} x {shape} ({args.mesh}) "
                      # repro: ignore[unseeded-randomness] — progress print
                      f"{time.time() - t0:.0f}s  "
                      f"cmp={r['t_compute']:.3e}s mem={r['t_memory']:.3e}s "
                      f"coll={r['t_collective']:.3e}s -> {r['bottleneck']} "
                      f"({p})")
        except Exception as e:
            failures += 1
            print(f"[FAIL] {arch} x {shape} ({args.mesh}): {e}")
            traceback.print_exc()
            with open(os.path.join(args.out,
                                   name.replace(".json", ".FAIL.txt")),
                      "w") as f:
                f.write(traceback.format_exc())
    if failures:
        raise SystemExit(f"{failures} dry-run failures")


if __name__ == "__main__":
    main()
