"""Benchmark entry point: one section per paper table/figure + roofline.

``PYTHONPATH=src python -m benchmarks.run``  (BENCH_SCALE=fast|full)

Run everything, or one or more named sections with an optional scale
flag:

``PYTHONPATH=src python -m benchmarks.run hier_scaling mobility_handover --fast``

Prints ``name,us_per_call,derived`` CSV lines per section plus the per-
table outputs, then one consolidated end-of-run table.  FL sections
share cached runs under experiments/fl/.

Every executed section also appends one **manifest-keyed trajectory
record** — the scalar metrics its spec (``benchmarks/specs.py``)
declares, extracted from the section's returned artifact dict — to
``BENCH_<section>.json`` at the repo root, which is what
``python -m benchmarks.gate`` diffs against the committed baseline.
Set ``BENCH_TRAJECTORY_ROOT`` or pass ``--no-trajectory`` to redirect
or suppress the append (tests, scratch runs).
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import common  # noqa: E402
from benchmarks.specs import spec_for  # noqa: E402


def _section(name, fn, *, trajectory: bool = True) -> dict:
    """Run one section; collect its returned artifact, extract the
    spec-declared metrics, and append the trajectory record."""
    print(f"\n===== {name} =====")
    t0 = time.time()
    ok, result = True, None
    try:
        result = fn()
    except Exception:
        traceback.print_exc()
        ok = False
    wall = time.time() - t0
    print(f"{name},{wall * 1e6:.0f},{'ok' if ok else 'FAILED'}")
    metrics = spec_for(name).extract(result) if ok else {}
    if ok and trajectory:
        common.append_trajectory(
            name, metrics, scale=os.environ.get("BENCH_SCALE", "fast"),
            wall_s=wall)
    return {"section": name, "ok": ok, "wall_s": wall,
            "metrics": metrics}


def _sections() -> dict:
    from benchmarks import (fig4_learning_curves, fig5a_ablation,
                            fig5bc_heterogeneity, fig5d_submodels,
                            kernel_micro, lemma1_divergence,
                            roofline_report, schedule_solver,
                            table1_cost_to_acc, theorem2_convergence)
    from benchmarks import (async_modes, fig1_breakdown, hier_scaling,
                            mobility_handover, selection_policies)
    return {
        "fig1_breakdown": fig1_breakdown.main,
        "async_modes": async_modes.main,
        "selection_policies": selection_policies.main,
        "hier_scaling": hier_scaling.main,
        "mobility_handover": mobility_handover.main,
        "kernel_micro": kernel_micro.main,
        "lemma1_divergence": lemma1_divergence.main,
        "theorem2_convergence": theorem2_convergence.main,
        "schedule_solver": schedule_solver.main,
        "roofline_report": roofline_report.main,
        "table1_cost_to_acc": table1_cost_to_acc.main,
        "fig4_learning_curves": fig4_learning_curves.main,
        "fig5bc_heterogeneity":
            lambda: {"compute": fig5bc_heterogeneity.main(kind="compute"),
                     "comm": fig5bc_heterogeneity.main(kind="comm")},
        "fig5a_ablation": fig5a_ablation.main,
        "fig5d_submodels": fig5d_submodels.main,
    }


def _summary_table(outcomes: list) -> None:
    """The consolidated end-of-run table: one row per executed section
    plus every trajectory-recorded metric underneath."""
    print(f"\n===== summary "
          f"(scale={os.environ.get('BENCH_SCALE', 'fast')}) =====")
    print(f"{'section':24s} {'status':>8s} {'wall_s':>9s} {'metrics':>8s}")
    for out in outcomes:
        print(f"{out['section']:24s} "
              f"{'ok' if out['ok'] else 'FAILED':>8s} "
              f"{out['wall_s']:9.1f} {len(out['metrics']):8d}")
    recorded = [(out["section"], path, value)
                for out in outcomes
                for path, value in sorted(out["metrics"].items())]
    if recorded:
        print(f"\n{'section':24s} {'metric':42s} {'value':>14s}")
        for section, path, value in recorded:
            print(f"{section:24s} {path:42s} {value:14.6g}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("sections", nargs="*", metavar="section",
                    help="run only the named sections (default: all)")
    ap.add_argument("--fast", action="store_true",
                    help="force BENCH_SCALE=fast")
    ap.add_argument("--full", action="store_true",
                    help="force BENCH_SCALE=full")
    ap.add_argument("--no-trajectory", action="store_true",
                    help="do not append BENCH_<section>.json records")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.fast:
        os.environ["BENCH_SCALE"] = "fast"
    elif args.full:
        os.environ["BENCH_SCALE"] = "full"
    sections = _sections()
    unknown = [s for s in args.sections if s not in sections]
    if unknown:
        raise SystemExit(f"unknown sections {unknown}; "
                         f"expected one of {sorted(sections)}")
    chosen = args.sections or list(sections)
    outcomes = [_section(name, sections[name],
                         trajectory=not args.no_trajectory)
                for name in chosen]
    _summary_table(outcomes)
    if not all(out["ok"] for out in outcomes):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
