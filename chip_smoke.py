#!/usr/bin/env python3
"""Bring-up smoke test of the AnycostFL round loop on a TPU.

    python chip_smoke.py              # one chip: phases A and B, kernel check
    python chip_smoke.py --chips 4    # four chips: mesh route vs streaming

Everything runs in this one process, through the FL CLI's own ``run_fl``
with arguments parsed by its own parser (``build_parser``), so it drives
exactly what ``python -m repro.launch.train --mode fl`` runs:

* phase A, flat sync rounds: fmnist-cnn at its published widths, the
  paper's fleet of 60 devices, synthetic data made from ``--seed``;
* phase B, the same fleet in 4 edge cells (``--topology hier``): the edge
  absorb and the cloud merge run the compiled Pallas ``aio_absorb`` /
  ``aio_merge`` kernels;
* kernel check: those two kernels against their jnp oracles in
  ``kernels/ref.py``, on update pytrees shaped like the FMNIST model.

``--chips 4`` runs only the path that exists across chips: a hier phase
on ``--agg-route mesh`` (cells sharded over a 4-device ``"cell"`` mesh
axis), and the streaming route on the same seed and rounds to compare it
with.

A phase fails unless every round aggregated at least one client, every
test loss is finite and the global model moved.  Wall times are
host-clock bring-up observations, not benchmark results.  There is no
CPU path: on any other platform the script exits non-zero before it runs
anything.  The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# FMNIST has 60000 training images, but at 1000 samples per device the
# CLI's fixed budgets (FleetConfig.T_max = 10 s, E_max ~ U[3, 9] J) leave
# Problem P4 infeasible for all 60 devices and no client trains.  8000
# (133 samples per device) is the largest size in steps of 1000 at which
# the whole fleet still trains (59-60 of 60 devices per round); at 9000
# it is 52-58, at 12000 only 17-24, which would cut the fleet.  The test
# set keeps FMNIST's size.
N_TRAIN, N_TEST = 8000, 10000
DEVICES, CELLS = 60, 4
# round 0 compiles; the later rounds run what it compiled
ROUNDS = 3
# Pallas absorb vs its oracle: each element is one f32 multiply of w*m by
# u and one add, which the kernel and XLA may fuse or order differently
# (a fused multiply-add rounds once where two ops round twice), so an
# element may move by a few ulps; 1e-6 relative is about 8 ulps of f32.
# The atol covers elements where the add cancels to near zero.  Merge is
# one add per element and is expected to match bitwise; it is held to the
# same bound and its bitwise agreement is printed.
KERNEL_RTOL, KERNEL_ATOL = 1e-6, 1e-6
# mesh vs streaming route: round 0 aggregates the same updates in a
# different order (cells over a psum vs the streaming edge fold), so its
# test loss agrees to float reordering: rtol 1e-5.  Later rounds train
# from params that differ in the last bits, and EMS re-sorts channels by
# norm, so a near tie can hand a device other channels and the gap grows
# by orders of magnitude a round.  Started from params one ulp apart, the
# same three hier rounds end with test losses 3.3e-4 apart (relative)
# and final params 2.1e-4 apart (the relative L2 gap below; small bias
# leaves move by up to 5% of their largest value).  Every round's loss
# and that params gap are held to 1e-3; a wrong reshard or a garbled
# merge moves them by far more.
ROUTE_LOSS_RTOL = 1e-5
ROUTE_DRIFT_RTOL = 1e-3

_ROUND_LINE = re.compile(r"\] round\s+(\d+) ")


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class _StampedLines(io.TextIOBase):
    """Passes stdout through and notes the host time of each line."""

    def __init__(self, out):
        self.out, self.buf, self.lines = out, "", []

    def write(self, s):
        self.out.write(s)
        self.buf += s
        while "\n" in self.buf:
            line, self.buf = self.buf.split("\n", 1)
            # repro: ignore[unseeded-randomness] — wall time of the
            # smoke run, printed only; never feeds the simulation.
            self.lines.append((time.perf_counter(), line))
        return len(s)

    def flush(self):
        self.out.flush()


def fl_args(seed: int, extra: list[str]):
    from repro.launch.train import build_parser
    return build_parser().parse_args([
        "--mode", "fl", "--arch", "fmnist-cnn", "--devices", str(DEVICES),
        "--n-train", str(N_TRAIN), "--n-test", str(N_TEST),
        "--rounds", str(ROUNDS), "--eval-every", "1", "--lr", "0.05",
        "--seed", str(seed)] + extra)


def init_params(seed: int):
    import jax

    from repro.configs import get_config
    from repro.models.registry import build_model
    return build_model(get_config("fmnist-cnn")).init(
        jax.random.PRNGKey(seed))


def sorted_leaves(a, b):
    """Pairs of leaves as sorted value sets: EMS keeps the server's
    params in a channel order of its own."""
    import jax
    import numpy as np
    return [(np.sort(np.asarray(x).ravel()), np.sort(np.asarray(y).ravel()))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]


def params_moved(before, after) -> bool:
    """Whether any leaf holds other values."""
    import numpy as np
    return any(not np.array_equal(x, y)
               for x, y in sorted_leaves(before, after))


def params_gap(a, b) -> float:
    """||a - b|| / ||b|| over the whole param tree, with each leaf as a
    sorted value set."""
    import numpy as np
    pairs = sorted_leaves(a, b)
    num = sum(float(np.sum(np.square(x - y, dtype=np.float64)))
              for x, y in pairs)
    den = sum(float(np.sum(np.square(y, dtype=np.float64)))
              for _, y in pairs)
    return math.sqrt(num / den)


def run_phase(name: str, seed: int, extra: list[str]):
    """One ``run_fl`` call; prints its per-round record and checks it."""
    import jax

    from repro.launch.train import run_fl
    args = fl_args(seed, extra)
    tee = _StampedLines(sys.stdout)
    # repro: ignore[unseeded-randomness] — printed wall time only
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(tee):
        hist = run_fl(args)
    stamps = {int(m.group(1)): t for t, line in tee.lines
              if (m := _ROUND_LINE.search(line))}
    check(sorted(stamps) == list(range(ROUNDS)),
          f"{name}: expected {ROUNDS} round lines, saw rounds "
          f"{sorted(stamps)}")
    ends = [stamps[r] for r in range(ROUNDS)]
    walls = [ends[0] - t0] + [b - a for a, b in zip(ends, ends[1:])]
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[{name}] args: {' '.join(extra) or '(flat sync)'}")
    print(f"[{name}] first round wall {walls[0]:.3f} s (set-up and "
          f"compiling included); later rounds "
          f"{', '.join(f'{w:.3f}' for w in walls[1:])} s")
    for r in hist.rounds:
        print(f"[{name}] round {r.round}: clients {r.n_clients} "
              f"flops {r.flops:.4g} loss {r.test_loss} acc {r.test_acc} "
              f"cells {r.n_cells_reporting}")
    print(f"[{name}] peak_bytes_in_use {peak}")
    for r in hist.rounds:
        check(r.n_clients > 0 and r.flops > 0,
              f"{name}: round {r.round} aggregated no client "
              f"(n_clients {r.n_clients}, flops {r.flops})")
        check(r.test_loss is not None and math.isfinite(r.test_loss),
              f"{name}: round {r.round} test loss {r.test_loss}")
    check(params_moved(init_params(seed), hist.params),
          f"{name}: the global params did not change")
    return hist, walls


def kernel_check(seed: int) -> None:
    """Compiled aio_absorb / aio_merge, through the aggregation rules the
    edge and cloud use, against the jnp oracles of kernels/ref.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import aggregation
    from repro.kernels import aio_agg, ref
    template = init_params(seed)
    leaves, treedef = jax.tree_util.tree_flatten(template)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1),
                                 8 * len(leaves)))

    def tree(draw):
        return treedef.unflatten([draw(next(keys), x.shape)
                                  for x in leaves])

    def normal(k, s):
        return jax.random.normal(k, s, jnp.float32)

    def uniform(k, s):
        return jax.random.uniform(k, s, jnp.float32)

    def bernoulli(k, s):
        return jax.random.bernoulli(k, 0.5, s).astype(jnp.float32)

    def flat(t):
        return treedef.flatten_up_to(t)

    def compare(what, got, want):
        worst, bitwise = 0.0, True
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            np.testing.assert_allclose(g, w, rtol=KERNEL_RTOL,
                                       atol=KERNEL_ATOL, err_msg=what)
            worst = max(worst, float(np.max(np.abs(g - w))))
            bitwise &= bool(np.array_equal(g, w))
        print(f"[kernels] {what}: {len(want)} leaves "
              f"({', '.join(str(int(np.size(w))) for w in want)} elements)"
              f" max |kernel - oracle| {worst:.3g}, bitwise {bitwise}")

    weight = 0.37
    num, den, u, m = tree(normal), tree(uniform), tree(normal), \
        tree(bernoulli)
    # oracles first: the kernels donate their accumulator operands
    want = [ref.aio_absorb_ref(*a, weight)
            for a in zip(flat(num), flat(den), flat(u), flat(m))]
    got_n, got_d = aggregation.absorb_trees(num, den, u, m, weight,
                                            use_kernel=True)
    compare("aio_absorb num", flat(got_n), [w[0] for w in want])
    compare("aio_absorb den", flat(got_d), [w[1] for w in want])
    na, da, nb, db = (tree(normal) for _ in range(4))
    want = [ref.aio_merge_ref(*a)
            for a in zip(flat(na), flat(da), flat(nb), flat(db))]
    got_n, got_d = aggregation.merge_trees(na, da, nb, db, use_kernel=True)
    compare("aio_merge num", flat(got_n), [w[0] for w in want])
    compare("aio_merge den", flat(got_d), [w[1] for w in want])
    print(f"[kernels] compiled programs: aio_absorb "
          f"{aio_agg.aio_absorb._cache_size()}, aio_merge "
          f"{aio_agg.aio_merge._cache_size()}; tolerance rtol "
          f"{KERNEL_RTOL} atol {KERNEL_ATOL}")


def one_chip(seed: int) -> None:
    from repro.kernels import aio_agg
    run_phase("phase A", seed, [])
    run_phase("phase B", seed, ["--topology", "hier", "--cells", str(CELLS)])
    n_abs, n_mrg = (aio_agg.aio_absorb._cache_size(),
                    aio_agg.aio_merge._cache_size())
    print(f"[phase B] Pallas programs compiled by the round loop: "
          f"aio_absorb {n_abs}, aio_merge {n_mrg}")
    check(n_abs > 0 and n_mrg > 0,
          "phase B did not reach the compiled aio_absorb/aio_merge")
    kernel_check(seed)


def four_chips(seed: int) -> None:
    import jax
    hier = ["--topology", "hier", "--cells", str(CELLS)]
    mesh, _ = run_phase("mesh route", seed, hier + ["--agg-route", "mesh"])
    spans = {d for x in jax.tree_util.tree_leaves(mesh.params)
             for d in x.sharding.device_set}
    print(f"[mesh route] final params span {len(spans)} devices: "
          f"{sorted(d.id for d in spans)}")
    check(len(spans) == 4, f"the mesh route spans {len(spans)} devices")
    stream, _ = run_phase("streaming route", seed, hier)
    for a, b in zip(mesh.rounds, stream.rounds):
        rtol = ROUTE_LOSS_RTOL if a.round == 0 else ROUTE_DRIFT_RTOL
        diff = abs(a.test_loss - b.test_loss)
        print(f"[routes] round {a.round}: loss mesh {a.test_loss} "
              f"streaming {b.test_loss} (|diff| {diff:.3g}, rtol {rtol}); "
              f"clients {a.n_clients} / {b.n_clients}")
        check(a.n_clients == b.n_clients,
              f"round {a.round}: the routes aggregated different cohorts")
        check(diff <= rtol * abs(b.test_loss),
              f"round {a.round} loss: mesh {a.test_loss} vs streaming "
              f"{b.test_loss} (rtol {rtol})")
    gap = params_gap(mesh.params, stream.params)
    print(f"[routes] final params, leaves as sorted value sets: "
          f"||mesh - streaming|| / ||streaming|| {gap:.3g} "
          f"(bound {ROUTE_DRIFT_RTOL})")
    check(gap <= ROUTE_DRIFT_RTOL,
          f"final params differ by {gap:.3g} (relative L2)")
    print("[routes] every round's loss and the final params agree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A, B and the kernel check; 4: the "
                         "mesh aggregation route against streaming")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {len(devs)} "
              f"{devs[0].platform} device(s) ({devs[0].device_kind})",
              file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} devices", file=sys.stderr)
        return 2
    print(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
          f"{cache}")
    try:
        (one_chip if args.chips == 1 else four_chips)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
