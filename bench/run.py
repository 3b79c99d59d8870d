#!/usr/bin/env python3
"""On-chip benchmark of the AnycostFL round loop: one cell, one run.

    python3 bench/run.py --workload fmnist.sync.unpooled --seed 7 --seconds 30 --trace 0

Sets the cell up (data and weights from ``--seed``, the program's
Simulation, the checked rounds, warm-up of every shape), runs whole
synchronous FL rounds for ``--seconds``, checks the checked rounds against
the plain reference, and prints one JSON line last.  ``--trace 1`` runs the
window under the profiler and reports the per-layer metrics instead of the
end-to-end ones.  Without a TPU, or with fewer chips than the cell needs,
it exits 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
