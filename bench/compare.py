"""The comparison that decides ``correct``.

The program and the plain reference (``bench/reference.py``) run the same
checked rounds from the same weights, clients and minibatches.  Each
number that the workload file gives a limit is compared against it:

* ``loss_gap``: the largest relative gap between the program's test loss
  after a checked round and the reference's;
* ``train_gap``: the gap between the norm of the clients' local changes
  (trained sub-model minus its start, all clients of the first checked
  round together, before FGC) and the reference's, over the reference's;
* ``update_gap``: the gap between the norm of the program's whole change
  of the parameters in the first checked round and the reference's, over
  the reference's.  Both start that round from the same weights.

The change is compared whole, not leaf by leaf: FGC keeps or drops whole
kernels by their norm, and a leaf left with a few kernels near the
threshold flips between kept and dropped under rounding alone, which moves
that leaf's norm by all of it (``worst_leaf_gap``, kept for the
calibration's look).  Later rounds' changes are not compared: from the
second round on the two sides start from weights a rounding apart, and
FGC's top-K and few-level quantization turn that into whole kernels and
levels.  ``diffs`` (the norm of the difference of the first round's local
and server changes) is the calibration's look at single clients.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

NEGLIGIBLE = 1e-3


def counted_leaves(ref_first: dict) -> set:
    """Leaves the reference changed by at least a thousandth of the median
    changed leaf (FGC can drop every kernel of a leaf: such a leaf is not
    changed at all, and does not count)."""
    moved = [v for v in ref_first.values() if v > 0]
    if not moved:
        return set()
    med = statistics.median(moved)
    return {k for k, v in ref_first.items() if v >= NEGLIGIBLE * med}


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """The largest gap of one counted leaf's norm, over the larger of the
    reference's norm of that leaf and of the median counted leaf."""
    keep = counted_leaves(ref)
    if not keep:     # the reference moved nothing: nor may the program
        return 0.0 if not any(prog.values()) else math.inf
    med = statistics.median(ref[k] for k in keep)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def update_gap(prog: dict, ref: dict) -> float:
    """Gap of the norm of the whole change, over the reference's."""
    p = math.sqrt(sum(v * v for v in prog.values()))
    r = math.sqrt(sum(v * v for v in ref.values()))
    if r == 0.0:     # the reference moved nothing: nor may the program
        return 0.0 if p == 0.0 else math.inf
    return abs(p - r) / r


def loss_gap(prog: list[float], ref: list[float]) -> float:
    gaps = [abs(p - r) / abs(r) if math.isfinite(p) else math.inf
            for p, r in zip(prog, ref)]
    return max(gaps)


def train_gap(prog_sq: float, ref_sq: float) -> float:
    """Gap of the norm of the clients' local changes, over the reference's."""
    p, r = math.sqrt(prog_sq), math.sqrt(ref_sq)
    if r == 0.0:
        return 0.0 if p == 0.0 else math.inf
    return abs(p - r) / r


def train_diff(prog: list, ref: list) -> float:
    """Norm of the difference of every client's local change, over the
    norm of the reference's."""
    num = sum(float(np.sum(np.square(p.astype(np.float64) - r)))
              for p, r in zip(prog, ref, strict=True))
    den = sum(float(np.sum(np.square(r.astype(np.float64)))) for r in ref)
    return math.sqrt(num / den) if den > 0 else math.inf


def update_diff(prog: dict, ref: dict) -> float:
    """Norm of the difference of the server's whole change of the
    parameters, over the norm of the reference's."""
    num = sum(float(np.sum(np.square(np.asarray(prog[k], np.float64)
                                     - ref[k]))) for k in ref)
    den = sum(float(np.sum(np.square(np.asarray(ref[k], np.float64))))
              for k in ref)
    return math.sqrt(num / den) if den > 0 else math.inf


def diffs(prog, ref) -> dict:
    """First-round differences of two ``reference.Rounds`` that keep it
    in full."""
    return {"train_diff": train_diff(prog.updates, ref.updates),
            "update_diff": update_diff(prog.change, ref.change)}


def numbers(prog, ref) -> dict:
    """The compared numbers of two ``reference.Rounds``: the test loss over
    every checked round, the local changes and the server's change of the
    first."""
    return {"loss_gap": loss_gap(prog.losses, ref.losses),
            "train_gap": train_gap(prog.change_sq[0], ref.change_sq[0]),
            "update_gap": update_gap(prog.deltas[0], ref.deltas[0])}


def verdict(values: dict, limits: dict) -> bool:
    """Every number at or under its limit (a NaN fails)."""
    return all(values[k] <= limits[k] for k in limits)
