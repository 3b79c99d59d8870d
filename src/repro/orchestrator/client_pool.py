"""Batched client execution: one jit'd ``jax.vmap`` step per width bucket.

The synchronous loop runs each simulated device's local SGD as its own
Python-level call — fine for 4 devices, hopeless for a 60-1000 device
fleet.  Devices in the same alpha bucket train the *same sub-model shape*
(EMS slices to the same widths), so their local rounds are one vmapped scan
over stacked minibatches:

* ``train_shared``  — all clients start from the same (sorted, shrunk)
  global params: ``in_axes=(None, 0)``, one shrink per bucket instead of
  one per client.  Used by the round-based policies.
* ``train_stacked`` — clients start from *different* model versions (the
  FedBuff buffer spans server versions): params are stacked along the vmap
  axis, ``in_axes=(0, 0)``.

Group sizes are padded up to the next power of two (repeating the first
job) so the jit cache holds at most ``log2(fleet)`` entries per
(alpha, n_steps) bucket instead of one per distinct group size.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import shrinking
from repro.core.anycost import AnycostClient
from repro.telemetry import profiler

PyTree = Any


@dataclasses.dataclass
class TrainJob:
    """One client's local round, ready to train."""
    client_id: int
    alpha: float                      # bucketed width
    batches: PyTree                   # (steps, B, ...) stacked minibatches
    sub_params: Optional[PyTree] = None   # only for train_stacked


def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _tree_index(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


# Groups are padded up to at least this many lanes. Compile time, not
# compute, dominates on the simulator's fleet sizes: a fedbuff buffer whose
# (alpha, shape) groups vary between 1 and K clients would otherwise compile
# one executable per size, while padding to one fixed width reuses a single
# executable (the wasted lanes are a few extra tiny SGD steps).
_PAD_MIN = 8


def _pad_size(n: int) -> int:
    p = _PAD_MIN
    while p < n:
        p *= 2
    return p


def _batch_signature(batches: PyTree) -> tuple:
    leaves = jax.tree_util.tree_leaves(batches)
    return tuple((tuple(x.shape), str(x.dtype)) for x in leaves)


class ClientPool:
    """Groups same-shape clients and trains each group in one vmapped call."""

    def __init__(self, client: AnycostClient):
        self.client = client
        self._vcache: dict = {}

    # ------------------------------------------------------------- internals

    def _vmapped(self, alpha: float, n_steps: int, n_pad: int, shared: bool):
        key = (alpha, n_steps, n_pad, shared)
        if key not in self._vcache:
            run = self.client._local_steps_fast(alpha, n_steps)
            in_axes = (None, 0) if shared else (0, 0)
            self._vcache[key] = jax.jit(jax.vmap(run, in_axes=in_axes))
        return self._vcache[key]

    def _groups(self, jobs: list[TrainJob]) -> dict:
        groups: dict[tuple, list[int]] = {}
        for j, job in enumerate(jobs):
            leaves = jax.tree_util.tree_leaves(job.batches)
            n_steps = int(leaves[0].shape[0])
            key = (job.alpha, n_steps, _batch_signature(job.batches))
            groups.setdefault(key, []).append(j)
        return groups

    def _run_group(self, alpha: float, n_steps: int, idxs: list[int],
                   jobs: list[TrainJob], params: PyTree, shared: bool
                   ) -> list[PyTree]:
        n = len(idxs)
        if n == 1:
            run = self.client._local_steps_fast(alpha, n_steps)
            p = params if shared else jobs[idxs[0]].sub_params
            return [run(p, jobs[idxs[0]].batches)]
        n_pad = _pad_size(n)
        pad = [idxs[0]] * (n_pad - n)
        stacked_b = _tree_stack([jobs[j].batches for j in idxs + pad])
        if not shared:
            params = _tree_stack([jobs[j].sub_params for j in idxs + pad])
        out = self._vmapped(alpha, n_steps, n_pad, shared)(params, stacked_b)
        # unstack on the host: eager x[i] slices would compile one tiny
        # executable per (leaf shape, index); numpy views are free, and the
        # downstream jit'd decode re-ingests them with identical avals
        out = profiler.read(out)
        return [_tree_index(out, i) for i in range(n)]

    # ----------------------------------------------------------- public API

    def train_shared(self, sorted_global: PyTree, jobs: list[TrainJob],
                     subs: Optional[dict] = None) -> list[PyTree]:
        """Train all jobs from one global model. Returns trained params
        per job, in job order. ``subs`` optionally maps alpha -> already
        shrunk params so the caller's slices are reused instead of
        re-shrinking per width bucket."""
        out: list = [None] * len(jobs)
        with profiler.span("fl.local_train", n_clients=len(jobs)):
            for (alpha, n_steps, _), idxs in self._groups(jobs).items():
                sub = (subs or {}).get(alpha)
                if sub is None:
                    sub = shrinking.shrink(sorted_global, alpha,
                                           self.client.spec)
                for j, trained in zip(idxs, self._run_group(
                        alpha, n_steps, idxs, jobs, sub, shared=True)):
                    out[j] = trained
        return out

    def train_stacked(self, jobs: list[TrainJob]) -> list[PyTree]:
        """Train jobs that carry their own (per-version) sub params."""
        out: list = [None] * len(jobs)
        with profiler.span("fl.local_train", n_clients=len(jobs)):
            for (alpha, n_steps, _), idxs in self._groups(jobs).items():
                single = jobs[idxs[0]].sub_params
                for j, trained in zip(idxs, self._run_group(
                        alpha, n_steps, idxs, jobs, single, shared=False)):
                    out[j] = trained
        return out
