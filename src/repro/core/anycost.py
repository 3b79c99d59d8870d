"""AnycostFL single-round orchestration (client + server), paper §III-A.

The three-step round:
  1) elastic local training  — shrink(w_t, alpha_i), tau local epochs of SGD
  2) flexible gradient upload — cmprs(u_i, beta_i) (FGC)
  3) parameter aggregation    — aioagg({u~_i}) with Theorem-1 weights

The simulation runs real numerics on CPU for the paper's models; the same
client/server code drives the pod-scale integration through
``core.distributed`` (where devices = data-parallel replicas).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import aggregation, compression, shrinking
from repro.core.schedule import Strategy
from repro.models.registry import Model, build_model, loss_fn
from repro.telemetry import profiler
from repro.utils.pytree import tree_size, tree_sub

PyTree = Any

# discrete alpha buckets: bounds jit re-compilation of the local step to a
# handful of sub-model widths (the paper's alpha is continuous; widths on
# real hardware are also bucketed to efficient sizes)
DEFAULT_ALPHA_BUCKETS = (0.25, 0.4, 0.55, 0.7, 0.85, 1.0)


def bucket_alpha(alpha: float, buckets=DEFAULT_ALPHA_BUCKETS) -> float:
    """Largest bucket <= alpha (never exceed the computed budget)."""
    below = [b for b in buckets if b <= alpha + 1e-9]
    return below[-1] if below else buckets[0]


@dataclasses.dataclass
class ClientUpdate:
    """What the device uploads (server view, decoded)."""
    values: PyTree             # full-coordinate update, zeros where absent
    mask: PyTree               # {0,1} transmitted-coordinate mask
    alpha: float
    beta_target: float
    beta_realized: float       # modelled wire bits / (32 * |update|)
    bits: float
    n_samples: int
    flops: float               # actual local training FLOPs spent


class AnycostClient:
    """Device-side logic. Holds jit caches keyed by sub-model width."""

    def __init__(self, model: Model, spec: shrinking.ShrinkSpec, *,
                 lr: float, batch_size: int,
                 alpha_buckets=DEFAULT_ALPHA_BUCKETS):
        self.model = model
        self.spec = spec
        self.lr = lr
        self.batch_size = batch_size
        self.alpha_buckets = alpha_buckets
        self._step_cache: dict = {}
        self._fast_step_cache: dict = {}
        self._finish_cache: dict = {}
        self._shrink_cache: dict = {}

    def _local_steps(self, alpha: float, n_steps: int):
        key = (alpha, n_steps)
        if key in self._step_cache:
            return self._step_cache[key]
        sub_cfg = shrinking.shrunk_config(self.model.cfg, alpha, self.spec)
        sub_model = build_model(sub_cfg)
        lr = self.lr

        @jax.jit
        def run(params, batches):
            def step(p, batch):
                g = jax.grad(lambda q: loss_fn(sub_model, q, batch,
                                               remat="none"))(p)
                new = jax.tree.map(lambda a, b: a - lr * b.astype(a.dtype),
                                   p, g)
                return new, None

            out, _ = jax.lax.scan(step, params, batches)
            return out

        self._step_cache[key] = run
        return run

    def _local_steps_fast(self, alpha: float, n_steps: int):
        """Unrolled variant of :meth:`_local_steps` for the orchestrator's
        hot paths. ``lax.scan``'s while-loop blocks XLA fusion on CPU (a
        1-step scan costs ~8x the step itself); unrolling the (static)
        step count recovers it and vmaps linearly. Numerically equivalent
        up to op scheduling — the synchronous loop keeps the scan version
        for bitwise reproducibility."""
        key = (alpha, n_steps)
        if key in self._fast_step_cache:
            return self._fast_step_cache[key]
        sub_cfg = shrinking.shrunk_config(self.model.cfg, alpha, self.spec)
        sub_model = build_model(sub_cfg)
        lr = self.lr

        @jax.jit
        def run(params, batches):
            p = params
            for i in range(n_steps):
                batch = jax.tree.map(lambda x: x[i], batches)
                g = jax.grad(lambda q: loss_fn(sub_model, q, batch,
                                               remat="none"))(p)
                p = jax.tree.map(lambda a, b: a - lr * b.astype(a.dtype),
                                 p, g)
            return p

        self._fast_step_cache[key] = run
        return run

    def local_round(self, sorted_global: PyTree, strategy: Strategy,
                    batches: PyTree, key, *,
                    planner: Optional[compression.BetaPlanner] = None,
                    w_per_sample: float = 0.0) -> ClientUpdate:
        """One full device round: shrink -> train -> compress -> (upload)."""
        alpha = bucket_alpha(strategy.alpha, self.alpha_buckets)
        sub = self.shrink(sorted_global, alpha)
        n_steps = jax.tree_util.tree_leaves(batches)[0].shape[0]
        trained = self._local_steps(alpha, n_steps)(sub, batches)
        return self.finish_round(sorted_global, alpha, trained, strategy,
                                 n_steps, key, planner=planner,
                                 w_per_sample=w_per_sample, sub=sub)

    def shrink(self, sorted_global: PyTree, alpha: float) -> PyTree:
        """jit'd EMS slice of the sorted global model (one compile per
        width bucket)."""
        if alpha not in self._shrink_cache:
            spec = self.spec

            @jax.jit
            def shrink(params):
                return shrinking.shrink(params, alpha, spec)

            self._shrink_cache[alpha] = shrink
        return self._shrink_cache[alpha](sorted_global)

    def _finish_core(self, alpha: float):
        """jit'd shrink-residual -> expand -> compress pipeline for one
        width bucket. One compile per alpha; (rho, n_levels, key) are
        traced, so per-round targets never retrace."""
        if alpha in self._finish_cache:
            return self._finish_cache[alpha]
        spec = self.spec

        @jax.jit
        def core(sub, trained, rho, n_levels, key):
            update_sub = tree_sub(sub, trained)       # u = w_before - w_after
            full_update, width_mask = shrinking.expand_update(
                update_sub, None, alpha, spec)
            comp = compression.compress_update(full_update, 0.0, key,
                                               rho=rho, n_levels=n_levels)
            # the transmitted mask = width mask AND sparsity mask
            mask = jax.tree.map(lambda a, b: a * b, width_mask, comp.mask)
            values = jax.tree.map(lambda v, m: v * m, comp.values, mask)
            return values, mask, comp.bits

        self._finish_cache[alpha] = core
        return core

    def finish_plan(self, beta: float,
                    planner: Optional[compression.BetaPlanner] = None
                    ) -> tuple[jax.Array, jax.Array]:
        """(rho, n_levels) for a target rate — planner map or Appendix A."""
        if planner is not None:
            rho, levels = planner.plan(beta)
            return jnp.float32(rho), jnp.float32(levels)
        return (compression.analytic_rho(beta),
                compression.analytic_levels(beta))

    def finish_from_parts(self, alpha: float, strategy: Strategy,
                          n_steps: int, values: PyTree, mask: PyTree,
                          bits, *, w_per_sample: float = 0.0
                          ) -> ClientUpdate:
        """Assemble a ClientUpdate from an already-decoded (values, mask,
        bits) triple (the jit'd / vmapped finish cores)."""
        n = tree_size(values)          # full-coordinate size
        n_samples = n_steps * self.batch_size
        bits = float(profiler.read(bits))
        return ClientUpdate(
            values=values, mask=mask, alpha=alpha,
            beta_target=float(strategy.beta),
            beta_realized=bits / (32.0 * n),
            bits=bits, n_samples=n_samples,
            flops=alpha * w_per_sample * n_samples)

    def finish_round_fast(self, alpha: float, trained: PyTree,
                          strategy: Strategy, n_steps: int, key, *,
                          sub: PyTree,
                          planner: Optional[compression.BetaPlanner] = None,
                          w_per_sample: float = 0.0) -> ClientUpdate:
        """Decode a trained sub-model, given the sub-model it started from,
        into the uploaded update: one call of the width bucket's compiled
        finish program."""
        rho, n_levels = self.finish_plan(float(strategy.beta), planner)
        values, mask, bits = self._finish_core(alpha)(sub, trained, rho,
                                                      n_levels, key)
        return self.finish_from_parts(alpha, strategy, n_steps, values,
                                      mask, bits,
                                      w_per_sample=w_per_sample)

    def finish_round(self, sorted_global: PyTree, alpha: float,
                     trained: PyTree, strategy: Strategy, n_steps: int,
                     key, *,
                     planner: Optional[compression.BetaPlanner] = None,
                     w_per_sample: float = 0.0,
                     sub: Optional[PyTree] = None) -> ClientUpdate:
        """Decode an already-trained sub-model into the uploaded update.

        Split out of :meth:`local_round` so the orchestrator's client pool
        can train many clients in one vmapped call and decode each result
        here. ``alpha`` must be the bucketed width actually trained; the
        sub-model it started from is sliced from ``sorted_global`` unless
        given as ``sub``.
        """
        if sub is None:
            sub = self.shrink(sorted_global, alpha)
        return self.finish_round_fast(alpha, trained, strategy, n_steps, key,
                                      sub=sub, planner=planner,
                                      w_per_sample=w_per_sample)


class AnycostServer:
    """Server-side: channel sorting, AIO aggregation, model update."""

    def __init__(self, model: Model, spec: shrinking.ShrinkSpec,
                 *, server_lr: float = 1.0):
        self.model = model
        self.spec = spec
        self.server_lr = server_lr

    def sort(self, params: PyTree) -> PyTree:
        return shrinking.sort_channels(params, self.spec)

    def apply_update(self, params: PyTree, agg: PyTree) -> PyTree:
        """One server step: w <- w - server_lr * aggregated update."""
        return jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - self.server_lr * g.astype(jnp.float32)
                          ).astype(p.dtype), params, agg)

    def aggregate(self, params: PyTree, updates: list[ClientUpdate],
                  *, weights: Optional[jax.Array] = None) -> PyTree:
        if weights is None:
            weights = aggregation.optimal_coefficients(
                [u.alpha for u in updates],
                [max(u.beta_target, 1e-6) for u in updates])
        agg = aggregation.aio_aggregate([u.values for u in updates],
                                        [u.mask for u in updates], weights)
        return self.apply_update(params, agg)
