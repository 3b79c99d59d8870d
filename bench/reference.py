"""Plain float32 reference of one AnycostFL round, independent of ``src/``.

It follows the paper, not the program: EMS (section III-B) ranks each
width group's channels by the L2 norm of the producing weight and keeps the
first ``ceil(size * sqrt(alpha))``; each client runs plain SGD on its
minibatches; FGC (section III-C, Appendix A) keeps the top
``ceil((1 - rho) K)`` kernels by L2 norm with ``rho = 1 - sqrt(beta)`` and
rounds the survivors stochastically onto ``L = 2 ** (32 sqrt(beta))``
levels; AIO (section III-D, Theorem 1) averages the masked updates
element-wise with weights ``1 / (1 - alpha (2 - alpha) sqrt(beta)) ** 2``
and the server subtracts the result.

Matrix products run at ``Precision.HIGHEST``: on a TPU the default runs
float32 products as one bfloat16 pass.  ``dtype=bfloat16`` turns the local
training into the lower-precision control that the output check must
reject.

Everything that depends on the model family is in two files, found by
name in the cell's directory (``bench/`` unless a test gives another) or
else in ``bench/``.  A model file, ``models/<arch>.py``, provides

* ``TASK``: the name of its task file;
* ``param_shapes()``: ``{name: {... {leaf: shape}}}``, dicts to any depth;
* ``init(key)``: float32 parameters of those shapes from the key;
* ``loss(params, batch, precision=HIGHEST)``: the mean loss over the rows
  of one minibatch dict, computed in the parameters' dtype;
* ``layer_flops(shapes)`` and ``train_flops(shapes)``: forward FLOPs per
  sample of each layer, and forward plus backward FLOPs per sample, of the
  model whose leaves have the shapes ``{path: shape}``;
* ``width_groups()``: its EMS width groups, a tuple of :class:`WidthGroup`;
* ``PASS_ROWS``, optional: the most rows one pass of ``loss`` may take,
  1000 where it is not given.  Local training takes a larger minibatch in
  equal passes and averages their gradients; the test loss runs in blocks
  of that many rows.

A task file, ``tasks/<TASK>.py``, provides

* ``PROGRAM_TASK``: the program's task function in
  ``repro.orchestrator.runner`` that the cell stands in for;
* ``make(key, config)``: the seeded (train, test) splits, as that
  function returns them;
* ``rows(split)``: a split as one minibatch dict, rows first;
* ``zero_batch(config, steps, batch)``: a minibatch dict of zeros for
  ``steps`` steps of ``batch`` rows (warm-up and compile);
* ``bytes_per_sample(config)``: the bytes one sample puts on the device.

Leaves are addressed by their dotted path in the parameter dicts.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import pathlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.paths import BENCH

HIGHEST = jax.lax.Precision.HIGHEST


class Entry(NamedTuple):
    """An axis of one leaf that a width group cuts, viewed as ``(outer,
    size, block)``: flattened feature maps feeding a dense layer have
    ``outer`` spatial positions, an attention projection's head has
    ``block`` lanes."""
    path: str
    axis: int
    outer: int = 1
    block: int = 1


class WidthGroup(NamedTuple):
    """Channels that share one width: ranked by the norms of ``sort_by``'s
    slices, cut alike in every entry."""
    name: str
    size: int
    sort_by: Entry
    entries: tuple        # Entry, ...


def _load(kind: str, name: str, bench_dir):
    for d in dict.fromkeys((pathlib.Path(bench_dir), BENCH)):
        path = d / kind / f"{name}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{name}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no file {kind}/{name}.py in {bench_dir} "
                            f"or {BENCH}")


def load_model(arch: str, bench_dir=BENCH):
    """The model file ``models/<arch>.py``."""
    return _load("models", arch, bench_dir)


def load_task(name: str, bench_dir=BENCH):
    """The task file ``tasks/<name>.py``."""
    return _load("tasks", name, bench_dir)


# ------------------------------------------------------------------ leaves

def flat(tree, prefix: str = "") -> dict:
    """``{dotted path: leaf}`` of a tree of dicts, in the tree's order."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = v
    return out


def nest(leaves: dict) -> dict:
    """The tree of dicts whose :func:`flat` is ``leaves``."""
    out: dict = {}
    for path, v in leaves.items():
        *parents, last = path.split(".")
        node = out
        for part in parents:
            node = node.setdefault(part, {})
        node[last] = v
    return out


def n_params(model) -> int:
    return sum(int(np.prod(s)) for s in flat(model.param_shapes()).values())


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (PRNGKey keeps only 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------- EMS

def widths(model, alpha: float) -> dict:
    m = math.sqrt(alpha)
    return {g.name: min(max(math.ceil(g.size * m), 1), g.size)
            for g in model.width_groups()}


def _view(x, e: Entry, size: int):
    """``x`` with ``e``'s axis split into (outer, size, block)."""
    s = x.shape
    if s[e.axis] != e.outer * size * e.block:
        raise ValueError(f"{e.path}: axis {e.axis} has {s[e.axis]} lanes, "
                         f"not {e.outer} x {size} x {e.block}")
    return x.reshape(s[:e.axis] + (e.outer, size, e.block) + s[e.axis + 1:])


def _unview(v, e: Entry):
    s = v.shape
    return v.reshape(s[:e.axis] + (-1,) + s[e.axis + 3:])


def _importance(w, e: Entry, size: int):
    """L2 norm of each of the ``size`` channels of ``w`` along ``e``."""
    v = jnp.moveaxis(_view(w, e, size), e.axis + 1, -1).reshape(-1, size)
    return jnp.sqrt(jnp.sum(jnp.square(v), axis=0))


def sort_channels(model, params) -> dict:
    """Rank each group's channels by the norms of its ``sort_by`` slices,
    descending, and permute every entry of the group alike."""
    p = flat(params)
    for g in model.width_groups():
        w = p[g.sort_by.path]
        idx = jnp.argsort(-_importance(w, g.sort_by, g.size))
        for e in g.entries:
            p[e.path] = _unview(jnp.take(_view(p[e.path], e, g.size),
                                         idx, axis=e.axis + 1), e)
    return nest(p)


def shrink(model, params, alpha: float) -> dict:
    """The first channels of every group, as many as ``alpha`` keeps."""
    p = flat(params)
    n = widths(model, alpha)
    for g in model.width_groups():
        for e in g.entries:
            p[e.path] = _unview(jax.lax.slice_in_dim(
                _view(p[e.path], e, g.size), 0, n[g.name],
                axis=e.axis + 1), e)
    return nest(p)


def expand(model, sub_update, full_shapes: dict) -> tuple[dict, dict]:
    """Zero-pad a sub-model update to the full shapes ``{path: shape}``;
    also the coverage mask."""
    sub = flat(sub_update)
    cuts = [(g.size, e) for g in model.width_groups() for e in g.entries]
    upd, mask = {}, {}
    for path, shape in full_shapes.items():
        u = sub[path]
        m = jnp.ones_like(u)
        for size, e in cuts:
            if e.path == path:
                u, m = _pad(u, e, size), _pad(m, e, size)
        if u.shape != tuple(shape):
            raise ValueError(f"{path}: expanded to {u.shape}, the model "
                             f"has {tuple(shape)}")
        upd[path], mask[path] = u, m
    return nest(upd), nest(mask)


def _pad(x, e: Entry, size: int):
    n = x.shape[e.axis] // (e.outer * e.block)
    v = _view(x, e, n)
    pads = [(0, 0)] * v.ndim
    pads[e.axis + 1] = (0, size - n)
    return _unview(jnp.pad(v, pads), e)


# ---------------------------------------------------------------------- FGC

def _kernel_norms(x):
    """L2 norm of each kernel of a leaf: its slices along the last axis, or
    the whole leaf where it has fewer than two axes."""
    if x.ndim < 2:
        return jnp.sqrt(jnp.sum(x * x)).reshape(1)
    return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(x.ndim - 1))))


def _kth_largest(x, k):
    """The ``k``-th largest of the non-negative floats ``x``, exactly, for
    ``1 <= k <= x.size``: a bisection over their bit patterns, which order
    such floats as integers do.  A sort gives the same value, but compiles
    far slower on the TPU at tens of thousands of kernels."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)

    def step(i, t):
        c = t | jnp.left_shift(1, 30 - i)
        return jnp.where(jnp.sum(bits >= c) >= k, c, t)

    return jax.lax.bitcast_convert_type(
        jax.lax.fori_loop(0, 31, step, jnp.int32(0)), x.dtype)


def fgc(update, beta, key):
    """Kernel-wise top-K sparsification and stochastic quantization of the
    update, leaf by leaf.  A kernel is a slice along a leaf's last axis (a
    leaf of fewer than two axes is one kernel); the top ``ceil((1 - rho)
    K)`` of all K kernels by L2 norm survive.  The rounding draws one
    uniform per element, the leaves in JAX's order.  Returns (values,
    sparsity mask)."""
    leaves, tree = jax.tree.flatten(update)
    sb = jnp.sqrt(jnp.asarray(beta, jnp.float32))
    rho = 1.0 - sb
    n_levels = jnp.clip(jnp.exp2(32.0 * sb), 2.0, 65535.0)
    norms = [_kernel_norms(x) for x in leaves]
    every = jnp.concatenate(norms)
    kid = every.size
    kept = jnp.ceil((1.0 - rho) * kid)
    thr = _kth_largest(every, jnp.clip(kept, 1, kid).astype(jnp.int32))
    live = [jnp.broadcast_to((n >= thr).reshape(x.shape[-1:] if x.ndim >= 2
                                                 else ()), x.shape)
            for n, x in zip(norms, leaves)]
    mags = [jnp.abs(x) * v.astype(jnp.float32) for x, v in zip(leaves, live)]
    lo = functools.reduce(jnp.minimum, [
        jnp.min(jnp.where(v & (m > 0), m, jnp.inf))
        for m, v in zip(mags, live)])
    lo = jnp.where(jnp.isfinite(lo), lo, 0.0)
    hi = functools.reduce(jnp.maximum, [
        jnp.max(jnp.where(v, m, -jnp.inf)) for m, v in zip(mags, live)])
    hi = jnp.where(jnp.isfinite(hi), hi, 0.0)
    step = jnp.maximum(hi - lo, 1e-20) / n_levels
    draw = jax.random.uniform(key, (sum(x.size for x in leaves),))
    values, at = [], 0
    for x, m, v in zip(leaves, mags, live):
        pos = jnp.clip((m - lo) / step, 0.0, n_levels)
        base = jnp.floor(pos)
        u = draw[at:at + x.size].reshape(x.shape)
        level = jnp.clip(base + (u < pos - base), 0.0, n_levels)
        values.append(jnp.where(v, (lo + level * step) * jnp.sign(x), 0.0))
        at += x.size
    return (tree.unflatten(values),
            tree.unflatten([v.astype(jnp.float32) for v in live]))


# ------------------------------------------------------------------- round

class Client(NamedTuple):
    """One client's round as the control plane decided it."""
    alpha: float
    beta: float
    key: jax.Array
    batches: dict          # the minibatch dict, each leaf (steps, B, ...)


def aio_weight(alpha: float, beta: float) -> float:
    d = 1.0 - alpha * (2.0 - alpha) * math.sqrt(max(beta, 1e-6))
    return 1.0 / max(d * d, 1e-12)


PASS_ROWS = 1000    # rows in one pass of a model file that names none


def _passes(model, rows: int) -> int:
    """Passes of equal size, of at most the model's ``PASS_ROWS`` rows
    each, that a minibatch of ``rows`` rows takes."""
    per = getattr(model, "PASS_ROWS", PASS_ROWS)
    n = -(-rows // per)
    if rows % n:
        raise ValueError(f"{rows} rows do not split into {n} equal passes "
                         f"of at most {per} rows")
    return n


@functools.lru_cache(maxsize=None)
def _client_fn(model, alpha: float, lr: float, dtype: str,
               half_batch: bool, highest: bool, keep: bool):
    """The client's round as one program: (masked values, mask as bool,
    squared norm of the local change, and with ``keep`` the local change
    itself)."""
    shapes = flat(model.param_shapes())
    prec = HIGHEST if highest else None

    def run(sorted_params, batches, beta, key):
        sub = shrink(model, sorted_params, alpha)
        sub = jax.tree.map(lambda x: x.astype(dtype), sub)
        if half_batch:
            batches = jax.tree.map(lambda x: x[:, : x.shape[1] // 2],
                                   batches)

        def grad(p, batch):
            return jax.grad(lambda q: model.loss(q, batch, prec))(p)

        def step(p, batch):
            n = _passes(model, len(jax.tree.leaves(batch)[0]))
            if n == 1:
                g = grad(p, batch)
            else:
                # the minibatch's mean loss is the mean of its equal
                # passes' means: their gradients summed, then divided
                g, _ = jax.lax.scan(
                    lambda acc, part: (jax.tree.map(jnp.add, acc,
                                                    grad(p, part)), None),
                    jax.tree.map(jnp.zeros_like, p),
                    jax.tree.map(lambda x: x.reshape((n, -1) + x.shape[1:]),
                                 batch))
                g = jax.tree.map(lambda d: d / n, g)
            return jax.tree.map(lambda a, d: a - lr * d.astype(a.dtype),
                                p, g), None

        trained, _ = jax.lax.scan(step, sub, batches)
        update = jax.tree.map(lambda a, t: a.astype(jnp.float32)
                              - t.astype(jnp.float32), sub, trained)
        # one sum over the update as one vector: a sum of per-leaf sums
        # would round otherwise, and ``train_gap`` reads this number
        sq = jnp.sum(jnp.square(jnp.concatenate(
            [u.reshape(-1) for u in jax.tree.leaves(update)])))
        full, width_mask = expand(model, update, shapes)
        values, mask = fgc(full, beta, key)
        mask = jax.tree.map(jnp.multiply, mask, width_mask)
        out = (jax.tree.map(jnp.multiply, values, mask),
               jax.tree.map(lambda m: m > 0, mask), sq)
        return out + (update,) if keep else out

    return jax.jit(run)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _absorb(num, den, values, mask, w):
    return (jax.tree.map(lambda n, v, m: n + w * m.astype(n.dtype) * v,
                         num, values, mask),
            jax.tree.map(lambda d, m: d + w * m.astype(d.dtype), den, mask))


@functools.partial(jax.jit, donate_argnums=1)
def _apply(sorted_params, num, den):
    agg = jax.tree.map(lambda n, d: jnp.where(d > 0, n / jnp.maximum(
        d, 1e-12), 0.0), num, den)
    return jax.tree.map(jnp.subtract, sorted_params, agg)


@functools.lru_cache(maxsize=None)
def _sort_fn(model):
    return jax.jit(functools.partial(sort_channels, model),
                   donate_argnums=0)


def fl_round(model, params, clients: list[Client], lr: float, *,
             dtype: str = "float32", half_batch: bool = False,
             highest: Optional[bool] = None, keep: bool = False
             ) -> tuple[dict, dict, float, Optional[list]]:
    """One synchronous round.  Returns (sorted params, new params, the
    squared norm of every client's local change before FGC, summed, and
    with ``keep`` each client's local change as a flat vector).  ``params``
    is donated: the round sorts it in place of its own buffers.

    Local training runs its products at ``Precision.HIGHEST`` in float32
    and at the default precision in a lower ``dtype``, unless ``highest``
    says otherwise."""
    if highest is None:
        highest = dtype == "float32"
    sorted_params = _sort_fn(model)(params)
    num = jax.tree.map(jnp.zeros_like, sorted_params)
    den = jax.tree.map(jnp.zeros_like, sorted_params)
    change_sq, updates = [], [] if keep else None
    for c in clients:
        fn = _client_fn(model, float(c.alpha), float(lr), dtype, half_batch,
                        highest, keep)
        values, mask, sq, *update = fn(
            sorted_params, jax.tree.map(jnp.asarray, c.batches),
            jnp.float32(c.beta), c.key)
        num, den = _absorb(num, den, values, mask,
                           jnp.float32(aio_weight(c.alpha, c.beta)))
        del values, mask    # freed before the next client's program runs
        change_sq.append(sq)
        if keep:
            updates.append(np.concatenate([np.asarray(u).reshape(-1) for u
                                           in jax.tree.leaves(update)]))
    return (sorted_params, _apply(sorted_params, num, den),
            float(sum(change_sq)), updates)


class Rounds(NamedTuple):
    """What one side made of the checked rounds, round by round, and of
    the first round in full."""
    deltas: list        # {leaf path: norm of the change}
    losses: list        # test loss after the round
    change_sq: list     # squared norm of the clients' local changes
    updates: list       # first round: each client's local change, flat
    change: dict        # first round: leaf path -> new - sorted


def follow(model, seed: int, rounds: list, lr: float, test: dict, *,
           keep: bool = False, **kw) -> Rounds:
    """Follow the checked rounds from the benchmark's weights for
    ``seed``.  ``rounds`` holds each round's list of :class:`Client`;
    ``test`` is the test split as one minibatch dict; ``keep`` keeps the
    first round in full; ``kw`` goes to :func:`fl_round`."""
    params = jax.jit(model.init)(jax.random.split(seed_key(seed))[1])
    out = Rounds([], [], [], [], {})
    for i, clients in enumerate(rounds):
        sorted_p, params, sq, updates = fl_round(
            model, params, clients, lr, keep=keep and i == 0, **kw)
        new, old = flat(params), flat(sorted_p)
        out.deltas.append({k: float(jnp.linalg.norm((x - old[k]).reshape(
            -1))) for k, x in new.items()})
        if keep and i == 0:
            out.updates.extend(updates)
            out.change.update(jax.device_get({k: x - old[k]
                                              for k, x in new.items()}))
        del sorted_p, old
        out.losses.append(test_loss(model, params, test))
        out.change_sq.append(sq)
    return out


@functools.lru_cache(maxsize=None)
def _loss_sum(model):
    return jax.jit(lambda p, batch: model.loss(p, batch)
                   * jax.tree.leaves(batch)[0].shape[0])


def test_loss(model, params, rows: dict) -> float:
    """Mean loss over a split given as one minibatch dict, in blocks of
    the model's ``PASS_ROWS`` rows."""
    total, n = 0.0, len(jax.tree.leaves(rows)[0])
    block = getattr(model, "PASS_ROWS", PASS_ROWS)
    for i in range(0, n, block):
        total += float(_loss_sum(model)(params, jax.tree.map(
            lambda x: jnp.asarray(x[i:i + block]), rows)))
    return total / n
