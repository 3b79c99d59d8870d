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
reject.  The model comes from ``bench/models/<arch>.py``.
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

MODELS = pathlib.Path(__file__).resolve().parent / "models"
HIGHEST = jax.lax.Precision.HIGHEST


def load_model(arch: str):
    """The layer table of ``bench/models/<arch>.py``."""
    path = MODELS / f"{arch}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reference model file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_model_{arch}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def param_shapes(model) -> dict:
    out = {}
    for name, kind, k, c_in, c_out, _, _ in model.LAYERS:
        w = (k, k, c_in, c_out) if kind == "conv" else (c_in, c_out)
        out[name] = {"b": (c_out,), "w": w}
    return out


def leaf_names(model) -> list[tuple[str, str]]:
    """Leaves in the order the update is flattened for FGC: layers by
    name, then bias before weight."""
    return [(layer, leaf) for layer in sorted(param_shapes(model))
            for leaf in ("b", "w")]


def n_params(model) -> int:
    return sum(int(np.prod(s)) for layer in param_shapes(model).values()
               for s in layer.values())


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (PRNGKey keeps only 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def init_params(model, key) -> dict:
    """He-normal weights (std sqrt(2 / fan_in) for convs, sqrt(1 / fan_in)
    for dense layers), zero biases, float32."""
    shapes = param_shapes(model)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, kind, *_rest) in zip(keys, model.LAYERS):
        w = shapes[name]["w"]
        fan_in = int(np.prod(w[:-1]))
        scale = math.sqrt(2.0) if kind == "conv" else 1.0
        out[name] = {"w": jax.random.normal(k, w, jnp.float32)
                     * (scale / math.sqrt(fan_in)),
                     "b": jnp.zeros(shapes[name]["b"], jnp.float32)}
    return out


# ------------------------------------------------------------------ forward

def forward(model, params, images, precision=HIGHEST):
    x = images.astype(params[model.LAYERS[0][0]]["w"].dtype)
    last = model.LAYERS[-1][0]
    for name, kind, _, _, _, _, pool in model.LAYERS:
        p = params[name]
        if kind == "conv":
            x = jax.lax.conv_general_dilated(
                x, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=precision) + p["b"]
        else:
            x = jnp.dot(x.reshape(x.shape[0], -1), p["w"],
                        precision=precision) + p["b"]
        if name != last:
            x = jax.nn.relu(x)
        if pool:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    return x


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


# ---------------------------------------------------------------------- EMS

def widths(model, alpha: float) -> dict:
    m = math.sqrt(alpha)
    return {g[0]: min(max(math.ceil(g[1] * m), 1), g[1])
            for g in model.GROUPS}


def _in_axis(params, layer):
    return 2 if params[layer]["w"].ndim == 4 else 0


def _permute_in(w, axis, outer, size, idx):
    """Take channels ``idx`` of an input axis laid out as (outer, size)."""
    shape = w.shape
    v = w.reshape(shape[:axis] + (outer, size) + shape[axis + 1:])
    v = jnp.take(v, idx, axis=axis + 1)
    return v.reshape(shape[:axis] + (outer * len(idx),)
                     + shape[axis + 1:])


def sort_channels(model, params) -> dict:
    """Rank each group's channels by the producer's output-slice norm,
    descending, and permute producer and consumer alike."""
    p = {k: dict(v) for k, v in params.items()}
    for _, size, prod, cons, outer in model.GROUPS:
        w = p[prod]["w"]
        norms = jnp.sqrt(jnp.sum(jnp.square(w.reshape(-1, size)), axis=0))
        idx = jnp.argsort(-norms)
        p[prod] = {"w": jnp.take(w, idx, axis=-1),
                   "b": jnp.take(p[prod]["b"], idx)}
        p[cons] = dict(p[cons])
        p[cons]["w"] = _permute_in(p[cons]["w"], _in_axis(p, cons), outer,
                                   size, idx)
    return p


def shrink(model, params, alpha: float) -> dict:
    p = {k: dict(v) for k, v in params.items()}
    for name, n in widths(model, alpha).items():
        _, size, prod, cons, outer = next(g for g in model.GROUPS
                                          if g[0] == name)
        idx = jnp.arange(n)
        p[prod] = {"w": p[prod]["w"][..., :n], "b": p[prod]["b"][:n]}
        p[cons] = dict(p[cons])
        p[cons]["w"] = _permute_in(p[cons]["w"], _in_axis(p, cons), outer,
                                   size, idx)
    return p


def expand(model, sub_update, full_shapes) -> tuple[dict, dict]:
    """Zero-pad a sub-model update to full width; also the coverage mask."""
    upd, mask = {}, {}
    for layer, leaves in full_shapes.items():
        upd[layer], mask[layer] = {}, {}
        for leaf, shape in leaves.items():
            u = sub_update[layer][leaf]
            m = jnp.ones_like(u)
            for _, size, prod, cons, outer in model.GROUPS:
                if layer == prod and leaf in ("w", "b"):
                    axis = u.ndim - 1
                    o = 1
                elif layer == cons and leaf == "w":
                    axis = 2 if u.ndim == 4 else 0
                    o = outer
                else:
                    continue
                n = u.shape[axis] // o
                u = _pad_in(u, axis, o, n, size)
                m = _pad_in(m, axis, o, n, size)
            if u.shape != tuple(shape):
                raise ValueError(f"{layer}.{leaf}: expanded to {u.shape}, "
                                 f"the model has {tuple(shape)}")
            upd[layer][leaf], mask[layer][leaf] = u, m
    return upd, mask


def _pad_in(x, axis, outer, n, size):
    shape = x.shape
    v = x.reshape(shape[:axis] + (outer, n) + shape[axis + 1:])
    pads = [(0, 0)] * v.ndim
    pads[axis + 1] = (0, size - n)
    v = jnp.pad(v, pads)
    return v.reshape(shape[:axis] + (outer * size,) + shape[axis + 1:])


# ---------------------------------------------------------------------- FGC

def fgc(model, update, beta, key):
    """Kernel-wise top-K sparsification and stochastic quantization of
    the whole update as one vector.  Returns (values, sparsity mask)."""
    names = leaf_names(model)
    leaves = [update[a][b] for a, b in names]
    vec = jnp.concatenate([x.reshape(-1) for x in leaves])
    seg, kid = [], 0
    for x in leaves:
        if x.ndim >= 2:
            k = x.shape[-1]
            seg.append(np.tile(np.arange(k), x.size // k) + kid)
        else:
            k = 1
            seg.append(np.full(x.size, kid))
        kid += k
    seg = jnp.asarray(np.concatenate(seg).astype(np.int32))
    sb = jnp.sqrt(jnp.asarray(beta, jnp.float32))
    rho = 1.0 - sb
    n_levels = jnp.clip(jnp.exp2(32.0 * sb), 2.0, 65535.0)
    norms = jnp.sqrt(jax.ops.segment_sum(vec * vec, seg, num_segments=kid))
    kept = jnp.ceil((1.0 - rho) * kid)
    thr = jnp.sort(norms)[jnp.clip(kid - kept, 0, kid - 1).astype(jnp.int32)]
    mask = (norms >= thr)[seg].astype(jnp.float32)
    mag = jnp.abs(vec) * mask
    live = mask > 0
    lo = jnp.min(jnp.where(live & (mag > 0), mag, jnp.inf))
    lo = jnp.where(jnp.isfinite(lo), lo, 0.0)
    hi = jnp.max(jnp.where(live, mag, -jnp.inf))
    hi = jnp.where(jnp.isfinite(hi), hi, 0.0)
    step = jnp.maximum(hi - lo, 1e-20) / n_levels
    pos = jnp.clip((mag - lo) / step, 0.0, n_levels)
    base = jnp.floor(pos)
    level = jnp.clip(base + (jax.random.uniform(key, vec.shape)
                             < pos - base), 0.0, n_levels)
    q = jnp.where(live, (lo + level * step) * jnp.sign(vec), 0.0)
    values, masks, at = {}, {}, 0
    for (a, b), x in zip(names, leaves):
        values.setdefault(a, {})[b] = q[at:at + x.size].reshape(x.shape)
        masks.setdefault(a, {})[b] = mask[at:at + x.size].reshape(x.shape)
        at += x.size
    return values, masks


# ------------------------------------------------------------------- round

class Client(NamedTuple):
    """One client's round as the control plane decided it."""
    alpha: float
    beta: float
    key: jax.Array
    images: np.ndarray     # (steps, B, H, W, C)
    labels: np.ndarray     # (steps, B)


def aio_weight(alpha: float, beta: float) -> float:
    d = 1.0 - alpha * (2.0 - alpha) * math.sqrt(max(beta, 1e-6))
    return 1.0 / max(d * d, 1e-12)


@functools.lru_cache(maxsize=None)
def _client_fn(model, alpha: float, lr: float, dtype: str,
               half_batch: bool, highest: bool):
    shapes = param_shapes(model)
    prec = HIGHEST if highest else None

    def run(sorted_params, images, labels, beta, key):
        sub = shrink(model, sorted_params, alpha)
        sub = jax.tree.map(lambda x: x.astype(dtype), sub)
        if half_batch:
            b = images.shape[1] // 2
            images, labels = images[:, :b], labels[:, :b]

        def step(p, batch):
            x, y = batch
            g = jax.grad(lambda q: cross_entropy(
                forward(model, q, x, prec), y))(p)
            return jax.tree.map(lambda a, d: a - lr * d.astype(a.dtype),
                                p, g), None

        trained, _ = jax.lax.scan(step, sub, (images, labels))
        update = jax.tree.map(lambda a, t: a.astype(jnp.float32)
                              - t.astype(jnp.float32), sub, trained)
        flat = jnp.concatenate([u.reshape(-1)
                                for u in jax.tree.leaves(update)])
        full, width_mask = expand(model, update, shapes)
        values, mask = fgc(model, full, beta, key)
        mask = jax.tree.map(jnp.multiply, mask, width_mask)
        return (jax.tree.map(jnp.multiply, values, mask), mask,
                jnp.sum(jnp.square(flat)), flat)

    return jax.jit(run)


@jax.jit
def _absorb(num, den, values, mask, w):
    return (jax.tree.map(lambda n, v, m: n + w * m * v, num, values, mask),
            jax.tree.map(lambda d, m: d + w * m, den, mask))


@jax.jit
def _apply(sorted_params, num, den):
    agg = jax.tree.map(lambda n, d: jnp.where(d > 0, n / jnp.maximum(
        d, 1e-12), 0.0), num, den)
    return jax.tree.map(jnp.subtract, sorted_params, agg)


def fl_round(model, params, clients: list[Client], lr: float, *,
             dtype: str = "float32", half_batch: bool = False,
             highest: Optional[bool] = None, keep: bool = False
             ) -> tuple[dict, dict, float, Optional[list]]:
    """One synchronous round.  Returns (sorted params, new params, the
    squared norm of every client's local change before FGC, summed, and
    with ``keep`` each client's local change as a flat vector).

    Local training runs its products at ``Precision.HIGHEST`` in float32
    and at the default precision in a lower ``dtype``, unless ``highest``
    says otherwise."""
    if highest is None:
        highest = dtype == "float32"
    sorted_params = jax.jit(functools.partial(sort_channels, model))(params)
    num = jax.tree.map(jnp.zeros_like, sorted_params)
    den = jax.tree.map(jnp.zeros_like, sorted_params)
    change_sq, updates = [], [] if keep else None
    for c in clients:
        fn = _client_fn(model, float(c.alpha), float(lr), dtype, half_batch,
                        highest)
        values, mask, sq, flat = fn(sorted_params, jnp.asarray(c.images),
                                    jnp.asarray(c.labels),
                                    jnp.float32(c.beta), c.key)
        num, den = _absorb(num, den, values, mask,
                           jnp.float32(aio_weight(c.alpha, c.beta)))
        change_sq.append(sq)
        if keep:
            updates.append(np.asarray(flat))
    return (sorted_params, _apply(sorted_params, num, den),
            float(sum(change_sq)), updates)


class Rounds(NamedTuple):
    """What one side made of the checked rounds, round by round, and of
    the first round in full."""
    deltas: list        # {(layer, leaf): norm of the change}
    losses: list        # test loss after the round
    change_sq: list     # squared norm of the clients' local changes
    updates: list       # first round: each client's local change, flat
    change: dict        # first round: (layer, leaf) -> new - sorted


def follow(model, seed: int, rounds: list, lr: float, test, *,
           keep: bool = False, **kw) -> Rounds:
    """Follow the checked rounds from the benchmark's weights for
    ``seed``.  ``rounds`` holds each round's list of :class:`Client`;
    ``keep`` keeps the first round in full; ``kw`` goes to
    :func:`fl_round`."""
    params = jax.jit(init_params, static_argnums=0)(
        model, jax.random.split(seed_key(seed))[1])
    out = Rounds([], [], [], [], {})
    for i, clients in enumerate(rounds):
        sorted_p, params, sq, updates = fl_round(
            model, params, clients, lr, keep=keep and i == 0, **kw)
        out.deltas.append({(a, b): float(jnp.linalg.norm(
            (params[a][b] - sorted_p[a][b]).reshape(-1)))
            for a in params for b in params[a]})
        if keep and i == 0:
            out.updates.extend(updates)
            out.change.update(jax.device_get({
                (a, b): params[a][b] - sorted_p[a][b]
                for a in params for b in params[a]}))
        out.losses.append(test_loss(model, params, test.x, test.y))
        out.change_sq.append(sq)
    return out


@functools.lru_cache(maxsize=None)
def _loss_sum(model):
    return jax.jit(lambda p, x, y: cross_entropy(forward(model, p, x), y)
                   * x.shape[0])


def test_loss(model, params, images, labels, block: int = 1000) -> float:
    """Mean cross entropy over the test set, in blocks of rows."""
    total, n = 0.0, len(labels)
    for i in range(0, n, block):
        total += float(_loss_sum(model)(params,
                                        jnp.asarray(images[i:i + block]),
                                        jnp.asarray(labels[i:i + block])))
    return total / n
