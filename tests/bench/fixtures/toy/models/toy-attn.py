"""A one-block decoder LM with grouped-query attention, for the tests.

Tokens are embedded, pass one block (causal attention of ``KV * Q_PER_KV``
query heads sharing ``KV`` key/value heads, then a ReLU MLP, each added to
the residual), and a dense head scores the next token.  Its width groups
are the query heads within each key/value head, viewed as ``(KV,
Q_PER_KV, HEAD)`` on the projections' head axes, and the MLP's hidden
units.
"""
import math

import jax
import jax.numpy as jnp

from bench.reference import HIGHEST, Entry, WidthGroup, flat, nest

TASK = "tokens"
VOCAB, D, KV, Q_PER_KV, HEAD, FF = 32, 16, 2, 3, 4, 24
SEQ = 12          # tokens per sample; SEQ - 1 are predicted


def param_shapes() -> dict:
    q, kv = KV * Q_PER_KV * HEAD, KV * HEAD
    return {"embed": {"w": (VOCAB, D)},
            "block": {"attn": {"wq": {"w": (D, q)}, "wk": {"w": (D, kv)},
                               "wv": {"w": (D, kv)}, "wo": {"w": (q, D)}},
                      "mlp": {"up": {"w": (D, FF), "b": (FF,)},
                              "down": {"w": (FF, D), "b": (D,)}}},
            "head": {"w": (D, VOCAB), "b": (VOCAB,)}}


def init(key) -> dict:
    """Normal weights of std sqrt(1 / fan_in), unit-normal embeddings,
    zero biases, float32."""
    shapes = flat(param_shapes())
    out = {}
    for k, (path, shape) in zip(jax.random.split(key, len(shapes)),
                                shapes.items()):
        if path.endswith(".b"):
            out[path] = jnp.zeros(shape, jnp.float32)
        else:
            scale = 1.0 if path == "embed.w" else 1.0 / math.sqrt(shape[0])
            out[path] = scale * jax.random.normal(k, shape, jnp.float32)
    return nest(out)


def loss(params, batch, precision=HIGHEST):
    tok = batch["tokens"]
    x = params["embed"]["w"][tok[:, :-1]]
    b, t, _ = x.shape
    a, m = params["block"]["attn"], params["block"]["mlp"]

    def dot(u, w):
        return jnp.dot(u, w, precision=precision)

    q = dot(x, a["wq"]["w"]).reshape(b, t, KV, -1, HEAD)
    k = dot(x, a["wk"]["w"]).reshape(b, t, KV, HEAD)
    v = dot(x, a["wv"]["w"]).reshape(b, t, KV, HEAD)
    s = jnp.einsum("btgqh,bsgh->bgqts", q, k,
                   precision=precision) / math.sqrt(HEAD)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s.astype(jnp.float32),
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bgqts,bsgh->btgqh", p, v, precision=precision)
    x = x + dot(o.reshape(b, t, -1), a["wo"]["w"])
    h = jax.nn.relu(dot(x, m["up"]["w"]) + m["up"]["b"])
    x = x + dot(h, m["down"]["w"]) + m["down"]["b"]
    logits = dot(x, params["head"]["w"]) + params["head"]["b"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tok[:, 1:, None], axis=-1))


def width_groups() -> tuple:
    wq = Entry("block.attn.wq.w", 1, outer=KV, block=HEAD)
    up = Entry("block.mlp.up.w", 1)
    return (WidthGroup("heads", Q_PER_KV, sort_by=wq, entries=(
                wq, Entry("block.attn.wo.w", 0, outer=KV, block=HEAD))),
            WidthGroup("ff", FF, sort_by=up, entries=(
                up, Entry("block.mlp.up.b", 0),
                Entry("block.mlp.down.w", 0))))


def layer_flops(shapes: dict) -> dict:
    """Per sample: 2 FLOPs per multiply-add of every projection at each of
    the ``SEQ - 1`` positions, and the scores and weighted values over all
    ``(SEQ - 1) ** 2`` position pairs (the causal mask saves none); the
    embedding lookup costs none."""
    t = SEQ - 1

    def mm(*paths):
        return sum(2 * t * math.prod(shapes[p]) for p in paths)

    q_width = shapes["block.attn.wq.w"][1]
    return {"attn": mm(*(f"block.attn.{w}.w" for w in
                         ("wq", "wk", "wv", "wo"))) + 4 * t * t * q_width,
            "mlp": mm("block.mlp.up.w", "block.mlp.down.w"),
            "head": mm("head.w")}


def train_flops(shapes: dict) -> int:
    """Every product takes a gradient for each of its two operands."""
    return 3 * sum(layer_flops(shapes).values())
