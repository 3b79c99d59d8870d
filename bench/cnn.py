"""The conv/dense layer tables of the image CNNs, as the reference reads them.

A model file holds ``LAYERS``, rows of ``(name, kind, kernel, c_in, c_out,
output map side before pooling, pool)``, and ``GROUPS``, rows of ``(name,
channels, producing layer, consuming layer, spatial positions per channel
in the consumer's input)``, and binds this module's functions to them
with ``functools.partial``.  Images are NHWC; convolutions are 'SAME' with
bias, every layer but the last is followed by a ReLU, and the flatten
before the first dense layer is (H, W, C) with the channel fastest.

FLOPs: a convolution of kernel ``k`` from ``c_in`` to ``c_out`` channels
over an ``s x s`` output map costs ``2 k^2 c_in c_out s^2``, multiply-adds
counted as two FLOPs each; a dense layer ``2 d_in d_out``.  Training is the
forward pass plus the backward pass, which computes a gradient for the
weights (one forward's worth) and one for the layer's input (another),
except at the first layer, whose input is the data.  Bias adds,
activations, pooling and the loss are not counted.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, Entry, WidthGroup


def param_shapes(layers) -> dict:
    out = {}
    for name, kind, k, c_in, c_out, _, _ in layers:
        w = (k, k, c_in, c_out) if kind == "conv" else (c_in, c_out)
        out[name] = {"b": (c_out,), "w": w}
    return out


def init(layers, key) -> dict:
    """He-normal weights (std sqrt(2 / fan_in) for convs, sqrt(1 / fan_in)
    for dense layers), zero biases, float32."""
    shapes = param_shapes(layers)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, kind, *_rest) in zip(keys, layers):
        w = shapes[name]["w"]
        fan_in = int(np.prod(w[:-1]))
        scale = math.sqrt(2.0) if kind == "conv" else 1.0
        out[name] = {"w": jax.random.normal(k, w, jnp.float32)
                     * (scale / math.sqrt(fan_in)),
                     "b": jnp.zeros(shapes[name]["b"], jnp.float32)}
    return out


def forward(layers, params, images, precision=HIGHEST):
    x = images.astype(params[layers[0][0]]["w"].dtype)
    last = layers[-1][0]
    for name, kind, _, _, _, _, pool in layers:
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


def loss(layers, params, batch, precision=HIGHEST):
    return cross_entropy(forward(layers, params, batch["images"], precision),
                         batch["labels"])


def width_groups(layers, groups) -> tuple:
    """Each group cuts its producer's output channels (weight and bias) and
    its consumer's input channels, ``outer`` positions per channel."""
    kinds = {row[0]: row[1] for row in layers}
    out = []
    for name, size, prod, cons, outer in groups:
        produced = Entry(f"{prod}.w", 3 if kinds[prod] == "conv" else 1)
        consumed = Entry(f"{cons}.w", 2 if kinds[cons] == "conv" else 0,
                         outer=outer)
        out.append(WidthGroup(name, size, sort_by=produced, entries=(
            produced, Entry(f"{prod}.b", 0), consumed)))
    return tuple(out)


def layer_flops(layers, shapes: dict) -> dict:
    """Forward FLOPs per sample of each layer, given ``{path: shape}``."""
    return {name: 2 * int(np.prod(shapes[f"{name}.w"]))
            * (side * side if kind == "conv" else 1)
            for name, kind, _, _, _, side, _ in layers}


def train_flops(layers, shapes: dict) -> int:
    first = layers[0][0]
    return sum(3 * f if name != first else 2 * f
               for name, f in layer_flops(layers, shapes).items())

