"""Model FLOPs per sample from the EMS sub-model's parameter shapes.

A convolution of kernel ``k`` from ``c_in`` to ``c_out`` channels over an
``s x s`` output map costs ``2 k^2 c_in c_out s^2`` multiply-adds counted
as two FLOPs each; a dense layer ``2 d_in d_out``.  At width ``alpha``
every EMS group keeps ``ceil(size * sqrt(alpha))`` channels, so a layer
between two groups costs about ``alpha`` of its full work and the first and
last layers about ``sqrt(alpha)``.  Training is the forward pass plus the
backward pass, which computes a gradient for the weights (one forward's
worth) and one for the layer's input (another), except at the first layer,
whose input is the data.  Bias adds, activations, pooling and the loss are
not counted.
"""
from __future__ import annotations

from bench import reference


def sub_shapes(model, alpha: float) -> dict:
    """``{layer: weight shape}`` of the width-``alpha`` sub-model."""
    w = reference.widths(model, alpha)
    out_w = {g[2]: w[g[0]] for g in model.GROUPS}
    in_w = {g[3]: w[g[0]] * g[4] for g in model.GROUPS}
    shapes = {}
    for name, kind, k, c_in, c_out, _, _ in model.LAYERS:
        c_in, c_out = in_w.get(name, c_in), out_w.get(name, c_out)
        shapes[name] = (k, k, c_in, c_out) if kind == "conv" \
            else (c_in, c_out)
    return shapes


def layer_forward(model, alpha: float) -> dict:
    """Forward FLOPs per sample of each layer."""
    shapes = sub_shapes(model, alpha)
    out = {}
    for name, kind, _, _, _, side, _ in model.LAYERS:
        n = 1
        for d in shapes[name]:
            n *= d
        out[name] = 2 * n * (side * side if kind == "conv" else 1)
    return out


def forward(model, alpha: float = 1.0) -> int:
    return sum(layer_forward(model, alpha).values())


def train(model, alpha: float = 1.0) -> int:
    """Forward plus backward FLOPs per sample."""
    per = layer_forward(model, alpha)
    first = model.LAYERS[0][0]
    return sum(3 * f if name != first else 2 * f
               for name, f in per.items())
