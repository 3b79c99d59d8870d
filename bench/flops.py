"""Model FLOPs per sample of the EMS sub-model at width ``alpha``.

Every width group keeps ``n`` of its ``size`` channels
(``reference.widths``), and each leaf it cuts keeps ``outer * n * block``
along the cut axis.  The model file counts the FLOPs of a model with those
leaf shapes (``layer_flops``, ``train_flops``).
"""
from __future__ import annotations

from bench import reference


def sub_shapes(model, alpha: float) -> dict:
    """``{leaf path: shape}`` of the width-``alpha`` sub-model."""
    shapes = {k: list(s) for k, s in
              reference.flat(model.param_shapes()).items()}
    n = reference.widths(model, alpha)
    for g in model.width_groups():
        for e in g.entries:
            shapes[e.path][e.axis] = e.outer * n[g.name] * e.block
    return {k: tuple(s) for k, s in shapes.items()}


def layer_forward(model, alpha: float = 1.0) -> dict:
    """Forward FLOPs per sample of each layer."""
    return model.layer_flops(sub_shapes(model, alpha))


def forward(model, alpha: float = 1.0) -> int:
    return sum(layer_forward(model, alpha).values())


def train(model, alpha: float = 1.0) -> int:
    """Forward plus backward FLOPs per sample."""
    return model.train_flops(sub_shapes(model, alpha))
