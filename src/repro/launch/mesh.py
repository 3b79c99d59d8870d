"""Production mesh definitions (TPU v5e pods).

Functions, not module-level constants — importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py forces
512 host devices via XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the logical-axis rules place
    arrays with ``with_sharding_constraint``, which refuses ``Explicit``
    axes (``jax.make_mesh``'s default)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, data_par: int = 16):
    """v5e pod mesh. ``data_par`` rebalances the 256 chips per pod between
    the data and model axes (a §Perf knob — same chips, different logical
    split); the default is the assigned 16x16."""
    model_par = 256 // data_par
    assert data_par * model_par == 256, data_par
    shape = (2, data_par, model_par) if multi_pod else (data_par, model_par)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a (1, N) data/model mesh — CPU tests."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))


def describe(mesh) -> str:
    return " x ".join(f"{k}={v}" for k, v in mesh.shape.items())
