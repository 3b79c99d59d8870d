"""Logical-axis sharding context (MaxText-style rules, minimal core).

Model code annotates activations with ``lc(x, ("batch", "seq", "embed"))``
and parameters carry logical axis tuples (see ``models.layers.param``). A
``ShardingRules`` context maps logical names -> mesh axes; outside the
context everything is the identity so CPU smoke tests never touch device
state.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, tuple]

# Default rules for the production mesh (single- or multi-pod). An entry maps
# a logical axis name to one mesh axis, a tuple of mesh axes, or None
# (replicated). Tuples mean the logical axis is sharded over the product.
DEFAULT_RULES: dict[str, MeshAxes] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": None,          # GQA: kv heads usually < model axis -> replicate
    "head_dim": None,
    "mlp_act": "model",
    "cache_seq": None,         # overridden to "data" for batch=1 long decode
    "frames": None,
    "patches": None,
    "inner_act": "model",      # ssm / rglru inner width
    "state": None,
    "experts_act": "model",    # expert dim of dispatched activations
    "capacity": None,
    "vocab_act": "model",      # logits vocab dim
    # params: "fsdp" is the ZeRO-style axis, "tp" the tensor-parallel axis
    "fsdp": "data",
    "tp": "model",
    "experts": "model",        # expert-parallel param axis
    "expert_in": "data",       # expert ffn input dim: ZeRO-style (train)
    "expert_ff": None,         # expert ffn hidden dim (decode: -> "data")
    "vocab": "model",          # embedding table rows
    "embed_fsdp": "data",      # embedding table feature dim
    "layers": None,            # stacked-layer leading axis (scan)
    "conv": None,
    "classes": None,
    "none": None,
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict[str, MeshAxes] = {}


_CTX = _Ctx()


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: Optional[dict] = None):
    """Activate logical-axis sharding for model code within this block."""
    prev = (_CTX.mesh, _CTX.rules)
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    # drop mesh axes that don't exist on this mesh (e.g. "pod" single-pod)
    names = set(mesh.axis_names)

    def _filter(v: MeshAxes) -> MeshAxes:
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in names else None
        t = tuple(a for a in v if a in names)
        return t if t else None

    _CTX.mesh = mesh
    _CTX.rules = {k: _filter(v) for k, v in merged.items()}
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def active() -> bool:
    return _CTX.mesh is not None


def spec_for(axes: Sequence[Optional[str]]) -> P:
    """Translate a tuple of logical axis names into a PartitionSpec."""
    if not active():
        return P()
    used: set[str] = set()
    parts = []
    for name in axes:
        v = _CTX.rules.get(name or "none")
        if v is None:
            parts.append(None)
            continue
        vt = (v,) if isinstance(v, str) else tuple(v)
        vt = tuple(a for a in vt if a not in used)
        if not vt:
            parts.append(None)
            continue
        used.update(vt)
        parts.append(vt if len(vt) > 1 else vt[0])
    return P(*parts)


def safe_spec(shape: Sequence[int], axes: Sequence[Optional[str]]) -> P:
    """Like spec_for but drops mesh axes that don't divide the dim size."""
    raw = spec_for(axes)
    parts = []
    for dim, entry in zip(shape, tuple(raw) + (None,) * (len(shape) - len(raw))):
        if entry is None:
            parts.append(None)
            continue
        entry_t = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        for a in entry_t:
            size *= _CTX.mesh.shape.get(a, 1)
        if size == 0 or dim % size != 0:
            # try progressively shorter prefixes (e.g. ("pod","data")->("pod",))
            kept = ()
            acc = 1
            for a in entry_t:
                if dim % (acc * _CTX.mesh.shape.get(a, 1)) == 0:
                    acc *= _CTX.mesh.shape.get(a, 1)
                    kept = kept + (a,)
                else:
                    break
            parts.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            parts.append(entry)
    return P(*parts)


def sharding_for(shape: Sequence[int],
                 axes: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    if not active():
        return None
    return NamedSharding(_CTX.mesh, safe_spec(shape, axes))


def lc(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Logical sharding constraint; identity outside a sharding context.

    Inside a ``shard_map`` region with manual axes (the anycost pod sync)
    the region traces against an abstract mesh whose manual axes differ
    from the concrete one, so the constraint is built on that mesh; the
    rules must not name the manual axes there (see ``steps.rules_for``).
    """
    if not active():
        return x
    assert len(axes) == x.ndim, (axes, x.shape)
    region = jax.sharding.get_abstract_mesh()
    if region.manual_axes:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(region, safe_spec(x.shape, axes)))
    return jax.lax.with_sharding_constraint(x, sharding_for(x.shape, axes))


def mesh_axis_size(name: str) -> int:
    if not active():
        return 1
    return _CTX.mesh.shape.get(name, 1)
