"""Wall-clock spans on the JAX profiler's clock, and the profiler itself.

Two clocks in this package: :mod:`~repro.telemetry.trace` owns the
*simulated* timeline (seconds of the modelled fleet); this module owns
the *host wall clock* as ``jax.profiler`` records it, beside the device
planes of the same trace.

* :func:`span` names a stretch of host work (``fl.round``,
  ``fl.finish``, ...).  It is a ``jax.profiler.TraceAnnotation``: it
  records only while a profiler trace runs, and otherwise costs about a
  microsecond.  No flag turns it on.
* :func:`put` and :func:`read` are the round path's explicit host-to-device
  and device-to-host transfers, spanned ``fl.h2d`` and ``fl.sync`` with the
  bytes moved.  Under a ``jit`` trace a put is a constant of the program
  and a read cannot happen, so neither emits anything there.
* :func:`profile_trace` runs the profiler around a body
  (``--jax-profile DIR``).  A profiler that cannot start or stop fails the
  run: a run asked for a profile never goes on without one.
"""
from __future__ import annotations

import contextlib
import os
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp

_Annotation = jax.profiler.TraceAnnotation


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` carrying ``stats`` (numbers) as trace stats."""
    return _Annotation(name, **stats)


def _recording() -> bool:
    """A profiler trace runs and this code runs eagerly, not under a
    ``jit`` trace."""
    return _Annotation.is_enabled() and jax.core.trace_ctx.is_top_level()


def put(x) -> jax.Array:
    """``jnp.asarray`` of a host (numpy) array, spanned ``fl.h2d``."""
    if not _recording():
        return jnp.asarray(x)
    with _Annotation("fl.h2d", bytes=int(x.nbytes)):
        return jnp.asarray(x)


def read(x) -> Any:
    """``jax.device_get`` of a device array or pytree, spanned ``fl.sync``
    with the bytes of its device arrays (host leaves move nothing)."""
    if not _recording():
        return jax.device_get(x)
    n = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(x)
            if isinstance(leaf, jax.Array))
    if not n:
        return jax.device_get(x)
    with _Annotation("fl.sync", bytes=int(n)):
        return jax.device_get(x)


@contextlib.contextmanager
def profile_trace(log_dir: Optional[str]) -> Iterator[Optional[str]]:
    """Run ``jax.profiler`` around the body, writing into ``log_dir``
    (``plugins/profile/<run>/*.xplane.pb``); yields ``log_dir``.  A None
    ``log_dir`` runs the body unprofiled."""
    if log_dir is None:
        yield None
        return
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()
