"""Optional ``jax.profiler`` hooks around the jit'd hot paths.

The simulator's own telemetry is simulated-time; this is the *host*
side: wrapping a run in ``profile_trace`` captures an XLA/TensorBoard
profile (kernel-level timing of the vmapped client pool, the donated
absorb/merge jits, the Pallas kernels) under ``<out_dir>/jax_profile``.
Strictly opt-in (``--jax-profile``).  A profiler that cannot start or
stop fails the run: a run asked for a profile never goes on without one.
"""
from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional


@contextlib.contextmanager
def profile_trace(out_dir: Optional[str], enabled: bool = True
                  ) -> Iterator[Optional[str]]:
    """Start/stop ``jax.profiler`` around the body; yields the profile
    directory (None when disabled)."""
    if not enabled or out_dir is None:
        yield None
        return
    import jax
    prof_dir = os.path.join(out_dir, "jax_profile")
    os.makedirs(prof_dir, exist_ok=True)
    jax.profiler.start_trace(prof_dir)
    try:
        yield prof_dir
    finally:
        jax.profiler.stop_trace()
