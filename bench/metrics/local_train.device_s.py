"""Device seconds per round of the local-step programs.

``ClientPool`` jits ``jax.vmap`` of ``AnycostClient._local_steps_fast``'s
``run`` (and ``run`` alone for a group of one); the unpooled route calls
``AnycostClient._local_steps``'s jitted ``run``.  All appear in the trace
as ``jit_run``.
"""
UNIT = "s/round"
PROGRAMS = ("jit_run",)


def read(r):
    s = r.program_seconds(PROGRAMS)
    return None if s is None else s / r.rounds
