"""Device seconds per round of the flat AIO aggregate and server apply.

``Simulation.aggregate`` on the pooled route jits ``agg`` (Eq. 5 over the
stacked cohort, then ``AnycostServer.apply_update``): ``jit_agg``.
"""
UNIT = "s/round"
PROGRAMS = ("jit_agg",)


def read(r):
    s = r.program_seconds(PROGRAMS)
    return None if s is None else s / r.rounds
