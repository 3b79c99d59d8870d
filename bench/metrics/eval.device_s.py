"""Device seconds per round of the test-set eval (``fl_loop._make_eval``'s
``ev``: ``jit_ev``)."""
UNIT = "s/round"
PROGRAMS = ("jit_ev",)


def read(r):
    s = r.program_seconds(PROGRAMS)
    return None if s is None else s / r.rounds
