"""Device seconds per round of the per-client finish: the sub-model's
residual expanded to full width and FGC-compressed (kernel top-K,
stochastic quantization, the entropy size model).

``AnycostClient._finish_core`` jits ``core`` once per alpha bucket, and
``finish_round_fast`` calls it once per client: ``jit_core``.
"""
UNIT = "s/round"
PROGRAMS = ("jit_core",)


def read(r):
    s = r.program_seconds(PROGRAMS)
    return None if s is None else s / r.rounds
