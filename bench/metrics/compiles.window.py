"""Programs lowered inside the measured window (compiles or cache loads).

Counted from JAX's ``jaxpr_to_mlir_module`` events; warm-up should leave
none for the window.
"""
UNIT = "count"


def read(r):
    return float(r.compiles)
