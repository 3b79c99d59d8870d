"""Model FLOPs of the window over the traced window times the chip's peak.

Model FLOPs are the forward and backward passes of every client's
sub-model over its real samples (padded vmap lanes are not counted) plus
the eval's forward pass over the test set, from ``bench/flops.py``.  The
peak is the bf16 one: float32 products run at the TPU's default precision.
"""
UNIT = "%"


def read(r):
    t = r.trace
    if t is None or t.window_s <= 0 or not r.model_flops:
        return None
    return 100.0 * r.model_flops / (t.window_s * r.peak_flops_per_s)
