"""``toy-attn`` at the widths of a cut catalog MoE's largest leaves: a
16,032-token vocabulary slice, model width 4096, 8 key/value heads of 4
query heads of 128 lanes, an MLP of 16,384 units; 307,531,424 float32
parameters, on rows of 2,048 tokens taken one row a pass.  It measures
the reference's memory on one chip (``bench/reference_probe.py``); no CPU
test trains it."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "toy_attn_300m_base", pathlib.Path(__file__).with_name("toy-attn.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
(_base.VOCAB, _base.D, _base.KV, _base.Q_PER_KV, _base.HEAD, _base.FF,
 _base.SEQ) = 16032, 4096, 8, 4, 128, 16384, 2048

TASK, SEQ = _base.TASK, _base.SEQ
PASS_ROWS = 1     # one row's attention scores are 32 x 2047 x 2047 floats
param_shapes, init, loss = _base.param_shapes, _base.init, _base.loss
width_groups = _base.width_groups
layer_flops, train_flops = _base.layer_flops, _base.train_flops
