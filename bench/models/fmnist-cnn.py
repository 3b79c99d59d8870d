"""FedAvg CNN (McMahan et al. 2017), as AnycostFL section V-A trains it.

Layer table read by ``bench/cnn.py``: 'SAME' 5x5 convolutions with bias
and ReLU, each followed by a 2x2 max pool, then a ReLU dense layer and the
10-class output.  Data from ``bench/tasks/images.py``.
"""
import functools

from bench import cnn

TASK = "images"

# (name, kind, kernel, c_in, c_out, output map side before pooling, pool)
LAYERS = (
    ("conv1", "conv", 5, 1, 32, 28, True),
    ("conv2", "conv", 5, 32, 64, 14, True),
    ("dense1", "dense", 0, 7 * 7 * 64, 512, 0, False),
    ("dense2", "dense", 0, 512, 10, 0, False),
)

# EMS width groups: (name, channels, producing layer, consuming layer,
# spatial positions per channel in the consumer's input).  Channels are
# ranked by the L2 norm of the producer's output slice.
GROUPS = (
    ("conv1", 32, "conv1", "conv2", 1),
    ("conv2", 64, "conv2", "dense1", 49),
    ("dense1", 512, "dense1", "dense2", 1),
)

param_shapes = functools.partial(cnn.param_shapes, LAYERS)
init = functools.partial(cnn.init, LAYERS)
loss = functools.partial(cnn.loss, LAYERS)
layer_flops = functools.partial(cnn.layer_flops, LAYERS)
train_flops = functools.partial(cnn.train_flops, LAYERS)
width_groups = functools.partial(cnn.width_groups, LAYERS, GROUPS)
