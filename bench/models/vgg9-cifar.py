"""VGG-9 for 32x32x3 images, as AnycostFL section V-A trains it.

Layer table read by ``bench/cnn.py``: six 'SAME' 3x3 convolutions with
bias and ReLU (64, 64, 128, 128, 256, 256 channels), a 2x2 max pool after
the 2nd, 4th and 6th, then dense 512, 512 with ReLU and the 10-class
output.  Data from ``bench/tasks/images.py``.
"""
import functools

from bench import cnn

TASK = "images"

# (name, kind, kernel, c_in, c_out, output map side before pooling, pool)
LAYERS = (
    ("conv1", "conv", 3, 3, 64, 32, False),
    ("conv2", "conv", 3, 64, 64, 32, True),
    ("conv3", "conv", 3, 64, 128, 16, False),
    ("conv4", "conv", 3, 128, 128, 16, True),
    ("conv5", "conv", 3, 128, 256, 8, False),
    ("conv6", "conv", 3, 256, 256, 8, True),
    ("dense1", "dense", 0, 4 * 4 * 256, 512, 0, False),
    ("dense2", "dense", 0, 512, 512, 0, False),
    ("dense3", "dense", 0, 512, 10, 0, False),
)

# EMS width groups: (name, channels, producing layer, consuming layer,
# spatial positions per channel in the consumer's input).
GROUPS = (
    ("conv1", 64, "conv1", "conv2", 1),
    ("conv2", 64, "conv2", "conv3", 1),
    ("conv3", 128, "conv3", "conv4", 1),
    ("conv4", 128, "conv4", "conv5", 1),
    ("conv5", 256, "conv5", "conv6", 1),
    ("conv6", 256, "conv6", "dense1", 16),
    ("dense1", 512, "dense1", "dense2", 1),
    ("dense2", 512, "dense2", "dense3", 1),
)

param_shapes = functools.partial(cnn.param_shapes, LAYERS)
init = functools.partial(cnn.init, LAYERS)
loss = functools.partial(cnn.loss, LAYERS)
layer_flops = functools.partial(cnn.layer_flops, LAYERS)
train_flops = functools.partial(cnn.train_flops, LAYERS)
width_groups = functools.partial(cnn.width_groups, LAYERS, GROUPS)
