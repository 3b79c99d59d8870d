"""bench/flops.py against FLOPs counted by hand from the layer shapes."""
import pytest

from bench import flops, reference

# forward FLOPs per sample, 2 per multiply-add:
# conv: 2 * k*k * c_in * c_out * side^2; dense: 2 * d_in * d_out
FMNIST_FULL = (2 * 25 * 1 * 32 * 28 * 28 + 2 * 25 * 32 * 64 * 14 * 14
               + 2 * 3136 * 512 + 2 * 512 * 10)
# alpha 0.25 keeps ceil(size * 0.5) channels of each group:
# conv1 16, conv2 32, dense1 256 (dense1's input is 7*7*32)
FMNIST_QUARTER = (2 * 25 * 1 * 16 * 28 * 28 + 2 * 25 * 16 * 32 * 14 * 14
                  + 2 * (49 * 32) * 256 + 2 * 256 * 10)
VGG9_FULL = (2 * 9 * (3 * 64 + 64 * 64) * 32 * 32
             + 2 * 9 * (64 * 128 + 128 * 128) * 16 * 16
             + 2 * 9 * (128 * 256 + 256 * 256) * 8 * 8
             + 2 * (4096 * 512 + 512 * 512 + 512 * 10))
VGG9_QUARTER = (2 * 9 * (3 * 32 + 32 * 32) * 32 * 32
                + 2 * 9 * (32 * 64 + 64 * 64) * 16 * 16
                + 2 * 9 * (64 * 128 + 128 * 128) * 8 * 8
                + 2 * (16 * 128 * 256 + 256 * 256 + 256 * 10))


@pytest.mark.parametrize("arch,alpha,fwd,first", [
    ("fmnist-cnn", 1.0, FMNIST_FULL, 2 * 25 * 32 * 28 * 28),
    ("fmnist-cnn", 0.25, FMNIST_QUARTER, 2 * 25 * 16 * 28 * 28),
    ("vgg9-cifar", 1.0, VGG9_FULL, 2 * 9 * 3 * 64 * 32 * 32),
    ("vgg9-cifar", 0.25, VGG9_QUARTER, 2 * 9 * 3 * 32 * 32 * 32),
])
def test_flops_match_hand_counts(arch, alpha, fwd, first):
    model = reference.load_model(arch)
    assert flops.forward(model, alpha) == fwd
    # backward: weight and input gradients, no input gradient at layer 1
    assert flops.train(model, alpha) == 3 * fwd - first


@pytest.mark.parametrize("arch", ["fmnist-cnn", "vgg9-cifar"])
def test_inner_layers_scale_with_alpha(arch):
    """EMS keeps ceil(size * sqrt(alpha)) channels, so a layer between two
    width groups does about alpha of its full work, and the whole model
    lies between alpha and sqrt(alpha) of it."""
    model = reference.load_model(arch)
    full = flops.layer_forward(model, 1.0)
    quarter = flops.layer_forward(model, 0.25)
    inner = [g[2] for g in model.GROUPS if g[2] in {h[3] for h in
                                                     model.GROUPS}]
    for name in inner:
        assert quarter[name] / full[name] == pytest.approx(0.25, rel=0.02)
    ratio = flops.forward(model, 0.25) / flops.forward(model, 1.0)
    assert 0.25 <= ratio <= 0.5
