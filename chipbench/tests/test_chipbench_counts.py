"""Operation and byte counts from shapes, on known shapes."""
import jax.numpy as jnp
import pytest

from chipbench import flops
from chipbench import run as bench


def one_conv(params, images, mm):
    x = mm.conv(images, params["c"]["w"], 2) + params["c"]["b"]
    return mm.dot(jnp.mean(x, axis=(1, 2)), params["d"]["w"])


def test_conv_and_dense_flops_by_hand():
    shapes = {"c": {"w": (3, 3, 4, 8), "b": (8,)}, "d": {"w": (8, 5)}}
    # stride-2 SAME conv on 16x16: 8x8 outputs of 8 channels, each 3*3*4
    # multiply-adds; then an (1, 8) x (8, 5) product
    want = 2 * (8 * 8 * 8) * (3 * 3 * 4) + 2 * 5 * 8
    assert flops.forward_flops(one_conv, shapes, (16, 16, 4)) == want


def test_param_bytes():
    shapes = {"c": {"w": (3, 3, 4, 8), "b": (8,)}, "d": {"w": (8, 5)}}
    assert flops.param_bytes(shapes, 14) == 14 * 4 * (288 + 8 + 40)


def squeezenet_by_hand(stem, fires, head):
    """2 x multiply-adds: the stem's 3x3 conv, then per fire module its
    squeeze and its 1x1 and 3x3 expands, then the 1x1 classifier, each at
    the side of its feature map."""
    def fire(h, cin, s, e):
        return h * h * (cin * s + s * e + 9 * s * e)
    plan = [(fires[0], 64, 16, 64), (fires[0], 128, 16, 64),
            (fires[1], 128, 32, 128), (fires[1], 256, 32, 128),
            (fires[2], 256, 48, 192), (fires[2], 384, 48, 192),
            (fires[2], 384, 64, 256), (fires[2], 512, 64, 256)]
    return 2 * (stem * stem * 27 * 64 + sum(fire(*f) for f in plan)
                + head * head * 512 * 5)


# side of the feature maps: the stem's VALID stride-2 conv, then the
# fires after each 3x3 stride-2 pool
@pytest.mark.parametrize("size,stem,fires", [
    (224, 111, (55, 27, 13)),
    (32, 15, (7, 3, 1)),
])
def test_cell_model(size, stem, fires):
    model = bench.load_module(bench.BENCH / "models" / "squeezenet1_1.py",
                              "squeezenet1_1")
    assert flops.forward_flops(model.forward, model.param_shapes(),
                               (size, size, 3)) == squeezenet_by_hand(
        stem, fires, fires[2])
    # SqueezeNet v1.1's 1,235,496 parameters, less 512 x 995 + 995 for
    # 5 classes in place of 1000
    assert flops.param_bytes(model.param_shapes(), 1) == 4 * 725061
