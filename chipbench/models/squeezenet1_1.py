"""SqueezeNet v1.1 at its published widths, the client CNN of a cell.

The layout of github.com/forresti/SqueezeNet (SqueezeNet_v1.1) and
torchvision's ``squeezenet1_1`` (arXiv:1602.07360 for the fire module):
a 3x3 stride-2 conv of 64 channels, then eight fire modules (a 1x1
squeeze, and 1x1 and 3x3 expands concatenated, each with ReLU) with
3x3 stride-2 max pools after the stem, fire3 and fire5, then a 1x1
conv to the classes, ReLU and global average pooling.

Departures, each named in the configuration's file: 5 classes (the DR
grades) in place of ImageNet's 1000; no dropout before the classifier
(the engine's local phase gives a model no key); pools without
``ceil_mode``, which give the same sizes at 224 px (111, 55, 27, 13).

Images are uint8 RGB, normalised inside the model with ImageNet's mean
and deviation. NHWC images, HWIO kernels; convolutions through ``mm``
(``chipbench.reference.Matmul``). The program's engine is handed this
forward at float32 as its client model; the plain reference runs it too.
"""
import jax
import jax.numpy as jnp
import numpy as np

N_CLASSES = 5
STEM = 64
# (squeeze, expand) of fire2 .. fire9; each expand is two convs of this width
FIRES = [(16, 64), (16, 64), (32, 128), (32, 128),
         (48, 192), (48, 192), (64, 256), (64, 256)]
POOL_AFTER = ("fire3", "fire5")   # and after the stem
MEAN = np.array([0.485, 0.456, 0.406], np.float32) * 255.0
STD = np.array([0.229, 0.224, 0.225], np.float32) * 255.0


def _conv(cin, cout, k):
    return {"w": (k, k, cin, cout), "b": (cout,)}


def param_shapes() -> dict:
    shapes = {"conv1": _conv(3, STEM, 3)}
    cin = STEM
    for i, (s, e) in enumerate(FIRES):
        shapes[f"fire{i + 2}"] = {"squeeze": _conv(cin, s, 1),
                                  "expand1x1": _conv(s, e, 1),
                                  "expand3x3": _conv(s, e, 3)}
        cin = 2 * e
    shapes["conv10"] = _conv(cin, N_CLASSES, 1)
    return shapes


def _maxpool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 3, 3, 1), (1, 2, 2, 1), "VALID")


def forward(params, images, mm):
    """Logits (B, 5) of uint8 images (B, H, W, 3)."""
    def conv(x, p, stride=1, padding="SAME"):
        return jax.nn.relu(mm.conv(x, p["w"], stride, padding) + p["b"])

    x = (images.astype(jnp.float32) - MEAN) / STD
    x = _maxpool(conv(x, params["conv1"], 2, "VALID"))
    for i in range(len(FIRES)):
        name = f"fire{i + 2}"
        p = params[name]
        s = conv(x, p["squeeze"])
        x = jnp.concatenate([conv(s, p["expand1x1"]),
                             conv(s, p["expand3x3"])], axis=-1)
        if name in POOL_AFTER:
            x = _maxpool(x)
    return jnp.mean(conv(x, params["conv10"]), axis=(1, 2))
