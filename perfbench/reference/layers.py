"""Layers shared by the plain float32 backbones of the benchmark's reference.

A backbone is a module ``backbone_<name>.py`` beside this file, found by the
configuration file's ``reference.backbone`` name, with two functions:

- ``specs(ref)``    -> [(path, shape, kind)] of every leaf, ``path`` being the
  "/"-joined name the detector's parameter tree gives it (the benchmark hands
  its own weights to the program under those names);
- ``features(ref, w, x)`` -> {level: (B, H_l, W_l, C)} from normalized pixels.

Follows He et al. 2016 (bottleneck v1, stride in the 3x3 as torchvision has
it, BatchNorm frozen to an affine), Lin et al. 2017 (FPN, nearest top-down,
P6 by subsampling P5) and Simonyan & Zisserman 2015 (VGG-16 without pool5).
No kernel, no blocking, no bf16: every matmul at ``highest``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


def conv(x, w, stride=1, pad=0, matmul=None):
    """NHWC x HWIO convolution in float32; ``matmul`` lets the control swap
    in a lower-precision operand rounding (see lowprec.py)."""
    if matmul is not None:
        x, w = matmul(x), matmul(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    )


def frozen_bn(w, name, x):
    mul = w[f"constants/{name}/scale"] / jnp.sqrt(w[f"constants/{name}/var"] + BN_EPS)
    return x * mul + (w[f"constants/{name}/bias"] - w[f"constants/{name}/mean"] * mul)


def maxpool(x, k, s, pad):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, k, k, 1), (1, s, s, 1),
        [(0, 0), (pad, pad), (pad, pad), (0, 0)],
    )


