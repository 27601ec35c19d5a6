"""ResNet-50 + FPN, plain float32 (He et al. 2016 bottleneck v1 with the stride
in the 3x3, BatchNorm frozen to an affine; Lin et al. 2017 FPN, nearest
top-down, P6 by subsampling P5)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference.layers import conv, frozen_bn, maxpool

R50_BLOCKS = (3, 4, 6, 3)
R50_WIDTHS = (64, 128, 256, 512)


def _r50_blocks():
    for i, (n, width) in enumerate(zip(R50_BLOCKS, R50_WIDTHS)):
        for b in range(n):
            yield i, b, width, (2 if (i > 0 and b == 0) else 1)


def specs(ref):
    out = []

    def bn(name, c):
        for leaf, kind in (("scale", "bn_scale"), ("bias", "bn_bias"),
                           ("mean", "bn_mean"), ("var", "bn_var")):
            out.append((f"constants/backbone/{name}/{leaf}", (c,), kind))

    out.append(("params/backbone/conv1/kernel", (7, 7, 3, 64), "he"))
    bn("bn1", 64)
    c_in = 64
    for i, b, width, _ in _r50_blocks():
        p = f"layer{i + 1}_block{b}"
        out.append((f"params/backbone/{p}/conv1/kernel", (1, 1, c_in, width), "he"))
        bn(f"{p}/bn1", width)
        out.append((f"params/backbone/{p}/conv2/kernel", (3, 3, width, width), "he"))
        bn(f"{p}/bn2", width)
        out.append((f"params/backbone/{p}/conv3/kernel", (1, 1, width, 4 * width), "he"))
        bn(f"{p}/bn3", 4 * width)
        # The last affine of a residual branch is kept small so that sixteen
        # blocks of random weights do not blow the activations up.
        out[-4] = (out[-4][0], out[-4][1], "bn_scale_res")
        if b == 0:
            out.append((f"params/backbone/{p}/downsample_conv/kernel",
                        (1, 1, c_in, 4 * width), "lecun"))
            bn(f"{p}/downsample_bn", 4 * width)
        c_in = 4 * width
    ch = ref["fpn_channels"]
    for lvl, c in zip((2, 3, 4, 5), (256, 512, 1024, 2048)):
        out.append((f"params/fpn/lateral{lvl}/kernel", (1, 1, c, ch), "lecun"))
        out.append((f"params/fpn/lateral{lvl}/bias", (ch,), "bias"))
        out.append((f"params/fpn/output{lvl}/kernel", (3, 3, ch, ch), "lecun"))
        out.append((f"params/fpn/output{lvl}/bias", (ch,), "bias"))
    return out


def features(ref, w, x, matmul=None):
    def cb(name, bn_name, x, stride, pad):
        y = conv(x, w[f"params/backbone/{name}/kernel"], stride, pad, matmul)
        return frozen_bn(w, f"backbone/{bn_name}", y)

    x = jax.nn.relu(cb("conv1", "bn1", x, 2, 3))
    x = maxpool(x, 3, 2, 1)
    feats = {}
    for i, b, width, stride in _r50_blocks():
        p = f"layer{i + 1}_block{b}"
        y = jax.nn.relu(cb(f"{p}/conv1", f"{p}/bn1", x, 1, 0))
        y = jax.nn.relu(cb(f"{p}/conv2", f"{p}/bn2", y, stride, 1))
        y = cb(f"{p}/conv3", f"{p}/bn3", y, 1, 0)
        if b == 0:
            x = cb(f"{p}/downsample_conv", f"{p}/downsample_bn", x, stride, 0)
        x = jax.nn.relu(y + x)
        if b == R50_BLOCKS[i] - 1:
            feats[i + 2] = x
    lat = {
        l: conv(feats[l], w[f"params/fpn/lateral{l}/kernel"], 1, 0, matmul)
        + w[f"params/fpn/lateral{l}/bias"]
        for l in (2, 3, 4, 5)
    }
    merged = {5: lat[5]}
    for l in (4, 3, 2):
        up = jnp.repeat(jnp.repeat(merged[l + 1], 2, axis=1), 2, axis=2)
        merged[l] = lat[l] + up
    out = {
        l: conv(merged[l], w[f"params/fpn/output{l}/kernel"], 1, 1, matmul)
        + w[f"params/fpn/output{l}/bias"]
        for l in (2, 3, 4, 5)
    }
    for l in range(6, ref["fpn_max_level"] + 1):
        out[l] = out[l - 1][:, ::2, ::2, :]
    return out


