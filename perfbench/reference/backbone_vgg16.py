"""VGG-16 conv1_1..conv5_3 without pool5, plain float32 (Simonyan & Zisserman
2015; stride 16 as Ren et al. 2015 use it)."""

from __future__ import annotations

import jax

from perfbench.reference.layers import conv, maxpool

VGG_GROUPS = ((64, 2), (128, 2), (256, 3), (512, 3), (512, 3))


def specs(ref):
    out = []
    c_in = 3
    for g, (ch, n) in enumerate(VGG_GROUPS):
        for c in range(n):
            p = f"params/backbone/group{g + 1}/conv{g + 1}_{c + 1}"
            out.append((f"{p}/kernel", (3, 3, c_in, ch), "he"))
            out.append((f"{p}/bias", (ch,), "bias"))
            c_in = ch
    return out


def features(ref, w, x, matmul=None):
    for g, (_, n) in enumerate(VGG_GROUPS):
        for c in range(n):
            p = f"params/backbone/group{g + 1}/conv{g + 1}_{c + 1}"
            x = jax.nn.relu(conv(x, w[f"{p}/kernel"], 1, 1, matmul) + w[f"{p}/bias"])
        if g < 4:
            x = maxpool(x, 2, 2, 0)
    return {4: x}


