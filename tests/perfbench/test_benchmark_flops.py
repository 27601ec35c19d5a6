"""The benchmark's own yardsticks agree with the program's today, and its
shape functions count what they say."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import flops, peaks  # noqa: E402


def _net(x, w1, w2):
    y = jax.lax.conv_general_dilated(
        x, w1, (2, 2), [(1, 1), (1, 1)], dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    y = jax.lax.scan(lambda c, _: (c @ w2, None), y.reshape(-1, 8), None, length=3)[0]
    return jnp.sum(y)


def _args():
    return jnp.ones((2, 16, 16, 4)), jnp.ones((3, 3, 4, 8)), jnp.ones((8, 8))


def test_copied_counter_agrees_with_the_programs():
    from mx_rcnn_tpu.utils.flops import count_matmul_flops

    for fn in (_net, jax.grad(_net, argnums=(1, 2))):
        assert flops.count_matmul_flops(fn, *_args()) == count_matmul_flops(fn, *_args())


def test_counter_by_hand():
    conv = 2 * 2 * 8 * 8 * 8 * 4 * 9
    dots = 3 * 2 * (2 * 8 * 8) * 8 * 8
    assert flops.count_matmul_flops(_net, *_args()) == conv + dots


def test_peaks_agree_and_unknown_kind_is_an_error():
    from mx_rcnn_tpu.utils.flops import PEAK_BF16_FLOPS

    for kind, value in PEAK_BF16_FLOPS.items():
        assert peaks.peak(kind)["bf16_flops"] == value
    with pytest.raises(ValueError):
        peaks.peak("cpu")


def test_roi_align_need():
    need = flops.roi_align_need(rois=4096, size=7, ratio=2, channels=256,
                                level_cells=8 * 89250, itemsize=2)
    assert need["flops"] == 4096 * 49 * 4 * 4 * 2 * 256
    pooled = 4096 * 49 * 256 * 2
    pyramid = 8 * 89250 * 256 * 2
    assert need["bytes"] == pooled + 4096 * 16 + pyramid  # the pyramid once is the smaller read
    few = flops.roi_align_need(rois=2, size=7, ratio=2, channels=256,
                               level_cells=8 * 89250, itemsize=2)
    assert few["bytes"] == 2 * 49 * 256 * 2 + 32 + 2 * 49 * 16 * 256 * 2  # the taps are smaller
    back = flops.roi_align_need(rois=4096, size=7, ratio=2, channels=256,
                                level_cells=8 * 89250, itemsize=2, backward=True)
    assert back["bytes"] == pooled + 4096 * 16 + 8 * 89250 * 256 * 4  # float32 gradient
    secs, bound = flops.least_seconds(need, peaks.peak("TPU v5 lite"))
    assert bound == "bytes" and secs == pytest.approx(need["bytes"] / 819e9)
