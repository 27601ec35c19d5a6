"""``perfbench/mixer_need.py``: the scan mixers' projection matmuls counted by
hand at each committed configuration, and against the matmul FLOPs of the plain
reference's own mixer with its recurrence left out (an abstract trace at the
configuration's sizes: nothing is computed)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import mixer_need  # noqa: E402
from perfbench.flops import count_matmul_flops, least_seconds  # noqa: E402

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
TOKENS = 2 * (800 // 16) * (1344 // 16)     # two images of 800 x 1344 in 16 x 16 patches


def ref_of(name):
    with open(os.path.join(REPO, "perfbench", "configs", f"{name}.json")) as f:
        return json.load(f)["reference"]


# (configuration, kind, layers, multiply-adds a token and layer, by hand from the published widths)
BY_HAND = [
    # q, k, v, f, g 2560 x 4096 each; beta 2560 x 32; o 4096 x 2560
    ("ling3_flash_vl_det", "kda", 6, 5 * 2560 * 4096 + 2560 * 32 + 4096 * 2560),
    # in_proj 2688 x (2 x 4096 + 2 x 8 x 128 + 64 = 10304); out_proj 4096 x 2688
    ("nemotron_twotower_det", "ssm", 6, 2688 * 10304 + 4096 * 2688),
    # in_proj 2560 x 10240; x_proj 5120 x (160 + 16 + 16); dt_proj 160 x 5120; out_proj 5120 x 2560
    ("phi4_mini_flash_det", "mamba", 3, 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560),
]


@pytest.mark.parametrize("name, kind, layers, macs", BY_HAND)
def test_the_need_is_the_projections_by_hand(name, kind, layers, macs):
    ref = ref_of(name)
    got_kind, got_layers, matmuls = mixer_need.projections(ref)
    assert (got_kind, got_layers) == (kind, layers)
    assert sum(i * o for i, o in matmuls) == macs
    need = mixer_need.mixer_proj_need(ref, 2)
    assert TOKENS == 8400
    assert need["flops"] == 3 * 2.0 * TOKENS * layers * macs       # forward, and twice that backward
    # each weight (bfloat16) once, each matmul's input and result once, a pass; three passes
    moved = layers * sum(2 * i * o + 2 * TOKENS * (i + o) for i, o in matmuls)
    assert need["bytes"] == 3.0 * moved
    assert least_seconds(need, PEAK)[1] == "flops"


@pytest.mark.parametrize("name, ms", [
    ("ling3_flash_vl_det", 96.70), ("nemotron_twotower_det", 59.42), ("phi4_mini_flash_det", 31.56),
])
def test_the_least_time_at_the_chip_s_peak(name, ms):
    least, _ = least_seconds(mixer_need.mixer_proj_need(ref_of(name), 2), PEAK)
    assert least * 1e3 == pytest.approx(ms, abs=0.01)


def test_a_configuration_without_a_scan_mixer_needs_nothing():
    assert mixer_need.projections(ref_of("vgg16_voc07")) == ("", 0, [])
    assert mixer_need.mixer_proj_need(ref_of("vgg16_voc07"), 16) is None


def _abstract_weights(backbone, ref):
    import jax
    import jax.numpy as jnp

    return {path: jax.ShapeDtypeStruct(shape, jnp.float32) for path, shape, _ in backbone.specs(ref)}


@pytest.mark.parametrize("name, module, mixer, scan, first", [
    ("ling3_flash_vl_det", "backbone_ling3_flash_vl", "kda", "delta_rule", "l0"),
    ("nemotron_twotower_det", "backbone_nemotron_twotower", "ssm", "recurrence", "l0"),
    ("phi4_mini_flash_det", "backbone_phi4_mini_flash", "mamba", "recurrence", "l0"),
])
def test_the_need_is_the_plain_reference_s_mixer_without_its_scan(monkeypatch, name, module, mixer,
                                                                  scan, first):
    import importlib

    import jax
    import jax.numpy as jnp

    backbone = importlib.import_module(f"perfbench.reference.{module}")
    ref = ref_of(name)
    dc = ref["decoder"]
    tokens = TOKENS // 2                     # the reference runs one image's sequence
    # the recurrence left out: zeros of its result's shape (x's, but for KDA: v's, the third operand)
    shaped_as = 2 if scan == "delta_rule" else 0
    monkeypatch.setattr(backbone, scan, lambda *operands: jnp.zeros_like(operands[shaped_as]))
    w = _abstract_weights(backbone, ref)
    x = jax.ShapeDtypeStruct((tokens, dc["hidden_size"]), jnp.float32)
    p = f"params/backbone/{first}/{mixer}"
    run = getattr(backbone, mixer)
    flops = count_matmul_flops(lambda w, x: run(dc, w, p, x, None), w, x)
    _, layers, matmuls = mixer_need.projections(ref)
    assert flops == 2.0 * tokens * sum(i * o for i, o in matmuls)
    # ... and the whole need: three passes of every layer over both images
    assert mixer_need.mixer_proj_need(ref, 2)["flops"] == 3 * 2 * layers * flops
