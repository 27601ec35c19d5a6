"""The ``granite4_h_micro`` need functions (``perfbench/granite_need.py``)
against the same work counted by hand at the configuration's own sizes, and the
new cell as the harness finds it."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import granite_need  # noqa: E402
from perfbench.flops import least_seconds  # noqa: E402


def conf(name="granite4_h_micro_det"):
    with open(os.path.join(REPO, "perfbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_the_new_cell_and_configuration_are_found_by_name():
    """The cell and its configuration are BENCHMARK.json's, and the cell
    reports the per-layer metrics that list no cells, the whole step's share
    of the peak among them."""
    from perfbench.spec import Spec

    spec = Spec(REPO)
    cell = spec.cell("granite4_h_micro_det.train_coco")
    assert cell["config"] == "granite4_h_micro_det" and cell["chips"] == 1
    assert cell["entry"] == "train_lean_granite" and cell["traffic"] == "train_coco"
    assert spec.config(cell["config"])["reference"]["backbone"] == "granite4_h_micro"
    names = [m["name"] for m in spec.metrics_of(cell["name"], "per_layer")]
    assert "step_mfu.train" in names and "device_idle_share.train" in names


def test_need_functions_by_hand():
    ref = conf()["reference"]
    tokens, images = 50 * 84, 2
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # Scan: 9 layers; per head and token 5 P N FLOPs; x, y bfloat16 and dt
    # float32 a head, B and C bfloat16 ONCE for all 64 heads; backward twice the forward.
    rows = images * tokens * 9
    scan = granite_need.ssm_scan_need(ref, images)
    assert scan["flops"] == pytest.approx(3 * rows * 64 * 5 * 64 * 128)
    assert scan["bytes"] == pytest.approx(3 * rows * (64 * (2 * 64 * 2 + 4) + 1 * 2 * 128 * 2))
    assert least_seconds(scan, peak)[1] == "bytes"
    # Attention: 1 layer; the causal half of the scores and of p v (64 each);
    # q, o moved a query head (32), k, v a key head (8).
    pairs = tokens * (tokens + 1) / 2
    attn = granite_need.attn_need(ref, images)
    assert attn["flops"] == pytest.approx(3 * images * 32 * pairs * 2 * (64 + 64))
    assert attn["bytes"] == pytest.approx(3 * images * tokens * (2 * 32 + 2 * 8) * 64 * 2)
    assert least_seconds(attn, peak)[1] == "flops"


def test_step_flops_is_the_issue_s_arithmetic():
    """A forward of two images: the SwiGLU sub-layers 8.46 T, the Mamba-2
    projections 3.90 T (counted by hand); the step is three forwards and the two
    ops' own work, about 39 T; no expert counter moves it."""
    ref = conf()["reference"]
    tokens = 2 * 4200
    mlp = 10 * tokens * 2 * 3 * 2048 * 8192
    mamba = 9 * tokens * 2 * (2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048)
    assert mlp / 1e12 == pytest.approx(8.46, abs=0.01)
    assert mamba / 1e12 == pytest.approx(3.90, abs=0.01)
    attn_proj = tokens * 2 * (2048 * (32 + 16) * 64 + 2048 * 2048)
    total = granite_need.step_flops(ref, 2)
    ops = granite_need.ssm_scan_need(ref, 2)["flops"] + granite_need.attn_need(ref, 2)["flops"]
    rest = total - ops - 3 * (mlp + mamba + attn_proj)
    assert 0 < rest < 3 * 0.35e12      # patchify, neck, RPN and box head
    assert 38e12 < total < 40e12
    assert granite_need.step_flops(ref, 2, slots_per_step=1e9) == total
