"""The ``nemotron_twotower`` readers on a hand-made trace fragment, and the
need functions (``perfbench/ssm_need.py``) against the same work counted by
hand at the configuration's own sizes."""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import readers, ssm_need  # noqa: E402
from perfbench.flops import least_seconds  # noqa: E402

MS = 1e6
FWD = "jit(step)/jit(main)/jvp(TwoStageDetector.features)/backbone/"
BWD = "jit(step)/jit(main)/transpose(jvp(TwoStageDetector.features))/backbone/"
REMAT = BWD + "rematted_computation/"

# (op, offset ms, duration ms, scope) of one 100 ms step
STEP = [
    ("convolution.1", 0, 1, FWD + "patchify/conv_general_dilated"),
    ("fusion.10", 1, 4, FWD + "l0/ssm/proj/dense/dot_general"),
    ("fusion.11", 5, 1, FWD + "l0/ssm/conv/mul"),
    ("fusion.12", 6, 2, FWD + "l0/ssm/scan/intra/dot_general"),
    ("while.13", 8, 3, FWD + "l0/ssm/scan/inter/while"),
    ("fusion.14", 9, 1, FWD + "l0/ssm/scan/inter/while/body/mul"),          # inside the loop
    ("fusion.15", 11, 1, FWD + "l0/ssm/norm/rsqrt"),
    ("fusion.20", 12, 2, FWD + "l5/gqa/proj/dense/dot_general"),
    ("fusion.21", 14, 2, FWD + "l5/gqa/attn/rows/dot_general"),
    ("fusion.30", 16, 1, FWD + "l1/moe/router/scores/dot_general"),
    ("fusion.31", 17, 1, FWD + "l1/moe/experts/dot_general"),
    ("fusion.32", 18, 2, FWD + "l1/moe/shared/dense/dot_general"),
    ("fusion.50", 30, 1, REMAT + "l1/moe/experts/dot_general"),
    ("fusion.51", 31, 2, BWD + "l1/moe/experts/transpose(dot_general)"),
    ("fusion.52", 33, 1, BWD + "l1/moe/dispatch/transpose(select_n)"),
    ("fusion.60", 34, 1, REMAT + "l5/gqa/attn/rows/dot_general"),
    ("fusion.61", 35, 3, BWD + "l5/gqa/attn/rows/transpose(dot_general)"),
    ("fusion.70", 40, 3, REMAT + "l0/ssm/scan/intra/dot_general"),
    ("fusion.71", 43, 5, BWD + "l0/ssm/scan/intra/transpose(dot_general)"),
    ("while.72", 48, 4, BWD + "l0/ssm/scan/inter/transpose(while)"),
    ("fusion.73", 52, 6, BWD + "l0/ssm/proj/dense/transpose(dot_general)"),
    ("copy.7", 62, 2, ""),                  # an op without a scope is no layer's
    ("fusion.90", 70, 20, "jit(step)/jit(main)/optimizer/mul"),
]
SSM_MS = 4 + 1 + 2 + 3 + 1 + 3 + 5 + 4 + 6
SSM_SCAN_MS = 2 + 3 + 3 + 5 + 4
GQA_ATTN_MS = 2 + 1 + 3
MOE_MS = 4 + 1 + 2 + 1
MOE_EXPERTS_MS = 1 + 1 + 2
SLOTS = 9000.0
SEVEN = ["ssm_ms.train", "ssm_scan_roofline.train", "gqa_attn_ms.train", "gqa_attn_roofline.train",
         "moe_mlp_ms.train", "moe_mlp_experts_roofline.train", "moe_mlp_load_max_over_mean.train"]


def conf(name="nemotron_twotower_det"):
    with open(os.path.join(REPO, "perfbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def reading(config=None, counters=True):
    ops, modules, host = [], [], [("sync", -1 * MS, 1 * MS), ("sync", 299 * MS, 1 * MS)]
    for i in range(3):
        t = i * 100 * MS
        modules.append((f"jit_step({i})", t, 100 * MS, ""))
        ops += [(nm, t + at * MS, d * MS, sc) for nm, at, d, sc in STEP]
    c = {"steps": 3, "global_batch": 2, "data_stall_s": 0.0, "sync_every": 3}
    if counters:
        c.update(moe_slots_here=SLOTS, moe_load_max_over_mean=6.5, moe_dropped_slots=0.0)
    r = {
        "trace": {"devices": {0: {
            "XLA Ops": ops,
            "XLA Modules": [(f"jit_step({i})", i * 100 * MS, 100 * MS, "") for i in (-3, -2, -1)]
            + modules,
        }}},
        "host_spans": [(n, s + 7e12, d) for n, s, d in host], "sync_every": 3,
        "program_name": "jit_step", "chips": 1, "counters": c,
        "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "config": conf() if config is None else config,
    }
    readers.prepare(r)
    return r


def metric(name):
    path = os.path.join(REPO, "perfbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_benchmark_json_lists_the_seven_for_the_new_cell_alone():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in SEVEN}
    assert sorted(listed) == sorted(SEVEN)
    for m in listed.values():
        assert m["workloads"] == ["nemotron_twotower_det.train_coco"]
        assert m["moves"] == "train_img_s_chip"
        assert os.path.exists(os.path.join(REPO, "perfbench", "metrics", m["name"] + ".py"))


@pytest.mark.parametrize("name,want", [
    ("ssm_ms.train", SSM_MS),                 # the loop op and the op inside it count once
    ("gqa_attn_ms.train", GQA_ATTN_MS),       # forward, recomputed forward and backward
    ("moe_mlp_ms.train", MOE_MS),
    ("moe_mlp_load_max_over_mean.train", 6.5),
])
def test_readers_on_the_fragment(name, want):
    r = reading()
    assert r["steps_traced"] == 3
    assert metric(name)(r) == pytest.approx(want)


@pytest.mark.parametrize("name,need,ms", [
    ("ssm_scan_roofline.train", lambda ref: ssm_need.ssm_scan_need(ref, 2), SSM_SCAN_MS),
    ("gqa_attn_roofline.train", lambda ref: ssm_need.gqa_attn_need(ref, 2), GQA_ATTN_MS),
    ("moe_mlp_experts_roofline.train", lambda ref: ssm_need.moe_mlp_experts_need(ref, SLOTS),
     MOE_EXPERTS_MS),
])
def test_roofline_readers_divide_the_need_by_the_scope_s_time(name, need, ms):
    r = reading()
    least, _ = least_seconds(need(r["config"]["reference"]), r["peak"])
    got = metric(name)(r)
    assert got == pytest.approx(100.0 * least / (ms * 1e-3))
    assert 0.0 < got


@pytest.mark.parametrize("config", ["none", "ling3_flash_vl_det"])
@pytest.mark.parametrize("name", SEVEN)
def test_a_program_without_the_backbone_reads_nothing(name, config):
    """The parent's step under this PR's benchmark files, or the other decoder
    family's: no such scope, no such counter, no state-space ``decoder`` block
    -> None, never 0 and never an error."""
    other = {"reference": {"canvas": [608, 1024]}} if config == "none" else conf(config)
    r = reading(config=other, counters=False)
    r["ops"] = [o for o in r["ops"] if "/backbone/" not in o[3]]
    assert metric(name)(r) is None


def test_need_functions_by_hand():
    ref = conf()["reference"]
    tokens, images = 50 * 84, 2
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # Scan: 6 layers; per head and token 5 P N FLOPs; x, y bfloat16 and dt
    # float32 a head, B and C bfloat16 a group of 8 heads; backward twice the forward.
    rows = images * tokens * 6
    scan = ssm_need.ssm_scan_need(ref, images)
    assert scan["flops"] == pytest.approx(3 * rows * 64 * 5 * 64 * 128)
    assert scan["bytes"] == pytest.approx(3 * rows * (64 * (2 * 64 * 2 + 4) + 8 * 2 * 128 * 2))
    assert least_seconds(scan, peak)[1] == "bytes"
    # Attention: 2 layers; the causal half of the scores and of p v (128 each);
    # q, o moved a query head (32), k, v a key head (2).
    pairs = tokens * (tokens + 1) / 2
    gqa = ssm_need.gqa_attn_need(ref, images)
    assert gqa["flops"] == pytest.approx(3 * images * 2 * 32 * pairs * 2 * (128 + 128))
    assert gqa["bytes"] == pytest.approx(3 * images * 2 * tokens * (2 * 32 + 2 * 2) * 128 * 2)
    assert least_seconds(gqa, peak)[1] == "flops"
    # Experts: two 2688 x 1856 matmuls a slot; 5 layers x 8 experts' weights once.
    moe = ssm_need.moe_mlp_experts_need(ref, SLOTS)
    assert moe["flops"] == pytest.approx(3 * SLOTS * 2 * 2 * 2688 * 1856)
    assert moe["bytes"] == pytest.approx(3 * (5 * 8 * 2 * 2688 * 1856 * 2 + SLOTS * 2 * 2688 * 2))
    # 394 token-slots a held expert and layer under a uniform router (ISSUE 32)
    assert ssm_need.uniform_slots(ref, images) == pytest.approx(5 * 8 * 393.75)


def test_step_flops_is_the_issue_s_arithmetic():
    """About 22 TFLOP a step of two images; forward by kind of layer: Mamba-2
    4.1 T (projections 3.9), experts 2.0, attention 1.4 (ISSUE 32)."""
    ref = conf()["reference"]
    total = ssm_need.step_flops(ref, 2)
    assert 21.5e12 < total < 23.5e12
    tokens = 2 * 4200
    mamba = 6 * tokens * 2 * (2688 * 10304 + 4096 * 2688)
    assert mamba / 1e12 == pytest.approx(3.9, abs=0.05)
    assert (mamba + ssm_need.ssm_scan_need(ref, 2)["flops"] / 3) / 1e12 == pytest.approx(4.1, abs=0.1)
    experts = 5 * tokens * 2 * (2688 * 128 + 2 * 2688 * 3712) \
        + ssm_need.moe_mlp_experts_need(ref, ssm_need.uniform_slots(ref, 2))["flops"] / 3
    assert experts / 1e12 == pytest.approx(2.0, abs=0.1)
    attention = 2 * tokens * 2 * (2688 * (32 + 4) * 128 + 4096 * 2688) \
        + ssm_need.gqa_attn_need(ref, 2)["flops"] / 3
    assert attention / 1e12 == pytest.approx(1.4, abs=0.1)
    # the step's own counter moves the experts' part and nothing else
    more = ssm_need.step_flops(ref, 2, slots_per_step=2 * ssm_need.uniform_slots(ref, 2))
    assert more - total == pytest.approx(
        ssm_need.moe_mlp_experts_need(ref, ssm_need.uniform_slots(ref, 2))["flops"])
