"""The ``ling3_flash_vl`` readers on a hand-made trace fragment, and the need
functions (``perfbench/ling_need.py``) against the same work counted by hand
at the configuration's own sizes."""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import ling_need, readers  # noqa: E402
from perfbench.flops import least_seconds  # noqa: E402

MS = 1e6
FWD = "jit(step)/jit(main)/jvp(TwoStageDetector.features)/backbone/"
BWD = "jit(step)/jit(main)/transpose(jvp(TwoStageDetector.features))/backbone/"
REMAT = BWD + "rematted_computation/"

# (op, offset ms, duration ms, scope) of one 100 ms step
STEP = [
    ("convolution.1", 0, 1, FWD + "patchify/conv_general_dilated"),
    ("fusion.10", 1, 4, FWD + "l6/kda/proj/dot_general"),
    ("fusion.11", 5, 2, FWD + "l6/kda/scan/intra/dot_general"),
    ("while.12", 7, 4, FWD + "l6/kda/scan/inter/while"),
    ("fusion.13", 8, 1, FWD + "l6/kda/scan/inter/while/body/dot_general"),   # inside the loop
    ("fusion.20", 11, 2, FWD + "l11/mla/proj/dot_general"),
    ("fusion.21", 13, 2, FWD + "l11/mla/attn/dot_general"),
    ("fusion.30", 15, 1, FWD + "l11/moe/router/dot_general"),
    ("fusion.31", 16, 1, FWD + "l11/moe/experts/ragged_dot"),
    ("fusion.32", 17, 1, FWD + "l11/moe/shared/dot_general"),
    ("fusion.40", 18, 4, FWD + "l0/ffn/dot_general"),
    ("fusion.50", 30, 1, REMAT + "l11/moe/experts/ragged_dot"),
    ("fusion.51", 31, 2, BWD + "l11/moe/experts/transpose(ragged_dot)"),
    ("fusion.52", 33, 1, BWD + "l11/moe/combine/scatter-add"),
    ("fusion.60", 34, 1, REMAT + "l11/mla/attn/dot_general"),
    ("fusion.61", 35, 3, BWD + "l11/mla/attn/transpose(dot_general)"),
    ("fusion.70", 40, 6, REMAT + "l6/kda/scan/inter/while"),
    ("while.71", 46, 8, BWD + "l6/kda/scan/inter/transpose(while)"),
    ("fusion.72", 54, 6, BWD + "l6/kda/proj/transpose(dot_general)"),
    ("ragged-dot-none.7", 62, 2, ""),       # XLA's grouped matmul: a custom call without a scope
    ("fusion.90", 70, 20, "jit(step)/jit(main)/optimizer/mul"),
]
KDA_MS = 4 + 2 + 4 + 6 + 8 + 6
KDA_SCAN_MS = 2 + 4 + 6 + 8
MLA_ATTN_MS = 2 + 1 + 3
MOE_MS = 3 + 1 + 2 + 1
MOE_EXPERTS_MS = 1 + 1 + 2 + 2      # the scope's ops and the custom call found by name
SLOTS = 6000.0


def conf():
    with open(os.path.join(REPO, "perfbench", "configs", "ling3_flash_vl_det.json")) as f:
        return json.load(f)


def reading(decoder=True, counters=True):
    ops, modules, host = [], [], [("sync", -1 * MS, 1 * MS), ("sync", 299 * MS, 1 * MS)]
    for i in range(3):
        t = i * 100 * MS
        modules.append((f"jit_step({i})", t, 100 * MS, ""))
        ops += [(nm, t + at * MS, d * MS, sc) for nm, at, d, sc in STEP]
    c = {"steps": 3, "global_batch": 2, "data_stall_s": 0.0, "sync_every": 3}
    if counters:
        c.update(moe_slots_here=SLOTS, moe_load_max_over_mean=1.5, moe_dropped_slots=0.0)
    r = {
        "trace": {"devices": {0: {
            "XLA Ops": ops,
            "XLA Modules": [(f"jit_step({i})", i * 100 * MS, 100 * MS, "") for i in (-3, -2, -1)]
            + modules,
        }}},
        "host_spans": [(n, s + 7e12, d) for n, s, d in host], "sync_every": 3,
        "program_name": "jit_step", "chips": 1, "counters": c,
        "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "config": conf() if decoder else {"reference": {"canvas": [608, 1024]}},
    }
    readers.prepare(r)
    return r


def metric(name):
    path = os.path.join(REPO, "perfbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name,want", [
    ("kda_ms.train", KDA_MS),                 # the loop op and the op inside it count once
    ("mla_attn_ms.train", MLA_ATTN_MS),       # forward, recomputed forward and backward
    ("moe_ms.train", MOE_MS),
    ("moe_load_max_over_mean.train", 1.5),
    ("moe_dropped_slots.train", 0.0),
])
def test_readers_on_the_fragment(name, want):
    r = reading()
    assert r["steps_traced"] == 3
    assert metric(name)(r) == pytest.approx(want)


@pytest.mark.parametrize("name,need,ms", [
    ("kda_scan_roofline.train", lambda ref: ling_need.kda_scan_need(ref, 2), KDA_SCAN_MS),
    ("mla_attn_roofline.train", lambda ref: ling_need.mla_attn_need(ref, 2), MLA_ATTN_MS),
    ("moe_experts_roofline.train", lambda ref: ling_need.moe_experts_need(ref, SLOTS),
     MOE_EXPERTS_MS),
])
def test_roofline_readers_divide_the_need_by_the_scope_s_time(name, need, ms):
    r = reading()
    least, _ = least_seconds(need(r["config"]["reference"]), r["peak"])
    got = metric(name)(r)
    assert got == pytest.approx(100.0 * least / (ms * 1e-3))
    assert 0.0 < got


@pytest.mark.parametrize("name", [
    "kda_ms.train", "mla_attn_ms.train", "moe_ms.train", "kda_scan_roofline.train",
    "mla_attn_roofline.train", "moe_experts_roofline.train", "moe_load_max_over_mean.train",
    "moe_dropped_slots.train",
])
def test_a_program_without_the_backbone_reads_nothing(name):
    """The parent's step under this PR's benchmark files: no such scope, no
    such counter, no ``decoder`` block -> None, never 0 and never an error."""
    r = reading(decoder=False, counters=False)
    r["ops"] = [o for o in r["ops"] if "/backbone/" not in o[3]]
    assert metric(name)(r) is None


def test_need_functions_by_hand():
    ref = conf()["reference"]
    tokens, heads, images = 50 * 84, 32, 2
    # KDA: 6 layers; per head and token 7 Dk Dv FLOPs; q, k, v, o in bfloat16,
    # the log-decay per channel and beta in float32; backward twice the forward.
    n = images * tokens * heads * 6
    kda = ling_need.kda_scan_need(ref, images)
    assert kda["flops"] == pytest.approx(3 * n * 7 * 128 * 128)
    assert kda["bytes"] == pytest.approx(3 * n * (4 * 128 * 2 + 128 * 4 + 4))
    assert least_seconds(kda, {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})[1] == "bytes"
    # MLA: 1 layer; the causal half of the scores (192) and of p v (128).
    pairs = tokens * (tokens + 1) / 2
    mla = ling_need.mla_attn_need(ref, images)
    assert mla["flops"] == pytest.approx(3 * images * heads * pairs * 2 * (192 + 128))
    assert mla["bytes"] == pytest.approx(3 * images * tokens * heads * (2 * 192 + 2 * 128) * 2)
    # Experts: three 2560 x 768 matmuls a slot; 6 layers x 8 experts' weights once.
    moe = ling_need.moe_experts_need(ref, SLOTS)
    assert moe["flops"] == pytest.approx(3 * SLOTS * 3 * 2 * 2560 * 768)
    assert moe["bytes"] == pytest.approx(3 * (6 * 8 * 3 * 2560 * 768 * 2 + SLOTS * 2 * 2560 * 2))
    # 131 token-slots a held expert and layer under a uniform router
    assert ling_need.uniform_slots(ref, images) == pytest.approx(6 * 8 * 131.25)


def test_step_flops_is_the_issue_s_arithmetic():
    """About 1 GFLOP a token forward in matmuls, 27 TFLOP a step of two."""
    ref = conf()["reference"]
    total = ling_need.step_flops(ref, 2)
    assert 26e12 < total < 29e12
    ops = (ling_need.kda_scan_need(ref, 2)["flops"] + ling_need.mla_attn_need(ref, 2)["flops"]
           + ling_need.moe_experts_need(ref, ling_need.uniform_slots(ref, 2))["flops"])
    forward_per_token = (total - ops) / 3.0 / (2 * 4200)
    assert 0.95e9 < forward_per_token < 1.10e9
    # the step's own counter moves the experts' part and nothing else
    more = ling_need.step_flops(ref, 2, slots_per_step=2 * ling_need.uniform_slots(ref, 2))
    assert more - total == pytest.approx(
        ling_need.moe_experts_need(ref, ling_need.uniform_slots(ref, 2))["flops"])
