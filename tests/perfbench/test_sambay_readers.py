"""The ``phi4_mini_flash`` readers on a hand-made trace fragment, and the need
functions (``perfbench/sambay_need.py``) against the same work counted by hand
at the configuration's own sizes."""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import readers, sambay_need  # noqa: E402
from perfbench.flops import least_seconds  # noqa: E402

MS = 1e6
FWD = "jit(step)/jit(main)/jvp(TwoStageDetector.features)/backbone/"
BWD = "jit(step)/jit(main)/transpose(jvp(TwoStageDetector.features))/backbone/"
REMAT = BWD + "rematted_computation/"

# (op, offset ms, duration ms, scope) of one 100 ms step
STEP = [
    ("convolution.1", 0, 1, FWD + "patchify/conv_general_dilated"),
    ("fusion.10", 1, 4, FWD + "l0/mamba/proj/dense/dot_general"),
    ("fusion.11", 5, 1, FWD + "l0/mamba/conv/mul"),
    ("while.12", 6, 3, FWD + "l0/mamba/scan/intra/while"),
    ("fusion.13", 7, 1, FWD + "l0/mamba/scan/intra/while/body/checkpoint/while/body/mul"),  # inside the loop
    ("while.14", 9, 2, FWD + "l0/mamba/scan/inter/while"),
    ("fusion.15", 11, 1, FWD + "l0/ffn/dense/dot_general"),
    ("fusion.20", 12, 2, FWD + "l1/swa/proj/dense/dot_general"),
    ("flash_attention_fwd.21", 14, 1, FWD + "l1/swa/attn/flash_attention_fwd"),
    ("fusion.22", 15, 1, FWD + "l1/swa/diff/rsqrt"),
    ("fusion.23", 16, 2, FWD + "l1/ffn/dense/dot_general"),
    ("flash_attention_fwd.30", 18, 2, FWD + "l17/full/attn/flash_attention_fwd"),
    ("fusion.31", 20, 1, FWD + "l18/gmu/proj/dense/dot_general"),
    ("flash_attention_fwd.32", 21, 2, FWD + "l19/xattn/attn/flash_attention_fwd"),
    ("fusion.33", 23, 1, FWD + "l19/xattn/diff/rsqrt"),
    ("fusion.50", 30, 2, REMAT + "l19/xattn/attn/flash_attention_fwd"),
    ("flash_attention_bwd.51", 32, 5, BWD + "l19/xattn/attn/flash_attention_bwd"),
    ("fusion.52", 37, 2, BWD + "l18/gmu/proj/dense/transpose(dot_general)"),
    ("flash_attention_bwd.53", 39, 4, BWD + "l17/full/attn/flash_attention_bwd"),
    ("flash_attention_bwd.60", 43, 2, BWD + "l1/swa/attn/flash_attention_bwd"),
    ("fusion.61", 45, 3, BWD + "l1/ffn/dense/transpose(dot_general)"),
    ("while.70", 48, 3, REMAT + "l0/mamba/scan/intra/while"),
    ("while.71", 51, 6, BWD + "l0/mamba/scan/intra/transpose(while)"),
    ("while.72", 57, 4, BWD + "l0/mamba/scan/inter/transpose(while)"),
    ("fusion.73", 61, 5, BWD + "l0/mamba/proj/dense/transpose(dot_general)"),
    ("copy.7", 66, 2, ""),                  # an op without a scope is no layer's
    ("fusion.90", 70, 20, "jit(step)/jit(main)/optimizer/mul"),
]
MAMBA_MS = 4 + 1 + 3 + 2 + 3 + 6 + 4 + 5
MAMBA_SCAN_MS = 3 + 2 + 3 + 6 + 4
SWA_ATTN_MS = 1 + 2
FULL_ATTN_MS = 2 + 2 + 2 + 5 + 4
GMU_MS = 1 + 2
FFN_MS = 1 + 2 + 3
EIGHT = ["mamba_ms.train", "mamba_scan_roofline.train", "swa_attn_ms.train",
         "swa_attn_roofline.train", "full_attn_ms.train", "full_attn_roofline.train",
         "gmu_ms.train", "ffn_ms.train"]
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def conf(name="phi4_mini_flash_det"):
    with open(os.path.join(REPO, "perfbench", "configs", f"{name}.json")) as f:
        return json.load(f)


def reading(config=None):
    ops, modules, host = [], [], [("sync", -1 * MS, 1 * MS), ("sync", 299 * MS, 1 * MS)]
    for i in range(3):
        t = i * 100 * MS
        modules.append((f"jit_step({i})", t, 100 * MS, ""))
        ops += [(nm, t + at * MS, d * MS, sc) for nm, at, d, sc in STEP]
    r = {
        "trace": {"devices": {0: {
            "XLA Ops": ops,
            "XLA Modules": [(f"jit_step({i})", i * 100 * MS, 100 * MS, "") for i in (-3, -2, -1)]
            + modules,
        }}},
        "host_spans": [(n, s + 7e12, d) for n, s, d in host], "sync_every": 3,
        "program_name": "jit_step", "chips": 1,
        "counters": {"steps": 3, "global_batch": 2, "data_stall_s": 0.0, "sync_every": 3},
        "peak": PEAK, "config": conf() if config is None else config,
    }
    readers.prepare(r)
    return r


def metric(name):
    path = os.path.join(REPO, "perfbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_benchmark_json_lists_the_eight_for_the_new_cell_alone():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in EIGHT}
    assert sorted(listed) == sorted(EIGHT)
    for m in listed.values():
        assert m["workloads"] == ["phi4_mini_flash_det.train_coco"]
        assert m["moves"] == "train_img_s_chip"
        assert os.path.exists(os.path.join(REPO, "perfbench", "metrics", m["name"] + ".py"))
    cell = next(w for w in bench["workloads"] if w["name"] == "phi4_mini_flash_det.train_coco")
    assert cell["chips"] == 1 and cell["traffic"] == "train_coco"


def test_the_configuration_s_file_holds_the_catalog_s_numbers_and_the_cut():
    c = conf()
    published = {"embd_pdrop": 0, "hidden_size": 2560, "intermediate_size": 10240,
                 "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
                 "num_attention_heads": 40, "num_key_value_heads": 20, "resid_pdrop": 0,
                 "sliding_window": 512}
    assert {k: c[k] for k in published} == published
    assert c["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 32, "vocab_size": 200064}
    assert (c["num_hidden_layers"], c["vocab_size"]) == (8, 0)
    dc = c["reference"]["decoder"]
    assert dc["layers"] == c["held_layers"] == [0, 1, 2, 3, 16, 17, 18, 19]
    assert [dc[k] for k in ("mamba_expand", "mamba_d_state", "mamba_d_conv", "mamba_dt_rank")] \
        == [2, 16, 4, 160]
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank"):
        assert key in c["assumed"]


@pytest.mark.parametrize("name,want", [
    ("mamba_ms.train", MAMBA_MS),             # a loop op and the op inside it count once
    ("swa_attn_ms.train", SWA_ATTN_MS),       # forward and backward kernels alike
    ("full_attn_ms.train", FULL_ATTN_MS),     # the full layer's and the cross layer's
    ("gmu_ms.train", GMU_MS),
    ("ffn_ms.train", FFN_MS),
])
def test_readers_on_the_fragment(name, want):
    r = reading()
    assert r["steps_traced"] == 3
    assert metric(name)(r) == pytest.approx(want)


@pytest.mark.parametrize("name,need,ms", [
    ("mamba_scan_roofline.train", sambay_need.mamba_scan_need, MAMBA_SCAN_MS),
    ("swa_attn_roofline.train", sambay_need.swa_attn_need, SWA_ATTN_MS),
    ("full_attn_roofline.train", sambay_need.full_attn_need, FULL_ATTN_MS),
])
def test_roofline_readers_divide_the_need_by_the_scope_s_time(name, need, ms):
    r = reading()
    least, _ = least_seconds(need(r["config"]["reference"], 2), r["peak"])
    got = metric(name)(r)
    assert got == pytest.approx(100.0 * least / (ms * 1e-3))
    assert 0.0 < got


@pytest.mark.parametrize("config", ["none", "ling3_flash_vl_det", "nemotron_twotower_det"])
@pytest.mark.parametrize("name", EIGHT)
def test_a_program_without_the_backbone_reads_nothing(name, config):
    """The parent's step under this PR's benchmark files, or another decoder
    family's (whose dense layer runs under ``ffn`` too): no such scope, no
    SambaY ``decoder`` block -> None, never 0 and never an error."""
    other = {"reference": {"canvas": [608, 1024]}} if config == "none" else conf(config)
    r = reading(config=other)
    keep = lambda sc: "/backbone/" not in sc or "/ffn/" in sc
    r["ops"] = [o for o in r["ops"] if keep(o[3])]
    assert metric(name)(r) is None


def test_need_functions_by_hand():
    ref = conf()["reference"]
    tokens, images = 50 * 84, 2
    # Scan: 3 Mamba layers; per channel (5120) and state (16) 7 FLOPs a token; x, y
    # bfloat16 and dt float32 a channel, B and C float32 a token; backward twice the forward.
    rows = images * tokens * 3
    scan = sambay_need.mamba_scan_need(ref, images)
    assert scan["flops"] == pytest.approx(3 * rows * 7 * 5120 * 16)
    assert scan["bytes"] == pytest.approx(3 * rows * (5120 * 8 + 2 * 16 * 4))
    assert least_seconds(scan, PEAK)[1] == "bytes"
    assert scan["bytes"] / 1e9 == pytest.approx(3.1, abs=0.05)          # ISSUE 34: 3.1 GB moved
    # Window attention: 2 layers, 40 maps (20 query pairs x 2) of key width 64 and value
    # width 128 over the band's pairs; q and both maps' results a query head, k a key
    # head, v a key pair.
    band = 512 * tokens - 512 * 511 / 2
    swa = sambay_need.swa_attn_need(ref, images)
    assert swa["flops"] == pytest.approx(3 * images * 2 * 40 * band * 2 * (64 + 128))
    moved = tokens * 2 * (40 * 64 + 40 * 128 + 20 * 64 + 10 * 128)
    assert swa["bytes"] == pytest.approx(3 * images * 2 * moved)
    assert swa["flops"] / 1e12 == pytest.approx(0.4, abs=0.05)          # ISSUE 34: 0.4 T
    # Full attention: the full layer and the cross layer over the triangle; k, v once.
    pairs = tokens * (tokens + 1) / 2
    full = sambay_need.full_attn_need(ref, images)
    assert full["flops"] == pytest.approx(3 * images * 2 * 40 * pairs * 2 * (64 + 128))
    q_o, k_v = tokens * 2 * (40 * 64 + 40 * 128), tokens * 2 * (20 * 64 + 10 * 128)
    assert full["bytes"] == pytest.approx(3 * images * (2 * q_o + k_v))
    assert full["flops"] / 1e12 == pytest.approx(1.6, abs=0.05)         # ISSUE 34: 1.6 T
    assert least_seconds(full, PEAK)[1] == "flops"
    # a window layer does about a quarter of a full layer's pairs at 4,200 positions
    assert band / pairs == pytest.approx(0.23, abs=0.01)


def test_no_share_can_pass_100_percent_of_what_the_chip_can_do():
    """The needs are the mathematics' least: a run as fast as the roofline
    reads 100, and any real one less."""
    r = reading()
    ref = r["config"]["reference"]
    for name, need, scopes in [
        ("mamba_scan_roofline.train", sambay_need.mamba_scan_need, ("/mamba/scan/",)),
        ("swa_attn_roofline.train", sambay_need.swa_attn_need, ("/swa/attn/",)),
        ("full_attn_roofline.train", sambay_need.full_attn_need, ("/full/attn/", "/xattn/attn/")),
    ]:
        least, _ = least_seconds(need(ref, 2), PEAK)
        ops = [o for o in r["ops"] if any(s in o[3] for s in scopes)]
        # squeeze the step's ops of that scope into exactly the roofline's time
        first = {}
        for nm, s, d, sc in ops:
            first.setdefault(int(s // (100 * MS)), (nm, s, sc))
        exact = dict(r, ops=[(nm, s, least * 1e9, sc) for nm, s, sc in first.values()])
        assert metric(name)(exact) == pytest.approx(100.0)


def test_step_flops_is_the_issue_s_arithmetic():
    """About 45 TFLOP a step of two images: 42.9 T of projections (851 M
    parameters in the blocks), 1.6 T full and 0.4 T window attention, 0.04 T of
    scan (ISSUE 34); the plain MLP is 74 % of the blocks' parameters."""
    ref = conf()["reference"]
    total = sambay_need.step_flops(ref, 2)
    assert 44e12 < total < 47e12
    d, wide, f = 2560, 5120, 10240
    mamba = d * 2 * wide + wide * 192 + 160 * wide + wide * d
    attn, cross, gmu, mlp = d * 5120 + d * d, 2 * d * d, 2 * d * wide, 3 * d * f
    blocks = 3 * mamba + 3 * attn + cross + gmu + 8 * mlp
    assert blocks / 1e6 == pytest.approx(851, abs=1.0)      # the matrices of ISSUE 34's 851.3 M
    assert 8 * mlp / blocks == pytest.approx(0.74, abs=0.005)
    assert 3 * 2 * 8400 * blocks / 1e12 == pytest.approx(42.9, abs=0.1)
    ops = sum(fn(ref, 2)["flops"] for fn in (sambay_need.mamba_scan_need,
                                              sambay_need.swa_attn_need, sambay_need.full_attn_need))
    assert ops / 1e12 == pytest.approx(1.6 + 0.4 + 0.04, abs=0.1)
    # nothing is routed: the expert families' counter moves nothing
    assert sambay_need.step_flops(ref, 2, slots_per_step=1e6) == total
