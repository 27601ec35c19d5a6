"""The ``ling3_flash_vl_det`` configuration cut to a size the CPU holds: every
kind of layer (dense + KDA, experts + KDA, experts + MLA), a router over 16
experts of which 4 are held, float32 compute on both sides."""

from __future__ import annotations

import contextlib
import copy
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _benchmark_tiny import TINY_OVERRIDES as _VGG_TINY, TINY_TRAFFIC  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DECODER = {
    "layers": [0, 4, 5], "hidden_size": 32, "num_attention_heads": 2, "head_dim": 16,
    "first_k_dense_replace": 1, "layer_group_size": 3, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_experts_published": 16, "experts_first": 0,
    "num_experts": 4, "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
}

# reference key -> the program's DecoderConfig field
FIELDS = {
    "layers": "layers", "hidden_size": "hidden_size", "num_attention_heads": "num_heads",
    "head_dim": "head_dim", "first_k_dense_replace": "first_k_dense",
    "layer_group_size": "layer_group_size", "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size", "num_experts_published": "num_experts",
    "experts_first": "experts_first", "num_experts": "experts_count",
    "num_experts_per_tok": "num_experts_per_tok", "n_group": "n_group",
    "topk_group": "topk_group", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
}

# The one-level middle cut as ``_benchmark_tiny.py`` cuts it for ``vgg16_voc07``
# (the same `_c4_model`).
TINY_OVERRIDES = _VGG_TINY["vgg16_voc07"] + ["model.backbone.decoder.neck_channels=32"]


@contextlib.contextmanager
def small_program_choices():
    """The program's fixed choices (``ops/kda.py::CHUNK``, ``ops/attention.py::
    BLOCK``, ``ops/moe.py::SEGMENT_FACTOR``) would each swallow a tiny image's
    64 positions whole.  While this is open the decoder calls its ops with
    chunks of 16, attention blocks of 24 (a ragged last block) and dispatch
    segments of 128 rows (four of them, some skipped), so the tiny model still
    crosses every seam the real one has."""
    import pytest

    from mx_rcnn_tpu.models import decoder

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decoder, "kda_chunked", functools.partial(decoder.kda_chunked, chunk=16))
        mp.setattr(decoder, "causal_attention",
                   functools.partial(decoder.causal_attention, block=24))
        mp.setattr(decoder, "segment_rows", lambda *sizes: 128)
        yield


def decoder_overrides(decoder=DECODER) -> list[str]:
    out = []
    for key, value in decoder.items():
        text = ",".join(map(str, value)) if isinstance(value, list) else str(value)
        out.append(f"model.backbone.decoder.{FIELDS[key]}={text}")
    return out


def tiny_config(**decoder) -> dict:
    with open(os.path.join(REPO, "perfbench", "configs", "ling3_flash_vl_det.json")) as f:
        conf = copy.deepcopy(json.load(f))
    ref = conf["reference"]
    ref["decoder"].update(DECODER, **decoder)
    ref.update(canvas=[128, 128], max_gt_boxes=8, feature_channels=32,
               anchor_scales=[1.0, 2.0, 4.0])
    ref["rpn"].update(batch_size=64, train_pre_nms_top_n=200, train_post_nms_top_n=64,
                      test_pre_nms_top_n=200, test_post_nms_top_n=64, channels=32)
    ref["rcnn"].update(roi_batch_size=32, hidden_dim=64)
    conf["overrides"] = TINY_OVERRIDES + decoder_overrides(dict(DECODER, **decoder))
    conf["name"] = "tiny_ling3_flash_vl_det"
    return conf


CELL = "tiny_ling3_flash_vl_det.train_b2"


def make_root(tmp: str, limits: dict) -> str:
    """A benchmark root holding the real data files plus the tiny
    configuration, its cell (entry ``train_lean``) and a tiny traffic mix."""
    import shutil

    root = os.path.join(tmp, "root")
    os.makedirs(root)
    for sub in ("cells", "configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "perfbench", sub), os.path.join(root, "perfbench", sub))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = "tiny_ling3_flash_vl_det"
    with open(os.path.join(root, "perfbench", "configs", f"{name}.json"), "w") as f:
        json.dump(tiny_config(), f)
    bench["configs"].append({"name": name, "source": "test", "reduced": [], "why": "test",
                             "file": f"perfbench/configs/{name}.json"})
    with open(os.path.join(root, "perfbench", "cells", f"{CELL}.json"), "w") as f:
        json.dump({"entry": "train_lean", "overrides": ["train.per_device_batch=2"],
                   "sync_every": 2, "steady": "params/rpn/", "limits": limits}, f)
    bench["workloads"].append(
        {"name": CELL, "config": name, "traffic": "tiny_squares", "chips": 1, "why": "test"}
    )
    for m in bench["per_layer"]:
        if "ling3_flash_vl_det.train_coco" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "perfbench", "traffic", "tiny_squares.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
