"""The ``nemotron_twotower_det`` configuration cut to a size the CPU holds:
every kind of layer (state-space, experts, grouped-query attention) in the
published order ``MEM*E``, heads sharing B and C in groups, 2 key heads for 4
query heads, a router over 16 relu^2 experts of which 4 are held, float32
compute on both sides."""

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
    "layers": [0, 1, 2, 3, 4], "pattern": "MEM*E", "hidden_size": 32,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "chunk_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "n_routed_experts_published": 16, "experts_first": 0, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 40,
}

# reference key -> the program's DecoderConfig field
FIELDS = {
    "layers": "layers", "pattern": "pattern", "hidden_size": "hidden_size",
    "mamba_num_heads": "ssm_heads", "mamba_head_dim": "ssm_head_dim", "n_groups": "ssm_groups",
    "ssm_state_size": "ssm_state", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "n_routed_experts_published": "num_experts", "experts_first": "experts_first",
    "n_routed_experts": "experts_count", "num_experts_per_tok": "num_experts_per_tok",
    "moe_intermediate_size": "moe_intermediate_size",
    "moe_shared_expert_intermediate_size": "shared_intermediate_size",
}

# The one-level middle cut as ``_benchmark_tiny.py`` cuts it for ``vgg16_voc07``
# (the same `_c4_model`).
TINY_OVERRIDES = _VGG_TINY["vgg16_voc07"] + ["model.backbone.decoder.neck_channels=32"]


@contextlib.contextmanager
def small_program_choices():
    """The program's fixed choices (``ops/ssd.py::CHUNK``, ``ops/attention.py::
    BLOCK``) would each swallow a tiny image's 64 positions whole.  While this
    is open the decoder calls its ops with chunks of 16 (four of them, the
    state carried across three seams) and attention blocks of 24 (a ragged
    last block), so the tiny model still crosses every seam the real one has
    (its experts run without a dispatch: no segments)."""
    import pytest

    from mx_rcnn_tpu.models import decoder

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decoder, "ssd_chunked", functools.partial(decoder.ssd_chunked, chunk=16))
        mp.setattr(decoder, "causal_attention",
                   functools.partial(decoder.causal_attention, block=24))
        yield


def decoder_overrides(decoder=DECODER) -> list[str]:
    out = []
    for key, value in decoder.items():
        if key in FIELDS:
            text = ",".join(map(str, value)) if isinstance(value, list) else str(value)
            out.append(f"model.backbone.decoder.{FIELDS[key]}={text}")
    return out


def tiny_config(**decoder) -> dict:
    with open(os.path.join(REPO, "perfbench", "configs", "nemotron_twotower_det.json")) as f:
        conf = copy.deepcopy(json.load(f))
    ref = conf["reference"]
    ref["decoder"].update(DECODER, **decoder)
    ref.update(canvas=[128, 128], max_gt_boxes=8, feature_channels=32,
               anchor_scales=[1.0, 2.0, 4.0])
    ref["rpn"].update(batch_size=64, train_pre_nms_top_n=200, train_post_nms_top_n=64,
                      test_pre_nms_top_n=200, test_post_nms_top_n=64, channels=32)
    ref["rcnn"].update(roi_batch_size=32, hidden_dim=64)
    conf["overrides"] = TINY_OVERRIDES + decoder_overrides(dict(DECODER, **decoder))
    conf["name"] = "tiny_nemotron_twotower_det"
    return conf


CELL = "tiny_nemotron_twotower_det.train_b2"


def make_root(tmp: str, limits: dict) -> str:
    """A benchmark root holding the real data files plus the tiny
    configuration, its cell (entry ``train_lean_ssm``) and a tiny traffic mix."""
    import shutil

    root = os.path.join(tmp, "root")
    os.makedirs(root)
    for sub in ("cells", "configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "perfbench", sub), os.path.join(root, "perfbench", sub))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = "tiny_nemotron_twotower_det"
    with open(os.path.join(root, "perfbench", "configs", f"{name}.json"), "w") as f:
        json.dump(tiny_config(), f)
    bench["configs"].append({"name": name, "source": "test", "reduced": [], "why": "test",
                             "file": f"perfbench/configs/{name}.json"})
    with open(os.path.join(root, "perfbench", "cells", f"{CELL}.json"), "w") as f:
        json.dump({"entry": "train_lean_ssm", "overrides": ["train.per_device_batch=2"],
                   "sync_every": 2, "steady": "params/rpn/", "limits": limits}, f)
    bench["workloads"].append(
        {"name": CELL, "config": name, "traffic": "tiny_squares", "chips": 1, "why": "test"}
    )
    for m in bench["per_layer"]:
        if "nemotron_twotower_det.train_coco" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "perfbench", "traffic", "tiny_squares.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
