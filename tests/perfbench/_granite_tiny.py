"""The ``granite4_h_micro_det`` configuration cut to a size the CPU holds:
published layers 3-6 by the published ``layer_types`` (Mamba-2, Mamba-2, the
NoPE attention layer at 5, Mamba-2), each with its SwiGLU, ONE group of B and
C for all heads, 4 query heads on 2 key heads, the published muP multipliers,
float32 compute on both sides."""

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
    "layers": [3, 4, 5, 6], "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 48,
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_n_groups": 1, "mamba_d_state": 16,
    "mamba_d_conv": 4, "chunk_size": 16,
}

# reference key -> the program's DecoderConfig field
FIELDS = {
    "layers": "layers", "layer_types": "layer_types", "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "intermediate_size",
    "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim", "mamba_n_groups": "ssm_groups",
    "mamba_d_state": "ssm_state", "mamba_d_conv": "short_conv_kernel",
    "residual_multiplier": "residual_multiplier",
    "embedding_multiplier": "embedding_multiplier",
    "attention_multiplier": "attention_multiplier", "rms_norm_eps": "rms_norm_eps",
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
    last block), so the tiny model still crosses every seam the real one has."""
    import pytest

    from mx_rcnn_tpu.models import decoder

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decoder, "ssd_chunked", functools.partial(decoder.ssd_chunked, chunk=16))
        mp.setattr(decoder, "causal_attention",
                   functools.partial(decoder.causal_attention, block=24))
        yield


def decoder_overrides(decoder: dict) -> list[str]:
    out = []
    for key, value in decoder.items():
        if key in FIELDS:
            text = ",".join(map(str, value)) if isinstance(value, list) else str(value)
            out.append(f"model.backbone.decoder.{FIELDS[key]}={text}")
    return out


def tiny_config(**decoder) -> dict:
    with open(os.path.join(REPO, "perfbench", "configs", "granite4_h_micro_det.json")) as f:
        conf = copy.deepcopy(json.load(f))
    ref = conf["reference"]
    ref["decoder"].update(DECODER, **decoder)
    ref.update(canvas=[128, 128], max_gt_boxes=8, feature_channels=32,
               anchor_scales=[1.0, 2.0, 4.0])
    ref["rpn"].update(batch_size=64, train_pre_nms_top_n=200, train_post_nms_top_n=64,
                      test_pre_nms_top_n=200, test_post_nms_top_n=64, channels=32)
    ref["rcnn"].update(roi_batch_size=32, hidden_dim=64)
    conf["overrides"] = TINY_OVERRIDES + decoder_overrides(ref["decoder"])
    conf["name"] = "tiny_granite4_h_micro_det"
    return conf


CELL = "tiny_granite4_h_micro_det.train_b2"


def make_root(tmp: str, limits: dict) -> str:
    """A benchmark root holding the real data files plus the tiny
    configuration, its cell (entry ``train_lean_granite``) and a tiny traffic mix."""
    import shutil

    root = os.path.join(tmp, "root")
    os.makedirs(root)
    for sub in ("cells", "configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "perfbench", sub), os.path.join(root, "perfbench", sub))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = "tiny_granite4_h_micro_det"
    with open(os.path.join(root, "perfbench", "configs", f"{name}.json"), "w") as f:
        json.dump(tiny_config(), f)
    bench["configs"].append({"name": name, "source": "test", "reduced": [], "why": "test",
                             "file": f"perfbench/configs/{name}.json"})
    with open(os.path.join(root, "perfbench", "cells", f"{CELL}.json"), "w") as f:
        json.dump({"entry": "train_lean_granite", "overrides": ["train.per_device_batch=2"],
                   "sync_every": 2, "steady": "params/rpn/", "limits": limits}, f)
    bench["workloads"].append(
        {"name": CELL, "config": name, "traffic": "tiny_squares", "chips": 1, "why": "test"}
    )
    for m in bench["per_layer"]:
        if "granite4_h_micro_det.train_coco" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "perfbench", "traffic", "tiny_squares.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
