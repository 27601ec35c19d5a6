"""A benchmark root at a size a test can hold: the real ``perfbench`` data
files copied into a temporary directory, plus a tiny configuration, a tiny
cell, a tiny traffic mix and an extra per-layer metric ADDED AS NEW FILES
ONLY - which is also the proof that the harness finds all four by name."""

from __future__ import annotations

import copy
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_TRAFFIC = {
    "what": "tiny squares for the CPU",
    "pool": 8, "sizes": [[128, 128, 8]], "objects": [2, 1, 3], "box_frac": [0.2, 0.5],
}

TINY_OVERRIDES = {
    "r50_fpn_coco": [],
    "vgg16_voc07": [
        "data.dataset=synthetic", "data.image_size=128,128", "data.short_side=128",
        "data.max_side=128", "data.max_gt_boxes=8", "model.backbone.dtype=float32",
        "model.rpn.batch_size=64", "model.rpn.train_pre_nms_top_n=200",
        "model.rpn.train_post_nms_top_n=64", "model.rpn.channels=32",
        "model.rcnn.roi_batch_size=32", "model.rcnn.hidden_dim=64",
        # 16 to 64 px anchors: the stated 128 to 512 px lie outside a 128 px canvas
        "model.anchors.scales=1,2,4",
    ],
}


def tiny_config(name: str) -> dict:
    """The real configuration file cut to the CPU: tiny canvas, few rois,
    float32 compute (so that program and reference agree to rounding)."""
    with open(os.path.join(REPO, "perfbench", "configs", f"{name}.json")) as f:
        conf = json.load(f)
    conf = copy.deepcopy(conf)
    ref = conf["reference"]
    ref.update(canvas=[128, 128], max_gt_boxes=8)
    ref["rpn"].update(batch_size=64, train_pre_nms_top_n=200, train_post_nms_top_n=64,
                      test_pre_nms_top_n=200, test_post_nms_top_n=64)
    ref["rcnn"].update(roi_batch_size=32)
    if name == "r50_fpn_coco":
        conf["preset"] = "tiny_synthetic"
        ref.update(num_classes=5)
        ref["rcnn"].update(hidden_dim=128)
        ref["optimizer"].update(base_lr=0.01, warmup_steps=10, reference_batch=0, frozen=[])
    else:
        ref.update(anchor_scales=[1.0, 2.0, 4.0])
        ref["rpn"].update(channels=32)
        ref["rcnn"].update(hidden_dim=64)
    conf["overrides"] = TINY_OVERRIDES[name]
    conf["name"] = f"tiny_{name}"
    return conf


def make_root(tmp: str, limits=None) -> str:
    """-> a root directory holding BENCHMARK.json and ``perfbench/`` data."""
    root = os.path.join(tmp, "root")
    os.makedirs(root)
    for sub in ("cells", "configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "perfbench", sub), os.path.join(root, "perfbench", sub))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    limits = limits or {"loss2": 1e-3, "loss3": 1e-3, "grad1": 1e-3, "change": 1e-2, "dir1": 1e-3, "dirc": 1e-3}
    for name in ("r50_fpn_coco", "vgg16_voc07"):
        tiny = f"tiny_{name}"
        with open(os.path.join(root, "perfbench", "configs", f"{tiny}.json"), "w") as f:
            json.dump(tiny_config(name), f)
        bench["configs"].append({
            "name": tiny, "source": "test", "file": f"perfbench/configs/{tiny}.json",
            "reduced": [], "why": "test",
        })
        cell = f"{tiny}.train_b2"
        with open(os.path.join(root, "perfbench", "cells", f"{cell}.json"), "w") as f:
            json.dump({
                "entry": "train", "overrides": ["train.per_device_batch=2"],
                "sync_every": 2, "steady": "params/rpn/", "limits": limits,
            }, f)
        bench["workloads"].append(
            {"name": cell, "config": tiny, "traffic": "tiny_squares", "chips": 1, "why": "test"}
        )
    with open(os.path.join(root, "perfbench", "traffic", "tiny_squares.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(root, "perfbench", "metrics", "steps_per_sync.train.py"), "w") as f:
        f.write("def read(reading):\n    return float(reading['counters']['sync_every'])\n")
    bench["per_layer"].append({
        "name": "steps_per_sync.train", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train loop", "moves": "train_img_s_chip",
        "workloads": ["tiny_r50_fpn_coco.train_b2"],
    })
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
