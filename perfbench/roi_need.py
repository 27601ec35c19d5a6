"""ROIAlign's need in one step of a cell, from the configuration's sizes."""

from __future__ import annotations

from perfbench.flops import roi_align_need


def need_of(reading: dict, backward: bool, train: bool = True) -> dict:
    ref = reading["config"]["reference"]
    c = ref["rcnn"]
    per_chip = reading["counters"]["global_batch"] // reading["chips"]
    rois = (c["roi_batch_size"] if train else ref["rpn"]["test_post_nms_top_n"]) * per_chip
    h, w = ref["canvas"]
    cells = sum((h // 2**l) * (w // 2**l) for l in ref["roi_levels"]) * per_chip
    return roi_align_need(
        rois, c["pooled_size"], c["sampling_ratio"], ref["feature_channels"], cells,
        itemsize=2, backward=backward,
    )
