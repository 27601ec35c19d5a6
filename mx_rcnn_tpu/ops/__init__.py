from mx_rcnn_tpu.ops.nms import batched_nms, nms_mask
from mx_rcnn_tpu.ops.roi_align import (
    multilevel_roi_align,
    roi_align,
    roi_align_matmul,
)
from mx_rcnn_tpu.ops.proposals import generate_proposals
from mx_rcnn_tpu.ops.sampling import sample_rois, assign_anchors
from mx_rcnn_tpu.ops.topk import hierarchical_top_k

__all__ = [
    "batched_nms",
    "nms_mask",
    "roi_align",
    "multilevel_roi_align",
    "roi_align_matmul",
    "generate_proposals",
    "sample_rois",
    "assign_anchors",
    "hierarchical_top_k",
]
