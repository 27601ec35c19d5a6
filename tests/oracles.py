"""Numpy oracle implementations used to validate the JAX/Pallas ops.

These follow the reference semantics (rcnn/processing/*, rcnn/cython/*) in
plain readable numpy — the same role the pure-python NMS in
``rcnn/processing/nms.py`` played as an implicit oracle, but actually wired
into an automated suite.
"""

from __future__ import annotations

import numpy as np


def iou_matrix_np(boxes: np.ndarray, query: np.ndarray, plus_one: bool = False):
    off = 1.0 if plus_one else 0.0
    n, k = len(boxes), len(query)
    out = np.zeros((n, k), dtype=np.float64)
    for i in range(n):
        for j in range(k):
            ix1 = max(boxes[i, 0], query[j, 0])
            iy1 = max(boxes[i, 1], query[j, 1])
            ix2 = min(boxes[i, 2], query[j, 2])
            iy2 = min(boxes[i, 3], query[j, 3])
            iw = max(ix2 - ix1 + off, 0.0)
            ih = max(iy2 - iy1 + off, 0.0)
            inter = iw * ih
            a1 = max(boxes[i, 2] - boxes[i, 0] + off, 0) * max(
                boxes[i, 3] - boxes[i, 1] + off, 0
            )
            a2 = max(query[j, 2] - query[j, 0] + off, 0) * max(
                query[j, 3] - query[j, 1] + off, 0
            )
            union = a1 + a2 - inter
            out[i, j] = inter / union if union > 0 else 0.0
    return out


def _iou_row_np(box: np.ndarray, boxes: np.ndarray):
    """``iou_matrix_np(box[None], boxes)[0]``, a row at a time: the same
    arithmetic in the same dtype, so the greedy oracle below can reach
    thousands of boxes."""
    iw = np.maximum(np.minimum(box[2], boxes[:, 2]) - np.maximum(box[0], boxes[:, 0]), 0.0)
    ih = np.maximum(np.minimum(box[3], boxes[:, 3]) - np.maximum(box[1], boxes[:, 1]), 0.0)
    inter = iw * ih
    a1 = max(box[2] - box[0], 0) * max(box[3] - box[1], 0)
    a2 = np.maximum(boxes[:, 2] - boxes[:, 0], 0) * np.maximum(boxes[:, 3] - boxes[:, 1], 0)
    union = a1 + a2 - inter
    return np.where(union > 0, inter / np.where(union > 0, union, 1.0), 0.0)


def greedy_nms_np(boxes: np.ndarray, scores: np.ndarray, iou_thresh: float):
    """Classic greedy NMS (rcnn/processing/nms.py::py_nms semantics, modern
    +0 box convention). Returns kept indices in descending-score order."""
    order = np.argsort(-scores, kind="stable")
    keep = []
    suppressed = np.zeros(len(boxes), dtype=bool)
    for idx in order:
        if suppressed[idx]:
            continue
        keep.append(idx)
        hit = _iou_row_np(boxes[idx], boxes) > iou_thresh
        hit[idx] = False
        suppressed |= hit
    return np.array(keep, dtype=np.int64)


def nms_mask_dense(boxes, scores, iou_threshold, valid=None):
    """The dense fixed-point NMS that ``mx_rcnn_tpu/ops/nms.py::nms_mask`` was
    until PR 29, moved here verbatim as the second oracle: the whole N x N
    IoU matrix, the whole suppression mask, one global fixed point.  The
    package keeps the tiled form alone; this one is what it must equal bit
    for bit."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mx_rcnn_tpu.geometry import iou_matrix, snap

    n = boxes.shape[0]
    if valid is None:
        valid = jnp.isfinite(scores)
    else:
        valid = valid & jnp.isfinite(scores)

    order = jnp.argsort(-scores)  # descending; stable for ties
    sboxes = jnp.take(boxes, order, axis=0)
    svalid = jnp.take(valid, order)

    # snap(): the > threshold suppression decision must not flip on
    # cross-compilation ulp noise (see geometry.boxes.snap); one flipped
    # suppression cascades through the whole greedy chain.
    iou = snap(iou_matrix(sboxes, sboxes))
    upper = jnp.triu(jnp.ones((n, n), dtype=bool), k=1)
    suppress = (iou > iou_threshold) & upper & svalid[:, None] & svalid[None, :]

    def cond(state):
        keep, prev = state
        return jnp.any(keep != prev)

    def body(state):
        keep, _ = state
        with jax.named_scope("nms_sweep"):
            new_keep = svalid & ~jnp.any(suppress & keep[:, None], axis=0)
        return new_keep, keep

    init = (svalid, jnp.zeros(n, dtype=bool))
    keep_sorted, _ = lax.while_loop(cond, body, init)

    return jnp.zeros(n, dtype=bool).at[order].set(keep_sorted)


def encode_np(boxes: np.ndarray, anchors: np.ndarray):
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    ax = anchors[:, 0] + 0.5 * aw
    ay = anchors[:, 1] + 0.5 * ah
    gw = boxes[:, 2] - boxes[:, 0]
    gh = boxes[:, 3] - boxes[:, 1]
    gx = boxes[:, 0] + 0.5 * gw
    gy = boxes[:, 1] + 0.5 * gh
    return np.stack(
        [(gx - ax) / aw, (gy - ay) / ah, np.log(gw / aw), np.log(gh / ah)], axis=1
    )


def roi_align_np(
    features: np.ndarray,
    rois: np.ndarray,
    output_size: int,
    spatial_scale: float,
    sampling_ratio: int = 2,
):
    """Reference ROIAlign (Mask R-CNN paper semantics, aligned=False):
    features (H, W, C), rois (N, 4) in image coords. Output (N, S, S, C)."""
    h, w, c = features.shape
    n = len(rois)
    out = np.zeros((n, output_size, output_size, c), dtype=np.float64)

    def bilinear(y, x):
        if y < -1.0 or y > h or x < -1.0 or x > w:
            return np.zeros(c)
        y = min(max(y, 0.0), h - 1)
        x = min(max(x, 0.0), w - 1)
        y0, x0 = int(np.floor(y)), int(np.floor(x))
        y1, x1 = min(y0 + 1, h - 1), min(x0 + 1, w - 1)
        ly, lx = y - y0, x - x0
        return (
            features[y0, x0] * (1 - ly) * (1 - lx)
            + features[y0, x1] * (1 - ly) * lx
            + features[y1, x0] * ly * (1 - lx)
            + features[y1, x1] * ly * lx
        )

    for i in range(n):
        x1, y1, x2, y2 = rois[i] * spatial_scale
        rw = max(x2 - x1, 1.0)
        rh = max(y2 - y1, 1.0)
        bin_w = rw / output_size
        bin_h = rh / output_size
        for py in range(output_size):
            for px in range(output_size):
                acc = np.zeros(c)
                for iy in range(sampling_ratio):
                    for ix in range(sampling_ratio):
                        sy = y1 + (py + (iy + 0.5) / sampling_ratio) * bin_h
                        sx = x1 + (px + (ix + 0.5) / sampling_ratio) * bin_w
                        acc += bilinear(sy, sx)
                out[i, py, px] = acc / (sampling_ratio * sampling_ratio)
    return out
