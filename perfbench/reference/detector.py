"""The plain reference of the two-stage detector: Faster R-CNN (Ren et al.
2015) with an optional FPN (Lin et al. 2017), forward, losses and gradients in
straightforward float32 ``jax.numpy``, every matmul at ``highest``, one image
at a time, no kernel, no blocking, no cache.  It imports nothing of the
program and takes nothing the program made: weights come from ``weights.py``
and the seed, pixels and boxes from the traffic generator.

``ref`` is the ``reference`` block of a configuration file under
``perfbench/configs/``: every size and threshold used here is read from it.

Departures from the papers, all of them the detector's own stated semantics
(so that the same samples are drawn and the same boxes compared):

- boxes are (x1, y1, x2, y2) with width x2 - x1 (no ``+ 1``);
- IoUs that feed a threshold or a tie are compared on a 2**-16 grid (2**-8
  in roi sampling), proposal boxes on a 2**-8 pixel grid;
- random subsampling draws one uniform number per candidate slot from a
  ``jax.random`` key and keeps the n largest (anchors) or n smallest (rois),
  ties to the lower index; padding slots carry weight 0 instead of being
  resampled with replacement;
- FPN level of a roi: k = floor(4 + log2(sqrt(wh) / 224)), raised until the
  roi spans at most ``max_extent_cells`` cells, clamped to the roi levels;
- ROIAlign samples outside (-1, H) x (-1, W) add zero, the rest clamp to the
  map (Detectron semantics); bins are at least one cell wide.
"""

from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.reference.layers import conv

XFORM_CLIP = math.log(1000.0 / 16.0)
HI = lax.Precision.HIGHEST


def backbone_of(ref):
    return importlib.import_module(f"perfbench.reference.backbone_{ref['backbone']}")


# -- parameters --------------------------------------------------------------


def head_specs(ref):
    c = ref["feature_channels"]
    k = len(ref["anchor_scales"]) * len(ref["anchor_ratios"])
    rc, hd, s = ref["rpn"]["channels"], ref["rcnn"]["hidden_dim"], ref["rcnn"]["pooled_size"]
    nc = ref["num_classes"]
    return [
        ("params/rpn/conv/kernel", (3, 3, c, rc), "he"),
        ("params/rpn/conv/bias", (rc,), "bias"),
        ("params/rpn/objectness/kernel", (1, 1, rc, k), "out_rpn"),
        ("params/rpn/objectness/bias", (k,), "bias"),
        ("params/rpn/deltas/kernel", (1, 1, rc, 4 * k), "out_box"),
        ("params/rpn/deltas/bias", (4 * k,), "bias"),
        ("params/box_head/fc6/kernel", (s * s * c, hd), "he"),
        ("params/box_head/fc6/bias", (hd,), "bias"),
        ("params/box_head/fc7/kernel", (hd, hd), "he"),
        ("params/box_head/fc7/bias", (hd,), "bias"),
        ("params/box_head/cls_score/kernel", (hd, nc), "out_cls"),
        ("params/box_head/cls_score/bias", (nc,), "cls_bias"),
        ("params/box_head/bbox_pred/kernel", (hd, 4 * nc), "out_box"),
        ("params/box_head/bbox_pred/bias", (4 * nc,), "bias"),
    ]


def all_specs(ref):
    return backbone_of(ref).specs(ref) + head_specs(ref)


def trainable(ref, path: str) -> bool:
    """A leaf the optimizer moves: a parameter outside the frozen prefixes."""
    if not path.startswith("params/"):
        return False
    return not any(path.startswith("params/" + p) for p in ref["optimizer"]["frozen"])


def decayed(path: str) -> bool:
    return path.rsplit("/", 1)[1] not in ("bias", "scale")


# -- boxes -------------------------------------------------------------------


def snap(x, bits):
    return jnp.round(x * 2.0**bits) * 2.0**-bits


def iou(a, b):
    lt = jnp.maximum(a[:, None, :2], b[None, :, :2])
    rb = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(rb - lt, 0.0)
    inter = wh[..., 0] * wh[..., 1]

    def area(x):
        return jnp.maximum(x[:, 2] - x[:, 0], 0.0) * jnp.maximum(x[:, 3] - x[:, 1], 0.0)

    union = area(a)[:, None] + area(b)[None, :] - inter
    return jnp.where(union > 0.0, inter / jnp.where(union > 0.0, union, 1.0), 0.0)


def _cwh(b):
    w, h = b[..., 2] - b[..., 0], b[..., 3] - b[..., 1]
    return w, h, b[..., 0] + 0.5 * w, b[..., 1] + 0.5 * h


def encode(boxes, anchors, weights=(1.0, 1.0, 1.0, 1.0)):
    aw, ah, ax, ay = _cwh(anchors)
    gw, gh, gx, gy = _cwh(boxes)
    aw, ah = jnp.maximum(aw, 1e-6), jnp.maximum(ah, 1e-6)
    return jnp.stack([
        weights[0] * (gx - ax) / aw,
        weights[1] * (gy - ay) / ah,
        weights[2] * jnp.log(jnp.maximum(gw, 1e-6) / aw),
        weights[3] * jnp.log(jnp.maximum(gh, 1e-6) / ah),
    ], axis=-1)


def decode(deltas, anchors, weights=(1.0, 1.0, 1.0, 1.0)):
    aw, ah, ax, ay = _cwh(anchors)
    dw = jnp.minimum(deltas[..., 2] / weights[2], XFORM_CLIP)
    dh = jnp.minimum(deltas[..., 3] / weights[3], XFORM_CLIP)
    cx = deltas[..., 0] / weights[0] * aw + ax
    cy = deltas[..., 1] / weights[1] * ah + ay
    w, h = jnp.exp(dw) * aw, jnp.exp(dh) * ah
    return jnp.stack([cx - 0.5 * w, cy - 0.5 * h, cx + 0.5 * w, cy + 0.5 * h], axis=-1)


def clip(boxes, h, w):
    return jnp.stack([
        jnp.clip(boxes[..., 0], 0.0, w), jnp.clip(boxes[..., 1], 0.0, h),
        jnp.clip(boxes[..., 2], 0.0, w), jnp.clip(boxes[..., 3], 0.0, h),
    ], axis=-1)


def level_anchors(ref, level, h, w):
    """(h*w*k, 4) anchors of one level, rows in (y, x, anchor) order; the
    anchor's base size is the level's stride, ratio-major then scale."""
    stride = 2**level
    ratios = np.asarray(ref["anchor_ratios"], np.float64)
    scales = np.asarray(ref["anchor_scales"], np.float64)
    ws = np.sqrt(stride * stride / ratios)
    hs = ws * ratios
    ws = (ws[:, None] * scales[None, :]).reshape(-1)
    hs = (hs[:, None] * scales[None, :]).reshape(-1)
    c = 0.5 * stride
    base = np.stack([c - 0.5 * ws, c - 0.5 * hs, c + 0.5 * ws, c + 0.5 * hs], 1)
    sx, sy = np.meshgrid(np.arange(w) * stride, np.arange(h) * stride)
    shifts = np.stack([sx, sy, sx, sy], -1).astype(np.float32)
    return (shifts[:, :, None, :] + base.astype(np.float32)[None, None]).reshape(-1, 4)


def smooth_l1(x, sigma):
    s2 = sigma * sigma
    ax = jnp.abs(x)
    return jnp.where(ax < 1.0 / s2, 0.5 * s2 * x * x, ax - 0.5 / s2)


# -- network heads -----------------------------------------------------------


def normalize(ref, images_u8):
    mean = jnp.asarray(ref["pixel_mean"], jnp.float32)
    std = jnp.asarray(ref["pixel_std"], jnp.float32)
    return (images_u8.astype(jnp.float32) - mean) / std


def rpn_head(ref, w, feats, matmul=None):
    """{level: (1, H, W, C)} -> per level (logits (H*W*k,), deltas (H*W*k, 4))."""
    out = {}
    for lvl in sorted(feats):
        y = jax.nn.relu(
            conv(feats[lvl], w["params/rpn/conv/kernel"], 1, 1, matmul)
            + w["params/rpn/conv/bias"]
        )
        lg = conv(y, w["params/rpn/objectness/kernel"], 1, 0, matmul) + w["params/rpn/objectness/bias"]
        dl = conv(y, w["params/rpn/deltas/kernel"], 1, 0, matmul) + w["params/rpn/deltas/bias"]
        out[lvl] = (lg.reshape(-1), dl.reshape(-1, 4))
    return out


def dense(x, k, b, matmul=None):
    if matmul is not None:
        x, k = matmul(x), matmul(k)
    return jnp.dot(x, k, precision=HI) + b


def box_head(ref, w, pooled, matmul=None):
    r = pooled.shape[0]
    x = pooled.reshape(r, -1)
    x = jax.nn.relu(dense(x, w["params/box_head/fc6/kernel"], w["params/box_head/fc6/bias"], matmul))
    x = jax.nn.relu(dense(x, w["params/box_head/fc7/kernel"], w["params/box_head/fc7/bias"], matmul))
    logits = dense(x, w["params/box_head/cls_score/kernel"], w["params/box_head/cls_score/bias"], matmul)
    deltas = dense(x, w["params/box_head/bbox_pred/kernel"], w["params/box_head/bbox_pred/bias"], matmul)
    return logits, deltas.reshape(r, -1, 4)


# -- anchor labels -----------------------------------------------------------


def _keep_largest(key, candidate, n, quota):
    """Boolean pick of the ``n`` candidates with the largest uniform draws."""
    pri = jnp.where(candidate, jax.random.uniform(key, candidate.shape), -1.0)
    _, idx = lax.top_k(pri, min(quota, candidate.shape[0]))
    take = jnp.arange(idx.shape[0]) < jnp.minimum(n, jnp.sum(candidate))
    return jnp.zeros(candidate.shape, bool).at[idx].set(take)


def assign_anchors(ref, key, anchors, gt_boxes, gt_valid, hw):
    """RPN labels (Ren et al. 2015 section 3.1.2): fg = IoU >= positive_iou or
    a gt's best inside anchor; bg = IoU < negative_iou; ``batch_size`` drawn
    with at most ``fg_fraction`` fg.  Returns (fg (A,), bg (A,), targets (A, 4))."""
    r = ref["rpn"]
    inside = (
        (anchors[:, 0] >= 0.0) & (anchors[:, 1] >= 0.0)
        & (anchors[:, 2] < hw[1]) & (anchors[:, 3] < hw[0])
    )
    ov = snap(iou(anchors, gt_boxes), 16) * gt_valid[None, :]
    max_ov = jnp.max(ov, axis=1)
    arg = jnp.argmax(ov, axis=1)
    ov_in = ov * inside[:, None]
    best = jnp.max(ov_in, axis=0)
    is_best = jnp.any((ov_in == best[None, :]) & gt_valid[None, :] & (best[None, :] > 0.0), axis=1)
    fg_c = inside & jnp.any(gt_valid) & ((max_ov >= r["positive_iou"]) | is_best)
    bg_c = inside & (max_ov < r["negative_iou"]) & ~fg_c
    quota = int(r["batch_size"] * r["fg_fraction"])
    k_fg, k_bg = jax.random.split(key)
    n_fg = jnp.minimum(quota, jnp.sum(fg_c))
    fg = _keep_largest(k_fg, fg_c, n_fg, quota)
    n_bg = jnp.minimum(r["batch_size"] - n_fg, jnp.sum(bg_c))
    bg = _keep_largest(k_bg, bg_c, n_bg, r["batch_size"])
    targets = jnp.where(fg[:, None], encode(gt_boxes[arg], anchors), 0.0)
    return fg, bg, targets


# -- proposals ---------------------------------------------------------------


def greedy_nms(boxes, scores, threshold):
    """Keep mask of greedy NMS in input order; -inf scores neither keep nor
    suppress.  One box at a time, best score first."""
    n = boxes.shape[0]
    order = jnp.argsort(-scores)
    b = boxes[order]
    ok = jnp.isfinite(scores[order])
    over = snap(iou(b, b), 16) > threshold
    later = jnp.arange(n)

    def body(i, keep):
        kill = over[i] & (later > i) & keep[i]
        return keep & ~kill

    keep = lax.fori_loop(0, n, body, ok)
    return jnp.zeros(n, bool).at[order].set(keep)


def _best_kept(keep, scores, boxes, n_out):
    """The ``n_out`` kept boxes of highest score, padded with invalid rows."""
    s = jnp.where(keep, scores, -jnp.inf)
    k = min(n_out, s.shape[0])
    top, idx = lax.top_k(s, k)
    valid = jnp.isfinite(top)
    out_b = boxes[idx] * valid[:, None]
    if k < n_out:
        out_b = jnp.concatenate([out_b, jnp.zeros((n_out - k, 4))])
        top = jnp.concatenate([top, jnp.full(n_out - k, -jnp.inf)])
        valid = jnp.concatenate([valid, jnp.zeros(n_out - k, bool)])
    return out_b, top, valid


def proposals(ref, rpn_out, anchors, hw, train: bool):
    """Per level: top ``pre`` by objectness, decode, clip, NMS, best ``post``;
    then the best ``post`` over the levels.  -> (rois (post, 4), valid)."""
    r = ref["rpn"]
    pre = r["train_pre_nms_top_n"] if train else r["test_pre_nms_top_n"]
    post = r["train_post_nms_top_n"] if train else r["test_post_nms_top_n"]
    per_level = []
    for lvl in sorted(rpn_out):
        logits, deltas = rpn_out[lvl]
        scores = snap(jax.nn.sigmoid(logits), 16)
        top, idx = lax.top_k(scores, min(pre, scores.shape[0]))
        boxes = snap(clip(decode(deltas[idx], anchors[lvl][idx]), hw[0], hw[1]), 8)
        w_, h_ = boxes[:, 2] - boxes[:, 0], boxes[:, 3] - boxes[:, 1]
        ms = r["min_size"]
        ok = (w_ >= ms) & (h_ >= ms) if ms > 0 else (w_ > 0) & (h_ > 0)
        top = jnp.where(ok, top, -jnp.inf)
        keep = greedy_nms(boxes, top, r["nms_threshold"])
        per_level.append(_best_kept(keep, top, boxes, post))
    if len(per_level) == 1:
        rois, _, valid = per_level[0]
        return rois, valid
    boxes = jnp.concatenate([p[0] for p in per_level])
    scores = jnp.concatenate([p[1] for p in per_level])
    rois, _, valid = _best_kept(jnp.isfinite(scores), scores, boxes, post)
    return rois, valid


# -- roi sampling ------------------------------------------------------------


def _rank(key, candidate):
    pri = jnp.where(candidate, jax.random.uniform(key, candidate.shape), 2.0)
    order = jnp.argsort(pri)
    return jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))


def sample_rois(ref, key, rois, roi_valid, gt_boxes, gt_classes, gt_valid):
    """Proposals + gt boxes -> a ``roi_batch_size`` minibatch (Girshick 2015):
    fg = IoU >= fg_iou (at most fg_fraction), bg = IoU in [bg_lo, bg_hi).
    -> rois (N, 4), labels (N,), weight (N,), targets (N, 4), fg (N,)."""
    c = ref["rcnn"]
    n = c["roi_batch_size"]
    allr = jnp.concatenate([rois, gt_boxes])
    ok = jnp.concatenate([roi_valid, gt_valid])
    ov = snap(iou(allr, gt_boxes), 8) * gt_valid[None, :]
    max_ov = jnp.where(ok, jnp.max(ov, axis=1), -1.0)
    arg = jnp.argmax(ov, axis=1)
    fg_c = ok & (max_ov >= c["fg_iou"])
    bg_c = ok & (max_ov < c["bg_iou_hi"]) & (max_ov >= c["bg_iou_lo"]) & ~fg_c
    quota = int(n * c["fg_fraction"])
    k_fg, k_bg = jax.random.split(key)
    fg_rank = _rank(k_fg, fg_c)
    n_fg = jnp.minimum(quota, jnp.sum(fg_c))
    fg = fg_c & (fg_rank < n_fg)
    bg_rank = _rank(k_bg, bg_c)
    n_bg = jnp.minimum(n - n_fg, jnp.sum(bg_c))
    bg = bg_c & (bg_rank < n_bg)
    # fg first, then bg, then padding: only the set matters to the losses.
    pri = jnp.where(fg, 3.0e9 - fg_rank, jnp.where(bg, 1.0e9 - bg_rank, -1.0))
    order = jnp.argsort(-pri)[:n]
    picked = pri[order] > 0.0
    out = allr[order]
    is_fg = fg[order]
    gt_i = arg[order]
    labels = jnp.where(is_fg, gt_classes[gt_i], 0)
    targets = jnp.where(is_fg[:, None], encode(gt_boxes[gt_i], out, c["bbox_weights"]), 0.0)
    return out, labels, picked.astype(jnp.float32), targets, is_fg


# -- ROIAlign ----------------------------------------------------------------


def roi_levels(ref, rois):
    c = ref["rcnn"]
    lo, hi = min(ref["roi_levels"]), max(ref["roi_levels"])
    w = jnp.maximum(rois[:, 2] - rois[:, 0], 1e-6)
    h = jnp.maximum(rois[:, 3] - rois[:, 1], 1e-6)
    k = jnp.floor(4.0 + jnp.log2(jnp.sqrt(w * h) / 224.0)).astype(jnp.int32)
    fit = jnp.ceil(jnp.log2(jnp.maximum(w, h) / c["max_extent_cells"])).astype(jnp.int32)
    return jnp.clip(jnp.maximum(k, fit), lo, hi)


def _roi_align_one(feat, rois, stride, size, ratio):
    """feat (H, W, C); rois (R, 4) -> (R, size, size, C): the mean of
    ratio x ratio bilinear samples per bin."""
    h, w, _ = feat.shape
    s = rois / stride
    x1, y1 = s[:, 0], s[:, 1]
    bw = jnp.maximum(s[:, 2] - x1, 1.0) / size
    bh = jnp.maximum(s[:, 3] - y1, 1.0) / size
    off = (jnp.arange(size)[:, None] + (jnp.arange(ratio)[None, :] + 0.5) / ratio).reshape(-1)
    sy = y1[:, None] + off[None, :] * bh[:, None]          # (R, size*ratio)
    sx = x1[:, None] + off[None, :] * bw[:, None]

    def taps(c, extent):
        inside = (c > -1.0) & (c < extent)
        c = jnp.clip(c, 0.0, extent - 1.0)
        c0 = jnp.floor(c)
        frac = c - c0
        i0 = c0.astype(jnp.int32)
        i1 = jnp.minimum(i0 + 1, extent - 1)
        return i0, i1, frac, inside

    y0, y1i, fy, iny = taps(sy, h)
    x0, x1i, fx, inx = taps(sx, w)

    def at(yi, xi):
        return feat[yi[:, :, None], xi[:, None, :]]        # (R, P, P, C)

    wy0, wy1 = (1.0 - fy)[:, :, None, None], fy[:, :, None, None]
    wx0, wx1 = (1.0 - fx)[:, None, :, None], fx[:, None, :, None]
    val = (
        at(y0, x0) * wy0 * wx0 + at(y0, x1i) * wy0 * wx1
        + at(y1i, x0) * wy1 * wx0 + at(y1i, x1i) * wy1 * wx1
    ) * (iny[:, :, None] & inx[:, None, :])[..., None]
    r = rois.shape[0]
    val = val.reshape(r, size, ratio, size, ratio, -1)
    return val.mean(axis=(2, 4))


def roi_align(ref, feats, rois):
    """feats {level: (1, H, W, C)}; every roi pooled from its own level."""
    c = ref["rcnn"]
    levels = ref["roi_levels"]
    if len(levels) == 1:
        l = levels[0]
        return _roi_align_one(feats[l][0], rois, 2.0**l, c["pooled_size"], c["sampling_ratio"])
    assigned = roi_levels(ref, rois)
    out = 0.0
    for l in levels:
        pooled = _roi_align_one(feats[l][0], rois, 2.0**l, c["pooled_size"], c["sampling_ratio"])
        out = out + pooled * (assigned == l)[:, None, None, None]
    return out


# -- one training image ------------------------------------------------------


def _anchor_grid(ref, feats):
    return {l: jnp.asarray(level_anchors(ref, l, f.shape[1], f.shape[2])) for l, f in feats.items()}


def image_sums(ref, w, image_u8, gt_boxes, gt_classes, gt_valid, hw, k_assign, k_sample,
               matmul=None):
    """The four un-normalized loss sums of one image and their two counts."""
    feats = backbone_of(ref).features(ref, w, normalize(ref, image_u8[None]), matmul)
    rpn_out = rpn_head(ref, w, feats, matmul)
    anchors = _anchor_grid(ref, feats)
    levels = sorted(rpn_out)
    logits = jnp.concatenate([rpn_out[l][0] for l in levels])
    deltas = jnp.concatenate([rpn_out[l][1] for l in levels])
    a_cat = jnp.concatenate([anchors[l] for l in levels])
    gv = gt_valid.astype(jnp.float32)

    fg, bg, tgt = assign_anchors(ref, k_assign, a_cat, gt_boxes, gv > 0, hw)
    picked = (fg | bg).astype(jnp.float32)
    isfg = fg.astype(jnp.float32)
    bce = -(isfg * jax.nn.log_sigmoid(logits) + (1.0 - isfg) * jax.nn.log_sigmoid(-logits))
    rpn_cls = jnp.sum(bce * picked)
    rpn_box = jnp.sum(smooth_l1((deltas - tgt) * isfg[:, None], 3.0))

    stopped = {l: (lax.stop_gradient(a), lax.stop_gradient(b)) for l, (a, b) in rpn_out.items()}
    rois, valid = proposals(ref, stopped, anchors, hw, train=True)
    rois, labels, weight, targets, is_fg = sample_rois(
        ref, k_sample, rois, valid, gt_boxes, gt_classes, gv > 0
    )
    pooled = roi_align(ref, feats, lax.stop_gradient(rois))
    cls_logits, box_deltas = box_head(ref, w, pooled, matmul)
    logp = jax.nn.log_softmax(cls_logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    rcnn_cls = jnp.sum(ce * weight)
    own = jnp.take_along_axis(box_deltas, labels[:, None, None].repeat(4, -1), axis=1)[:, 0]
    rcnn_box = jnp.sum(smooth_l1((own - targets) * is_fg[:, None], 1.0))
    return {
        "rpn_cls": rpn_cls, "rpn_box": rpn_box, "rcnn_cls": rcnn_cls, "rcnn_box": rcnn_box,
        "n_rpn": jnp.sum(picked), "n_rcnn": jnp.sum(weight),
    }


def image_loss(ref, w, n_rpn, n_rcnn, *args, matmul=None):
    """This image's share of the batch loss, normalized by the batch's counts
    (Ren et al. 2015 eq. 1 with both normalizers the sampled counts)."""
    s = image_sums(ref, w, *args, matmul=matmul)
    rpn = (s["rpn_cls"] + s["rpn_box"]) / jnp.maximum(n_rpn, 1.0)
    rcnn = (s["rcnn_cls"] + s["rcnn_box"]) / jnp.maximum(n_rcnn, 1.0)
    return rpn + rcnn, s
