"""In-graph RPN proposal generation.

Replaces the reference Proposal custom op (``rcnn/symbol/proposal.py``,
and the engine's ``mx.contrib.symbol.Proposal`` behind CXX_PROPOSAL):
decode RPN outputs into scored boxes, pre-NMS top-k, NMS, and emit a fixed
``post_nms_top_n`` roi set — with zero host interaction.  The reference
pays a device->host->device round-trip plus a CUDA NMS here every
iteration (SURVEY.md section 4.5); this version is one fused XLA region.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from mx_rcnn_tpu.geometry import clip_boxes, decode_boxes, snap, valid_box_mask
from mx_rcnn_tpu.ops.nms import nms_indices
from mx_rcnn_tpu.ops.topk import hierarchical_top_k


class Proposals(NamedTuple):
    rois: jnp.ndarray    # (post_nms_top_n, 4)
    scores: jnp.ndarray  # (post_nms_top_n,)
    valid: jnp.ndarray   # (post_nms_top_n,) bool


def generate_proposals(
    scores: jnp.ndarray,
    deltas: jnp.ndarray,
    anchors: jnp.ndarray,
    image_height,
    image_width,
    pre_nms_top_n: int = 6000,
    post_nms_top_n: int = 300,
    nms_threshold: float = 0.7,
    min_size: float = 0.0,
    topk_impl: str = "hier",
    topk_recall: float = 0.95,
    topk_block: int = 32768,
) -> Proposals:
    """Single-level proposal generation.

    Args:
      scores: (A,) objectness probabilities (post-sigmoid/softmax-fg).
      deltas: (A, 4) RPN regression output.
      anchors: (A, 4) matching anchor boxes.
      image_height/image_width: true (unpadded) image extent, may be traced.
      pre_nms_top_n / post_nms_top_n / nms_threshold / min_size: the
        reference's RPN_PRE_NMS_TOP_N / RPN_POST_NMS_TOP_N /
        config.TRAIN.RPN_NMS_THRESH / RPN_MIN_SIZE.
      topk_impl / topk_recall / topk_block: pre-NMS selection operator —
        see ``RPNConfig.topk_impl`` (config.py) for the semantics/parity
        argument.  ``"hier"`` (default) is the blocked exact top-k
        (bit-identical to ``"exact"``, see ``ops/topk.py``); only the
        strict-subset case (k < A) can go approx; k == A is a plain sort
        either way.

    Returns:
      Fixed-size Proposals; invalid slots carry zeros.
    """
    boxes, masked_scores = _pre_nms_candidates(
        scores, deltas, anchors, image_height, image_width,
        pre_nms_top_n, min_size, topk_impl, topk_recall, topk_block,
    )
    with jax.named_scope("nms"):
        keep_idx, keep_valid = nms_indices(
            boxes, masked_scores, nms_threshold, post_nms_top_n
        )
    rois = jnp.take(boxes, keep_idx, axis=0) * keep_valid[:, None]
    out_scores = jnp.where(keep_valid, jnp.take(masked_scores, keep_idx), 0.0)
    return Proposals(rois=rois, scores=out_scores, valid=keep_valid)


def _pre_nms_candidates(
    scores, deltas, anchors, image_height, image_width,
    pre_nms_top_n: int, min_size: float, topk_impl: str, topk_recall: float,
    topk_block: int = 32768,
):
    """Shared pre-NMS front half: score snap, top-k by objectness, decode,
    clip, and min-size masking.  Returns (boxes (k, 4), masked_scores (k,))
    in score-descending, index-ascending-tie order, with suppressed/invalid
    candidates at ``-inf`` score."""
    a = scores.shape[0]
    k = min(pre_nms_top_n, a)
    if topk_impl not in ("hier", "exact", "approx"):
        raise ValueError(
            f"topk_impl must be 'hier', 'exact' or 'approx', got {topk_impl!r}"
        )
    with jax.named_scope("topk"):
        # snap(): top-k ranking and the NMS visit order are discrete in
        # the scores; snapped scores + index-stable tie-breaks (lax.top_k
        # and argsort both prefer the lower index) give the same candidate
        # ordering in every compilation of this graph (see
        # geometry.boxes.snap).
        scores = snap(scores)

        if topk_impl == "approx" and k < a:
            top_scores, top_idx = lax.approx_max_k(
                scores, k, recall_target=topk_recall
            )
        elif topk_impl == "hier":
            # Blocked exact top-k — bit-identical to lax.top_k including
            # the snapped-score index-stable tie-breaks (proof in
            # ops/topk.py).
            top_scores, top_idx = hierarchical_top_k(
                scores, k, block=topk_block
            )
        else:
            top_scores, top_idx = lax.top_k(scores, k)
        top_deltas = jnp.take(deltas, top_idx, axis=0)
        top_anchors = jnp.take(anchors, top_idx, axis=0)
    with jax.named_scope("decode"):
        boxes = decode_boxes(top_deltas, top_anchors)
        boxes = clip_boxes(boxes, image_height, image_width)
        # snap to a 1/256-px grid: decode/clip arithmetic carries a few
        # ulps of cross-compilation noise at coordinate scale (~1e-5 px),
        # which is the same magnitude as the fine IoU snap grid downstream
        # — snapping the coordinates themselves makes every IoU consumer
        # (NMS here, roi sampling later) see bit-identical boxes.  1/256 px
        # is far below anything detection quality can notice.
        boxes = snap(boxes, bits=8)

        ok = valid_box_mask(boxes, min_size=min_size)
        masked_scores = jnp.where(ok, top_scores, -jnp.inf)
    return boxes, masked_scores


def generate_fpn_proposals(
    level_scores: dict[int, jnp.ndarray],
    level_deltas: dict[int, jnp.ndarray],
    level_anchors: dict[int, jnp.ndarray],
    image_height,
    image_width,
    pre_nms_top_n: int = 2000,
    post_nms_top_n: int = 1000,
    nms_threshold: float = 0.7,
    min_size: float = 0.0,
    topk_impl: str = "hier",
    topk_recall: float = 0.95,
    topk_block: int = 32768,
) -> Proposals:
    """FPN-style proposals: per-level top-k + NMS, then global top-k by score.

    (Detectron recipe: PRE_NMS_TOPK per level, POST_NMS_TOPK across the
    union — the configuration the BASELINE north star's >=37 mAP requires.)

    The per-level NMS runs as ONE vmapped fixed point over the level axis
    (short levels padded to the widest k with ``-inf`` scores — padding
    never keeps nor suppresses, so each lane equals its standalone NMS
    bit-for-bit, tested).  L sequential while-loops would pay L
    convergence latencies back-to-back; one batched loop pays the
    worst lane's.  r4 A/B on the train step: see BASELINE.md.
    """
    # Detectron recipe: each level may keep up to post_nms_top_n proposals;
    # the global top-k over the union then trims to post_nms_top_n total.
    levels = sorted(level_scores.keys())
    cand = [
        _pre_nms_candidates(
            level_scores[lvl], level_deltas[lvl], level_anchors[lvl],
            image_height, image_width,
            pre_nms_top_n, min_size, topk_impl, topk_recall, topk_block,
        )
        for lvl in levels
    ]
    kmax = max(b.shape[0] for b, _ in cand)
    bx = jnp.stack(
        [jnp.pad(b, ((0, kmax - b.shape[0]), (0, 0))) for b, _ in cand]
    )                                                       # (L, kmax, 4)
    sc = jnp.stack(
        [
            jnp.pad(s, (0, kmax - s.shape[0]), constant_values=-jnp.inf)
            for _, s in cand
        ]
    )                                                       # (L, kmax)

    with jax.named_scope("nms"):
        keep_idx, keep_valid = jax.vmap(
            lambda b, s: nms_indices(b, s, nms_threshold, post_nms_top_n)
        )(bx, sc)                                           # (L, post) x2
    rois_l = jnp.take_along_axis(
        bx, keep_idx[..., None], axis=1
    ) * keep_valid[..., None]
    scores_l = jnp.where(
        keep_valid, jnp.take_along_axis(sc, keep_idx, axis=1), 0.0
    )

    rois = rois_l.reshape(-1, 4)
    scores = scores_l.reshape(-1)
    valid = keep_valid.reshape(-1)

    masked = jnp.where(valid, scores, -jnp.inf)
    k = min(post_nms_top_n, rois.shape[0])
    top_scores, top_idx = lax.top_k(masked, k)
    out_valid = jnp.isfinite(top_scores)
    out_rois = jnp.take(rois, top_idx, axis=0) * out_valid[:, None]
    return Proposals(
        rois=out_rois,
        scores=jnp.where(out_valid, top_scores, 0.0),
        valid=out_valid,
    )
