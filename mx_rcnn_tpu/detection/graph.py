"""Train / inference computations for the two-stage detector.

This file is the TPU-native replacement for the reference's whole execution
sandwich (SURVEY.md section 4.1): the symbolic train graph with two
host-round-trip custom ops in its middle (``rcnn/symbol/proposal.py``,
``rcnn/symbol/proposal_target.py``), the host-side anchor labeling inside
the loader (``rcnn/io/rpn.py::assign_anchor``), and the test-time
``rcnn/core/tester.py::im_detect`` + per-class NMS loop.  Everything here is
a pure function of (variables, batch, rng) with static shapes — one jitted
region per train/eval step, zero host interaction.

Shape conventions:
  B = batch, G = max gt boxes, A = total anchors over levels,
  R = proposals per image, S = pooled size, C = num classes (incl. bg 0).
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from mx_rcnn_tpu.config import ModelConfig
from mx_rcnn_tpu.detection.detector import TwoStageDetector
from mx_rcnn_tpu.geometry import (
    clip_boxes,
    decode_boxes,
    generate_base_anchors,
    masked_softmax_cross_entropy,
    shifted_anchors_np,
    weighted_smooth_l1,
)
from mx_rcnn_tpu.ops import assign_anchors, generate_proposals, sample_rois
from mx_rcnn_tpu.ops.nms import batched_nms, nms_indices
from mx_rcnn_tpu.ops.pallas.roi_align import (
    POOL_WINDOW,
    multilevel_roi_align_fast,
    pallas_supported,
    sharded_multilevel_roi_align,
)
from mx_rcnn_tpu.ops.proposals import Proposals, generate_fpn_proposals
from mx_rcnn_tpu.ops.roi_align import multilevel_roi_align, roi_align_matmul

# Batch moved to data/batch.py (jax-free) so input-service workers can
# unpickle batches without importing the model stack; re-exported here so
# every historical `from mx_rcnn_tpu.detection.graph import Batch` holds.
from mx_rcnn_tpu.data.batch import Batch  # noqa: F401  (re-export)


class Detections(NamedTuple):
    boxes: jnp.ndarray    # (B, D, 4) in input-image coordinates
    scores: jnp.ndarray   # (B, D)
    classes: jnp.ndarray  # (B, D) int32, 1-based foreground ids
    valid: jnp.ndarray    # (B, D) bool
    masks: Optional[jnp.ndarray] = None  # (B, D, M, M) probabilities


# ---------------------------------------------------------------------------
# Anchors


@lru_cache(maxsize=64)
def _cached_level_anchor(stride: int, ratios, scales, h: int, w: int):
    """One level's anchor grid, memoized as host numpy.

    ``generate_base_anchors``/``shifted_anchors`` enumerate the grid in
    host numpy — O(H*W*k) work the old code redid on EVERY trace (retrace
    per canvas orientation, per eval bucket, per chaos-restart).  The
    geometry is a pure function of this static key, so cache it; repeated
    traces of the same shapes reuse it for free.  Cached in NUMPY form on
    purpose: a jnp array built while tracing is a tracer, and handing a
    cached tracer to a later trace leaks it.  ``level_anchors`` does the
    (cheap, constant-embedding) jnp.asarray per trace.
    """
    base = generate_base_anchors(base_size=stride, ratios=ratios, scales=scales)
    return shifted_anchors_np(base, stride, h, w)


def level_anchors(
    cfg: ModelConfig, feats: dict[int, jnp.ndarray]
) -> dict[int, jnp.ndarray]:
    """Static per-level anchor grids for the given feature shapes.

    Anchor base size is the level stride (FPN: one octave per level); the C4
    recipe's single level 4 with scales (8, 16, 32) reproduces the
    reference's 128/256/512-pixel anchors exactly.
    """
    out = {}
    for lvl in sorted(feats):
        stride = 2**lvl
        _, h, w, _ = feats[lvl].shape
        out[lvl] = jnp.asarray(_cached_level_anchor(
            stride, tuple(cfg.anchors.ratios), tuple(cfg.anchors.scales), h, w
        ))
    return out


# ---------------------------------------------------------------------------
# Losses


def _rpn_losses(rpn_logits, rpn_deltas, targets, loss_impl: str = "dense"):
    """RPN objectness + box losses, per reference normalization.

    rpn_logits (B, A), rpn_deltas (B, A, 4); targets from assign_anchors
    vmapped over B.  Objectness is sigmoid BCE over sampled anchors
    normalized by valid count (the reference's 2-way softmax with
    ignore_label=-1 and normalization='valid' — same quantity); box loss is
    smooth_l1(sigma=3) on fg anchors normalized by the same count
    (reference grad_scale = 1/RPN_BATCH_SIZE per image).

    ``loss_impl``: "dense" reduces over the full (B, A) anchor axis with
    masks (bit-identical to the historical form); "compact" reduces only
    the Q sampled rows via AnchorTargets.sel_* — same terms, different
    summation order (see RPNConfig.loss_impl).
    """
    with jax.named_scope("rpn_loss"):
        if loss_impl == "compact":
            if targets.sel_idx is None:
                raise ValueError(
                    "loss_impl='compact' needs AnchorTargets.sel_* (produced "
                    "by assign_anchors)"
                )
            return _rpn_losses_compact(rpn_logits, rpn_deltas, targets)
        if loss_impl != "dense":
            raise ValueError(
                f"rpn.loss_impl must be 'dense' or 'compact', got {loss_impl!r}"
            )
        return _rpn_losses_impl(rpn_logits, rpn_deltas, targets)


def _rpn_losses_impl(rpn_logits, rpn_deltas, targets):
    # Accumulation-precision entry (mixed policy: the head emits bf16).
    # The upcast happens HERE, inside the rpn_loss named scope — the
    # tpulint TPU006 allowlist — so loss sums always run in f32.  No-op
    # on f32 inputs.  The dense form pays a (B, A) f32 materialization;
    # the compact form below upcasts after the Q-row gather instead.
    rpn_logits = rpn_logits.astype(jnp.float32)
    rpn_deltas = rpn_deltas.astype(jnp.float32)
    labels = targets.labels            # (B, A) 1/0/-1
    valid = targets.valid_mask         # (B, A)
    fg = targets.fg_mask               # (B, A)
    n_valid = jnp.maximum(jnp.sum(valid), 1.0)

    logp = jax.nn.log_sigmoid(rpn_logits)
    log1mp = jax.nn.log_sigmoid(-rpn_logits)
    is_fg = (labels == 1).astype(rpn_logits.dtype)
    bce = -(is_fg * logp + (1.0 - is_fg) * log1mp)
    cls_loss = jnp.sum(bce * valid) / n_valid

    box_loss = weighted_smooth_l1(
        rpn_deltas,
        targets.bbox_targets,
        inside_weight=fg[..., None].astype(rpn_deltas.dtype),
        sigma=3.0,
        normalizer=n_valid,
    )

    pred_fg = rpn_logits > 0.0
    acc = jnp.sum((pred_fg == (labels == 1)) * valid) / n_valid
    return cls_loss, box_loss, acc


def _rpn_losses_compact(rpn_logits, rpn_deltas, targets):
    """RPN losses over the Q sampled anchor rows only.

    The dense form reduces BCE over all (B, A) anchors with at most
    ``batch_size`` nonzero terms per image; here the assignment masks are
    fused into the loss by gathering the sampled rows assign_anchors
    already knows (``sel_idx`` — the subsample top_k's own output), so
    forward AND backward touch Q = fg_quota + batch_size rows per image
    instead of A = 268k.  Same loss terms (every masked-out dense term is
    an exact 0.0); only the summation order differs, so metrics agree to
    f32 round-off rather than bitwise.  The accuracy metric is a 0/1
    count and matches the dense value exactly.
    """
    idx = targets.sel_idx              # (B, Q)
    take = targets.sel_take.astype(jnp.float32)
    is_fg = targets.sel_fg             # (B, Q)
    n_valid = jnp.maximum(jnp.sum(take), 1.0)

    # Gather in the head's output dtype, upcast only the Q selected rows
    # (accumulation allowlist: we are inside the rpn_loss named scope).
    logit_sel = jnp.take_along_axis(rpn_logits, idx, axis=1)      # (B, Q)
    logit_sel = logit_sel.astype(jnp.float32)
    fgf = is_fg.astype(jnp.float32)
    bce = -(
        fgf * jax.nn.log_sigmoid(logit_sel)
        + (1.0 - fgf) * jax.nn.log_sigmoid(-logit_sel)
    )
    cls_loss = jnp.sum(bce * take) / n_valid

    deltas_sel = jnp.take_along_axis(rpn_deltas, idx[..., None], axis=1)
    deltas_sel = deltas_sel.astype(jnp.float32)
    targets_sel = jnp.take_along_axis(targets.bbox_targets, idx[..., None], axis=1)
    box_loss = weighted_smooth_l1(
        deltas_sel,
        targets_sel,
        inside_weight=fgf[..., None],
        sigma=3.0,
        normalizer=n_valid,
    )

    pred_fg = logit_sel > 0.0
    acc = jnp.sum((pred_fg == is_fg) * take) / n_valid
    return cls_loss, box_loss, acc


def _rcnn_losses(cls_logits, box_deltas, samples, class_agnostic: bool):
    """R-CNN classification + per-class box regression losses.

    cls_logits (N, C), box_deltas (N, C or 1, 4) over N = B*roi_batch
    flattened samples.  Matches the reference's SoftmaxOutput
    (normalization='valid') + smooth_l1(sigma=1) scaled 1/BATCH_ROIS.
    """
    with jax.named_scope("rcnn_loss"):
        return _rcnn_losses_impl(cls_logits, box_deltas, samples,
                                 class_agnostic)


def _rcnn_losses_impl(cls_logits, box_deltas, samples, class_agnostic: bool):
    # Accumulation-precision entry (see _rpn_losses_impl): N = B*roi_batch
    # rows only, upcast inside the rcnn_loss named scope.
    cls_logits = cls_logits.astype(jnp.float32)
    box_deltas = box_deltas.astype(jnp.float32)
    labels = samples.labels.reshape(-1)            # (N,)
    weights = samples.label_weights.reshape(-1)    # (N,)
    fg = samples.fg_mask.reshape(-1)               # (N,)
    targets = samples.bbox_targets.reshape(-1, 4)  # (N, 4)
    n_valid = jnp.maximum(jnp.sum(weights), 1.0)

    cls_loss = masked_softmax_cross_entropy(cls_logits, labels, weights)

    if class_agnostic:
        sel = box_deltas[:, 0, :]
    else:
        idx = jnp.clip(labels, 0, box_deltas.shape[1] - 1)
        sel = jnp.take_along_axis(box_deltas, idx[:, None, None].repeat(4, -1), axis=1)[:, 0, :]
    box_loss = weighted_smooth_l1(
        sel,
        targets,
        inside_weight=fg[:, None].astype(sel.dtype),
        sigma=1.0,
        normalizer=n_valid,
    )

    pred = jnp.argmax(cls_logits, axis=-1)
    acc = jnp.sum((pred == labels) * weights) / n_valid
    return cls_loss, box_loss, acc


# ---------------------------------------------------------------------------
# Proposal plumbing (per-image, vmapped)


def _propose_one(cfg: ModelConfig, train: bool):
    """Builds the per-image proposal fn over concatenated level outputs."""
    rpn_cfg = cfg.rpn
    pre = rpn_cfg.train_pre_nms_top_n if train else rpn_cfg.test_pre_nms_top_n
    post = rpn_cfg.train_post_nms_top_n if train else rpn_cfg.test_post_nms_top_n

    def single(level_scores, level_deltas, level_anchor, hw) -> Proposals:
        if len(level_scores) == 1:
            (s,), (d,), (a,) = (
                list(level_scores.values()),
                list(level_deltas.values()),
                list(level_anchor.values()),
            )
            return generate_proposals(
                s, d, a, hw[0], hw[1],
                pre_nms_top_n=pre, post_nms_top_n=post,
                nms_threshold=rpn_cfg.nms_threshold, min_size=rpn_cfg.min_size,
                topk_impl=rpn_cfg.topk_impl, topk_recall=rpn_cfg.topk_recall,
                topk_block=rpn_cfg.topk_block,
            )
        return generate_fpn_proposals(
            level_scores, level_deltas, level_anchor, hw[0], hw[1],
            pre_nms_top_n=pre, post_nms_top_n=post,
            nms_threshold=rpn_cfg.nms_threshold, min_size=rpn_cfg.min_size,
            topk_impl=rpn_cfg.topk_impl, topk_recall=rpn_cfg.topk_recall,
            topk_block=rpn_cfg.topk_block,
        )

    return single


def _slice_levels(levels, anchors, score_row, delta_row):
    """Split concatenated per-anchor rows back into per-level dicts, paired
    with each level's static anchor grid.  Shared by train and inference."""
    off = 0
    s_lvls, d_lvls, a_lvls = {}, {}, {}
    for l in levels:
        n = anchors[l].shape[0]
        s_lvls[l] = score_row[off:off + n]
        d_lvls[l] = delta_row[off:off + n]
        a_lvls[l] = anchors[l]
        off += n
    return s_lvls, d_lvls, a_lvls


# Trace-time record of the backend _pool_rois last selected ("pallas",
# "pallas-shardmap", "xla", or "matmul" on a one-level pyramid) — set while
# jit traces, so tests and the driver dryrun can assert which path a
# compiled program actually took.
LAST_POOL_IMPL: Optional[str] = None


def _pallas_interpret() -> bool:
    """Off-TPU escape hatch: MX_RCNN_PALLAS_INTERPRET=1 runs the kernel in
    pallas interpret mode (pure-JAX emulation of grid/DMA) so fake-mesh CPU
    tests and the driver's multichip dryrun exercise the production path."""
    import os

    return (
        jax.default_backend() != "tpu"
        and os.environ.get("MX_RCNN_PALLAS_INTERPRET") == "1"
    )


def _pool_rois(cfg: ModelConfig, feats, rois, pooled_size: int, roi_level_set,
               mesh=None):
    # Named scope so per-component cost attribution (utils/hlo_profile.py)
    # can see the parameter-free ROI stage, which no flax module names.
    with jax.named_scope("roi_align"):
        return _pool_rois_impl(
            cfg, feats, rois, pooled_size, roi_level_set, mesh
        )


def _pool_rois_impl(cfg: ModelConfig, feats, rois, pooled_size: int,
                    roi_level_set, mesh=None):
    """ROIAlign over the batch. rois: (B, R, 4) -> (B, R, S, S, C).

    A single-level (C4 / VGG) pyramid has ONE path on every platform:
    ``ops/roi_align.py::roi_align_matmul``, the dense interpolation matmul
    over the whole map ("matmul").  ``cfg.rcnn.roi_align_impl`` is not
    read there: the Pallas kernel's window bounds a roi's extent through
    FPN level reassignment, which one level cannot do, and the XLA gather
    is only the oracle.

    On a multi-level pyramid ``cfg.rcnn.roi_align_impl`` picks the
    backend: "pallas" (default — ONE batch-folded kernel launch per step)
    or "xla" (flattened-pyramid gather — the oracle).  "pallas" still
    takes the XLA gather off-TPU without MX_RCNN_PALLAS_INTERPRET=1 (the
    design for CPU tests; decided from what the code can see, not a
    failure).
    On a TPU, a multi-level pyramid whose layout the kernel cannot slice
    (:func:`pallas_supported`) RAISES: a TPU path was asked for, and
    nothing quietly stands in for it.  The pallas path's backward is a
    Pallas window-RMW kernel too (ops/pallas/roi_align.py::_bwd_kernel;
    MX_RCNN_POOL_BWD=xla restores the autodiff-of-XLA backward).

    ``mesh``: a >1-data-axis mesh wraps the kernel in ``shard_map`` so each
    chip pools its own images (the kernel's per-shard contract) instead of
    GSPMD replicating the opaque kernel call; None = single-device jit or
    a caller that keeps the XLA path (spatial partitioning).
    """
    global LAST_POOL_IMPL
    if cfg.rcnn.roi_align_impl not in ("xla", "pallas"):
        raise ValueError(
            f"rcnn.roi_align_impl must be 'xla' or 'pallas', "
            f"got {cfg.rcnn.roi_align_impl!r}"
        )
    if cfg.rcnn.roi_align_bwd_impl not in ("xla", "pallas"):
        raise ValueError(
            f"rcnn.roi_align_bwd_impl must be 'xla' or 'pallas', "
            f"got {cfg.rcnn.roi_align_bwd_impl!r}"
        )
    levels = sorted(feats)
    if len(levels) == 1:
        lvl = levels[0]
        LAST_POOL_IMPL = "matmul"
        return roi_align_matmul(
            feats[lvl], rois, pooled_size, 1.0 / (2**lvl),
            cfg.rcnn.sampling_ratio,
        )

    roi_levels = {l: f for l, f in feats.items() if l in roi_level_set}
    on_tpu = jax.default_backend() == "tpu"
    interpret = _pallas_interpret()
    if cfg.rcnn.roi_align_impl == "pallas" and (on_tpu or interpret):
        if pallas_supported(roi_levels):
            from mx_rcnn_tpu.parallel.mesh import DATA_AXIS

            if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1:
                LAST_POOL_IMPL = "pallas-shardmap"
                return sharded_multilevel_roi_align(
                    roi_levels, rois, pooled_size, cfg.rcnn.sampling_ratio,
                    mesh, DATA_AXIS, interpret=interpret,
                    bwd_impl=cfg.rcnn.roi_align_bwd_impl,
                )
            # Whole batch in ONE kernel launch: the batch folds into the
            # pallas grid (B*R roi steps), no per-image python unroll.
            LAST_POOL_IMPL = "pallas"
            return multilevel_roi_align_fast(
                roi_levels, rois, pooled_size, cfg.rcnn.sampling_ratio,
                POOL_WINDOW, interpret, cfg.rcnn.roi_align_bwd_impl,
            )
        if on_tpu:
            raise ValueError(
                "rcnn.roi_align_impl='pallas' on a TPU, but the kernel "
                "cannot slice this pyramid (levels "
                f"{ {l: tuple(f.shape) for l, f in sorted(roi_levels.items())} }: "
                "it needs more than one level and channels in multiples "
                "of 128).  Set rcnn.roi_align_impl='xla' to take the XLA "
                "gather by name — it is not chosen silently"
            )
    LAST_POOL_IMPL = "xla"
    return jax.vmap(
        lambda fs, r: multilevel_roi_align(
            fs, r, output_size=pooled_size, sampling_ratio=cfg.rcnn.sampling_ratio
        )
    )(roi_levels, rois)


# ---------------------------------------------------------------------------
# Mask branch (Mask R-CNN, BASELINE config #5)


def crop_gt_masks(gt_masks, gt_boxes, gt_idx, rois, out_size: int):
    """Bilinear-crop each roi's matched gt mask to the mask-head grid.

    ``gt_masks`` are rasterized box-relative on the host
    (data/loader.py::GT_MASK_SIZE): mask pixel (v, u) spans its gt box
    uniformly.  For a sampled roi that only overlaps its gt, the crop maps
    roi-grid centers into the gt box frame; points outside the box are
    background (0).  Replaces the host-side polygon rasterization inside
    Detectron-style loaders with an in-graph resample.

    Args: gt_masks (G, Hm, Wm); gt_boxes (G, 4); gt_idx (B,); rois (B, 4).
    Returns: (B, out_size, out_size) float32 in [0, 1].
    """
    hm, wm = gt_masks.shape[-2:]
    masks = jnp.take(gt_masks, gt_idx, axis=0)      # (B, Hm, Wm)
    boxes = jnp.take(gt_boxes, gt_idx, axis=0)      # (B, 4)

    def one(mask, box, roi):
        # +1: the host rasterizer (data/loader.py::_rasterize_mask) spreads
        # the mask grid over the inclusive-pixel box extent (x2-x1+1); the
        # inverse mapping here must use the same convention or targets
        # shrink toward the top-left by 1/(bw+1).
        bw = jnp.maximum(box[2] - box[0] + 1.0, 1e-3)
        bh = jnp.maximum(box[3] - box[1] + 1.0, 1e-3)
        ys = roi[1] + (jnp.arange(out_size) + 0.5) / out_size * (roi[3] - roi[1])
        xs = roi[0] + (jnp.arange(out_size) + 0.5) / out_size * (roi[2] - roi[0])
        v = (ys - box[1]) / bh * hm - 0.5            # mask pixel coords
        u = (xs - box[0]) / bw * wm - 0.5
        inside = ((v > -1.0) & (v < hm))[:, None] & ((u > -1.0) & (u < wm))[None, :]
        v = jnp.clip(v, 0.0, hm - 1.0)
        u = jnp.clip(u, 0.0, wm - 1.0)
        v0 = jnp.floor(v).astype(jnp.int32)
        u0 = jnp.floor(u).astype(jnp.int32)
        lv = v - v0
        lu = u - u0
        v1 = jnp.minimum(v0 + 1, hm - 1)
        u1 = jnp.minimum(u0 + 1, wm - 1)
        val = (
            mask[v0][:, u0] * (1 - lv)[:, None] * (1 - lu)[None, :]
            + mask[v0][:, u1] * (1 - lv)[:, None] * lu[None, :]
            + mask[v1][:, u0] * lv[:, None] * (1 - lu)[None, :]
            + mask[v1][:, u1] * lv[:, None] * lu[None, :]
        )
        return val * inside

    return jax.vmap(one)(masks, boxes, rois)


def _mask_loss(mask_logits, samples, gt_masks, gt_boxes, resolution: int):
    """Per-fg-roi binary CE on the matched-class mask channel.

    mask_logits: (B_rois, M, M, C); averaged over fg rois x pixels
    (Mask R-CNN: the loss is defined only on positives' own class channel).
    """
    with jax.named_scope("mask_loss"):
        return _mask_loss_impl(
            mask_logits, samples, gt_masks, gt_boxes, resolution
        )


def _mask_loss_impl(mask_logits, samples, gt_masks, gt_boxes, resolution: int):
    targets = crop_gt_masks(
        gt_masks, gt_boxes, samples.gt_indices, samples.rois, resolution
    )                                                    # (B, M, M)
    b = mask_logits.shape[0]
    own = mask_logits[jnp.arange(b), :, :, samples.labels]  # (B, M, M)
    own = own.astype(jnp.float32)
    per_pix = optax_sigmoid_ce(own, targets)
    w = (samples.fg_mask & (samples.label_weights > 0)).astype(jnp.float32)
    per_roi = per_pix.mean(axis=(1, 2))
    return jnp.sum(per_roi * w) / jnp.maximum(jnp.sum(w), 1.0)


def optax_sigmoid_ce(logits, labels):
    """Numerically-stable sigmoid cross-entropy (optax formulation)."""
    return jnp.maximum(logits, 0.0) - logits * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logits))
    )


# ---------------------------------------------------------------------------
# Public graphs


def prep_images(images: jnp.ndarray, pixel_stats=None) -> jnp.ndarray:
    """In-graph image normalization for uint8 batches.

    The reference normalizes on host (``rcnn/io/image.py::transform``) and
    ships float32 — 12 MB/image at the recipe canvas.  Shipping the uint8
    letterboxed pixels instead quarters host->device bytes and the
    device_prefetch HBM footprint; the (x - mean) / std here is one fused
    subtract/multiply XLA folds into the first conv's input, and it is the
    same float32 math either side of the transfer.  The arithmetic follows
    the native fused kernel's convention, (x - mean) * (1/std) with the
    reciprocal precomputed in float32 (native/src/native.cc inv_std) — the
    reciprocal is materialized HERE rather than left to XLA so the result
    is bit-identical to that host path by construction, not by hoping the
    compiler's divide-by-constant canonicalization rounds the same way (a
    jnp divide measured 1 ULP off the host value on XLA:CPU).  The numpy
    normalize_image divide can differ from either by 1 ULP per pixel.
    float32 inputs pass through unchanged (they arrive already
    normalized).  Padding behaves identically too: uint8 zeros normalize
    to (0 - mean) * (1/std), the value the native kernel pads with.
    """
    if images.dtype != jnp.uint8:
        return images
    if pixel_stats is None:
        raise ValueError(
            "uint8 Batch.images need pixel_stats=(mean, std) for in-graph "
            "normalization (pass cfg.data.pixel_mean / pixel_std)"
        )
    import numpy as np

    mean = np.asarray(pixel_stats[0], np.float32)
    inv_std = np.float32(1.0) / np.asarray(pixel_stats[1], np.float32)
    with jax.named_scope("prep_images"):
        return (
            images.astype(jnp.float32) - jnp.asarray(mean)
        ) * jnp.asarray(inv_std)


def init_detector(model: TwoStageDetector, rng: jax.Array, image_size, batch: int = 1):
    """Initialize all variables (params + frozen-BN constants)."""
    h, w = image_size
    dummy = jnp.zeros((batch, h, w, 3), jnp.float32)
    return model.init(rng, dummy)


def forward_train(model: TwoStageDetector, variables, rng: jax.Array, batch: Batch,
                  mesh=None, pixel_stats=None, rngs=None):
    """One full training forward pass -> (total_loss, metrics dict).

    Differentiable w.r.t. ``variables['params']``.  Equivalent of the
    reference's train symbol forward (SURVEY.md section 4.1 hot loop) with
    both CustomOp host syncs replaced by in-graph ops.  ``mesh``: >1-chip
    data mesh for the shard_map'd Pallas ROIAlign (see :func:`_pool_rois`).
    ``pixel_stats``: (mean, std) for uint8 batches (see :func:`prep_images`).

    ``rngs``: optional ``(assign_keys, sample_keys)`` per-image key arrays
    (each (B, 2), rows as produced by ``jax.random.split(..., B)``) that
    REPLACE the internal split of ``rng`` (then ignored; pass None).  The
    gradient-accumulation step uses this to hand each microbatch its slice
    of the keys a single big batch would derive, so microbatched and
    monolithic steps sample identical anchors/rois per image
    (parallel/step.py).  When omitted the split happens here exactly as it
    always has — the default trace is unchanged.
    """
    cfg = model.cfg
    images = prep_images(batch.images, pixel_stats)
    # ``counters``: what a backbone sows of its own routing (models/decoder.py);
    # empty for the convolutional ones, whose trace this leaves as it was.
    feats, sown = model.apply(variables, images, method="features", mutable=["counters"])

    b = images.shape[0]
    rng_assign = rng_sample = None
    if rngs is None:
        with jax.named_scope("rng"):
            rng_assign, rng_sample = jax.random.split(rng)

    # gt_ignore=None keeps the cheaper no-IoA graph (in_axes=None maps the
    # leafless None through vmap untouched; the callees skip the overlap
    # computation entirely).
    gt_ignore = batch.gt_ignore
    gi_axis = 0 if gt_ignore is not None else None

    use_ext = batch.ext_rois is not None
    if use_ext and batch.ext_valid is None:
        raise ValueError("Batch.ext_rois requires ext_valid (pad mask)")
    if use_ext and cfg.rpn.loss_weight == 0.0:
        # Fast R-CNN mode (reference ``rcnn/tools/train_rcnn.py``): the box
        # head trains on externally supplied proposals and the RPN never
        # enters the graph — no head apply, no anchor labeling, no losses.
        rpn_cls = rpn_box = rpn_acc = jnp.zeros((), jnp.float32)
    else:
        rpn_out = model.apply(variables, feats, method="rpn")
        anchors = level_anchors(cfg, feats)
        levels = sorted(rpn_out)
        logits_cat = jnp.concatenate([rpn_out[l][0] for l in levels], axis=1)
        deltas_cat = jnp.concatenate([rpn_out[l][1] for l in levels], axis=1)
        anchors_cat = jnp.concatenate([anchors[l] for l in levels], axis=0)

        with jax.named_scope("assign_anchors"):
            targets = jax.vmap(
                lambda k, gt, gv, gi, hw: assign_anchors_cfg(
                    cfg, k, anchors_cat, gt, gv, hw[0], hw[1], gt_ignore=gi
                ),
                in_axes=(0, 0, 0, gi_axis, 0),
            )(
                rngs[0] if rngs is not None else jax.random.split(rng_assign, b),
                batch.gt_boxes,
                batch.gt_valid,
                gt_ignore,
                batch.image_hw,
            )

        rpn_cls, rpn_box, rpn_acc = _rpn_losses(
            logits_cat, deltas_cat, targets, cfg.rpn.loss_impl
        )

    if use_ext:
        prop_rois, prop_valid = batch.ext_rois, batch.ext_valid
    else:
        # Proposals are detached: the reference never backprops through the
        # Proposal op either (CustomOp forward-only); gradients reach the
        # RPN exclusively through its losses.
        with jax.named_scope("proposals"):
            scores = jax.nn.sigmoid(lax.stop_gradient(logits_cat))
            deltas_sg = lax.stop_gradient(deltas_cat)
            propose = _propose_one(cfg, train=True)
            props = jax.vmap(
                lambda s_row, d_row, hw: propose(*_slice_levels(levels, anchors, s_row, d_row), hw)
            )(scores, deltas_sg, batch.image_hw)  # Proposals (B, R, ...)
        prop_rois, prop_valid = props.rois, props.valid

    with jax.named_scope("sample_rois"):
        samples = jax.vmap(
            lambda k, rois, rv, gt, gc, gv, gi: sample_rois(
                k, rois, rv, gt, gc, gv,
                batch_size=cfg.rcnn.roi_batch_size,
                fg_fraction=cfg.rcnn.fg_fraction,
                fg_iou=cfg.rcnn.fg_iou,
                bg_iou_hi=cfg.rcnn.bg_iou_hi,
                bg_iou_lo=cfg.rcnn.bg_iou_lo,
                bbox_weights=cfg.rcnn.bbox_weights,
                gt_ignore=gi,
                roi_block=cfg.rcnn.roi_block,
            ),
            in_axes=(0, 0, 0, 0, 0, 0, gi_axis),
        )(
            rngs[1] if rngs is not None else jax.random.split(rng_sample, b),
            prop_rois,
            prop_valid,
            batch.gt_boxes,
            batch.gt_classes.astype(jnp.int32),
            batch.gt_valid,
            gt_ignore,
        )

    pooled = _pool_rois(
        cfg, feats, samples.rois, cfg.rcnn.pooled_size, model.roi_levels,
        mesh=mesh,
    )
    s = cfg.rcnn.pooled_size
    pooled_flat = pooled.reshape(-1, s, s, pooled.shape[-1])
    cls_logits, box_deltas = model.apply(variables, pooled_flat, method="box")

    rcnn_cls, rcnn_box, rcnn_acc = _rcnn_losses(
        cls_logits, box_deltas, samples, cfg.rcnn.class_agnostic
    )

    with jax.named_scope("total_loss"):
        total = (
            cfg.rpn.loss_weight * (rpn_cls + rpn_box)
            + cfg.rcnn.loss_weight * (rcnn_cls + rcnn_box)
        )
    metrics = {
        # Names mirror the reference's six EvalMetrics (rcnn/core/metric.py).
        "RPNAcc": rpn_acc,
        "RPNLogLoss": rpn_cls,
        "RPNL1Loss": rpn_box,
        "RCNNAcc": rcnn_acc,
        "RCNNLogLoss": rcnn_cls,
        "RCNNL1Loss": rcnn_box,
        "loss": total,
    }
    for name, (value,) in sorted(sown.get("counters", {}).get("backbone", {}).items()):
        metrics[name] = value

    if cfg.mask.enabled and batch.gt_masks is not None:
        # sample_rois compacts fg into a leading block, so the static fg
        # quota prefix contains every positive — the mask branch only needs
        # those rows (4x fewer rois at the default 0.25 fg fraction).
        n_fg = max(int(cfg.rcnn.roi_batch_size * cfg.rcnn.fg_fraction), 1)
        fg = jax.tree_util.tree_map(lambda x: x[:, :n_fg], samples)
        sm = cfg.mask.pooled_size
        pooled_m = _pool_rois(cfg, feats, fg.rois, sm, model.roi_levels, mesh=mesh)
        m_logits = model.apply(
            variables, pooled_m.reshape(-1, sm, sm, pooled_m.shape[-1]),
            method="mask",
        )                                                  # (B*n_fg, M, M, C)
        m_logits = m_logits.reshape(b, -1, *m_logits.shape[1:])
        mask_loss = jnp.mean(
            jax.vmap(
                lambda ml, sm_, gm, gb: _mask_loss(
                    ml, sm_, gm, gb, cfg.mask.resolution
                )
            )(m_logits, fg, batch.gt_masks, batch.gt_boxes)
        )
        total = total + cfg.mask.loss_weight * mask_loss
        metrics["MaskLogLoss"] = mask_loss
        metrics["loss"] = total

    return total, metrics


def assign_anchors_cfg(cfg: ModelConfig, key, anchors, gt, gv, h, w, gt_ignore=None):
    return assign_anchors(
        key, anchors, gt, gv, h, w,
        batch_size=cfg.rpn.batch_size,
        fg_fraction=cfg.rpn.fg_fraction,
        positive_iou=cfg.rpn.positive_iou,
        negative_iou=cfg.rpn.negative_iou,
        allowed_border=cfg.rpn.allowed_border,
        gt_ignore=gt_ignore,
        assign_block=cfg.rpn.assign_block,
        topk_block=cfg.rpn.topk_block,
    )


def forward_inference(model: TwoStageDetector, variables, batch: Batch,
                      mesh=None, pixel_stats=None,
                      box_head_apply=None) -> Detections:
    """Full inference: proposals -> box head -> per-class NMS -> top-D.

    Replaces ``rcnn/core/tester.py::im_detect`` + the per-class python NMS
    loop in ``pred_eval`` with one jitted region; detections come back
    padded to ``cfg.test.max_detections`` with a validity mask.  ``mesh``/
    ``pixel_stats``: see :func:`forward_train`.

    ``box_head_apply``: optional drop-in for the box-head apply —
    ``f(pooled_flat) -> (cls_logits (R, C), box_deltas (R, n_reg, 4))``,
    the exact :class:`~mx_rcnn_tpu.models.heads.BoxHead` contract.  The
    int8/bf16 serving program (serve/quantize.py) injects here; the rest
    of the graph (backbone, RPN, pooling, postprocess) is shared.
    """
    cfg = model.cfg
    feats = model.apply(
        variables, prep_images(batch.images, pixel_stats), method="features"
    )
    if batch.ext_rois is not None:
        # Fast R-CNN test mode (reference ``test_rcnn --has_rpn false``):
        # score externally supplied proposals; the RPN never runs.
        if batch.ext_valid is None:
            raise ValueError("Batch.ext_rois requires ext_valid (pad mask)")
        props = Proposals(
            rois=batch.ext_rois,
            scores=jnp.zeros(batch.ext_valid.shape, jnp.float32),
            valid=batch.ext_valid,
        )
    else:
        props = _propose_on_features(model, variables, feats, batch)

    pooled = _pool_rois(
        cfg, feats, props.rois, cfg.rcnn.pooled_size, model.roi_levels,
        mesh=mesh,
    )
    s = cfg.rcnn.pooled_size
    pooled_flat = pooled.reshape(-1, s, s, pooled.shape[-1])
    if box_head_apply is None:
        cls_logits, box_deltas = model.apply(
            variables, pooled_flat, method="box"
        )
    else:
        cls_logits, box_deltas = box_head_apply(pooled_flat)

    b, r = props.rois.shape[:2]
    num_classes = cfg.num_classes
    # Scores and box coordinates stay f32 through postprocess regardless
    # of the head's output dtype: the softmax/decode operands here are
    # (B*R, C)-sized — trivial next to the backbone — and f32 scores keep
    # ranking/threshold behavior identical across precision policies.
    cls_prob = jax.nn.softmax(
        cls_logits.astype(jnp.float32), axis=-1
    ).reshape(b, r, num_classes)
    box_deltas = box_deltas.astype(jnp.float32).reshape(b, r, -1, 4)

    if cfg.test.nms_mode == "fused":
        post_one = _postprocess_one_fused
    elif cfg.test.nms_mode == "per_class":
        post_one = _postprocess_one
    else:
        raise ValueError(
            f"test.nms_mode must be 'per_class' or 'fused', "
            f"got {cfg.test.nms_mode!r}"
        )
    post = jax.vmap(
        lambda rois, rv, probs, deltas, hw: post_one(
            cfg, rois, rv, probs, deltas, hw
        )
    )(props.rois, props.valid, cls_prob, box_deltas, batch.image_hw)
    dets = Detections(*post)

    if cfg.mask.enabled:
        # Mask branch on the final detections (Mask R-CNN inference order:
        # boxes first, then one mask crop per kept detection).
        sm = cfg.mask.pooled_size
        pooled_m = _pool_rois(cfg, feats, dets.boxes, sm, model.roi_levels,
                              mesh=mesh)
        m_logits = model.apply(
            variables, pooled_m.reshape(-1, sm, sm, pooled_m.shape[-1]),
            method="mask",
        )                                                  # (B*D, M, M, C)
        d = dets.boxes.shape[1]
        cls_flat = dets.classes.reshape(-1)
        own = m_logits[jnp.arange(m_logits.shape[0]), :, :, cls_flat]
        probs_m = jax.nn.sigmoid(own.astype(jnp.float32))
        dets = dets._replace(masks=probs_m.reshape(b, d, *own.shape[1:]))
    return dets


def _propose_on_features(model, variables, feats, batch: Batch) -> Proposals:
    """Shared RPN->proposal front-end of inference and the RPN-dump path."""
    cfg = model.cfg
    rpn_out = model.apply(variables, feats, method="rpn")
    anchors = level_anchors(cfg, feats)
    levels = sorted(rpn_out)
    logits_cat = jnp.concatenate([rpn_out[l][0] for l in levels], axis=1)
    deltas_cat = jnp.concatenate([rpn_out[l][1] for l in levels], axis=1)
    scores = jax.nn.sigmoid(logits_cat)
    propose = _propose_one(cfg, train=False)
    return jax.vmap(
        lambda s_row, d_row, hw: propose(*_slice_levels(levels, anchors, s_row, d_row), hw)
    )(scores, deltas_cat, batch.image_hw)


def forward_proposals(model: TwoStageDetector, variables, batch: Batch,
                      pixel_stats=None) -> Proposals:
    """RPN-only inference: backbone -> RPN -> proposal generation.

    Replaces ``rcnn/core/tester.py::generate_proposals`` (used by
    ``rcnn/tools/test_rpn.py`` to dump the proposal pkl between alternate
    training phases).  Returns padded Proposals (rois, scores, valid) in
    input-image coordinates.
    """
    feats = model.apply(
        variables, prep_images(batch.images, pixel_stats), method="features"
    )
    props = _propose_on_features(model, variables, feats, batch)
    # Proposal scores cross into host numpy on the serving/RPN-dump paths;
    # emit f32 however the head computed them ((B, post_nms) — tiny).
    return props._replace(scores=props.scores.astype(jnp.float32))


def _postprocess_one(cfg: ModelConfig, rois, roi_valid, probs, deltas, hw):
    """Per-image postprocess: decode per class, threshold, per-class NMS,
    global top-D.  All static shapes: (R rois) x (C-1 fg classes)."""
    num_classes = cfg.num_classes
    r = rois.shape[0]
    d_out = cfg.test.max_detections
    per_class_k = min(r, max(2 * d_out, 100))

    def one_class(c):
        delta_c = deltas[:, 0, :] if cfg.rcnn.class_agnostic else deltas[:, c, :]
        boxes = decode_boxes(delta_c, rois, weights=cfg.rcnn.bbox_weights)
        boxes = clip_boxes(boxes, hw[0], hw[1])
        sc = jnp.where(
            roi_valid & (probs[:, c] >= cfg.test.score_threshold),
            probs[:, c],
            -jnp.inf,
        )
        top_s, top_i = lax.top_k(sc, per_class_k)
        top_b = jnp.take(boxes, top_i, axis=0)
        keep_i, keep_v = nms_indices(
            top_b, top_s, cfg.test.nms_threshold, per_class_k
        )
        out_b = jnp.take(top_b, keep_i, axis=0)
        out_s = jnp.where(keep_v, jnp.take(top_s, keep_i), -jnp.inf)
        return out_b, out_s

    # vmap over foreground classes (1..C-1).
    cls_ids = jnp.arange(1, num_classes)
    all_b, all_s = jax.vmap(one_class)(cls_ids)        # (C-1, K, 4), (C-1, K)
    flat_b = all_b.reshape(-1, 4)
    flat_s = all_s.reshape(-1)
    flat_c = jnp.repeat(cls_ids, per_class_k)

    top_s, top_i = lax.top_k(flat_s, d_out)
    valid = jnp.isfinite(top_s)
    return (
        jnp.take(flat_b, top_i, axis=0) * valid[:, None],
        jnp.where(valid, top_s, 0.0),
        jnp.where(valid, jnp.take(flat_c, top_i), 0).astype(jnp.int32),
        valid,
    )


def _postprocess_one_fused(cfg: ModelConfig, rois, roi_valid, probs, deltas, hw):
    """Fused postprocess: global top-K candidates, ONE class-offset NMS.

    Same decode/threshold/suppression math as :func:`_postprocess_one`,
    restructured for the TPU: instead of C-1 per-class passes (each a
    top-k plus an NMS fixed point that vmap runs until the slowest class
    converges), score-rank ALL (roi, class) pairs once, keep the top
    ``cfg.test.fused_top_k``, decode only those, and suppress with one
    ``batched_nms`` (boxes translated to per-class disjoint regions, so
    one pass equals independent per-class NMS).  Equal output whenever no
    per-class/global candidate cap binds — the caps are the only
    semantic difference, and both are far above the reference's
    max-100-detections regime.
    """
    num_classes = cfg.num_classes
    r = rois.shape[0]
    d_out = cfg.test.max_detections
    fg = num_classes - 1
    k = min(r * fg, cfg.test.fused_top_k)

    sc = jnp.where(
        roi_valid[:, None] & (probs[:, 1:] >= cfg.test.score_threshold),
        probs[:, 1:],
        -jnp.inf,
    )                                                   # (R, C-1)
    top_s, top_i = lax.top_k(sc.reshape(-1), k)         # flat id = roi*fg + (c-1)
    roi_i = top_i // fg
    cls = top_i % fg + 1                                # 1-based fg class

    cand_rois = jnp.take(rois, roi_i, axis=0)
    if cfg.rcnn.class_agnostic:
        delta_sel = deltas[roi_i, 0, :]
    else:
        delta_sel = deltas[roi_i, cls, :]
    boxes = decode_boxes(delta_sel, cand_rois, weights=cfg.rcnn.bbox_weights)
    boxes = clip_boxes(boxes, hw[0], hw[1])

    cand_valid = jnp.isfinite(top_s)
    keep = batched_nms(
        boxes, top_s, cls, cfg.test.nms_threshold, valid=cand_valid
    )
    kept_s = jnp.where(keep, top_s, -jnp.inf)
    out_s, out_i = lax.top_k(kept_s, min(d_out, k))
    if k < d_out:
        pad = d_out - k
        out_s = jnp.concatenate([out_s, jnp.full(pad, -jnp.inf, out_s.dtype)])
        out_i = jnp.concatenate([out_i, jnp.zeros(pad, out_i.dtype)])
    valid = jnp.isfinite(out_s)
    return (
        jnp.take(boxes, out_i, axis=0) * valid[:, None],
        jnp.where(valid, out_s, 0.0),
        jnp.where(valid, jnp.take(cls, out_i), 0).astype(jnp.int32),
        valid,
    )
