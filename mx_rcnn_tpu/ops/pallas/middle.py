"""Pallas TPU fused proposal middle: decode -> clip -> snap -> NMS in VMEM.

The proposal "middle" — everything between the RPN head's raw outputs and
the ranked roi set — historically ran as a string of small XLA programs
(``ops/proposals.py`` decode/clip, ``geometry/boxes.py`` snapping,
``ops/nms.py`` suppression), each round-tripping its (k, 4)/(k,) operands
through HBM.  This kernel keeps the per-level candidate tiles VMEM-resident
across the whole chain: one launch per proposal call (grid over FPN
levels) reads the gathered (anchors, deltas, scores) rows and writes
decoded/clipped/snapped boxes, masked scores, and the greedy-NMS keep mask.

Exactness contract (asserted bitwise in tests/test_fused_middle.py):

- Decode/clip replicate ``geometry.boxes.decode_boxes``/``clip_boxes`` to
  the operation (weights (1,1,1,1), modern width convention, the same
  ``BBOX_XFORM_CLIP`` bound), and the results ride the same 1/256-px
  coordinate snap the dense path applies — so the few ulps any backend
  reassociation could introduce round away exactly as they do there.
- IoU uses ``geometry.boxes.iou_matrix``'s formula (clamped areas,
  zero-union guard) snapped on the 2**-16 grid before the threshold
  compare, matching ``ops/nms.py::nms_mask``.
- NMS runs greedily in POSITIONAL order.  That equals the oracle's
  argsort order bit-for-bit because the kernel's inputs come from top-k:
  scores are positionally descending with index-ascending tie-breaks, so
  the oracle's stable ``argsort(-scores)`` is the identity on valid lanes,
  and ``-inf`` lanes (min-size-rejected or padding) neither keep nor
  suppress under either order.

The top-k front half stays in XLA (``ops/topk.py``'s blocked reduction is
already one fused program) — the kernel takes over exactly where the HBM
round-trips began.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.geometry.boxes import BBOX_XFORM_CLIP


def _snap(x, bits: int):
    """In-kernel twin of geometry.boxes.snap (power-of-two grid round)."""
    scale = 2.0 ** bits
    return jnp.round(x * scale) * (1.0 / scale)


def _middle_kernel(data_ref, hw_ref, out_ref, *, n: int,
                   min_size: float, thresh: float):
    # data rows: 0-3 anchors (x1, y1, x2, y2); 4-7 deltas (dx, dy, dw, dh);
    # 8 snapped top-k scores; 9-15 zero pad.  Everything (1, N) f32.
    ax1 = data_ref[0, 0:1, :]
    ay1 = data_ref[0, 1:2, :]
    ax2 = data_ref[0, 2:3, :]
    ay2 = data_ref[0, 3:4, :]
    d_x = data_ref[0, 4:5, :]
    d_y = data_ref[0, 5:6, :]
    d_w = data_ref[0, 6:7, :]
    d_h = data_ref[0, 7:8, :]
    score = data_ref[0, 8:9, :]
    img_h = hw_ref[0, 0]
    img_w = hw_ref[0, 1]

    # decode_boxes (weights (1,1,1,1), modern convention).
    aw = ax2 - ax1
    ah = ay2 - ay1
    acx = ax1 + 0.5 * aw
    acy = ay1 + 0.5 * ah
    dw = jnp.minimum(d_w, BBOX_XFORM_CLIP)
    dh = jnp.minimum(d_h, BBOX_XFORM_CLIP)
    cx = d_x * aw + acx
    cy = d_y * ah + acy
    bw = jnp.exp(dw) * aw
    bh = jnp.exp(dh) * ah
    x1 = cx - 0.5 * bw
    y1 = cy - 0.5 * bh
    x2 = cx + 0.5 * bw
    y2 = cy + 0.5 * bh

    # clip_boxes + the dense path's 1/256-px coordinate snap.
    x1 = _snap(jnp.clip(x1, 0.0, img_w), 8)
    y1 = _snap(jnp.clip(y1, 0.0, img_h), 8)
    x2 = _snap(jnp.clip(x2, 0.0, img_w), 8)
    y2 = _snap(jnp.clip(y2, 0.0, img_h), 8)

    # valid_box_mask + score masking (ops/proposals.py::_pre_nms_candidates).
    w = x2 - x1
    h = y2 - y1
    if min_size <= 0.0:
        ok = (w > 0.0) & (h > 0.0)
    else:
        ok = (w >= min_size) & (h >= min_size)
    masked = jnp.where(ok, score, -jnp.inf)
    valid = ok & jnp.isfinite(score)

    # Greedy NMS in positional (= score) order; same recurrence as
    # ops/pallas/nms.py::_nms_kernel.  Scalars come out by masked
    # reduction (no dynamic lane extraction in Mosaic); alive is f32
    # 1.0/0.0 (i1 carries don't legalize through scf.for).
    area = jnp.maximum(w, 0.0) * jnp.maximum(h, 0.0)
    col = lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def body(i, alive):
        sel = (col == i).astype(jnp.float32)
        bx1 = jnp.sum(x1 * sel)
        by1 = jnp.sum(y1 * sel)
        bx2 = jnp.sum(x2 * sel)
        by2 = jnp.sum(y2 * sel)
        b_area = jnp.sum(area * sel)
        ai = jnp.sum(alive * sel)

        iw = jnp.maximum(jnp.minimum(x2, bx2) - jnp.maximum(x1, bx1), 0.0)
        ih = jnp.maximum(jnp.minimum(y2, by2) - jnp.maximum(y1, by1), 0.0)
        inter = iw * ih
        union = area + b_area - inter
        iou = jnp.where(
            union > 0.0, inter / jnp.where(union > 0.0, union, 1.0), 0.0
        )
        # The oracle compares snap(iou) > thresh — identical grid here.
        iou = _snap(iou, 16)
        suppress = jnp.where((iou > thresh) & (col > i), ai, 0.0)
        return alive * (1.0 - suppress)

    alive = lax.fori_loop(0, n, body, valid.astype(jnp.float32))

    out_ref[0, 0:1, :] = x1
    out_ref[0, 1:2, :] = y1
    out_ref[0, 2:3, :] = x2
    out_ref[0, 3:4, :] = y2
    out_ref[0, 4:5, :] = masked
    out_ref[0, 5:6, :] = alive
    out_ref[0, 6:7, :] = jnp.zeros((1, n), jnp.float32)
    out_ref[0, 7:8, :] = jnp.zeros((1, n), jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("min_size", "iou_threshold", "interpret"),
)
def fused_middle_levels(
    anchors: jnp.ndarray,
    deltas: jnp.ndarray,
    scores: jnp.ndarray,
    image_height,
    image_width,
    min_size: float = 0.0,
    iou_threshold: float = 0.7,
    interpret: bool = False,
):
    """Run the fused middle over stacked per-level top-k candidates.

    Args:
      anchors: (L, k, 4) gathered anchor boxes in top-k score order
        (zero rows on lanes past a level's true k).
      deltas: (L, k, 4) gathered RPN deltas (zero rows on pad lanes).
      scores: (L, k) snapped top-k scores, ``-inf`` on pad lanes.
      image_height / image_width: true image extent (may be traced).
      min_size / iou_threshold: RPNConfig.min_size / nms_threshold.
      interpret: run the kernel in interpret mode (CPU CI).

    Returns:
      (boxes (L, k, 4), masked_scores (L, k), keep (L, k) bool) — the
      decoded/clipped/snapped candidates, their ``-inf``-masked scores,
      and the greedy-NMS keep mask, each bit-identical to the dense path
      through ``_pre_nms_candidates`` + ``nms_mask``.
    """
    lvls, k = scores.shape
    n_pad = -(-k // 128) * 128
    pad = n_pad - k
    if pad:
        anchors = jnp.pad(anchors, ((0, 0), (0, pad), (0, 0)))
        deltas = jnp.pad(deltas, ((0, 0), (0, pad), (0, 0)))
        scores = jnp.pad(scores, ((0, 0), (0, pad)),
                         constant_values=-jnp.inf)

    # (L, 16, N): anchor rows, delta rows, score row, zero pad rows —
    # one contiguous VMEM block per level.
    data = jnp.concatenate(
        [
            jnp.swapaxes(anchors, 1, 2),                    # (L, 4, N)
            jnp.swapaxes(deltas, 1, 2),                     # (L, 4, N)
            scores[:, None, :],                             # (L, 1, N)
            jnp.zeros((lvls, 7, n_pad), jnp.float32),       # (L, 7, N)
        ],
        axis=1,
    ).astype(jnp.float32)
    hw = jnp.stack(
        [jnp.asarray(image_height, jnp.float32),
         jnp.asarray(image_width, jnp.float32)]
    ).reshape(1, 2)

    out = pl.pallas_call(
        functools.partial(
            _middle_kernel,
            n=n_pad,
            # Static kwargs (static_argnames above) — plain Python floats
            # at trace time, never tracers.
            min_size=min_size,
            thresh=iou_threshold,
        ),
        grid=(lvls,),
        in_specs=[
            pl.BlockSpec((1, 16, n_pad), lambda l: (l, 0, 0)),
            pl.BlockSpec((1, 2), lambda l: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 8, n_pad), lambda l: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((lvls, 8, n_pad), jnp.float32),
        interpret=interpret,
        name="fused_middle",
    )(data, hw)

    boxes = jnp.swapaxes(out[:, 0:4, :k], 1, 2)             # (L, k, 4)
    masked_scores = out[:, 4, :k]                           # (L, k)
    keep = out[:, 5, :k] > 0.0                              # (L, k)
    return boxes, masked_scores, keep
