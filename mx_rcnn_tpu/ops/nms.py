"""Static-shape non-maximum suppression, fully in-graph.

TPU-native replacement for the reference's three NMS backends
(``rcnn/processing/nms.py``: py_nms / cpu_nms / gpu_nms and the CUDA
bitmask kernel ``rcnn/cython/nms_kernel.cu``).  The reference runs NMS on
the host (or a CUDA kernel) with a device round-trip inside the Proposal
custom op; here NMS stays inside the jitted step.

Algorithm: exact greedy NMS, tile by tile in score order, with no N x N
array anywhere.  Sort by score (stable), cut the candidates into
``ceil(N / TILE)`` tiles and take them in order.  For a tile:

1. *Across tiles.*  Every suppressor outside the tile has a better score,
   sits in an earlier tile and is final already:

       alive[i] = valid[i] and not OR_j (keep[j] and iou[j, i] > thresh)

   over the boxes ``j`` of the tiles done, a TILE x TILE block of pairs at
   a time — a compare-and-reduce whose IoU block is made inside the
   reduction's fusion and never written out.  Only blocks above the
   diagonal exist: half the pair arithmetic of a full matrix.
2. *Inside the tile.*  On the TILE x TILE block (strictly upper triangular:
   an earlier box can suppress a later one) iterate

       keep[i] <- alive[i] and not OR_{j<i} (keep[j] and iou[j, i] > thresh)

   to a fixed point with ``lax.while_loop``.  Any fixed point of this map is
   exactly the greedy-NMS solution of the tile given ``alive`` (induction
   over i), the iteration finalizes at least one undecided box per sweep,
   and by (1) everything outside the tile that could suppress box i was
   final before the tile started — so the tiles' results, in order, are the
   greedy solution of the whole set, bit for bit what the dense form gives.

N <= TILE is one tile: the plain fixed point on the whole matrix, which is
what per-class ``batched_nms`` over a few hundred detections and every
small shape run.

Why tiles (TPU v5e, PERF.md sections 5-6, PR 29).  The form this replaced
built the whole 6000 x 6000 IoU matrix in float32 and a boolean mask per
image and swept the mask whole: on the benchmark's VGG-16 step (16 images,
6000 -> 2000) that was 39.6 ms of a 174 ms step (ledger, PR 27) — 7 ms
writing the matrix, 7 the mask, and 28-30 sweeps of 0.77 ms each, every one
a read of 576 MB at 91 % of the chip's memory bandwidth, paid by all sixteen
images until the slowest converged.  What NMS needs from memory is the boxes
(0.5 MB).  Here a tile's block (4 MB for sixteen images) stays in VMEM
through its sweeps, ~140 of 7 us a step; the same 16 x 6000 candidates take
6.2 ms standalone against 35.3 (tests/_kernels_tpu_worker.py).

Why the tile loop is a ``fori_loop`` over row blocks and not unrolled.  Both
take the same time (5.7 ms a call).  Unrolled, with the tile's block stored,
the cross-tile reduction came out WRONG on the chip under two nested
``vmap``s — images x pyramid levels, how every FPN preset reaches this —
while one ``vmap`` and the CPU agreed with the oracle bit for bit: 10,509
of 40,000 keep bits at 8 x 5 x 1000, whichever way the block was stored
(``optimization_barrier``, a concatenate, bit-packed words).  The loop form
is right on every shape tried, flat and nested, and the worker's probe holds
it there.  Timed beside them on the same candidates: tiles of 256 and 1024
(6.5 ms each against 6.1 at 512), one global fixed point over a bit-packed
mask (9.5), the tile's sweep as an MXU product (5.8).  The sequential
alternatives lost long before: a 64-box blocked-greedy ``lax.scan`` needs
~2N/64 tiny steps, a one-box-at-a-time Pallas sweep N of them (9.7 ms
against 2.3 an image at N = 2000; deleted in PR 30).  The dense form lives
on as the oracle in ``tests/oracles.py::nms_mask_dense``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from mx_rcnn_tpu.geometry import area, snap


# Candidates per tile.  Read against the static N: N <= TILE is one tile, the
# plain fixed point on the whole (small) matrix.  Picked on the chip at
# 16 x 6000 and 40 x 2000 (PERF.md section 6, PR 29); a program constant
# like ``ops/kda.py::CHUNK``, not a setting.
TILE = 512


def _overlaps(rows: jnp.ndarray, cols: jnp.ndarray, iou_threshold: float):
    """(R, C) bool: does box ``rows[j]`` overlap box ``cols[i]`` past the
    threshold.  Made to be consumed inside a reduction's fusion, never stored
    at R x C for a large R.

    ``geometry.iou_matrix``'s arithmetic to the letter, one coordinate at a
    time: its ``(R, C, 2)`` corner arrays are what XLA wrote out to HBM
    (a trailing axis of 2 fuses into nothing on this chip).
    """
    rx1, ry1, rx2, ry2 = (rows[:, c, None] for c in range(4))
    cx1, cy1, cx2, cy2 = (cols[None, :, c] for c in range(4))
    w = jnp.maximum(jnp.minimum(rx2, cx2) - jnp.maximum(rx1, cx1), 0.0)
    h = jnp.maximum(jnp.minimum(ry2, cy2) - jnp.maximum(ry1, cy1), 0.0)
    inter = w * h
    union = area(rows)[:, None] + area(cols)[None, :] - inter
    iou = jnp.where(union > 0.0, inter / jnp.where(union > 0.0, union, 1.0), 0.0)
    # snap(): the > threshold suppression decision must not flip on
    # cross-compilation ulp noise (see geometry.boxes.snap); one flipped
    # suppression cascades through the whole greedy chain.
    return snap(iou) > iou_threshold


def _tile_fixed_point(alive, suppress):
    """``keep[i] <- alive[i] & ~any_j(keep[j] & suppress[j, i])`` iterated
    from ``keep = alive`` until it stops changing."""

    def cond(state):
        keep, prev = state
        return jnp.any(keep != prev)

    def body(state):
        keep, _ = state
        # One sweep = one run of this scope's ops: the trace shows a loop
        # body's ops once per iteration, so sweeps are countable per step
        # (perfbench/metrics/nms_sweeps.train.py).
        with jax.named_scope("nms_sweep"):
            new_keep = alive & ~jnp.any(suppress & keep[:, None], axis=0)
        return new_keep, keep

    keep, _ = lax.while_loop(cond, body, (alive, jnp.zeros_like(alive)))
    return keep


def nms_mask(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Greedy NMS as a boolean keep-mask in *input* order.

    Args:
      boxes: (N, 4).
      scores: (N,) — padded/invalid entries should carry ``-inf`` or use
        ``valid``.
      iou_threshold: suppression threshold (reference default 0.7 for RPN
        proposals, 0.3 at test time).
      valid: optional (N,) bool; invalid entries never keep nor suppress.

    Returns:
      (N,) bool keep mask.
    """
    n = boxes.shape[0]
    if valid is None:
        valid = jnp.isfinite(scores)
    else:
        valid = valid & jnp.isfinite(scores)

    order = jnp.argsort(-scores)  # descending; stable for ties
    sboxes = jnp.take(boxes, order, axis=0)
    svalid = jnp.take(valid, order)

    tile = min(n, TILE)
    upper = jnp.triu(jnp.ones((tile, tile), dtype=bool), k=1)
    if n <= TILE:  # one tile: the plain fixed point on the whole matrix
        keep_sorted = _tile_fixed_point(
            svalid, _overlaps(sboxes, sboxes, iou_threshold) & upper
        )
        return jnp.zeros(n, dtype=bool).at[order].set(keep_sorted)

    tiles = -(-n // tile)
    pad = tiles * tile - n  # the tail's padding is invalid: never keeps nor suppresses
    tboxes = jnp.pad(sboxes, ((0, pad), (0, 0))).reshape(tiles, tile, 4)
    tvalid = jnp.pad(svalid, (0, pad)).reshape(tiles, tile)

    def one_tile(k, kept):
        """``kept`` (tiles, tile): final for the tiles before ``k``, False after."""
        cols = tboxes[k]

        def rows_of(j, dead):
            # Tile j < k is final: its survivors against this tile's boxes,
            # the block made inside the reduction and never stored.
            with jax.named_scope("nms_cross"):
                hit = _overlaps(tboxes[j], cols, iou_threshold) & kept[j][:, None]
                return dead | jnp.any(hit, axis=0)

        dead = lax.fori_loop(0, k, rows_of, jnp.zeros(tile, dtype=bool))
        # The barrier makes the block a stored operand of the loop (4 MB for
        # 16 images, which XLA then keeps in VMEM); without it XLA sinks the
        # IoU arithmetic into the loop body and pays it every sweep (8.8 ms
        # against 5.7 at 16 x 6000, PERF.md section 6, PR 29).
        suppress = lax.optimization_barrier(
            _overlaps(cols, cols, iou_threshold) & upper
        )
        keep_k = _tile_fixed_point(tvalid[k] & ~dead, suppress)
        return kept.at[k].set(keep_k)

    kept = lax.fori_loop(
        0, tiles, one_tile, jnp.zeros((tiles, tile), dtype=bool)
    )
    return jnp.zeros(n, dtype=bool).at[order].set(kept.reshape(-1)[:n])


def rank_keep(keep: jnp.ndarray, scores: jnp.ndarray, max_outputs: int):
    """Rank a keep mask by score into up to ``max_outputs`` indices.

    The back half of :func:`nms_indices`: kept entries first, best score
    first, padded slots index 0 with ``out_valid`` False.
    """
    n = keep.shape[0]
    neg = jnp.where(keep, -scores, jnp.inf)
    order = jnp.argsort(neg)  # kept entries first, best score first
    k = min(n, max_outputs)
    idx = order[:k]
    kept = jnp.take(keep, idx)
    if k < max_outputs:
        pad = max_outputs - k
        idx = jnp.concatenate([idx, jnp.zeros(pad, idx.dtype)])
        kept = jnp.concatenate([kept, jnp.zeros(pad, bool)])
    out_valid = kept & (jnp.arange(max_outputs) < jnp.sum(keep))
    return jnp.where(out_valid, idx, 0), out_valid


@partial(jax.jit, static_argnums=(2, 3))
def nms_indices(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    max_outputs: int,
    valid: jnp.ndarray | None = None,
):
    """NMS returning up to ``max_outputs`` kept indices, score-descending.

    Static output shape: ``(indices (max_outputs,), out_valid (max_outputs,))``.
    Padded slots hold index 0 with ``out_valid`` False — the static-shape
    replacement for the reference Proposal op's pad-with-repeats
    (``rcnn/symbol/proposal.py`` pads rois to RPN_POST_NMS_TOP_N).
    """
    return rank_keep(
        nms_mask(boxes, scores, iou_threshold, valid), scores, max_outputs
    )


def batched_nms(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    classes: jnp.ndarray,
    iou_threshold: float,
    valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Per-class NMS in one shot via the coordinate-offset trick.

    Boxes of different classes are translated to disjoint regions so they
    can never overlap; one NMS pass then equals independent per-class NMS.
    Replaces the reference's per-class python loop in
    ``rcnn/core/tester.py::pred_eval``.
    """
    span = jnp.max(boxes) - jnp.min(boxes) + 1.0
    offset = classes.astype(boxes.dtype)[:, None] * span
    return nms_mask(boxes + offset, scores, iou_threshold, valid)
