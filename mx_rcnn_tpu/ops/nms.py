"""Static-shape non-maximum suppression, fully in-graph.

TPU-native replacement for the reference's three NMS backends
(``rcnn/processing/nms.py``: py_nms / cpu_nms / gpu_nms and the CUDA
bitmask kernel ``rcnn/cython/nms_kernel.cu``).  The reference runs NMS on
the host (or a CUDA kernel) with a device round-trip inside the Proposal
custom op; here NMS stays inside the jitted step.

Algorithm: score-sort, build the O(N^2) IoU "suppression" matrix (strictly
upper-triangular: an earlier box can suppress a later one), then iterate

    keep[i] <- not OR_{j<i} (keep[j] AND iou[j, i] > thresh)

to a fixed point with ``lax.while_loop``.  Any fixed point of this map is
exactly the greedy-NMS solution (induction over i), and the iteration
finalizes at least one undecided box per sweep, so it terminates in at most
N sweeps — in practice a handful, each an O(N^2) VPU-friendly masked
reduction, with no host sync and no dynamic shapes.

Measured alternative (v5e, honest chained timing): a Detectron-style
64-box blocked-greedy lax.scan has a FIXED O(N^2/B) cost, but its ~2N/B
sequential tiny steps serialize poorly on TPU — 116 ms vs this
implementation's 91 ms even on the adversarial case (2000 iid random
boxes, where the sweep count is worst-case), and it loses ~0.8 img/s on
the full train-step bench (where RPN's score-sorted boxes converge in a
few sweeps).  The data-dependent sweep count is the better trade here.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from mx_rcnn_tpu.geometry import iou_matrix, snap


def nms_mask(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    valid: jnp.ndarray | None = None,
    sweep_cap: int = 0,
) -> jnp.ndarray:
    """Greedy NMS as a boolean keep-mask in *input* order.

    Args:
      boxes: (N, 4).
      scores: (N,) — padded/invalid entries should carry ``-inf`` or use
        ``valid``.
      iou_threshold: suppression threshold (reference default 0.7 for RPN
        proposals, 0.3 at test time).
      valid: optional (N,) bool; invalid entries never keep nor suppress.
      sweep_cap: 0 (default) iterates the fixed point to convergence —
        exact greedy NMS.  > 0 bounds the while_loop to that many sweeps:
        each sweep finalizes at least one undecided box, so any cap >= N
        is still exact, and score-sorted RPN boxes converge in a handful
        of sweeps regardless; a small cap trades exactness on adversarial
        inputs for a hard latency bound (the batched per-level lane then
        pays a bounded worst case instead of the slowest lane's
        data-dependent sweep count).  Opt-in via ``RPNConfig.nms_sweep_cap``.

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

    # snap(): the > threshold suppression decision must not flip on
    # cross-compilation ulp noise (see geometry.boxes.snap); one flipped
    # suppression cascades through the whole greedy chain.
    iou = snap(iou_matrix(sboxes, sboxes))
    upper = jnp.triu(jnp.ones((n, n), dtype=bool), k=1)
    suppress = (iou > iou_threshold) & upper & svalid[:, None] & svalid[None, :]

    if sweep_cap and sweep_cap > 0:
        # Bounded variant: identical iteration, with a sweep counter in
        # the carry.  Convergence before the cap gives the exact greedy
        # fixed point; hitting the cap returns the current iterate.
        def cond(state):
            keep, prev, it = state
            return jnp.any(keep != prev) & (it < sweep_cap)

        def body(state):
            keep, _, it = state
            with jax.named_scope("nms_sweep"):
                new_keep = svalid & ~jnp.any(
                    suppress & keep[:, None], axis=0
                )
            return new_keep, keep, it + 1

        init = (svalid, jnp.zeros(n, dtype=bool), jnp.asarray(0, jnp.int32))
        keep_sorted, _, _ = lax.while_loop(cond, body, init)
    else:
        def cond(state):
            keep, prev = state
            return jnp.any(keep != prev)

        def body(state):
            keep, _ = state
            # One sweep = one run of this scope's ops: the trace shows a
            # loop body's ops once per iteration, so sweeps are countable
            # per step (perfbench/metrics/nms_sweeps.train.py).
            with jax.named_scope("nms_sweep"):
                new_keep = svalid & ~jnp.any(
                    suppress & keep[:, None], axis=0
                )
            return new_keep, keep

        init = (svalid, jnp.zeros(n, dtype=bool))
        keep_sorted, _ = lax.while_loop(cond, body, init)

    return jnp.zeros(n, dtype=bool).at[order].set(keep_sorted)


def rank_keep(keep: jnp.ndarray, scores: jnp.ndarray, max_outputs: int):
    """Rank a keep mask by score into up to ``max_outputs`` indices.

    The back half of :func:`nms_indices`, shared with the fused middle
    (``ops/pallas/middle.py`` computes the keep mask in-kernel and hands
    it here): kept entries first, best score first, padded slots index 0
    with ``out_valid`` False.
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


@partial(
    jax.jit,
    static_argnums=(2, 3),
    static_argnames=("sweep_cap", "nms_impl", "interpret"),
)
def nms_indices(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    max_outputs: int,
    valid: jnp.ndarray | None = None,
    sweep_cap: int = 0,
    nms_impl: str = "xla",
    interpret: bool = False,
):
    """NMS returning up to ``max_outputs`` kept indices, score-descending.

    Static output shape: ``(indices (max_outputs,), out_valid (max_outputs,))``.
    Padded slots hold index 0 with ``out_valid`` False — the static-shape
    replacement for the reference Proposal op's pad-with-repeats
    (``rcnn/symbol/proposal.py`` pads rois to RPN_POST_NMS_TOP_N).

    ``nms_impl`` selects the keep-mask backend: ``"xla"`` (default) is the
    batched while-loop fixed point above; ``"pallas"`` routes through the
    VMEM-resident greedy sweep (``ops/pallas/nms.py::nms_mask_pallas``,
    bit-identical keep bits — it snaps IoU on the same 2**-16 grid before
    the threshold compare).  The pallas sweep is always-exact greedy, so
    ``sweep_cap`` does not apply to it (the cap exists to bound the XLA
    fixed point's data-dependent sweep count).  ``interpret`` runs the
    pallas kernel in interpret mode (CPU CI).
    """
    if nms_impl == "pallas":
        from mx_rcnn_tpu.ops.pallas.nms import nms_mask_pallas

        keep = nms_mask_pallas(
            boxes, scores, iou_threshold, valid, interpret=interpret
        )
    elif nms_impl == "xla":
        keep = nms_mask(
            boxes, scores, iou_threshold, valid, sweep_cap=sweep_cap
        )
    else:
        raise ValueError(f"nms_impl must be 'xla' or 'pallas', got {nms_impl!r}")
    return rank_keep(keep, scores, max_outputs)


def batched_nms(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    classes: jnp.ndarray,
    iou_threshold: float,
    valid: jnp.ndarray | None = None,
    sweep_cap: int = 0,
) -> jnp.ndarray:
    """Per-class NMS in one shot via the coordinate-offset trick.

    Boxes of different classes are translated to disjoint regions so they
    can never overlap; one NMS pass then equals independent per-class NMS.
    Replaces the reference's per-class python loop in
    ``rcnn/core/tester.py::pred_eval``.
    """
    span = jnp.max(boxes) - jnp.min(boxes) + 1.0
    offset = classes.astype(boxes.dtype)[:, None] * span
    return nms_mask(boxes + offset, scores, iou_threshold, valid,
                    sweep_cap=sweep_cap)
