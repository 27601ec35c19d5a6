"""Pallas TPU greedy NMS.

Replaces the reference's CUDA bitmask kernel (``rcnn/cython/nms_kernel.cu``
— the repo's only hand-written GPU kernel, SURVEY.md §3.5) inside the
jitted step.  The XLA fallback (:func:`mx_rcnn_tpu.ops.nms.nms_mask`)
materializes the full N×N IoU matrix in HBM and sweeps it to a fixed point
(O(sweeps·N²) HBM traffic); this kernel keeps everything VMEM-resident and
does the exact greedy recurrence in one pass:

    for i in score order:  alive[j>i] &= ~(alive[i] & iou(i, j) > t)

Per iteration it extracts box i's scalars by masked reduction and does one
N-wide VPU suppression update — O(N) VMEM traffic per step, no HBM round
trips, and bit-identical keep decisions to the greedy definition.

Measured on a v5e at N=2000: 9.7ms vs the XLA path's 2.3ms — the XLA
fixed-point converges in a handful of N² sweeps while this kernel always
pays N sequential iterations, so **the XLA implementation remains the
production path**; this kernel is kept as the latency-predictable
alternative (worst-case XLA sweeps = suppression-chain depth) and as the
in-graph replacement story for the reference's CUDA bitmask kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _nms_kernel(data_ref, keep_ref, *, n: int, thresh: float):
    x1 = data_ref[0:1, :]     # (1, N)
    y1 = data_ref[1:2, :]
    x2 = data_ref[2:3, :]
    y2 = data_ref[3:4, :]
    areas = data_ref[4:5, :]
    valid = data_ref[5:6, :] > 0.0

    col = lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def body(i, alive):  # alive: (1, N) float32 1.0/0.0 (i1 carries don't
        # legalize through Mosaic's scf.for).  All per-box scalars come out
        # as masked reductions — Mosaic has neither dynamic lane extraction
        # from vectors nor room in SMEM for an N-row scalar table.
        sel = (col == i).astype(jnp.float32)
        bx1 = jnp.sum(x1 * sel)
        by1 = jnp.sum(y1 * sel)
        bx2 = jnp.sum(x2 * sel)
        by2 = jnp.sum(y2 * sel)
        b_area = (bx2 - bx1) * (by2 - by1)
        ai = jnp.sum(alive * sel)

        iw = jnp.maximum(jnp.minimum(x2, bx2) - jnp.maximum(x1, bx1), 0.0)
        ih = jnp.maximum(jnp.minimum(y2, by2) - jnp.maximum(y1, by1), 0.0)
        inter = iw * ih
        union = areas + b_area - inter
        iou = jnp.where(union > 0.0, inter / jnp.where(union > 0.0, union, 1.0), 0.0)
        # Same 2**-16 IoU snap as the XLA oracle (ops/nms.py::nms_mask):
        # the > threshold compare must make the identical decision on both
        # backends, including inputs sitting ulps from the threshold.
        iou = jnp.round(iou * 65536.0) * (1.0 / 65536.0)

        suppress = jnp.where((iou > thresh) & (col > i), ai, 0.0)
        return alive * (1.0 - suppress)

    alive = lax.fori_loop(0, n, body, valid.astype(jnp.float32))
    keep_ref[:, :] = (alive > 0.0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("iou_threshold", "interpret"))
def nms_mask_pallas(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float,
    valid: jnp.ndarray | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drop-in for :func:`mx_rcnn_tpu.ops.nms.nms_mask` (same contract:
    keep mask in input order; invalid/-inf entries neither keep nor
    suppress).  Pads N to a lane multiple internally."""
    n = boxes.shape[0]
    if valid is None:
        valid = jnp.isfinite(scores)
    else:
        valid = valid & jnp.isfinite(scores)

    order = jnp.argsort(-scores)
    sboxes = jnp.take(boxes, order, axis=0)
    svalid = jnp.take(valid, order)

    n_pad = -(-n // 128) * 128
    pad = n_pad - n
    if pad:
        sboxes = jnp.concatenate([sboxes, jnp.zeros((pad, 4), sboxes.dtype)])
        svalid = jnp.concatenate([svalid, jnp.zeros(pad, bool)])

    area = (sboxes[:, 2] - sboxes[:, 0]) * (sboxes[:, 3] - sboxes[:, 1])
    data = jnp.stack(
        [sboxes[:, 0], sboxes[:, 1], sboxes[:, 2], sboxes[:, 3],
         area, svalid.astype(sboxes.dtype),
         jnp.zeros(n_pad, sboxes.dtype), jnp.zeros(n_pad, sboxes.dtype)],
    ).astype(jnp.float32)                               # (8, N)

    keep_sorted = pl.pallas_call(
        functools.partial(_nms_kernel, n=n_pad, thresh=float(iou_threshold)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=interpret,
        name="nms_sweep_pallas",
    )(data)[0, :n] > 0

    return jnp.zeros(n, dtype=bool).at[order].set(keep_sorted)
