"""ROIAlign in plain XLA: the gather form (the oracle, and the multilevel
FPN dispatch off the TPU) and the one-level matmul form (the path every
one-level pyramid takes).

Replaces the engine-side ``mx.symbol.ROIPooling`` CUDA op the reference's
R-CNN head depends on (SURVEY.md section 3.5), upgraded to ROIAlign per the
BASELINE north star.  Design notes for TPU:

- All shapes static: (R rois) x (S x S bins) x (sr x sr samples/bin).
- ``roi_align`` / ``multilevel_roi_align`` express the bilinear sample as 4
  corner gathers from the flattened (H*W, C) feature map with computed flat
  indices.  XLA lowers this to dynamic-gather and, in the backward, to a
  scatter-add per image: correct and slow on the chip (PERF.md section 6,
  PR 26).  They are the reference semantics every other form is tested
  against, and the multi-level path wherever the Pallas kernel
  (ops/pallas/roi_align.py) does not run.
- ``roi_align_matmul`` is the same arithmetic for ONE level as a dense
  matmul over the whole map: ROIAlign is linear in the features, so each
  output bin is a row of interpolation weights against the (H*W, C) map.
  No gather, no scatter; the backward is the transposed matmul.
- In the gather form sample points are accumulated one at a time (sr*sr
  iterations, unrolled at trace time) so the peak intermediate is
  (R, S, S, C), not (R, S*sr, S*sr, C).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnums=(2, 3, 4))
def roi_align(
    features: jnp.ndarray,
    rois: jnp.ndarray,
    output_size: int = 7,
    spatial_scale: float = 1.0 / 16.0,
    sampling_ratio: int = 2,
) -> jnp.ndarray:
    """ROIAlign on a single feature map.

    Args:
      features: (H, W, C) feature map.
      rois: (R, 4) boxes in input-image coordinates (x1, y1, x2, y2).
      output_size: S — pooled bins per side (7 for box head, 14 for mask).
      spatial_scale: 1/stride of this feature map.
      sampling_ratio: sr — bilinear samples per bin side.

    Returns:
      (R, S, S, C) pooled features.
    """
    h, w, c = features.shape
    flat = features.reshape(h * w, c)

    scaled = rois * spatial_scale
    x1, y1 = scaled[:, 0], scaled[:, 1]
    rw = jnp.maximum(scaled[:, 2] - x1, 1.0)
    rh = jnp.maximum(scaled[:, 3] - y1, 1.0)
    bin_w = rw / output_size  # (R,)
    bin_h = rh / output_size

    bins = jnp.arange(output_size, dtype=jnp.float32)  # (S,)

    out = jnp.zeros((rois.shape[0], output_size, output_size, c), jnp.float32)
    for iy in range(sampling_ratio):
        fy = (iy + 0.5) / sampling_ratio
        # (R, S): absolute y of this sample row in every bin
        sy = y1[:, None] + (bins[None, :] + fy) * bin_h[:, None]
        for ix in range(sampling_ratio):
            fx = (ix + 0.5) / sampling_ratio
            sx = x1[:, None] + (bins[None, :] + fx) * bin_w[:, None]
            out = out + _bilinear_gather(flat, h, w, sy, sx)
    # f32 interpolation arithmetic, result back in the features' dtype
    # (keeps the Pallas kernel and this reference bit-for-bit interchangeable
    # inside a bf16 train graph, including cotangent dtypes in custom_vjp).
    return (out / (sampling_ratio * sampling_ratio)).astype(features.dtype)


def _axis_weights(start, bin_size, output_size, sampling_ratio, extent, cell):
    """Interpolation weights of one axis, the bin's subsample mean folded in.

    ``start``, ``bin_size``: (B, R) in cells.  ``cell``: (K,) int32, the
    index along THIS axis of each of the K columns asked for (``arange`` for
    the axis itself; ``k // W`` or ``k % W`` for the flattened map).
    Returns (B, S, R, K) float32: row (b, p, r) holds, for each of the ``sr``
    samples of bin p, the two bilinear taps of ``_bilinear_gather_flat`` —
    a sample counts iff -1 < s < extent, is clipped to [0, extent - 1], and
    its upper tap is clamped to extent - 1 so both merge on the edge cell —
    averaged over the samples.
    """
    bins = jnp.arange(output_size, dtype=jnp.float32)[:, None, None]
    sub = (jnp.arange(sampling_ratio, dtype=jnp.float32)[:, None] + 0.5) / sampling_ratio
    s = start[:, None, None, :] + (bins + sub) * bin_size[:, None, None, :]  # (B,S,sr,R)
    inside = (s > -1.0) & (s < extent)
    c = jnp.clip(s, 0.0, extent - 1.0)
    c0 = jnp.floor(c)
    frac = c - c0
    i0 = c0.astype(jnp.int32)
    i1 = jnp.minimum(i0 + 1, extent - 1)
    taps = (
        jnp.where(cell == i0[..., None], (1.0 - frac)[..., None], 0.0)
        + jnp.where(cell == i1[..., None], frac[..., None], 0.0)
    )  # (B, S, sr, R, K)
    taps = jnp.where(inside[..., None], taps, 0.0)
    return taps.sum(axis=2) / sampling_ratio


@partial(jax.jit, static_argnums=(2, 3, 4))
def roi_align_matmul(
    features: jnp.ndarray,
    rois: jnp.ndarray,
    output_size: int = 7,
    spatial_scale: float = 1.0 / 16.0,
    sampling_ratio: int = 2,
) -> jnp.ndarray:
    """ROIAlign over one level for a whole batch, as one matmul per image.

    ``pooled[r, p, q, c] = sum_yx Wy[r, p, y] * Wx[r, q, x] * F[y, x, c]``:
    the two factors are multiplied out into ONE (S*S*R, H*W) weight matrix
    per image and contracted with the (H*W, C) map on the MXU.  The weights
    are ``roi_align``'s to the letter (``_axis_weights``); most are zero,
    and the chip multiplies by them anyway — that is cheaper there than a
    gather (measured: PERF.md section 6, PR 26).  The cost grows with
    H*W*C per roi, not with the roi's extent.

    Precision is the features': float32 features dot float32 weights at
    ``HIGHEST``; bfloat16 features dot the weights split ``w = hi + lo``
    into two bfloat16 halves (about 2**-17 relative: a single bfloat16
    weight would shift WHERE a feature is sampled by 2**-8, the Pallas
    kernel's rule) with float32 accumulation, and the sum is rounded once,
    to the features' dtype.  The backward is autodiff's: the same two
    matmuls transposed, each accumulated in float32 inside the MXU.  The
    rois carry no gradient (proposals are decoded from stop-gradient
    scores and deltas).

    Args:
      features: (B, H, W, C).
      rois: (B, R, 4) boxes in input-image coordinates (x1, y1, x2, y2).
      output_size, spatial_scale, sampling_ratio: as ``roi_align``.

    Returns:
      (B, R, S, S, C) pooled features in the features' dtype.
    """
    b, h, w, c = features.shape
    r = rois.shape[1]
    s = output_size
    # Rows of the weight matrix are ordered (p, q, r) with R padded to a
    # multiple of 8: R then fills whole sublanes of the chip's (8, 128)
    # tiles and the reshape below is a relabelling, not a copy (300 eval
    # rois unpadded: 12.4 ms where padded takes 9.0, PERF.md, PR 26).
    rois = jnp.pad(rois, ((0, 0), (0, -r % 8), (0, 0)))
    rp = rois.shape[1]
    scaled = jax.lax.stop_gradient(rois) * spatial_scale
    x1, y1 = scaled[..., 0], scaled[..., 1]
    rw = jnp.maximum(scaled[..., 2] - x1, 1.0)
    rh = jnp.maximum(scaled[..., 3] - y1, 1.0)
    k = jnp.arange(h * w, dtype=jnp.int32)
    wy = _axis_weights(y1, rh / s, s, sampling_ratio, h, k // w)  # (B, S, R, HW)
    wx = _axis_weights(x1, rw / s, s, sampling_ratio, w, k % w)
    weights = (wy[:, :, None] * wx[:, None, :]).reshape(b, s * s * rp, h * w)
    flat = features.reshape(b, h * w, c)
    dot = partial(jnp.einsum, "bmk,bkc->bmc", preferred_element_type=jnp.float32)
    if features.dtype == jnp.bfloat16:
        hi = weights.astype(jnp.bfloat16)
        lo = (weights - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        out = dot(hi, flat) + dot(lo, flat)
    else:
        out = dot(
            weights, flat.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
        )
    out = out.astype(features.dtype).reshape(b, s, s, rp, c)[:, :, :, :r]
    return out.transpose(0, 3, 1, 2, 4)


def _bilinear_gather(flat, h, w, sy, sx):
    """Bilinear sample at (sy (R,S), sx (R,S)) -> (R, S, S, C).

    Out-of-range samples (beyond one pixel outside the map, matching
    Detectron ROIAlign semantics) contribute zero.  The single-map case of
    ``_bilinear_gather_flat`` with constant per-roi extents.
    """
    r = sy.shape[0]
    ones = jnp.ones((r,), jnp.float32)
    return _bilinear_gather_flat(
        flat,
        h * ones,
        w * ones,
        jnp.full((r,), w, jnp.int32),
        jnp.zeros((r,), jnp.int32),
        sy,
        sx,
    )


# Default bound on a roi's extent in feature cells at its assigned level.
# MUST equal the Pallas kernel's window - 10 (ops/pallas/roi_align.py,
# default T=48: 1 cell of bilinear margin per side + up to 7 cells lost to
# the 8-aligned x-origin + 1 tap) so the XLA and Pallas paths assign rois
# to identical levels.  Rois whose span would exceed it (pathologically
# thin-and-long boxes — small area, huge extent — that the area heuristic
# sends to a fine level) are bumped to a coarser level where they fit.
MAX_EXTENT_CELLS = 38


def fpn_level_assignment(
    rois: jnp.ndarray,
    min_level: int = 2,
    max_level: int = 5,
    canonical_scale: float = 224.0,
    canonical_level: int = 4,
    max_extent_cells: int | None = MAX_EXTENT_CELLS,
) -> jnp.ndarray:
    """FPN paper eq. 1: level k = k0 + log2(sqrt(area)/224), clamped; plus
    the extent bound above (pass ``max_extent_cells=None`` for the pure
    paper heuristic)."""
    w = jnp.maximum(rois[:, 2] - rois[:, 0], 1e-6)
    h = jnp.maximum(rois[:, 3] - rois[:, 1], 1e-6)
    k = canonical_level + jnp.log2(jnp.sqrt(w * h) / canonical_scale)
    k = jnp.floor(k).astype(jnp.int32)
    if max_extent_cells is not None:
        extent = jnp.maximum(w, h)
        k_fit = jnp.ceil(jnp.log2(extent / max_extent_cells)).astype(jnp.int32)
        k = jnp.maximum(k, k_fit)
    return jnp.clip(k, min_level, max_level)


def multilevel_roi_align(
    feature_pyramid: dict[int, jnp.ndarray],
    rois: jnp.ndarray,
    output_size: int = 7,
    sampling_ratio: int = 2,
    max_extent_cells: int | None = MAX_EXTENT_CELLS,
) -> jnp.ndarray:
    """ROIAlign over an FPN pyramid with per-roi level assignment.

    ``feature_pyramid`` maps level -> (H_l, W_l, C); stride of level l is
    2**l.  The levels are flattened and concatenated into ONE (sum H_l*W_l,
    C) buffer and each roi gathers through a per-roi base offset into it —
    one bilinear gather pass total (and one scatter-add in the backward),
    versus pooling every roi at every level and masking (4x the gather and
    scatter volume; kept as ``_multilevel_roi_align_dense``, the oracle).
    All shapes static, no host interaction.
    """
    levels = sorted(feature_pyramid.keys())
    c = feature_pyramid[levels[0]].shape[-1]
    flat = jnp.concatenate(
        [feature_pyramid[l].reshape(-1, c) for l in levels], axis=0
    )
    hs, ws, bases = [], [], []
    off = 0
    for l in levels:
        h, w, _ = feature_pyramid[l].shape
        hs.append(h)
        ws.append(w)
        bases.append(off)
        off += h * w
    hs = jnp.asarray(hs, jnp.float32)
    ws_f = jnp.asarray(ws, jnp.float32)
    ws_i = jnp.asarray(ws, jnp.int32)
    bases = jnp.asarray(bases, jnp.int32)

    assignment = fpn_level_assignment(
        rois, min_level=levels[0], max_level=levels[-1],
        max_extent_cells=max_extent_cells,
    )
    li = assignment - levels[0]                       # (R,) index into arrays
    scale = 2.0 ** (-assignment.astype(jnp.float32))  # (R,) 1/stride per roi
    h_r = jnp.take(hs, li)                            # (R,) float
    w_r = jnp.take(ws_f, li)
    wi_r = jnp.take(ws_i, li)                         # (R,) int row pitch
    base_r = jnp.take(bases, li)                      # (R,) int

    scaled = rois * scale[:, None]
    x1, y1 = scaled[:, 0], scaled[:, 1]
    rw = jnp.maximum(scaled[:, 2] - x1, 1.0)
    rh = jnp.maximum(scaled[:, 3] - y1, 1.0)
    bin_w = rw / output_size
    bin_h = rh / output_size
    bins = jnp.arange(output_size, dtype=jnp.float32)

    out = jnp.zeros((rois.shape[0], output_size, output_size, c), jnp.float32)
    for iy in range(sampling_ratio):
        fy = (iy + 0.5) / sampling_ratio
        sy = y1[:, None] + (bins[None, :] + fy) * bin_h[:, None]  # (R, S)
        for ix in range(sampling_ratio):
            fx = (ix + 0.5) / sampling_ratio
            sx = x1[:, None] + (bins[None, :] + fx) * bin_w[:, None]
            out = out + _bilinear_gather_flat(
                flat, h_r, w_r, wi_r, base_r, sy, sx
            )
    return (out / (sampling_ratio * sampling_ratio)).astype(flat.dtype)


def _bilinear_gather_flat(flat, h_r, w_r, wi_r, base_r, sy, sx):
    """Per-roi-extent bilinear sample into a concatenated pyramid buffer.

    Same semantics as ``_bilinear_gather`` with the map bounds (h_r, w_r),
    row pitch (wi_r) and flat-index base (base_r) varying per roi.
    """
    inside = (
        (sy[:, :, None] > -1.0)
        & (sy[:, :, None] < h_r[:, None, None])
        & (sx[:, None, :] > -1.0)
        & (sx[:, None, :] < w_r[:, None, None])
    )  # (R, S, S)

    y = jnp.clip(sy, 0.0, h_r[:, None] - 1)  # (R, S)
    x = jnp.clip(sx, 0.0, w_r[:, None] - 1)
    y0 = jnp.floor(y)
    x0 = jnp.floor(x)
    ly = y - y0
    lx = x - x0
    y0i = y0.astype(jnp.int32)
    x0i = x0.astype(jnp.int32)
    y1i = jnp.minimum(y0i + 1, h_r[:, None].astype(jnp.int32) - 1)
    x1i = jnp.minimum(x0i + 1, w_r[:, None].astype(jnp.int32) - 1)

    def gather(yi, xi):  # yi (R,S), xi (R,S) -> (R, S, S, C)
        idx = base_r[:, None, None] + yi[:, :, None] * wi_r[:, None, None] + xi[:, None, :]
        return jnp.take(flat, idx.reshape(-1), axis=0).reshape(*idx.shape, -1)

    wy0 = (1.0 - ly)[:, :, None, None]
    wy1 = ly[:, :, None, None]
    wx0 = (1.0 - lx)[:, None, :, None]
    wx1 = lx[:, None, :, None]

    val = (
        gather(y0i, x0i) * wy0 * wx0
        + gather(y0i, x1i) * wy0 * wx1
        + gather(y1i, x0i) * wy1 * wx0
        + gather(y1i, x1i) * wy1 * wx1
    )
    return val * inside[..., None]


def _multilevel_roi_align_dense(
    feature_pyramid: dict[int, jnp.ndarray],
    rois: jnp.ndarray,
    output_size: int = 7,
    sampling_ratio: int = 2,
    max_extent_cells: int | None = MAX_EXTENT_CELLS,
) -> jnp.ndarray:
    """Oracle: pool every roi at every level, mask-select by assignment.

    4x the gather volume of ``multilevel_roi_align`` — kept for tests (the
    two must agree exactly) and as the reference semantics."""
    levels = sorted(feature_pyramid.keys())
    assignment = fpn_level_assignment(
        rois, min_level=levels[0], max_level=levels[-1],
        max_extent_cells=max_extent_cells,
    )
    out = None
    for lvl in levels:
        pooled = roi_align(
            feature_pyramid[lvl],
            rois,
            output_size=output_size,
            spatial_scale=1.0 / (2**lvl),
            sampling_ratio=sampling_ratio,
        )
        sel = (assignment == lvl).astype(pooled.dtype)[:, None, None, None]
        out = pooled * sel if out is None else out + pooled * sel
    return out
