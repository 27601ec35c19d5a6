"""Pallas TPU ROIAlign over an FPN pyramid.

The TPU-native replacement for the reference's engine-side ROIPooling CUDA
kernel (``mx.symbol.ROIPooling``; SURVEY.md §3.5 "engine-side native ops"),
upgraded to ROIAlign.  The XLA fallback (:mod:`mx_rcnn_tpu.ops.roi_align`)
pools every roi from every pyramid level and masks (4x redundant compute,
gather-bound); this kernel does one pass:

- grid = one step per roi, across the WHOLE batch (B*R steps — batching
  is a column of the per-roi parameter block, not a loop of kernel calls);
- each roi's parameter row (geometry + assigned level + window origin +
  batch index) streams in as a tiny per-step SMEM block — NOT a
  scalar-prefetch table, which costs ~512 B of smem per row and cannot
  hold a batched-eval grid (see _kernel);
- the roi's assigned level selects which HBM feature map a ``(T, T, C)``
  window is DMA'd from — only the window travels over HBM, never a whole
  pyramid level per roi;
- bilinear interpolation is expressed as two small matmuls with sparse
  interpolation matrices ``pooled = mean_pool(Wy @ window @ Wx^T)`` — the
  MXU-friendly formulation of "gather 4 corners per sample" (each Wy/Wx row
  holds the two bilinear taps of one sample coordinate);
- bin-averaging folds into the same reshape.

The window size T (default 40) bounds the roi extent in feature cells at
its assigned level: :func:`fpn_level_assignment` is extent-aware (rois
whose span would exceed T-2 cells are bumped to a coarser level), so the
kernel is exact whenever the coarsest map fits the window — canvases up to
(T-2) * 2^max_level px, i.e. 1216px at P5 with the default T.  Beyond
that, samples past the window clamp to its edge (only for rois spanning
more than T-2 cells at the coarsest level).

Numerics match the XLA reference: samples outside (-1, H) x (-1, W)
contribute zero; in-range samples clamp to the [0, H-1] cell range
(Detectron ROIAlign semantics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mx_rcnn_tpu.ops.roi_align import fpn_level_assignment

# Default roi window in feature cells — the single knob every entry point
# below defaults to.  MUST stay 10 above ops.roi_align.MAX_EXTENT_CELLS so
# the XLA and Pallas paths assign rois to identical levels (see there);
# detection/graph.py threads this SAME constant into both the single-chip
# and shard_map'd call sites so the two can never silently diverge.
POOL_WINDOW = 48


def window_classes(t: int) -> tuple[tuple[int, int], ...]:
    """Per-roi (Ty, Tx) window classes, smallest first; the last is the
    full (t, t) fallback whose clamp semantics define exactness.

    The kernels are window-DMA-bound (cost tracks Ty*Tx*C), and the FPN
    level assignment targets ~7-20 cells of roi extent, so most rois need
    far less than the worst-case window.  The r4 eval-shape distribution
    probe (random-weight proposals, recipe canvas): y-need p50/p90 =
    10/20 cells, x-need (which carries the origin's 8-alignment slack,
    up to +7) p50/p90 = 21/25 — (16, 24) fits 72% of rois and (24, 32)
    fits 100%, where the single 32-corner class shipped 1024 cells for
    every one of them.  Ty is unconstrained (H is the untiled dim); Tx
    must be a multiple of 8 (Mosaic sublane slicing).
    """
    base = [(ty, tx) for ty, tx in ((16, 24), (24, 32)) if ty < t and tx < t]
    return tuple(base) + ((t, t),)


def _interp_matrix(start, bin_size, num_bins, sr, extent, origin, t):
    """Rows = P = num_bins*sr sample coords; cols = T window cells.

    Row p holds the two bilinear taps of sample p, zeroed when the sample
    falls outside (-1, extent); both taps merge on the edge cell when the
    sample clamps to extent-1 (weights sum to 1, matching the XLA path).
    """
    p = num_bins * sr
    pid_i = jax.lax.broadcasted_iota(jnp.int32, (p, 1), 0)  # (P, 1)
    s = (pid_i // sr).astype(jnp.float32)
    frac = ((pid_i % sr).astype(jnp.float32) + 0.5) / sr
    coord = start + (s + frac) * bin_size                    # absolute cells
    inside = (coord > -1.0) & (coord < extent)
    c = jnp.clip(coord, 0.0, extent - 1.0)
    c0 = jnp.floor(c)
    lc = c - c0
    # Window-relative taps.  Negative is impossible (the origin sits one
    # cell below the roi start); > t-1 only for rois spanning more than the
    # window — those clamp to the window edge (see module docstring).
    c0i = jnp.clip(c0.astype(jnp.int32) - origin, 0, t - 1)
    c1i = jnp.clip(
        jnp.minimum(c0i + 1, (extent - 1.0).astype(jnp.int32) - origin), 0, t - 1
    )
    cells = jax.lax.broadcasted_iota(jnp.int32, (p, t), 1)
    w = jnp.where(cells == c0i, 1.0 - lc, 0.0) + jnp.where(cells == c1i, lc, 0.0)
    return w * inside.astype(jnp.float32)                    # (P, T)


def _interp_matrix_avg(start, bin_size, num_bins, sr, extent, origin, t):
    """(S, T) interpolation matrix with the sr-subsample bin mean BAKED IN.

    Row i = (1/sr) * sum of the sr bilinear-tap rows of bin i, i.e. the
    mean over subsamples folded into the weights (mean of linear maps =
    linear map).  Halving the matmul row count this way took the kernel's
    x-interpolation matmul — measured as its LARGEST compute component at
    eval shapes (N = P*C with P = S*sr) — down by 2x with no semantics
    change beyond f32 summation order (weights are computed in f32; /sr is
    exact for the power-of-two default)."""
    w = _interp_matrix(start, bin_size, num_bins, sr, extent, origin, t)
    return w.reshape(num_bins, sr, t).sum(axis=1) / sr       # (S, T)


def _dot_q(a, b, dn, interpret):
    """dot_general of already-quantized low-precision operands, f32 accum.

    On TPU the operands dot natively (full-rate bf16 MXU passes, f32
    accumulation).  Under ``interpret`` (the CPU emulation used by tests
    and the multichip dryrun) the same VALUES dot in f32 instead — the CPU
    runtime has no BF16xBF16=F32 dot thunk.  The two are value-equivalent
    up to f32 summation order: each bf16 product is exact in f32, but the
    backends may reduce in different orders, so interpret-mode tests are
    an up-to-rounding oracle for the TPU path, NOT a bitwise one (the
    on-TPU parity check lives in tests/test_overfit_tpu.py)."""
    if interpret:
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
    return jax.lax.dot_general(
        a, b, dimension_numbers=dn, preferred_element_type=jnp.float32
    )


def _kernel(
    roi_ref,       # SMEM block (G, 1, 9+2K) f32, G rois per grid step:
                   # [x1, y1, bin_w, bin_h, H, W, level_idx, batch,
                   #  (oy_c, ox_c) x K classes, cls]
                   # Streamed per step, NOT scalar-prefetched: a prefetch
                   # table costs ~512 B of smem PER ROW, so an N = B*R
                   # batched-eval grid (8000 rois) would need 4 MB of the
                   # 1 MB smem.  The indices ride as f32 (exact < 2^24).
    *rest,
    num_levels: int,
    t: int,
    output_size: int,
    sampling_ratio: int,
    group: int,
    interpret: bool = False,
):
    feat_refs = rest[:num_levels]
    out_ref = rest[num_levels]
    win = rest[num_levels + 1]     # (G, T, T, C) VMEM scratch
    sem = rest[num_levels + 2]     # DMA sems, shape (G,)
    classes = window_classes(t)

    # Phase 1: start ALL G window DMAs, then wait — the copies fly
    # concurrently, amortizing HBM latency across the group (a 1-roi-per-
    # step grid serializes fetch->compute->fetch and measured ~10 ms for
    # 1024 train rois; grouped fetches overlap).  Each roi copies only its
    # CLASS window corner (see _prep); cells beyond it hold stale finite
    # scratch that every interpolation weight zeroes — which needs the
    # scratch to START finite: uninitialized VMEM can hold NaN and 0 * NaN
    # poisons the matmul, so step 0 memsets all windows once (later steps
    # inherit real features or these zeros).
    @pl.when(pl.program_id(0) == 0)
    def _():
        for g in range(group):
            win[g] = jnp.zeros((t, t, win.shape[-1]), win.dtype)

    # (Cells a DMA never reaches — undersized levels, class corners —
    # need no per-step re-zeroing: the extent/corner masking in the interp
    # matrices gives them exactly-zero weight, and the step-0 memset keeps
    # them finite for the whole grid.)
    cls_col = 8 + 2 * len(classes)
    for phase in ("start", "wait"):
        for g in range(group):
            level = roi_ref[g, 0, 6].astype(jnp.int32)
            bi = roi_ref[g, 0, 7].astype(jnp.int32)
            cls = roi_ref[g, 0, cls_col].astype(jnp.int32)
            for ci, (ty, tx) in enumerate(classes):
                oy_c = roi_ref[g, 0, 8 + 2 * ci].astype(jnp.int32)
                ox_c = pl.multiple_of(
                    roi_ref[g, 0, 9 + 2 * ci].astype(jnp.int32), 8
                )
                for i, f in enumerate(feat_refs):
                    th = min(ty, f.shape[1])
                    tw = min(tx, f.shape[2])

                    @pl.when((level == i) & (cls == ci))
                    def _(g=g, f=f, th=th, tw=tw, oy_c=oy_c, ox_c=ox_c,
                          bi=bi, phase=phase):
                        getattr(pltpu.make_async_copy(
                            f.at[bi, pl.ds(oy_c, th), pl.ds(ox_c, tw), :],
                            win.at[g, pl.ds(0, th), pl.ds(0, tw), :],
                            sem.at[g],
                        ), phase)()

    # Phase 2: interpolate each roi's window — per CLASS, at the class's
    # static (Ty, Tx) widths: the matmul cost tracks Ty*Tx*C exactly like
    # the DMA does, so a (16, 24)-class roi runs 1/6 the full-window
    # matmul FLOPs, not just 1/6 the copy bytes.  The sr x sr bin mean is
    # baked into the interpolation matrices (see _interp_matrix_avg).
    s, sr = output_size, sampling_ratio
    c = win.shape[-1]
    for g in range(group):
        x1 = roi_ref[g, 0, 0]
        y1 = roi_ref[g, 0, 1]
        bin_w = roi_ref[g, 0, 2]
        bin_h = roi_ref[g, 0, 3]
        hl = roi_ref[g, 0, 4]
        wl = roi_ref[g, 0, 5]
        cls = roi_ref[g, 0, cls_col].astype(jnp.int32)
        for ci, (ty, tx) in enumerate(classes):
            # The interpolation origin must match whichever class window
            # was DMA'd; each roi matches exactly one class branch, so
            # out_ref[g] is written exactly once.
            oy_c = roi_ref[g, 0, 8 + 2 * ci].astype(jnp.int32)
            ox_c = roi_ref[g, 0, 9 + 2 * ci].astype(jnp.int32)

            @pl.when(cls == ci)
            def _(g=g, ty=ty, tx=tx, oy_c=oy_c, ox_c=ox_c):
                wy = _interp_matrix_avg(y1, bin_h, s, sr, hl, oy_c, ty)
                wx = _interp_matrix_avg(x1, bin_w, s, sr, wl, ox_c, tx)

                # rows: (S, Ty) @ (Ty, Tx*C) -> (S, Tx, C).
                #
                # Precision, by feature dtype:
                # - f32 windows (CPU-recipe tests, goldens): HIGHEST with
                #   exact f32 weights — bit-stable vs the XLA oracle at
                #   atol 1e-4.
                # - bf16 windows (the production train/eval graphs): the
                #   old path upcast the whole window to f32 just so a
                #   same-dtype HIGHEST dot could run (6 MXU passes).  The
                #   r4c cost probe showed that cast + those passes were
                #   the kernel's single largest compute component (first
                #   dot ~9.8 of 28.8 ms at batch-8 eval), so bf16 windows
                #   now dot DIRECTLY against hi/lo SPLIT bf16 weights:
                #   w = w_hi + w_lo reconstructs the f32 weight to ~2^-17
                #   relative, so the geometric concern that forbids plain
                #   bf16 weights (a ~2^-8 shift of where features are
                #   sampled) does not arise — two full-rate bf16 passes
                #   with f32 accumulation replace six.  The intermediate
                #   rows then take ONE bf16 quantization (~2^-8, the same
                #   granularity as the bf16 output itself) before the x
                #   dot, also split.  Measured (r4c, same-session A/B):
                #   standalone fwd kernel 8.0 -> 5.9 ms at train shapes,
                #   27.1 -> 25.6 ms at batch-8 eval.  A THREE-dot exact
                #   split of the x dot was probed and is SLOWER than the
                #   f32 path (42 ms eval): per-dot issue overhead, not
                #   pass count, prices each extra dot (~0.7 us/roi), so
                #   the one-quantization two-dot form is the optimum.
                sub = win[g, pl.ds(0, ty), pl.ds(0, tx), :]
                if win.dtype == jnp.bfloat16:
                    wy_hi = wy.astype(jnp.bfloat16)
                    wy_lo = (wy - wy_hi.astype(jnp.float32)).astype(jnp.bfloat16)
                    wx_hi = wx.astype(jnp.bfloat16)
                    wx_lo = (wx - wx_hi.astype(jnp.float32)).astype(jnp.bfloat16)
                    sub_b = sub.reshape(ty, tx * c)
                    dn = (((1,), (0,)), ((), ()))
                    rows = (
                        _dot_q(wy_hi, sub_b, dn, interpret)
                        + _dot_q(wy_lo, sub_b, dn, interpret)
                    ).reshape(s, tx, c).astype(jnp.bfloat16)
                    dn2 = (((1,), (1,)), ((), ()))
                    qpc = (
                        _dot_q(wx_hi, rows, dn2, interpret)
                        + _dot_q(wx_lo, rows, dn2, interpret)
                    )                                             # (Sx, Sy, C)
                else:
                    rows = jax.lax.dot_general(
                        wy, sub.astype(jnp.float32).reshape(ty, tx * c),
                        dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                    ).reshape(s, tx, c)
                    qpc = jax.lax.dot_general(
                        wx, rows,
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                    )                                             # (Sx, Sy, C)
                out_ref[g] = jnp.swapaxes(qpc, 0, 1).astype(out_ref.dtype)


def _prep(feature_pyramid, rois, output_size, window):
    """Shared forward/backward preprocessing: pad level widths to the
    Mosaic sublane multiple and build the per-roi parameter table.

    Returns (levels, feats (padded, batched), ws_true, roi_params, b,
    r_per, batched).  Forward and backward MUST agree on every field here
    (level assignment, window origins), so it is factored out."""
    levels = sorted(feature_pyramid.keys())
    batched = rois.ndim == 3
    if not batched:
        feature_pyramid = {l: f[None] for l, f in feature_pyramid.items()}
        rois = rois[None]
    feats = [feature_pyramid[l] for l in levels]
    b, r_per = rois.shape[:2]
    flat = rois.reshape(-1, 4)
    t = window
    # Mosaic's HBM window slice needs the sublane (W) dim to be a multiple
    # of 8; recipe canvases (800x1344) give odd widths at coarse levels
    # (84/42/21 cells).  Pad those levels' W with zeros — geometry and
    # extent masking below keep using the TRUE widths, so padded cells get
    # zero interpolation weight and the result is unchanged.  The pads copy
    # only the small coarse maps (P4+), nothing at P2/P3 scale.
    ws_true = [f.shape[2] for f in feats]
    feats = [
        jnp.pad(f, ((0, 0), (0, 0), (0, -f.shape[2] % 8), (0, 0)))
        if f.shape[2] % 8
        else f
        for f in feats
    ]

    assignment = fpn_level_assignment(
        flat, min_level=levels[0], max_level=levels[-1],
        max_extent_cells=window - 10,
    )
    level_idx = assignment - levels[0]                         # 0-based

    # Per-roi geometry in its level's cell units (gather per-level consts).
    scale = jnp.asarray([1.0 / (1 << l) for l in levels], jnp.float32)[level_idx]
    hs = jnp.asarray([f.shape[1] for f in feats], jnp.float32)[level_idx]
    ws = jnp.asarray(ws_true, jnp.float32)[level_idx]
    ws_pad = jnp.asarray([f.shape[2] for f in feats], jnp.float32)[level_idx]
    x1 = flat[:, 0] * scale
    y1 = flat[:, 1] * scale
    rw = jnp.maximum(flat[:, 2] * scale - x1, 1.0)
    rh = jnp.maximum(flat[:, 3] * scale - y1, 1.0)
    roi_geom = [x1, y1, rw / output_size, rh / output_size, hs, ws]

    bidx = jnp.repeat(jnp.arange(b, dtype=jnp.int32), r_per)

    # Window classes (smallest first; the last is the (t, t) fallback —
    # see window_classes).  Per class: origin with one cell of bilinear
    # margin, clamped into the map; ox floors to a multiple of 8 (Mosaic
    # requires provable sublane alignment for HBM slices in the tiled
    # second-to-last dim; the up-to-7-cell loss is budgeted both in
    # max_extent_cells and in each class's fit test).  A roi takes the
    # SMALLEST class whose every nonzero tap fits the class window at its
    # clamped origin; cells beyond the DMA'd corner hold stale scratch
    # with exactly-zero interpolation weight (finite garbage x 0).
    #
    # Highest cell any sample can tap: floor of the largest clipped sample
    # coordinate, +1 for the second bilinear tap, +1 more as f32 slack (the
    # kernel recomputes coords as y1 + k*(rh/S), which can exceed y1 + rh
    # by an ULP — the slack makes the bound robustly conservative).
    classes = window_classes(t)
    y_hi = jnp.minimum(
        jnp.floor(jnp.clip(y1 + rh, 0.0, hs - 1.0)) + 2.0, hs - 1.0
    )
    x_hi = jnp.minimum(
        jnp.floor(jnp.clip(x1 + rw, 0.0, ws - 1.0)) + 2.0, ws - 1.0
    )
    origin_cols = []
    cls = jnp.full(x1.shape, len(classes) - 1, jnp.int32)
    for ci in reversed(range(len(classes))):
        ty, tx = classes[ci]
        oy_c = jnp.clip(
            jnp.floor(y1) - 1, 0, jnp.maximum(hs - ty, 0)
        ).astype(jnp.int32)
        ox_c = jnp.clip(
            jnp.floor(x1) - 1, 0, jnp.maximum(ws_pad - tx, 0)
        ).astype(jnp.int32)
        ox_c = (ox_c // 8) * 8
        if ci < len(classes) - 1:
            fits = (
                (y_hi - oy_c.astype(jnp.float32) <= ty - 1)
                & (x_hi - ox_c.astype(jnp.float32) <= tx - 1)
            )
            cls = jnp.where(fits, ci, cls)
        origin_cols = [oy_c.astype(jnp.float32), ox_c.astype(jnp.float32)] + origin_cols

    # Indices ride the same f32 table as the geometry (exact for values
    # < 2^24; feature maps are nowhere near that) — see _kernel docstring.
    roi_params = jnp.stack(
        roi_geom
        + [level_idx.astype(jnp.float32), bidx.astype(jnp.float32)]
        + origin_cols
        + [cls.astype(jnp.float32)],
        axis=1,
    ).astype(jnp.float32)[:, None, :]              # (N, 1, 9 + 2K)
    # 3-D so the SMEM block's last two dims equal the array's (Mosaic's
    # block-shape divisibility rule exempts full-extent dims).
    return levels, feats, ws_true, roi_params, b, r_per, batched


@functools.partial(
    jax.jit,
    static_argnames=("output_size", "sampling_ratio", "window", "interpret", "group"),
)
def multilevel_roi_align_pallas(
    feature_pyramid: dict[int, jnp.ndarray],
    rois: jnp.ndarray,
    output_size: int = 7,
    sampling_ratio: int = 2,
    window: int = POOL_WINDOW,
    interpret: bool = False,
    group: int = 8,
) -> jnp.ndarray:
    """Drop-in replacement for :func:`multilevel_roi_align`.

    Accepts the per-image contract — pyramid {level: (H_l, W_l, C)},
    rois (R, 4) → (R, S, S, C) — or the batched one: {level: (B, H_l, W_l,
    C)}, rois (B, R, 4) → (B, R, S, S, C).  The batch folds into the
    kernel grid (one step per ``group`` rois across ALL images), so a
    batched call is ONE pallas_call, not B.  ``group`` rois per step issue
    their window DMAs together (concurrent fetches — measured ~3x over the
    1-roi-per-step grid at train shapes); the roi count is padded to a
    multiple of ``group`` with row-0 copies whose outputs are sliced off.
    """
    levels, feats, ws_true, roi_params, b, r_per, batched = _prep(
        feature_pyramid, rois, output_size, window
    )
    n = b * r_per
    c = feats[0].shape[-1]
    t = window
    # The (G, T, T, C) window scratch must fit scoped VMEM (16 MB budget,
    # shared with the out block): G=8 bf16 windows at T=48/C=256 are
    # 9.4 MB, but an f32 pyramid (the tiny CPU-recipe configs) doubles
    # that past the limit — shrink the group to fit ~12 MB of scratch.
    itemsize = jnp.dtype(feats[0].dtype).itemsize
    budget = max(1, (12 * 1024 * 1024) // (t * t * c * itemsize))
    grp = max(1, min(group, budget, n))
    n_pad = -n % grp
    nf = roi_params.shape[-1]
    if n_pad:
        roi_params = jnp.concatenate(
            [roi_params, jnp.broadcast_to(roi_params[:1], (n_pad, 1, nf))]
        )

    kernel = functools.partial(
        _kernel,
        num_levels=len(levels),
        t=t,
        output_size=output_size,
        sampling_ratio=sampling_ratio,
        group=grp,
        interpret=interpret,
    )
    out = pl.pallas_call(
        kernel,
        grid=((n + n_pad) // grp,),
        in_specs=[
            pl.BlockSpec(
                (grp, 1, nf), lambda r: (r, 0, 0), memory_space=pltpu.SMEM
            )
        ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in levels],
        out_specs=pl.BlockSpec(
            (grp, output_size, output_size, c),
            lambda r: (r, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((grp, t, t, c), feats[0].dtype),
            pltpu.SemaphoreType.DMA((grp,)),
        ],
        out_shape=jax.ShapeDtypeStruct(
            (n + n_pad, output_size, output_size, c), feats[0].dtype
        ),
        interpret=interpret,
        # The name the kernel runs under in a device trace: the benchmark's
        # roofline readers find it by ``roi_align`` without ``bwd``.
        name="roi_align_fwd",
    )(roi_params, *feats)
    out = out[:n].reshape(b, r_per, output_size, output_size, c)
    return out if batched else out[0]


def _bwd_kernel(
    roi_ref,       # SMEM (1, 1, 9+2K) f32 — same fields as the forward.
    g_ref,         # VMEM (1, S, S, C) — cotangent of this roi's pooled out.
    *rest,
    num_levels: int,
    t: int,
    output_size: int,
    sampling_ratio: int,
    interpret: bool = False,
):
    """Transpose of :func:`_kernel`, accumulated by read-modify-write.

    The forward is two interpolation matmuls of a DMA'd window; its exact
    transpose is two transposed matmuls producing a (T, T, C) window
    gradient, ADDED into the roi's window slice of its level's gradient
    buffer.  The XLA autodiff of the gather formulation instead emits an
    HBM scatter-add with ~16 duplicate-index rows per bin, which the TPU
    serializes — measured 18-19 ms/step at train shapes (b2 x 512 rois,
    R101-FPN) vs ~3 ms for this kernel.

    Correctness of the accumulation: the TPU grid is sequential on a core,
    and each step's read-DMA waits before the add and the write-DMA waits
    before the step ends, so overlapping windows of different rois
    serialize cleanly (no lost updates).  The buffers accumulate in f32 —
    strictly tighter than the XLA path's feature-dtype (bf16 in the train
    graph) scatter accumulation.
    """
    # rest: [grad_level ANY ×L (in, aliased)] + [grad_level ANY ×L (out)] +
    # scratch [win2 (T,T,C) f32 VMEM, sem].  The aliased inputs are not
    # read through their input refs — RMW goes through the OUTPUT refs,
    # which point at the same buffers.
    out_refs = rest[num_levels: 2 * num_levels]
    win2 = rest[2 * num_levels]
    sem = rest[2 * num_levels + 1]

    classes = window_classes(t)
    cls_col = 8 + 2 * len(classes)
    level = roi_ref[0, 0, 6].astype(jnp.int32)
    bi = roi_ref[0, 0, 7].astype(jnp.int32)
    x1 = roi_ref[0, 0, 0]
    y1 = roi_ref[0, 0, 1]
    bin_w = roi_ref[0, 0, 2]
    bin_h = roi_ref[0, 0, 3]
    hl = roi_ref[0, 0, 4]
    wl = roi_ref[0, 0, 5]
    # Window classes (see _prep/_kernel): the RMW traffic — 2x window
    # bytes per roi — AND the transposed matmuls shrink with the class,
    # exactly like the forward.  The interp origins must match the window
    # actually read back.
    cls = roi_ref[0, 0, cls_col].astype(jnp.int32)
    s, sr = output_size, sampling_ratio
    c = win2.shape[-1]

    # d_out (S_y, S_x, C) -> d_qpc (S_x, S_y, C): just the transpose of the
    # forward's (x, y) -> (y, x) swap — the sr x sr subsample mean lives in
    # the averaged interpolation matrices (forward and backward MUST use
    # the same baked form; _interp_matrix_avg), so the old /sr^2 scale and
    # subsample broadcast are gone.  Stays in the cotangent's NATIVE dtype
    # (bf16 in the train graph).
    g = g_ref[0]                                               # (S, S, C)
    d_qpc = jnp.swapaxes(g, 0, 1)                              # (S_x, S_y, C)

    # Precision of the two transposed matmuls: bf16 cotangents (the train
    # graph) take DEFAULT — one MXU pass with f32 accumulation.  The
    # operands' information content is already bf16 (the cotangent arrives
    # in the graph's compute dtype), so truncating the exact-f32 weights
    # costs ~2^-8 relative.  The SECOND dot additionally truncates the f32
    # intermediate d_rows_t: each of its rows is a <=2-tap combination
    # (weights summing <=1) of bf16-valued cotangent entries, so that
    # rounding is one more independent ~2^-8 relative error — no
    # amplification, still below the cotangent's own quantization and
    # strictly tighter than the bf16-ACCUMULATING XLA scatter-add this
    # kernel replaced (hundreds of bf16 += per P2 cell).  On-chip check
    # (the off-TPU interpret tests can't see MXU truncation): max
    # |pallas - xla-autodiff| feature-grad diff at train shapes is
    # within bf16 output granularity — gated by the opt-in
    # RUN_KERNELS_TPU=1 probe (tests/test_kernels_tpu.py; r5 recorded
    # worst_rel 0.0092 ~ 2.4 roundings).  Measured 10.7 -> 6.1 ms at R101
    # train shapes vs HIGHEST.  f32 cotangents (CPU-recipe tests, golden
    # paths) keep the exact HIGHEST dot.  The FORWARD stays HIGHEST always:
    # weight truncation there shifts where features are SAMPLED (a
    # systematic geometric error, not gradient noise) and its measured win
    # was only ~1.5 ms.
    bf16_cot = g.dtype == jnp.bfloat16
    for ci, (ty, tx) in enumerate(classes):
        oy_c = roi_ref[0, 0, 8 + 2 * ci].astype(jnp.int32)
        ox_c = pl.multiple_of(roi_ref[0, 0, 9 + 2 * ci].astype(jnp.int32), 8)

        @pl.when(cls == ci)
        def _(ty=ty, tx=tx, oy_c=oy_c, ox_c=ox_c):
            wy = _interp_matrix_avg(y1, bin_h, s, sr, hl, oy_c, ty)  # (S, Ty)
            wx = _interp_matrix_avg(x1, bin_w, s, sr, wl, ox_c, tx)  # (S, Tx)
            # d_rows_T[tx, sy, c] = sum_sx wx[sx, tx] * d_qpc[sx, sy, c] —
            # the SMALL matmul (N = S*C), against the native cotangent.
            # bf16 cotangents dot DIRECTLY as bf16 operands with
            # single-bf16 weights (no f32 upcast of the cotangent): the
            # ~2^-8 weight truncation is plain gradient noise here, below
            # the cotangent's own quantization (the precision note above);
            # the geometric-exactness argument that makes the FORWARD use
            # hi/lo split weights does not apply to a backward.
            dn1 = (((0,), (0,)), ((), ()))
            dn2 = (((0,), (1,)), ((), ()))
            if bf16_cot:
                d_rows_t = _dot_q(
                    wx.astype(g.dtype), d_qpc.reshape(s, s * c), dn1, interpret
                ).reshape(tx, s, c)                            # (Tx, Sy, C)
                d_window = _dot_q(
                    wy.astype(g.dtype), d_rows_t.astype(g.dtype), dn2, interpret
                )                                              # (Ty, Tx, C)
            else:
                d_rows_t = jax.lax.dot_general(
                    wx, d_qpc.astype(jnp.float32).reshape(s, s * c),
                    dimension_numbers=dn1,
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                ).reshape(tx, s, c)                            # (Tx, Sy, C)
                d_window = jax.lax.dot_general(
                    wy, d_rows_t,
                    dimension_numbers=dn2,
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                )                                              # (Ty, Tx, C)

            for i, gl in enumerate(out_refs):
                th = min(ty, gl.shape[1])
                tw = min(tx, gl.shape[2])

                @pl.when(level == i)
                def _(gl=gl, th=th, tw=tw, d_window=d_window):
                    # Read-modify-write of the roi's class-window slice.
                    # Taps beyond the level's true extent (and beyond the
                    # class corner) carry zero weight in the interp
                    # matrices, so adding the [:th, :tw] corner is exact.
                    rd = pltpu.make_async_copy(
                        gl.at[bi, pl.ds(oy_c, th), pl.ds(ox_c, tw), :],
                        win2.at[pl.ds(0, th), pl.ds(0, tw), :],
                        sem,
                    )
                    rd.start()
                    rd.wait()
                    win2[:th, :tw, :] = (
                        win2[:th, :tw, :] + d_window[:th, :tw, :]
                    )
                    wr = pltpu.make_async_copy(
                        win2.at[pl.ds(0, th), pl.ds(0, tw), :],
                        gl.at[bi, pl.ds(oy_c, th), pl.ds(ox_c, tw), :],
                        sem,
                    )
                    wr.start()
                    wr.wait()


@functools.partial(
    jax.jit, static_argnames=("output_size", "sampling_ratio", "window", "interpret")
)
def multilevel_roi_align_bwd_pallas(
    feature_pyramid: dict[int, jnp.ndarray],
    rois: jnp.ndarray,
    g: jnp.ndarray,
    output_size: int = 7,
    sampling_ratio: int = 2,
    window: int = POOL_WINDOW,
    interpret: bool = False,
) -> dict[int, jnp.ndarray]:
    """Feature-pyramid gradient of :func:`multilevel_roi_align_pallas`.

    ``g``: cotangent of the pooled output — (R, S, S, C) or batched
    (B, R, S, S, C).  Returns a pyramid-shaped dict of gradients in the
    features' dtype.  Accumulation is f32 via per-roi window RMW
    (see :func:`_bwd_kernel`)."""
    levels, feats, ws_true, roi_params, b, r_per, batched = _prep(
        feature_pyramid, rois, output_size, window
    )
    n = b * r_per
    c = feats[0].shape[-1]
    t = window
    s = output_size
    g2 = g.reshape(n, s, s, c)
    zeros = [jnp.zeros(f.shape, jnp.float32) for f in feats]

    kernel = functools.partial(
        _bwd_kernel,
        num_levels=len(levels),
        t=t,
        output_size=s,
        sampling_ratio=sampling_ratio,
        interpret=interpret,
    )
    grads = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(
                (1, 1, roi_params.shape[-1]), lambda r: (r, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(
                (1, s, s, c), lambda r: (r, 0, 0, 0), memory_space=pltpu.VMEM
            ),
        ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in levels],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY) for _ in levels],
        scratch_shapes=[
            pltpu.VMEM((t, t, c), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(f.shape, jnp.float32) for f in feats
        ],
        input_output_aliases={2 + i: i for i in range(len(levels))},
        interpret=interpret,
        name="roi_align_bwd",  # read by name: ``roi_align.*bwd``
    )(roi_params, g2, *zeros)

    out = {}
    for i, l in enumerate(levels):
        gl = grads[i][:, :, : ws_true[i], :].astype(feature_pyramid[l].dtype)
        out[l] = gl if batched else gl[0]
    return out


def pallas_supported(feature_pyramid: dict, window: int = POOL_WINDOW) -> bool:
    """Static check that every level's layout is Mosaic-DMA-sliceable:
    channels must be a multiple of 128 (lane dim).  The x (sublane-tiled)
    dim, which the window copy slices, is zero-padded to a multiple of 8
    inside the kernel wrapper, so odd widths (recipe canvases) are fine.
    Single-level (C4) pyramids use the XLA path (their roi extent is
    unbounded by level reassignment)."""
    for f in feature_pyramid.values():
        if f.shape[-1] % 128 != 0:
            return False
    return len(feature_pyramid) > 1


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6)
)
def multilevel_roi_align_fast(
    feature_pyramid: dict[int, jnp.ndarray],
    rois: jnp.ndarray,
    output_size: int = 7,
    sampling_ratio: int = 2,
    window: int = POOL_WINDOW,
    interpret: bool = False,
    bwd_impl: str = "pallas",
) -> jnp.ndarray:
    """Pallas forward + selectable backward.

    Forward runs the kernel above; ``bwd_impl`` picks the VJP — "pallas"
    (default) is the window-RMW scatter-accumulate kernel
    (:func:`multilevel_roi_align_bwd_pallas`), "xla" differentiates the
    XLA implementation of the same function (:func:`multilevel_roi_align`
    with the matching extent-aware level assignment), which is exact
    because both compute identical outputs.  The config spelling is
    ``rcnn.roi_align_bwd_impl``; the MX_RCNN_POOL_BWD env var overrides
    either at trace time (A/B without touching the config).  Roi
    coordinates get no gradient (the reference's Proposal/ProposalTarget
    custom ops are forward-only too — SURVEY.md §4.1).  ``interpret``
    runs the kernel's pure-JAX emulation (CPU fake-mesh tests and the
    driver's multichip dryrun)."""
    return multilevel_roi_align_pallas(
        feature_pyramid, rois, output_size=output_size,
        sampling_ratio=sampling_ratio, window=window, interpret=interpret,
    )


def _fast_fwd(feature_pyramid, rois, output_size, sampling_ratio, window,
              interpret, bwd_impl):
    out = multilevel_roi_align_fast(
        feature_pyramid, rois, output_size, sampling_ratio, window, interpret,
        bwd_impl,
    )
    return out, (feature_pyramid, rois)


def _fast_bwd(output_size, sampling_ratio, window, interpret, bwd_impl, res, g):
    import os

    feature_pyramid, rois = res

    # Pallas window-RMW backward by default (the XLA autodiff backward is
    # a duplicate-index HBM scatter-add the TPU serializes: 18-19 ms/step
    # at R101-FPN train shapes vs ~3 ms for the kernel — see _bwd_kernel).
    # rcnn.roi_align_bwd_impl="xla" (or MX_RCNN_POOL_BWD=xla, which wins)
    # restores the old path for A/B and debugging.
    if os.environ.get("MX_RCNN_POOL_BWD", bwd_impl) != "xla":
        grad_pyramid = multilevel_roi_align_bwd_pallas(
            feature_pyramid, rois, g, output_size=output_size,
            sampling_ratio=sampling_ratio, window=window, interpret=interpret,
        )
        return grad_pyramid, jnp.zeros_like(rois)

    from mx_rcnn_tpu.ops.roi_align import multilevel_roi_align

    def ref(p, rr):
        return multilevel_roi_align(
            p, rr, output_size=output_size, sampling_ratio=sampling_ratio,
            max_extent_cells=window - 10,
        )

    if rois.ndim == 3:  # batched: vmap the XLA reference over images
        fn = lambda p: jax.vmap(ref)(p, rois)  # noqa: E731
    else:
        fn = lambda p: ref(p, rois)  # noqa: E731
    _, vjp = jax.vjp(fn, feature_pyramid)
    (grad_pyramid,) = vjp(g)
    return grad_pyramid, jnp.zeros_like(rois)


multilevel_roi_align_fast.defvjp(_fast_fwd, _fast_bwd)


def sharded_multilevel_roi_align(
    feature_pyramid: dict[int, jnp.ndarray],
    rois: jnp.ndarray,
    output_size: int,
    sampling_ratio: int,
    mesh,
    data_axis: str,
    window: int = POOL_WINDOW,
    interpret: bool = False,
    bwd_impl: str = "pallas",
) -> jnp.ndarray:
    """The kernel's multi-chip form: :func:`multilevel_roi_align_fast`
    per data-axis shard via ``jax.shard_map``.

    The batched kernel contract is already per-shard exact — each shard
    holds whole images (pyramid (B/n, H, W, C) + rois (B/n, R, 4)) and
    batch indices are computed from local shapes — so the wrap needs no
    collectives; it only stops GSPMD from replicating the opaque kernel
    call (gathering every image's pyramid to every chip), which is what a
    bare pallas_call under a sharded jit would get.

    Manual over EVERY mesh axis, with specs that name only ``data_axis``:
    jax refuses to lower a Mosaic kernel under a shard_map that leaves any
    axis to GSPMD ("Mosaic kernels cannot be automatically partitioned"),
    even an axis of size 1 — which a partial-manual wrap over the
    (data, model) mesh did until PR 21, unseen because interpret mode has
    no Mosaic call to refuse (tests/test_pallas.py now lowers this wrap
    for the TPU platform from the CPU).  Callers only take this path on a
    pure data-parallel mesh (spatial partitioning keeps the XLA form), so
    the other axes have size 1 and replicate nothing.
    ``check_vma=False``: the pallas out_shape carries no varying-mesh-axes
    annotation.  The custom_vjp rides inside, so the backward (the Pallas
    window-RMW kernel by default since r3; autodiff-of-XLA under
    ``bwd_impl="xla"`` or MX_RCNN_POOL_BWD=xla) is per-shard too."""
    from jax.sharding import PartitionSpec as P

    # Positional call: custom_vjp nondiff_argnums forbid keywords.
    def fn(pyramid, shard_rois):
        return multilevel_roi_align_fast(
            pyramid, shard_rois, output_size, sampling_ratio, window, interpret,
            bwd_impl,
        )

    wrapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(data_axis), P(data_axis)),
        out_specs=P(data_axis),
        check_vma=False,
    )
    return wrapped(feature_pyramid, rois)
