"""On-TPU probe of every ``pallas_call`` site (Mosaic compiles it at recipe
shapes and it matches its XLA oracle), of the one-level matmul ROIAlign
against the gather form, and of the tiled NMS against the dense oracle.

Runs on the REAL chip (one process, jax's default platform — the parent
test refuses anything but a TPU).  The interpret-mode CPU tests cannot see
what Mosaic accepts, MXU bf16 truncation, or VMEM limits, so this is the
only oracle for the on-chip claims in ``ops/pallas/``.  Shapes are the
``r50_fpn_coco`` recipe: 800x1344 canvas, P2-P5 at 256 channels, bf16.

Probes, each printed as one entry of the final ``RESULT {json}`` line:

- ``roi_align_fwd[train|eval|f32]`` — ``multilevel_roi_align_pallas`` at
  b2 x 512 rois (train), b8 x 1000 rois (eval), and b2 x 512 in f32 (the
  tiny_synthetic / overfit-golden dtype) vs ``multilevel_roi_align``.
- ``roi_align_bwd[train]`` — the window-RMW backward vs autodiff of the
  XLA reference (``MX_RCNN_POOL_BWD=xla``) with a bf16 cotangent.
- ``roi_align_matmul[vgg16_voc07.train_b16]`` — the one-level path
  (``ops/roi_align.py::roi_align_matmul``, plain XLA) at the benchmark
  cell's shape, 16 x 38 x 64 x 512 bf16 and 16 x 128 rois, vs the vmapped
  gather form, forward and feature gradient, with the measured time of
  each (the readings PERF.md quotes).
- ``nms_tiled[vgg16_voc07.train_b16,seed941]`` — the tiled
  ``nms_indices`` (``ops/nms.py``, plain XLA, the main path) vs the dense
  fixed point it replaced (``tests/oracles.py::nms_mask_dense``) at 16 x
  6000 -> 2000 on the pre-NMS candidates of a real step of the benchmark
  cell (seed 941's weights and first batch), with the measured time of
  each (the readings PERF.md quotes), and under two nested ``vmap``s
  (8 images x 5 levels cut from the same candidates).

Every probe is the main path of some preset: a failure is recorded with the
compiler's message and the process exits non-zero.

Each tolerance is written next to its check with its reason.  Also prints
two facts about the runtime the benchmark's timing method leans on
(``runtime``): what one dispatch costs, and whether ``block_until_ready``
is a true barrier.

- ``kda_intra[ling3_flash_vl_det.train_coco]`` — the chunk-local part of
  the KDA scan as the Pallas kernel pair (``ops/pallas/kda.py``) at the
  decoder cell's shape vs the XLA form, float32 against float32 and
  bfloat16 against float32, on the inputs that broke the XLA form on this
  chip, with the measured time of each.

- ``ssd[nemotron_twotower_det.train_coco]`` — the chunked state-space scan
  (``ops/ssd.py::ssd_chunked``) alone at the state-space cell's shape, as the
  Pallas kernel pair (``ssd_fwd`` / ``ssd_bwd``) and as the chunked XLA form,
  each vs the plain reference's token-by-token recurrence, bfloat16 and
  float32 at ``highest``, with the measured time of each forward and forward
  + backward.

- ``flash_attention[<cell>]`` — causal attention as the Pallas kernel pair
  (``ops/pallas/attention.py``) at each decoder cell's shape, q
  ``bf16[2, 4200, 32, 128]`` on 2 key heads and ``bf16[2, 4200, 32, 192]`` on
  32 with 128-wide values, vs the blocked XLA form it replaces on the TPU:
  float32 at ``highest`` both sides, bfloat16 of both against that float32,
  with the measured time of each form forward and forward + backward; and at
  a length whose last tile is narrower and ends at T exactly (2,304); and at
  the SambaY cell's shape (20 query pairs on 10 key pairs, keys 64, values
  128) over the whole prefix and under the window of 512, the windowed kernel
  also against the dense oracle.

- ``selective_scan[phi4_mini_flash_det.train_coco]`` — the selective scan
  (``ops/selective_scan.py``) alone at the SambaY cell's shape, as the Pallas
  kernel pair (``selective_scan_fwd`` / ``selective_scan_bwd``) and as the
  chunked XLA form, each vs the plain reference's token-by-token recurrence,
  with the measured time of each forward and forward + backward.

Run directly: python tests/_kernels_tpu_worker.py [word ...] (only the
probes whose name holds one of the words)
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CANVAS = (800, 1344)
CHANNELS = 256
# Largest relative rounding error of one bf16 rounding (8 significand bits).
BF16_EPS = 2.0 ** -8


def _least_ms(fn, *args, reps=3, calls=10):
    """Least mean time of ``calls`` chained calls over ``reps`` tries, ms."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best * 1e3


def _pyramid(rng, batch, dtype):
    import jax.numpy as jnp

    h, w = CANVAS
    return {
        lvl: jnp.asarray(
            rng.standard_normal((batch, h // s, w // s, CHANNELS)), dtype
        )
        for lvl, s in ((2, 4), (3, 8), (4, 16), (5, 32))
    }


def _rois(rng, batch, n):
    """Boxes log-uniform in size 16..600 px so all four levels get rois."""
    import jax.numpy as jnp
    import numpy as np

    h, w = CANVAS
    sizes = np.exp(rng.uniform(np.log(16), np.log(600), (batch, n, 2)))
    cx = rng.uniform(0, w, (batch, n))
    cy = rng.uniform(0, h, (batch, n))
    x1 = np.clip(cx - sizes[..., 0] / 2, 0, w - 2)
    y1 = np.clip(cy - sizes[..., 1] / 2, 0, h - 2)
    x2 = np.clip(x1 + sizes[..., 0], x1 + 1, w - 1)
    y2 = np.clip(y1 + sizes[..., 1], y1 + 1, h - 1)
    return jnp.asarray(np.stack([x1, y1, x2, y2], -1), jnp.float32)


def probe_roi_align_fwd(batch, n_rois, dtype_name):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mx_rcnn_tpu.ops.pallas.roi_align import (
        POOL_WINDOW,
        multilevel_roi_align_pallas,
    )
    from mx_rcnn_tpu.ops.roi_align import multilevel_roi_align

    rng = np.random.default_rng(0)
    dtype = jnp.dtype(dtype_name)
    pyramid = _pyramid(rng, batch, dtype)
    rois = _rois(rng, batch, n_rois)

    got = multilevel_roi_align_pallas(pyramid, rois)
    jax.block_until_ready(got)

    # Oracle one image at a time (lax.map): the XLA gather's intermediates
    # at b8 x 1000 rois need not fit beside the pyramid all at once.
    @jax.jit
    def oracle(p, r):
        return jax.lax.map(
            lambda pr: multilevel_roi_align(
                pr[0], pr[1], max_extent_cells=POOL_WINDOW - 10
            ),
            (p, r),
        )

    want = oracle(pyramid, rois)
    a = np.asarray(jax.device_get(got), np.float32)
    b = np.asarray(jax.device_get(want), np.float32)
    feat_scale = max(
        float(jnp.max(jnp.abs(f.astype(jnp.float32))))
        for f in pyramid.values()
    )
    rel = float(np.abs(a - b).max()) / feat_scale
    if dtype == jnp.bfloat16:
        # The oracle interpolates in f32 and rounds ONCE to bf16.  The
        # kernel's hi/lo split weights are f32-exact to ~2^-17, its
        # intermediate rows take one bf16 rounding (<= eps * max|feature|,
        # carried through x-weights that sum to <= 1), and its output is
        # rounded to bf16 too — two roundings of nearby values can land on
        # adjacent bf16 numbers (<= 2 eps * |out|).  Sum: 3 eps of the
        # feature scale.
        ceiling = 3 * BF16_EPS
    else:
        # f32 features take the HIGHEST-precision dots with exact f32
        # weights; only summation order differs from the oracle.  1e-4 of
        # the feature scale is the bound the CPU interpret tests hold the
        # same path to (atol 1e-4 on unit-scale features).
        ceiling = 1e-4
    return {
        "ok": bool(np.isfinite(a).all() and rel <= ceiling),
        "max_abs_diff_over_feature_scale": rel,
        "ceiling": ceiling,
        "feature_scale": feat_scale,
        "shape": list(a.shape),
    }


def probe_roi_align_bwd(batch, n_rois):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mx_rcnn_tpu.ops.pallas.roi_align import multilevel_roi_align_fast

    rng = np.random.default_rng(0)
    pyramid = _pyramid(rng, batch, jnp.bfloat16)
    rois = _rois(rng, batch, n_rois)
    # Fixed bf16 cotangent via a linear loss: grad arrives in the output
    # dtype (bf16), exactly as in the train graph.
    cot = jnp.asarray(
        rng.standard_normal((batch, n_rois, 7, 7, CHANNELS)), jnp.bfloat16
    )

    # Two distinct traced functions: the env var is read at TRACE time
    # inside _fast_bwd, and reusing one jitted function would silently
    # replay the first trace's choice.
    def make_loss():
        def loss(p):
            out = multilevel_roi_align_fast(p, rois)
            return jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32))

        return loss

    try:
        os.environ["MX_RCNN_POOL_BWD"] = "pallas"
        g_pallas = jax.jit(jax.grad(make_loss()))(pyramid)
        jax.block_until_ready(g_pallas)
        os.environ["MX_RCNN_POOL_BWD"] = "xla"
        g_xla = jax.jit(jax.grad(make_loss()))(pyramid)
    finally:
        del os.environ["MX_RCNN_POOL_BWD"]

    levels, worst = {}, 0.0
    for lvl in pyramid:
        a = np.asarray(jax.device_get(g_pallas[lvl]), np.float32)
        b = np.asarray(jax.device_get(g_xla[lvl]), np.float32)
        scale = float(np.abs(b).max()) or 1.0
        diff = float(np.abs(a - b).max())
        levels[f"P{lvl}"] = {
            "max_abs_diff": diff, "grad_scale": scale, "rel": diff / scale,
        }
        worst = max(worst, diff / scale)
    # Normalized (per-level max-abs / grad-scale) disagreement.  Both
    # backends round in bf16: the kernel truncates weights and its
    # intermediate once each, the XLA scatter-add ACCUMULATES in bf16
    # (hundreds of += per P2 cell), so the band is a few bf16 roundings of
    # the gradient scale, not one.  0.03 ~ 8 eps; the value measured when
    # the kernel was written was 0.0092, and PR 21's run on the local
    # libtpu is recorded in PERF.md.
    ceiling = 0.03
    return {
        "ok": bool(worst <= ceiling),
        "worst_rel": worst,
        "ceiling": ceiling,
        "levels": levels,
    }


def probe_roi_align_matmul(batch, h, w, channels, n_rois):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mx_rcnn_tpu.ops.roi_align import roi_align, roi_align_matmul

    rng = np.random.default_rng(0)
    feat = jnp.asarray(
        rng.standard_normal((batch, h, w, channels)), jnp.bfloat16
    )
    # Boxes log-uniform from one cell to past the whole map, centres
    # anywhere on it: some reach past every border.
    size = np.exp(rng.uniform(np.log(16), np.log(w * 16 * 1.1), (batch, n_rois, 2)))
    cx = rng.uniform(0, w * 16, (batch, n_rois))
    cy = rng.uniform(0, h * 16, (batch, n_rois))
    rois = jnp.asarray(
        np.stack(
            [cx - size[..., 0] / 2, cy - size[..., 1] / 2,
             cx + size[..., 0] / 2, cy + size[..., 1] / 2], -1,
        ),
        jnp.float32,
    )
    cot = jnp.asarray(
        rng.standard_normal((batch, n_rois, 7, 7, channels)), jnp.bfloat16
    )

    def both_ways(pool):
        def run(f, r, g):
            out, vjp = jax.vjp(lambda x: pool(x, r), f)
            return out, vjp(g)[0]

        return jax.jit(run)

    forms = {
        "matmul": both_ways(lambda f, r: roi_align_matmul(f, r, 7, 1 / 16.0, 2)),
        "gather": both_ways(
            jax.vmap(lambda f, r: roi_align(f, r, 7, 1 / 16.0, 2))
        ),
    }
    out, ms = {}, {}
    for name, fn in forms.items():
        out[name] = jax.block_until_ready(fn(feat, rois, cot))
        ms[name] = round(_least_ms(fn, feat, rois, cot, calls=5), 3)

    def f32(x):
        return np.asarray(jax.device_get(x), np.float32)

    feat_scale = float(jnp.max(jnp.abs(feat.astype(jnp.float32))))
    fwd_rel = float(np.abs(f32(out["matmul"][0]) - f32(out["gather"][0])).max()) / feat_scale
    g_m, g_g = f32(out["matmul"][1]), f32(out["gather"][1])
    bwd_rel = float(np.abs(g_m - g_g).max()) / float(np.abs(g_g).max())
    # Forward: both forms interpolate to f32 accuracy (the matmul's split
    # weights are exact to ~2^-17) and round once to bf16, so they differ
    # by adjacent bf16 numbers at most — inside the 3 eps of the feature
    # scale the Pallas forward is held to.  Backward: the gather's
    # scatter-add accumulates in bf16 (the band of probe_roi_align_bwd,
    # 0.03 of the gradient scale); the matmul accumulates in f32.
    fwd_ceiling, bwd_ceiling = 3 * BF16_EPS, 0.03
    return {
        "ok": bool(
            np.isfinite(g_m).all()
            and fwd_rel <= fwd_ceiling and bwd_rel <= bwd_ceiling
        ),
        "fwd_max_abs_diff_over_feature_scale": fwd_rel,
        "fwd_ceiling": fwd_ceiling,
        "bwd_max_abs_diff_over_grad_scale": bwd_rel,
        "bwd_ceiling": bwd_ceiling,
        "fwd_plus_bwd_ms": ms,
        "shape": list(out["matmul"][0].shape),
    }


def probe_nms_tiled(seed):
    """The tiled ``nms_indices`` (ops/nms.py, PR 29) against the dense fixed
    point it replaced (tests/oracles.py) on a real step's candidates, bit
    for bit: at the benchmark cell's shape, 16 images x 6000 candidates ->
    2000 at 0.7, with the measured time of each; and under two nested
    ``vmap``s (images x levels, as every pyramid preset reaches it), where
    an unrolled tile loop came out WRONG on this chip while the CPU agreed
    (PERF.md section 6, PR 29) - several tiles (8 x 5 x <= 2000) and one
    (8 x 5 x 300)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from oracles import nms_mask_dense  # tests/, this script's own directory

    from mx_rcnn_tpu.ops.nms import TILE, nms_indices, rank_keep

    def tiled(k):
        return lambda b, s: nms_indices(b, s, 0.7, k)

    def dense(k):
        return jax.jit(jax.vmap(
            lambda b, s: rank_keep(nms_mask_dense(b, s, 0.7), s, k)
        ))

    def slots_that_differ(got, want):
        # Exact: the same snapped IoUs meet the same threshold in both, and
        # the greedy result is a function of those decisions alone.
        return sum(
            int((np.asarray(g).reshape(w.shape) != np.asarray(w)).sum())
            for g, w in zip(jax.device_get(got), jax.device_get(want))
        )

    boxes, scores = real_step_candidates(seed)
    flat, flat_dense = jax.jit(jax.vmap(tiled(2000))), dense(2000)
    want = flat_dense(boxes, scores)
    bad = slots_that_differ(flat(boxes, scores), want)

    # Levels: every third candidate of an image at three offsets and two
    # shorter ones, padded with -inf as generate_fpn_proposals pads.
    n, lens = boxes.shape[1], ((0, 2000), (1, 2000), (2, 2000), (0, 1200), (1, 300))
    lb = np.zeros((8, len(lens), 2000, 4), np.float32)
    ls = np.full((8, len(lens), 2000), -np.inf, np.float32)
    for i in range(8):
        for l, (off, ln) in enumerate(lens):
            idx = np.arange(off, n, 3)[:ln]
            lb[i, l, :len(idx)] = np.asarray(boxes[i])[idx]
            ls[i, l, :len(idx)] = np.asarray(scores[i])[idx]
    nested_bad = {}
    for k in (2000, 300):
        b, s = jnp.asarray(lb[:, :, :k]), jnp.asarray(ls[:, :, :k])
        nested_bad[f"8x5x{k}"] = slots_that_differ(
            jax.jit(jax.vmap(jax.vmap(tiled(k))))(b, s),
            dense(k)(b.reshape(-1, k, 4), s.reshape(-1, k)),
        )
    return {
        "ok": bad == 0 and not any(nested_bad.values()),
        "mismatched_slots": bad,
        "mismatched_slots_nested": nested_bad,
        "kept": int(np.asarray(jax.device_get(want[1])).sum()),
        "shape": list(boxes.shape),
        "tile": TILE,
        "tiled_ms": _least_ms(flat, boxes, scores),
        "dense_ms": _least_ms(flat_dense, boxes, scores),
    }


def probe_kda_intra(b, t, h):
    """The chunk-local part of the KDA scan as the Pallas kernel pair
    (``ops/pallas/kda.py``: ``kda_intra_fwd``, ``kda_intra_bwd``) at the
    decoder cell's shape, q ``bf16[2, 4200, 32, 128]``: Mosaic compiles both;
    the six results and the five gradients in float32 against the XLA form
    (``ops/kda.py::_intra``), where both are float32 at ``highest`` and only
    the order of sums differs; the whole ``kda_chunked`` in bfloat16, result
    and gradients, against its float32 self on the XLA form; the residual of
    the kernel's triangular inverse where keys look alike; and the time of
    each form, forward and forward + backward.  The heads hold what broke
    the XLA form on this chip (PERF.md section 6, PR 27): head 0 sits at the
    safe gate's lower bound, head 1 at its upper, head 2 has keys that are
    one vector but for 5 % (I + A near the all-ones triangle), the rest draw
    the gate over its whole range."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from mx_rcnn_tpu.ops import kda
    from mx_rcnn_tpu.ops.pallas import kda as kernel

    d, far = 128, 41.0
    ks = jax.random.split(jax.random.PRNGKey(31), 8)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    alike = unit(jax.random.normal(ks[5], (b, 1, 1, d))
                 + 0.05 * jax.random.normal(ks[6], (b, t, 1, d)))
    q = unit(jax.random.normal(ks[0], (b, t, h, d))) * d**-0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, d))).at[:, :, 2:3].set(alike)
    v = jax.random.normal(ks[2], (b, t, h, d))
    g = -5.0 * jax.nn.sigmoid(2.3 * jax.random.normal(ks[3], (b, t, h, d)))
    g = g.at[:, :, 0].set(-5.0).at[:, :, 1:3].set(-1e-3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))).at[:, :, 2].set(0.97)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    args = (q, k, v, g, beta)
    cot = jax.random.normal(ks[7], (b, t, h, d))
    rel = lambda got, want: float(
        jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want))

    def xla_intra(dtype):
        def fn(q, k, v, g, beta):
            n = -(-t // kda.CHUNK)
            ch = lambda x, kind: jnp.moveaxis(jnp.pad(
                x.astype(kind), ((0, 0), (0, n * kda.CHUNK - t)) + ((0, 0),) * (x.ndim - 2)
            ).reshape((b, n, kda.CHUNK) + x.shape[2:]), 3, 1)
            xs = jax.checkpoint(kda._intra, static_argnums=(5, 6, 7))(
                ch(q, dtype), ch(k, dtype), ch(v, dtype), ch(g, jnp.float32),
                ch(beta, jnp.float32)[..., None], 16, far, dtype)
            return tuple(jnp.moveaxis(x, 2, 0) for x in xs)
        return fn

    def with_grads(fn):
        """The results and, against fixed cotangents, the five gradients."""
        def loss(*a):
            out = fn(*a)
            return sum(jnp.sum(x.astype(jnp.float32) * jnp.cos(0.1 * i + x.astype(jnp.float32)))
                       for i, x in enumerate(out)), out
        return jax.jit(lambda *a: jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*a))

    # 1. float32 against float32: the kernel's arithmetic is the XLA form's.
    parts = {}
    wide = tuple(x.astype(jnp.float32) for x in args)
    got = with_grads(lambda *a: kernel.kda_intra(*a, jnp.float32, far))(*wide)
    want = with_grads(xla_intra(jnp.float32))(*wide)
    for name, x, y in zip(("w", "u0", "qe", "p", "ke", "eg"), got[0][1], want[0][1]):
        parts[name] = rel(x, y)
    for name, x, y in zip(("dq", "dk", "dv", "dg", "dbeta"), got[1], want[1]):
        parts[name] = rel(x, y.astype(jnp.float32))
    del got, want

    # 2. the whole op in bfloat16 against its float32 self on the XLA form.
    def chunked(dtype, takes_kernel):
        def fn(*a):
            held, kda._takes_kernel = kda._takes_kernel, lambda *shape: takes_kernel
            try:
                return (kda.kda_chunked(*a, dtype=dtype),)
            finally:
                kda._takes_kernel = held
        return fn

    want = with_grads(chunked(jnp.float32, False))(*args)
    whole = {}
    for form, takes_kernel in (("kernel", True), ("xla", False)):
        got = with_grads(chunked(jnp.bfloat16, takes_kernel))(*args)
        whole[form] = {"o": rel(got[0][1][0], want[0][1][0])}
        for name, x, y in zip(("dq", "dk", "dv", "dg", "dbeta"), got[1], want[1]):
            whole[form][name] = rel(x, y.astype(jnp.float32))
    del got, want

    # 3. the kernel's inverse where keys look alike: |(I + A) T - I|.
    kk = alike[0, :kernel.CHUNK, 0].astype(jnp.bfloat16).astype(jnp.float32)
    pos = jnp.arange(kernel.CHUNK)
    a = jnp.where(pos[:, None] > pos[None, :], 0.97 * jnp.dot(kk, kk.T, precision="highest"), 0.0)

    def inverse(a_ref, t_ref):
        row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
        t_ref[...] = kernel._alone(kernel._unit_lower_inverse(a_ref[...], row, col))

    tri = pl.pallas_call(inverse, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
                         interpret=False)(a)
    eye = np.eye(kernel.CHUNK)
    residual = float(np.abs((eye + np.asarray(a, np.float64)) @ np.asarray(tri, np.float64) - eye).max())

    # 4. the time of each form on the scan's own operands (bfloat16).
    fwd = lambda fn: jax.jit(fn)
    kern, xla = lambda *a: kernel.kda_intra(*a, jnp.bfloat16, far), xla_intra(jnp.bfloat16)
    ms = {
        "kernel_ms": _least_ms(fwd(kern), *args), "xla_ms": _least_ms(fwd(xla), *args),
        "kernel_fwd_bwd_ms": _least_ms(with_grads(kern), *args),
        "xla_fwd_bwd_ms": _least_ms(with_grads(xla), *args),
    }
    # float32 sums in another order: 1e-4 is a hundred roundings' room and a
    # tenth of what one bfloat16 operand inside a chunk would read; the decay's
    # gradient is a small difference of large terms where a channel decays hard.
    # bfloat16 against float32: what the XLA form reads on the same inputs
    # (0.15-0.22 % forward, 0.24-0.44 % on the gradients on plain draws on
    # this chip, PERF.md section 6, PR 27) and a fifth more, for the roundings
    # that fall otherwise.
    ok = (all(x < (1e-3 if n == "dg" else 1e-4) for n, x in parts.items())
          and all(x <= 1.2 * whole["xla"][n] for n, x in whole["kernel"].items())
          and residual <= 1e-5)
    return {"ok": ok, "rel_l2_f32_kernel_vs_xla": parts, "rel_l2_bf16_vs_f32": whole,
            "inverse_residual": residual, **ms, "shape": [b, t, h, d],
            "chunks_per_step": kernel._chunks_per_step(-(-t // kernel.CHUNK))}


def probe_flash_attention(b, t, h, hkv, dk, dv, window=None):
    """Causal attention as the Pallas kernel pair (``ops/pallas/attention.py``:
    ``flash_attention_fwd``, ``flash_attention_bwd``) at a decoder cell's
    shape: Mosaic compiles both; result and the three gradients in float32
    against the blocked XLA form (``ops/attention.py::causal_attention`` as
    every other platform runs it), where both multiply at ``highest`` and only
    the order of sums differs; bfloat16 operands, kernel and XLA form, against
    that float32 (the same roundings: one of q, k, v, one of the probabilities
    and of the scores' cotangents as a matmul's operand); and the time of each
    form, forward and forward + backward.  Query head 0 (and key head 0) of
    every image holds a flat image's patch tokens: queries, keys and values
    that are each one vector but for 5 %, so the softmax is near uniform over
    thousands of keys, the scores' cotangents sum to nothing over a row and dq
    is the small remainder (``dq_alike``, read on that head alone: the number
    that caught a kernel whose sum(o * do) saw another rounding of do than its
    dp, PERF.md section 6, PR 33); query head 1 is ten times the size (a
    softmax near one-hot: the running max moves at every tile).  Under a
    ``window`` both forms take it, and the kernel in float32 is also held to
    the DENSE oracle (the whole masked score matrix at ``highest``) on the
    first two key heads and their query heads (``rel_l2_f32_kernel_vs_dense``)."""
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.ops import attention
    from mx_rcnn_tpu.ops.pallas import attention as kernel

    ks = jax.random.split(jax.random.PRNGKey(33), 6)
    alike = lambda key, d: (jax.random.normal(jax.random.fold_in(key, 0), (b, 1, d))
                            + 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (b, t, d)))
    q = jax.random.normal(ks[0], (b, t, h, dk)).at[:, :, 1].multiply(10.0)
    q = q.at[:, :, 0].set(alike(ks[4], dk))
    k = jax.random.normal(ks[1], (b, t, hkv, dk)).at[:, :, 0].set(alike(ks[5], dk))
    v = jax.random.normal(ks[2], (b, t, hkv, dv)).at[:, :, 0].set(alike(ks[0], dv))
    # what the mixers hand over: q, k, v already rounded to bfloat16
    args = tuple(x.astype(jnp.bfloat16).astype(jnp.float32) for x in (q, k, v))
    cot = jax.random.normal(ks[3], (b, t, h, dv))
    scale = dk ** -0.5
    rel = lambda got, want: float(
        jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want))
    assert kernel.supported(t, h, hkv, dk, dv, jnp.bfloat16)

    def form(takes_kernel, dtype):
        def fn(*a):
            held, attention._takes_kernel = attention._takes_kernel, lambda *shape: takes_kernel
            try:
                return attention.causal_attention(*a, scale, dtype=dtype, window=window)
            finally:
                attention._takes_kernel = held
        return fn

    def with_grads(fn, cot=cot):
        loss = lambda *a: (jnp.sum(fn(*a) * cot), fn(*a))
        return jax.jit(lambda *a: jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(*a))

    def readings(got, want):
        out = {"o": rel(got[0][1], want[0][1])}
        out.update({n: rel(x, y) for n, x, y in zip(("dq", "dk", "dv"), got[1], want[1])})
        out["dq_alike"] = rel(got[1][0][:, :, 0], want[1][0][:, :, 0])
        return out

    # float32 operands at the chip's default precision are one bfloat16 pass:
    # the XLA form is held at ``highest``, as the kernel holds itself.
    with jax.default_matmul_precision("highest"):
        want = with_grads(form(False, jnp.float32))(*args)
    wide = readings(with_grads(form(True, jnp.float32))(*args), want)
    half = {name: readings(with_grads(form(takes, jnp.bfloat16))(*args), want)
            for name, takes in (("kernel", True), ("xla", False))}
    finite = all(bool(jnp.isfinite(x).all()) for x in with_grads(form(True, jnp.bfloat16))(*args)[1])
    del want
    dense = None
    if window is not None:
        few = 2 * (h // hkv)
        part = (args[0][:, :, :few], args[1][:, :, :2], args[2][:, :, :2])
        oracle = lambda *a: attention.causal_attention_dense(*a, scale, window=window)
        dense = readings(with_grads(form(True, jnp.float32), cot[:, :, :few])(*part),
                         with_grads(oracle, cot[:, :, :few])(*part))
    narrow = tuple(x.astype(jnp.bfloat16) for x in args)
    ms = {}
    for name, takes in (("kernel", True), ("xla", False)):
        fn = form(takes, jnp.bfloat16)
        ms[name + "_ms"] = _least_ms(jax.jit(fn), *narrow)
        ms[name + "_fwd_bwd_ms"] = _least_ms(with_grads(fn), *narrow)
    # float32 sums in another order: 1e-4 is a hundred roundings' room and a
    # twentieth of what one bfloat16 operand reads.  bfloat16 against float32:
    # what the XLA form reads on the same inputs and a fifth more, for the
    # roundings that fall otherwise (the kernel casts the probabilities before
    # the division by their sum, the XLA form after it); twice on the alike
    # head's dq, a remainder a hundred times smaller than its terms (a kernel
    # that breaks the cancellation reads ten to two hundred times).
    # ``dq_alike`` in float32 is a small difference of large sums on both sides.
    near = lambda found: all(x < (1e-2 if n == "dq_alike" else 1e-4) for n, x in found.items())
    ok = (finite and near(wide) and (dense is None or near(dense))
          and all(x <= (2.0 if n == "dq_alike" else 1.2) * half["xla"][n]
                  for n, x in half["kernel"].items()))
    out = {"ok": ok, "rel_l2_f32_kernel_vs_xla": wide, "rel_l2_bf16_vs_f32": half, **ms,
           "shape": [b, t, h, hkv, dk, dv], "tile": kernel.TILE}
    if window is not None:
        out.update(window=window, rel_l2_f32_kernel_vs_dense=dense)
    return out


def probe_selective_scan(b, t):
    """The selective scan (``ops/selective_scan.py::selective_scan_chunked``) in
    both its forms - the chunked XLA form and the Pallas kernel pair
    (``ops/pallas/selective_scan.py``, what ``selective_scan_chunked`` takes on
    a TPU) - alone at the SambaY cell's shape, x ``bf16[2, 4200, 5120]``, dt
    ``f32[2, 4200, 5120]``, B and C ``f32[2, 4200, 16]``, chunk 128: result and
    the six gradients of each against the float32 token-by-token oracle (the
    plain reference's ``recurrence``, a scan of checkpointed scans), and each
    one's time forward and forward + backward.  The channels hold the ends of the ranges:
    channel 0 ``dt`` 1e-3 with A 1 (a chunk keeps 88 % of its state: the carry
    is everything), channel 1 ``dt`` 8 with A 1-16 (forgets within a token:
    the exponents a quotient form would overflow on), channel 2 sees tokens
    that are one value but for 5 % (a flat image's); the rest draw ``dt``
    log-uniform in 1e-3..0.5 and A uniform in 1..16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mx_rcnn_tpu.ops import selective_scan as scan
    from mx_rcnn_tpu.ops.pallas import selective_scan as kernel
    from mx_rcnn_tpu.ops.selective_scan import CHUNK, selective_scan_chunked
    from perfbench.reference.backbone_phi4_mini_flash import recurrence

    ch, n = 5120, 16
    ks = jax.random.split(jax.random.PRNGKey(34), 8)
    x = jax.random.normal(ks[0], (b, t, ch))
    x = x.at[:, :, 2].set(1.0 + 0.05 * jax.random.normal(ks[6], (b, t)))
    bm, cm = jax.random.normal(ks[1], (b, t, n)), jax.random.normal(ks[2], (b, t, n))
    lo, hi = np.log(1e-3), np.log(0.5)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(ks[3], (b, t, ch)))
    dt = dt.at[:, :, 0].set(1e-3).at[:, :, 1].set(8.0)
    a = -jax.random.uniform(ks[4], (ch, n), minval=1.0, maxval=16.0).at[0].set(1.0)
    d = jax.random.uniform(ks[5], (ch,), minval=0.7, maxval=1.0)
    x = x.astype(jnp.bfloat16).astype(jnp.float32)      # what the mixer hands over
    args = (x, dt, a, bm, cm, d)
    cot = jax.random.normal(jax.random.PRNGKey(35), (b, t, ch))
    rel = lambda got, want: float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    names = ("dx", "ddt", "da", "db", "dc", "dd")

    def with_grads(fn):
        loss = lambda *m: (jnp.sum(fn(*m) * cot), fn(*m))
        return jax.jit(lambda *m: jax.value_and_grad(loss, argnums=range(6), has_aux=True)(*m))

    def oracle(x, dt, a, bm, cm, d):
        one = lambda x, dt, bm, cm: recurrence(x, dt, a, bm, cm)
        return jax.lax.map(lambda m: one(*m), (x, dt, bm, cm)) + d * x

    def xla_form(*m):       # the path off the TPU, whatever the backend
        takes, scan._takes_kernel = scan._takes_kernel, lambda *_: False
        try:
            return selective_scan_chunked(*m)
        finally:
            scan._takes_kernel = takes

    assert scan._takes_kernel(t, ch, n, CHUNK), "the cell's shape is one the kernel pair takes"
    forms = {"chunked": xla_form, "kernel": selective_scan_chunked}
    want = with_grads(oracle)(*args)
    narrow = (x.astype(jnp.bfloat16),) + args[1:]
    found, ms, finite = {}, {}, True
    for name, form in forms.items():
        got = with_grads(form)(*args)
        found[name] = {"y": rel(got[0][1], want[0][1])}
        found[name].update({k: rel(u, v) for k, u, v in zip(names, got[1], want[1])})
        finite = finite and all(bool(jnp.isfinite(u).all()) for u in got[1])
        del got
        ms[name + "_ms"] = _least_ms(jax.jit(form), *narrow)
        ms[name + "_fwd_bwd_ms"] = _least_ms(with_grads(form), *narrow)
    # float32 on all sides, sums in another order
    ok = finite and all(v < 1e-4 for one in found.values() for v in one.values())
    return {"ok": ok, "rel_l2_vs_recurrence": found["chunked"],
            "rel_l2_kernel_vs_recurrence": found["kernel"], **ms, "shape": [b, t, ch, n],
            "chunk": CHUNK, "kernel_chunk": kernel.CHUNK, "kernel_block": kernel.BLOCK,
            "kernel_unroll": kernel.UNROLL}


def probe_ssd(b, t, g=8):
    """The chunked state-space scan (``ops/ssd.py::ssd_chunked``) in both its
    forms - the chunked XLA form and the Pallas kernel pair
    (``ops/pallas/ssd.py``, what ``ssd_chunked`` takes on a TPU) - alone at a
    state-space cell's shape, x ``bf16[2, 4200, 64, 64]``, B and C
    ``bf16[2, 4200, g, 128]`` (``g`` 8 on the nemotron cell, ONE group for all
    64 heads on the granite cell), chunk 128: result and the six gradients of each
    form with bfloat16 operands, and in float32 at ``highest`` (order of sums
    only), against the float32 token-by-token oracle (the plain reference's
    ``recurrence``, a scan of checkpointed scans: the backward of
    ``ssd_recurrent`` would keep 4,200 states of 4 MB an image), and each
    one's time forward and forward + backward in the layouts the mixer hands
    over (x (B, T, H P)).  The heads hold the ends of the
    published ranges: head 0 ``dt`` at ``time_step_min`` with A 1 (a chunk
    keeps 88 % of its state: the carry is everything), head 1 ``dt`` at
    ``time_step_max`` with A 16 (forgets within a few tokens), head 2 sees
    tokens that are one vector but for 5 % (a flat image's: ``alike`` reads
    its own dx, ddt and dA, what a hand-written backward must still cancel as
    autodiff did), the rest draw ``dt`` log-uniform and A uniform over the
    ranges."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mx_rcnn_tpu.ops import ssd as scan
    from mx_rcnn_tpu.ops.pallas import ssd as kernel
    from mx_rcnn_tpu.ops.ssd import CHUNK, ssd_chunked
    from perfbench.reference.backbone_nemotron_twotower import recurrence

    h, p, n = 64, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(32), 8)
    x = jax.random.normal(ks[0], (b, t, h, p))
    alike = jax.random.normal(ks[6], (b, 1, 1, p)) + 0.05 * jax.random.normal(ks[7], (b, t, 1, p))
    x = x.at[:, :, 2:3].set(alike)
    bm, cm = jax.random.normal(ks[1], (b, t, g, n)), jax.random.normal(ks[2], (b, t, g, n))
    lo, hi = np.log(1e-3), np.log(0.1)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(ks[3], (b, t, h)))
    dt = dt.at[:, :, 0].set(1e-3).at[:, :, 1].set(0.1)
    a = -jax.random.uniform(ks[4], (h,), minval=1.0, maxval=16.0).at[0].set(1.0).at[1].set(16.0)
    d = jax.random.uniform(ks[5], (h,), minval=0.7, maxval=1.0)
    # what the mixer hands over: x, B, C already rounded to bfloat16
    x, bm, cm = (m.astype(jnp.bfloat16).astype(jnp.float32) for m in (x, bm, cm))
    args = (x, dt, a, bm, cm, d)
    narrow = (x.astype(jnp.bfloat16), dt, a, bm.astype(jnp.bfloat16), cm.astype(jnp.bfloat16), d)
    cot = jax.random.normal(jax.random.PRNGKey(33), (b, t, h, p))
    rel = lambda got, want: float(
        jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want))
    names = ("dx", "ddt", "da", "db", "dc", "dd")

    def with_grads(fn, cot=cot):
        def loss(*m):
            y = fn(*m)                  # once: a kernel's forward is not merged as XLA's is
            return jnp.sum(y * cot), y
        return jax.jit(lambda *m: jax.value_and_grad(loss, argnums=range(6), has_aux=True)(*m))

    def as_the_mixer_hands_over(form):
        """x (B, T, H P), B and C (B, T, G N) in, y (B, T, H P) out: the layouts
        of the mixer's step, where the reshapes cost nothing.  (Handed (B, T, H,
        P), the kernel pair pays a relayout of x and of the float32 y, 0.7 ms
        forward at this shape.)"""
        def flat(x, dt, a, bm, cm, d):
            return form(x.reshape(b, t, h, p), dt, a, bm.reshape(b, t, g, n),
                        cm.reshape(b, t, g, n), d).reshape(b, t, h * p)
        return flat

    def oracle(x, dt, a, bm, cm, d):
        heads = lambda m: jnp.repeat(m, h // g, axis=1)
        one = lambda x, dt, bm, cm: recurrence(x, dt, a, heads(bm), heads(cm))
        return jax.vmap(one)(x, dt, bm, cm) + d[:, None] * x

    def xla_form(dtype):        # the path off the TPU, whatever the backend
        def form(*m):
            takes, scan._takes_kernel = scan._takes_kernel, lambda *_: False
            try:
                return ssd_chunked(*m, dtype=dtype)
            finally:
                scan._takes_kernel = takes
        return form

    assert scan._takes_kernel(t, h, p, g, n, CHUNK), "the cell's shape is one the kernel pair takes"
    forms = {"chunked": xla_form, "kernel": lambda dtype: lambda *m: ssd_chunked(*m, dtype=dtype)}
    want = with_grads(oracle)(*args)
    flat_narrow = (narrow[0].reshape(b, t, h * p), dt, a, narrow[3].reshape(b, t, g * n),
                   narrow[4].reshape(b, t, g * n), d)
    found, alike_found, ms, finite = {}, {}, {}, True
    for name, form in forms.items():
        for kind, dtype, inputs in (("bf16", jnp.bfloat16, narrow), ("f32", jnp.float32, args)):
            # float32 operands at the chip's default precision are one bfloat16
            # pass: the float32 forms are held at ``highest``, as the oracle is.
            with jax.default_matmul_precision("highest" if kind == "f32" else "default"):
                got = with_grads(form(dtype))(*inputs)
            one = found[f"{name}_{kind}"] = {"y": rel(got[0][1], want[0][1])}
            one.update({k: rel(u, v) for k, u, v in zip(names, got[1], want[1])})
            alike_found[f"{name}_{kind}"] = {
                "dx": rel(got[1][0][:, :, 2], want[1][0][:, :, 2]),
                "ddt": rel(got[1][1][:, :, 2], want[1][1][:, :, 2]),
                "da": rel(got[1][2][2:3], want[1][2][2:3])}
            finite = finite and all(bool(jnp.isfinite(u).all()) for u in got[1])
            del got
        scan_bf16 = as_the_mixer_hands_over(form(jnp.bfloat16))
        ms[name + "_ms"] = _least_ms(jax.jit(scan_bf16), *flat_narrow)
        ms[name + "_fwd_bwd_ms"] = _least_ms(
            with_grads(scan_bf16, cot.reshape(b, t, h * p)), *flat_narrow)
    # float32 sums in another order; bfloat16 operands read what the other
    # decoder cell's scan does against its oracle (0.2-0.8 %, PERF.md section 6);
    # the kernel pair no farther from the oracle than 1.25 x the XLA form, reading
    # by reading (ISSUE 37), or than float32's own rounding (dD: the XLA form's
    # sum is the oracle's to the bit, 0 on the CPU)
    ok = finite and all(v < 1e-3 for k in ("chunked_f32", "kernel_f32") for v in found[k].values()) \
        and all(v < 2e-2 for k in ("chunked_bf16", "kernel_bf16") for v in found[k].values()) \
        and all(found["kernel_bf16"][k] <= max(1.25 * found["chunked_bf16"][k], 1e-5)
                for k in found["kernel_bf16"])
    return {"ok": ok, "rel_l2_vs_recurrence": found, "alike_head_rel_l2": alike_found, **ms,
            "shape": [b, t, h, p], "groups": g, "chunk": CHUNK, "kernel_chunk": kernel.CHUNK}


def real_step_candidates(seed, workload="vgg16_voc07.train_b16"):
    """The pre-NMS candidates of a real step: the benchmark cell's model with
    the weights and the first batch of ``--seed``, forward to the RPN, top-k,
    decode and clip as ``generate_proposals`` does it.  -> boxes (B, N, 4),
    scores (B, N) with ``-inf`` on what the min-size mask dropped: what
    ``nms_indices`` is handed inside the step, so the chain depth is the
    cell's and not a generator's."""
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.detection.graph import level_anchors, prep_images
    from mx_rcnn_tpu.ops.proposals import _pre_nms_candidates
    from mx_rcnn_tpu.train.loop import build_all, build_plan
    from mx_rcnn_tpu.train.state import state_variables
    from perfbench import program, traffic
    from perfbench import weights as W
    from perfbench.reference import detector as D
    from perfbench.spec import Spec

    spec = Spec(REPO)
    cell = spec.cell(workload)
    conf = spec.config(cell["config"])
    cfg = program.load_config(conf, cell)
    model, _, state, _, global_batch = build_all(cfg, None)
    w0 = W.make_weights(seed, D.all_specs(conf["reference"]))
    variables = state_variables(state)
    variables = {
        k: program._place_weights(program._unfreeze(v), w0, k)
        for k, v in variables.items()
    }
    images, boxes, classes = traffic.make_images(
        spec.traffic(cell["traffic"]), conf["reference"]["num_classes"], seed
    )
    feed = program.train_feed(
        cfg, build_plan(cfg, None, model=model), None,
        program.records(images, boxes, classes), global_batch,
        seed % (2**31), program.prefetch_stats(),
    )
    try:
        batch = next(feed)
    finally:
        feed.close()
    rpn = cfg.model.rpn

    @jax.jit
    def candidates(variables, batch):
        x = prep_images(batch.images, (cfg.data.pixel_mean, cfg.data.pixel_std))
        feats, _ = model.apply(variables, x, method="features", mutable=["counters"])
        out = model.apply(variables, feats, method="rpn")
        anchors = level_anchors(cfg.model, feats)
        (lvl,) = sorted(out)
        scores = jax.nn.sigmoid(out[lvl][0])
        return jax.vmap(
            lambda s, d, hw: _pre_nms_candidates(
                s, d, anchors[lvl], hw[0], hw[1], rpn.train_pre_nms_top_n,
                rpn.min_size, rpn.topk_impl, rpn.topk_recall, rpn.topk_block,
            )
        )(scores, out[lvl][1], batch.image_hw)

    b, s = jax.device_get(candidates(variables, batch))
    return jnp.asarray(b, jnp.float32), jnp.asarray(s, jnp.float32)


def runtime_facts():
    """Set-up facts about the runtime, not speeds of the detector."""
    import jax
    import jax.numpy as jnp

    inc = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8, 128), jnp.float32)
    jax.block_until_ready(inc(x))
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        x = inc(x)
    jax.block_until_ready(x)
    dispatch_us = (time.perf_counter() - t0) / n * 1e6

    mm = jax.jit(lambda a: (a @ a) * (1.0 / 4096.0))
    a = jnp.ones((4096, 4096), jnp.bfloat16)
    jax.block_until_ready(mm(a))

    def chain(sync):
        y = a
        t = time.perf_counter()
        for _ in range(50):
            y = mm(y)
        sync(y)
        return time.perf_counter() - t

    chain(jax.block_until_ready)
    t_block = chain(jax.block_until_ready)
    t_fetch = chain(lambda y: jax.device_get(y[0, 0]))
    return {
        "dispatch_us_per_call": round(dispatch_us, 1),
        "chain50_block_until_ready_s": round(t_block, 4),
        "chain50_device_get_s": round(t_fetch, 4),
    }


PROBES = (
    # (name, fn, args)
    ("roi_align_fwd[train,b2x512,bf16]",
     probe_roi_align_fwd, (2, 512, "bfloat16")),
    ("roi_align_fwd[eval,b8x1000,bf16]",
     probe_roi_align_fwd, (8, 1000, "bfloat16")),
    ("roi_align_fwd[b2x512,f32]",
     probe_roi_align_fwd, (2, 512, "float32")),
    ("roi_align_bwd[train,b2x512,bf16]", probe_roi_align_bwd, (2, 512)),
    ("roi_align_matmul[vgg16_voc07.train_b16]",
     probe_roi_align_matmul, (16, 38, 64, 512, 128)),
    ("nms_tiled[vgg16_voc07.train_b16,seed941]", probe_nms_tiled, (941,)),
    ("kda_intra[ling3_flash_vl_det.train_coco]", probe_kda_intra, (2, 4200, 32)),
    ("ssd[nemotron_twotower_det.train_coco]", probe_ssd, (2, 4200)),
    ("ssd[granite4_h_micro_det.train_coco,one_group]", probe_ssd, (2, 4200, 1)),
    ("flash_attention[nemotron_twotower_det.train_coco]",
     probe_flash_attention, (2, 4200, 32, 2, 128, 128)),
    ("flash_attention[ling3_flash_vl_det.train_coco]",
     probe_flash_attention, (2, 4200, 32, 32, 192, 128)),
    # a 768 x 768 canvas's 2,304 positions: four whole tiles and one of 256 that
    # ends at T exactly, the case a backward's loop over whole tiles must stop short of
    ("flash_attention[768x768,last_tile_narrow]",
     probe_flash_attention, (1, 2304, 4, 2, 128, 128)),
    # one map of the SambaY cell's differential attention: 20 query pairs on 10 key
    # pairs, keys of 64 (padded to 128), values of 128; the whole prefix, then the window
    ("flash_attention[phi4_mini_flash_det.train_coco,full]",
     probe_flash_attention, (2, 4200, 20, 10, 64, 128)),
    ("flash_attention[phi4_mini_flash_det.train_coco,window512]",
     probe_flash_attention, (2, 4200, 20, 10, 64, 128, 512)),
    ("selective_scan[phi4_mini_flash_det.train_coco]", probe_selective_scan, (2, 4200)),
)


def main() -> int:
    from mx_rcnn_tpu.utils.compile_cache import configure_cache
    from mx_rcnn_tpu.utils.runtime import device_record, runtime_versions

    out = {**device_record(), **runtime_versions(), "probes": {}}
    print("DEVICE " + json.dumps(out), flush=True)
    configure_cache()
    all_ok = True
    only = sys.argv[1:]     # probes whose name holds one of these words; all if none
    for name, fn, args in PROBES:
        if only and not any(word in name for word in only):
            continue
        t0 = time.perf_counter()
        try:
            res = fn(*args)
        except Exception as e:  # noqa: BLE001 - the message IS the finding
            traceback.print_exc()
            res = {
                "ok": False,
                "error": f"{type(e).__name__}: {e}"[:3000],
            }
        res["wall_s"] = round(time.perf_counter() - t0, 1)
        out["probes"][name] = res
        print(f"PROBE {name} " + json.dumps(res), flush=True)
        all_ok = all_ok and res["ok"]
    out["runtime"] = runtime_facts()
    print("RESULT " + json.dumps(out), flush=True)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
