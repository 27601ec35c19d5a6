import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.geometry import encode_boxes, generate_base_anchors, shifted_anchors
from mx_rcnn_tpu.ops import (
    assign_anchors,
    batched_nms,
    generate_proposals,
    multilevel_roi_align,
    nms_mask,
    roi_align,
    roi_align_matmul,
    sample_rois,
)
from mx_rcnn_tpu.ops.nms import TILE, nms_indices, rank_keep
from mx_rcnn_tpu.ops.roi_align import fpn_level_assignment

from oracles import greedy_nms_np, nms_mask_dense, roi_align_np


def random_boxes(rng, n, size=100.0):
    xy = rng.uniform(0, size * 0.7, (n, 2))
    wh = rng.uniform(2, size * 0.3, (n, 2))
    return np.concatenate([xy, xy + wh], axis=1).astype(np.float32)


# ---------------- NMS ----------------


@pytest.mark.parametrize("n,thresh", [(20, 0.5), (100, 0.3), (100, 0.7), (257, 0.5)])
def test_nms_matches_greedy_oracle(rng, n, thresh):
    boxes = random_boxes(rng, n)
    scores = rng.uniform(0, 1, n).astype(np.float32)
    keep = np.asarray(nms_mask(jnp.asarray(boxes), jnp.asarray(scores), thresh))
    want = np.zeros(n, dtype=bool)
    want[greedy_nms_np(boxes, scores, thresh)] = True
    np.testing.assert_array_equal(keep, want)


def test_nms_identical_boxes_keeps_best():
    boxes = jnp.asarray([[0, 0, 10, 10]] * 5, dtype=jnp.float32)
    scores = jnp.asarray([0.1, 0.9, 0.5, 0.3, 0.7])
    keep = np.asarray(nms_mask(boxes, scores, 0.5))
    np.testing.assert_array_equal(keep, [False, True, False, False, False])


def test_nms_invalid_entries_never_keep_or_suppress(rng):
    boxes = np.asarray([[0, 0, 10, 10], [1, 1, 11, 11], [0, 0, 10, 10]], np.float32)
    scores = np.asarray([0.9, 0.5, 0.8], np.float32)
    # Entry 0 invalid: should not suppress entry 1; entry 2 should suppress 1.
    valid = jnp.asarray([False, True, True])
    keep = np.asarray(nms_mask(jnp.asarray(boxes), jnp.asarray(scores), 0.5, valid))
    np.testing.assert_array_equal(keep, [False, False, True])


def test_nms_neg_inf_scores_are_invalid():
    boxes = jnp.asarray([[0, 0, 10, 10], [20, 20, 30, 30]], dtype=jnp.float32)
    scores = jnp.asarray([-jnp.inf, 0.5])
    keep = np.asarray(nms_mask(boxes, scores, 0.5))
    np.testing.assert_array_equal(keep, [False, True])


def test_nms_indices_padding(rng):
    boxes = random_boxes(rng, 30)
    scores = rng.uniform(0, 1, 30).astype(np.float32)
    idx, valid = nms_indices(jnp.asarray(boxes), jnp.asarray(scores), 0.5, 50)
    idx, valid = np.asarray(idx), np.asarray(valid)
    n_kept = len(greedy_nms_np(boxes, scores, 0.5))
    assert valid.sum() == n_kept
    assert idx.shape == (50,)
    # Valid indices sorted by descending score.
    s = scores[idx[valid]]
    assert np.all(np.diff(s) <= 0)
    # Padded slots are 0/False.
    assert np.all(idx[~valid] == 0)


def test_batched_nms_is_per_class(rng):
    # Two perfectly overlapping boxes, different classes: both kept.
    boxes = jnp.asarray([[0, 0, 10, 10], [0, 0, 10, 10]], dtype=jnp.float32)
    scores = jnp.asarray([0.9, 0.8])
    classes = jnp.asarray([1, 2])
    keep = np.asarray(batched_nms(boxes, scores, classes, 0.5))
    np.testing.assert_array_equal(keep, [True, True])
    # Same class: one suppressed.
    keep2 = np.asarray(batched_nms(boxes, scores, jnp.asarray([1, 1]), 0.5))
    np.testing.assert_array_equal(keep2, [True, False])


def test_nms_jit_no_retrace(rng):
    boxes = jnp.asarray(random_boxes(rng, 64))
    scores = jnp.asarray(rng.uniform(0, 1, 64).astype(np.float32))
    f = jax.jit(lambda b, s: nms_mask(b, s, 0.5))
    f(boxes, scores).block_until_ready()
    n0 = f._cache_size()
    f(boxes, scores + 0.01).block_until_ready()
    assert f._cache_size() == n0


# The tiled NMS (ops/nms.py, PR 29) against both oracles, bit for bit.  Every
# older NMS test stays under one tile (N <= TILE) and runs the plain fixed
# point; these cross tile edges.  Boxes sit on whole pixels and the threshold
# is 0.5, so no IoU lies within 2**-17 of it and the greedy oracle, which
# compares the unsnapped IoU, decides every pair as the snapped ones do.

NMS_THRESH = 0.5


def _grid_boxes(rng, n, canvas=240):
    xy = rng.randint(0, canvas, (n, 2))
    wh = rng.randint(4, 31, (n, 2))
    return np.concatenate([xy, xy + wh], axis=1).astype(np.float32)


def _descending(n):
    return np.linspace(1.0, 0.0, n, dtype=np.float32)


def _nms_case(case, rng, n):
    """-> boxes (n, 4), scores (n,), valid (n,) bool or None."""
    if case == "random":
        return _grid_boxes(rng, n), rng.permutation(n).astype(np.float32), None
    if case == "chain":
        # Box i overlaps only its neighbours (IoU 2/3 with i +- 1, 3/7 with
        # i +- 2): whether i survives hangs on i - 1, a chain N deep that
        # crosses every tile edge.
        x = 2.0 * np.arange(n, dtype=np.float32)
        boxes = np.stack([x, np.zeros(n, np.float32), x + 10.0, np.full(n, 10.0, np.float32)], 1)
        return boxes, _descending(n), None
    if case == "identical":
        return np.tile(np.asarray([[3, 5, 40, 33]], np.float32), (n, 1)), _descending(n), None
    if case == "tile_invalid":
        # Score order is input order, so the second tile (or what there is
        # of a second half) is invalid as a whole.
        valid = np.ones(n, bool)
        lo = TILE if n >= 2 * TILE else n // 2
        valid[lo:lo + TILE] = False
        return _grid_boxes(rng, n, canvas=120), _descending(n), valid
    if case == "ties_and_inf":
        scores = rng.randint(0, 7, n).astype(np.float32)
        scores[rng.rand(n) < 0.1] = -np.inf
        return _grid_boxes(rng, n, canvas=120), scores, None
    raise AssertionError(case)


def _greedy_mask(boxes, scores, valid=None, thresh=NMS_THRESH):
    ok = np.isfinite(scores) if valid is None else valid & np.isfinite(scores)
    idx = np.flatnonzero(ok)
    want = np.zeros(len(boxes), bool)
    want[idx[greedy_nms_np(boxes[idx], scores[idx], thresh)]] = True
    return want


def _dense_mask(boxes, scores, valid=None, thresh=NMS_THRESH):
    v = None if valid is None else jnp.asarray(valid)
    return np.asarray(nms_mask_dense(jnp.asarray(boxes), jnp.asarray(scores), thresh, v))


def _tiled_case(rng, case, n):
    boxes, scores, valid = _nms_case(case, rng, n)
    v = None if valid is None else jnp.asarray(valid)
    got = np.asarray(nms_mask(jnp.asarray(boxes), jnp.asarray(scores), NMS_THRESH, v))
    np.testing.assert_array_equal(got, _greedy_mask(boxes, scores, valid))
    # The dense fixed point takes one sweep per link of the chain over the
    # whole matrix: at 6000 that is the minutes this PR exists to remove.
    if not (case == "chain" and n > 4 * TILE):
        np.testing.assert_array_equal(got, _dense_mask(boxes, scores, valid))


def _vmap_case(rng, _, n):
    # Images whose chains differ in depth share every tile's loop under
    # vmap: the shallow ones must sit still while the deep one converges.
    cases = [_nms_case(c, rng, n) for c in ("chain", "random", "identical", "ties_and_inf")]
    boxes = np.stack([b for b, _, _ in cases])
    scores = np.stack([s for _, s, _ in cases])
    got = np.asarray(jax.vmap(lambda b, s: nms_mask(b, s, NMS_THRESH))(
        jnp.asarray(boxes), jnp.asarray(scores)))
    for i in range(len(cases)):
        np.testing.assert_array_equal(got[i], _greedy_mask(boxes[i], scores[i]))
        np.testing.assert_array_equal(got[i], _dense_mask(boxes[i], scores[i]))


def _indices_case(rng, max_outputs, n):
    boxes, scores, _ = _nms_case("ties_and_inf", rng, n)
    idx, ok = nms_indices(jnp.asarray(boxes), jnp.asarray(scores), NMS_THRESH, max_outputs)
    want_idx, want_ok = rank_keep(
        jnp.asarray(_dense_mask(boxes, scores)), jnp.asarray(scores), max_outputs)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(want_ok))
    kept = np.flatnonzero(_greedy_mask(boxes, scores))
    assert int(np.asarray(ok).sum()) == min(len(kept), max_outputs)
    assert set(np.asarray(idx)[np.asarray(ok)]) <= set(kept)


def _batched_case(rng, _, n):
    boxes, scores = _grid_boxes(rng, n, canvas=60), rng.permutation(n).astype(np.float32)
    classes = rng.randint(1, 5, n)
    got = np.asarray(batched_nms(
        jnp.asarray(boxes), jnp.asarray(scores), jnp.asarray(classes), NMS_THRESH))
    want = np.zeros(n, bool)
    for c in np.unique(classes):
        idx = np.flatnonzero(classes == c)
        want[idx[greedy_nms_np(boxes[idx], scores[idx], NMS_THRESH)]] = True
    np.testing.assert_array_equal(got, want)
    span = boxes.max() - boxes.min() + 1.0
    np.testing.assert_array_equal(
        got, _dense_mask(boxes + classes[:, None].astype(np.float32) * span, scores))


_TILED_SIZES = (TILE - 1, TILE, TILE + 1, 3 * TILE + 5, 6000)
_TILED_CASES = [
    pytest.param(_tiled_case, case, n, id=f"{case}-{n}")
    for case in ("random", "chain", "identical", "tile_invalid", "ties_and_inf")
    for n in _TILED_SIZES
] + [
    pytest.param(_vmap_case, "", 3 * TILE + 5, id="vmap-depths-differ"),
    pytest.param(_indices_case, 2000, 6000, id="nms_indices-6000-2000"),
    pytest.param(_indices_case, 300, 6000, id="nms_indices-6000-300"),
    pytest.param(_batched_case, "", 3 * TILE + 5, id="batched_nms"),
]


@pytest.mark.parametrize("check,case,n", _TILED_CASES)
def test_tiled_nms_equals_both_oracles(rng, check, case, n):
    check(rng, case, n)


def _array_sizes(hlo_text):
    for dims in re.findall(r"\b(?:pred|[fsu]\d+|bf16)\[([0-9,]+)\]", hlo_text):
        yield int(np.prod([int(d) for d in dims.split(",")]))


def test_no_n_by_n_buffer_under_nms_indices():
    """The mechanism, held: at N = 6000 the compiled ``nms_indices`` holds no
    array of N x N elements, in the program or in its temporaries (the dense
    form's mask alone is N x N bytes): three loops over TILE x TILE blocks;
    and N <= TILE is one loop, the plain fixed point."""
    n = 6000
    args = (jax.ShapeDtypeStruct((n, 4), jnp.float32), jax.ShapeDtypeStruct((n,), jnp.float32))
    lowered = nms_indices.lower(*args, 0.7, 2000)
    compiled = lowered.compile()
    assert max(_array_sizes(compiled.as_text())) < n * n // 4
    assert compiled.memory_analysis().temp_size_in_bytes < n * n // 4
    # the tile loop, the row-block loop inside it, and a tile's sweeps
    assert lowered.as_text().count("stablehlo.while") == 3

    dense = jax.jit(lambda b, s: nms_mask_dense(b, s, 0.7)).lower(*args).compile()
    assert max(_array_sizes(dense.as_text())) >= n * n  # the test can see one

    small = (jax.ShapeDtypeStruct((TILE, 4), jnp.float32),
             jax.ShapeDtypeStruct((TILE,), jnp.float32))
    assert nms_indices.lower(*small, 0.7, 300).as_text().count("stablehlo.while") == 1


# ---------------- ROIAlign ----------------


def test_roi_align_matches_oracle(rng):
    feat = rng.rand(16, 16, 3).astype(np.float32)
    rois = np.asarray(
        [[8.0, 8.0, 100.0, 120.0], [0.0, 0.0, 64.0, 64.0], [40.0, 30.0, 200.0, 220.0]],
        np.float32,
    )
    got = np.asarray(roi_align(jnp.asarray(feat), jnp.asarray(rois), 7, 1 / 16.0, 2))
    want = roi_align_np(feat, rois, 7, 1 / 16.0, 2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_roi_align_constant_map(rng):
    # Pooling a constant feature map must return the constant everywhere
    # the roi is in-bounds.
    feat = jnp.full((20, 20, 4), 3.5)
    rois = jnp.asarray([[16.0, 16.0, 160.0, 160.0]])
    out = np.asarray(roi_align(feat, rois, 7, 1 / 16.0, 2))
    np.testing.assert_allclose(out, 3.5, rtol=1e-6)


def test_roi_align_gradient_flows(rng):
    feat = jnp.asarray(rng.rand(10, 10, 2).astype(np.float32))
    rois = jnp.asarray([[10.0, 10.0, 80.0, 80.0]])

    def f(x):
        return roi_align(x, rois, 7, 1 / 16.0, 2).sum()

    g = jax.grad(f)(feat)
    assert bool(jnp.isfinite(g).all())
    assert float(jnp.abs(g).sum()) > 0


# The one-level matmul form (ops/roi_align.py::roi_align_matmul) against the
# gather form and the numpy oracle.  A non-square, odd-sized map at stride
# 16 (144 x 208 px), so a transposed axis or an off-by-one extent shows.
_MAP_H, _MAP_W = 9, 13
_BF16_EPS = 2.0 ** -8  # one bf16 rounding (tests/_kernels_tpu_worker.py)

def _random_rois(batch=3, n=12):
    r = np.random.RandomState(7)
    x1 = r.uniform(-60, _MAP_W * 16, (batch, n))
    y1 = r.uniform(-60, _MAP_H * 16, (batch, n))
    bw = np.exp(r.uniform(np.log(4), np.log(_MAP_W * 16 * 1.3), (batch, n)))
    bh = np.exp(r.uniform(np.log(4), np.log(_MAP_H * 16 * 1.3), (batch, n)))
    return np.stack([x1, y1, x1 + bw, y1 + bh], -1)


_ROI_CASES = {
    # (B, R, 4) boxes in image pixels.
    "past_left": [[[-70.0, 20.5, 60.0, 100.0], [-300.0, 10.0, -2.0, 90.0]]],
    "past_top": [[[30.0, -55.5, 120.0, 40.0], [10.0, -400.0, 190.0, -20.0]]],
    "past_right": [[[150.0, 30.0, 290.5, 110.0], [230.0, 5.0, 400.0, 80.0]]],
    "past_bottom": [[[20.0, 100.0, 90.0, 201.5], [5.0, 170.0, 200.0, 300.0]]],
    "under_one_cell": [[[40.3, 50.7, 44.1, 53.2], [100.0, 100.0, 100.0, 100.0]]],
    "whole_map": [[[0.0, 0.0, 208.0, 144.0], [-40.0, -30.0, 260.5, 190.25]]],
    "batch_of_three": _random_rois(),
}
_CASE_NAMES = list(_ROI_CASES)


def _gather_form(feat, rois, s, sr):
    return jax.vmap(lambda f, r: roi_align(f, r, s, 1 / 16.0, sr))(feat, rois)


def _one_level_inputs(case, channels=6):
    rois = np.asarray(_ROI_CASES[case], np.float32)
    feat = np.random.RandomState(3).standard_normal(
        (rois.shape[0], _MAP_H, _MAP_W, channels)
    ).astype(np.float32)
    return feat, rois


@pytest.mark.parametrize("sr", [1, 2])
@pytest.mark.parametrize("s", [7, 14])
@pytest.mark.parametrize("case", _CASE_NAMES)
def test_roi_align_matmul_matches_gather_and_oracle_f32(case, s, sr):
    feat, rois = _one_level_inputs(case)
    got = np.asarray(roi_align_matmul(jnp.asarray(feat), jnp.asarray(rois), s, 1 / 16.0, sr))
    assert got.shape == (rois.shape[0], rois.shape[1], s, s, feat.shape[-1])
    assert got.dtype == np.float32
    gather = np.asarray(_gather_form(jnp.asarray(feat), jnp.asarray(rois), s, sr))
    np.testing.assert_allclose(got, gather, rtol=0, atol=1e-5)
    oracle = np.stack(
        [roi_align_np(f, r, s, 1 / 16.0, sr) for f, r in zip(feat, rois)]
    )
    np.testing.assert_allclose(got, oracle, rtol=0, atol=1e-5)


@pytest.mark.parametrize("case", _CASE_NAMES)
def test_roi_align_matmul_bf16_within_the_pallas_ceiling(case):
    # The ceiling the chip worker holds the Pallas forward to: 3 bf16
    # roundings of the feature scale (tests/_kernels_tpu_worker.py).
    feat, rois = _one_level_inputs(case)
    got = roi_align_matmul(
        jnp.asarray(feat, jnp.bfloat16), jnp.asarray(rois), 7, 1 / 16.0, 2
    )
    assert got.dtype == jnp.bfloat16
    truth = np.asarray(
        _gather_form(
            jnp.asarray(feat, jnp.bfloat16).astype(jnp.float32),
            jnp.asarray(rois), 7, 2,
        )
    )
    err = np.abs(np.asarray(got, np.float32) - truth).max()
    assert err <= 3 * _BF16_EPS * np.abs(feat).max()


@pytest.mark.parametrize("sr", [1, 2])
@pytest.mark.parametrize("case", _CASE_NAMES)
def test_roi_align_matmul_feature_gradient_f32(case, sr):
    feat, rois = _one_level_inputs(case)
    cot = np.random.RandomState(5).standard_normal(
        (*rois.shape[:2], 7, 7, feat.shape[-1])
    ).astype(np.float32)

    def grad_of(fn):
        return np.asarray(
            jax.grad(lambda f: jnp.sum(fn(f) * cot))(jnp.asarray(feat))
        )

    got = grad_of(lambda f: roi_align_matmul(f, jnp.asarray(rois), 7, 1 / 16.0, sr))
    want = grad_of(lambda f: _gather_form(f, jnp.asarray(rois), 7, sr))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("case", _CASE_NAMES)
def test_roi_align_matmul_bf16_no_further_from_f32_than_the_gather(case, what):
    """The precision rule: split (hi + lo) bf16 weights and float32
    accumulation.  A single-pass bf16 weight, or a bf16 accumulator, reads
    several times the gather form's own bf16 error here."""
    feat, rois = _one_level_inputs(case, channels=64)
    rois = jnp.asarray(rois)
    feat16 = jnp.asarray(feat, jnp.bfloat16)
    cot = jnp.asarray(
        np.random.RandomState(5).standard_normal(
            (*rois.shape[:2], 7, 7, feat.shape[-1])
        ),
        jnp.bfloat16,
    )
    forms = {
        "matmul": lambda f: roi_align_matmul(f, rois, 7, 1 / 16.0, 2),
        "gather": lambda f: _gather_form(f, rois, 7, 2),
    }

    def value(form, f):
        if what == "forward":
            return np.asarray(forms[form](f), np.float32)
        loss = lambda x: jnp.sum(  # noqa: E731
            forms[form](x).astype(jnp.float32) * cot.astype(jnp.float32)
        )
        return np.asarray(jax.grad(loss)(f), np.float32)

    truth = value("gather", feat16.astype(jnp.float32))
    err = {
        form: np.linalg.norm(value(form, feat16) - truth) / np.linalg.norm(truth)
        for form in forms
    }
    # 1 %: both round a float32 sum once, in another summation order.
    assert err["matmul"] <= 1.01 * err["gather"], err


def test_one_level_pyramid_pools_by_matmul_without_gather_or_scatter():
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.detection import graph

    cfg = get_config("vgg16_voc07").model
    feat, rois = map(jnp.asarray, _one_level_inputs("batch_of_three", channels=8))

    def pool(f):
        return graph._pool_rois(cfg, {4: f}, rois, 7, (4,))

    graph.LAST_POOL_IMPL = None
    got = pool(feat)
    assert graph.LAST_POOL_IMPL == "matmul"
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_gather_form(feat, rois, 7, 2)),
        rtol=0, atol=1e-5,
    )
    forward = str(jax.make_jaxpr(pool)(feat))
    backward = str(jax.make_jaxpr(jax.grad(lambda f: jnp.sum(pool(f) ** 2)))(feat))
    for text in (forward, backward):
        assert "dot_general" in text
        assert "gather" not in text
        assert "scatter" not in text


def test_fpn_level_assignment():
    rois = jnp.asarray(
        [
            [0, 0, 56, 56],     # small -> level 2
            [0, 0, 224, 224],   # canonical -> level 4
            [0, 0, 896, 896],   # huge -> clamped to 5
            [0, 0, 10, 10],     # tiny -> clamped to 2
        ],
        dtype=jnp.float32,
    )
    lv = np.asarray(fpn_level_assignment(rois))
    np.testing.assert_array_equal(lv, [2, 4, 5, 2])


def test_multilevel_roi_align_selects_level(rng):
    # Make each level a distinct constant; the output constant identifies
    # which level was pooled.
    pyramid = {l: jnp.full((32, 32, 1), float(l)) for l in (2, 3, 4, 5)}
    rois = jnp.asarray([[0, 0, 56, 56], [0, 0, 224, 224], [0, 0, 896, 896]])
    out = np.asarray(multilevel_roi_align(pyramid, rois, output_size=2))
    np.testing.assert_allclose(out[0], 2.0)
    np.testing.assert_allclose(out[1], 4.0)
    np.testing.assert_allclose(out[2], 5.0)


def test_multilevel_flat_matches_dense(rng):
    """The flattened-pyramid single-gather path must equal the dense
    pool-every-level oracle — values AND gradients — including
    out-of-bounds and degenerate rois."""
    from mx_rcnn_tpu.ops.roi_align import _multilevel_roi_align_dense

    canvas = 256
    pyramid = {
        l: jnp.asarray(
            rng.rand(canvas // 2**l, canvas // 2**l, 8).astype(np.float32)
        )
        for l in (2, 3, 4, 5)
    }
    r = 64
    x1 = rng.uniform(-30, canvas, r)
    y1 = rng.uniform(-30, canvas, r)
    bw = rng.uniform(0, canvas, r)
    bh = rng.uniform(0, canvas, r)
    rois = np.stack([x1, y1, x1 + bw, y1 + bh], axis=1).astype(np.float32)
    rois[0] = [10, 10, 10, 10]          # degenerate
    rois[1] = [0, 0, 0, 0]              # zero (padding)
    rois[2] = [-50, -50, -10, -10]      # fully outside
    rois = jnp.asarray(rois)

    got = multilevel_roi_align(pyramid, rois, output_size=7, sampling_ratio=2)
    want = _multilevel_roi_align_dense(
        pyramid, rois, output_size=7, sampling_ratio=2
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def loss_flat(pyr):
        return jnp.sum(multilevel_roi_align(pyr, rois, 7, 2) ** 2)

    def loss_dense(pyr):
        return jnp.sum(_multilevel_roi_align_dense(pyr, rois, 7, 2) ** 2)

    g_flat = jax.grad(loss_flat)(pyramid)
    g_dense = jax.grad(loss_dense)(pyramid)
    for l in pyramid:
        np.testing.assert_allclose(
            np.asarray(g_flat[l]), np.asarray(g_dense[l]),
            rtol=1e-4, atol=1e-5, err_msg=f"level {l}",
        )


# ---------------- proposals ----------------


def _rpn_inputs(rng, h=10, w=12):
    base = generate_base_anchors(16, (0.5, 1.0, 2.0), (8,))
    anchors = shifted_anchors(jnp.asarray(base), 16, h, w)
    a = anchors.shape[0]
    scores = jnp.asarray(rng.uniform(0, 1, a).astype(np.float32))
    deltas = jnp.asarray(rng.normal(0, 0.1, (a, 4)).astype(np.float32))
    return anchors, scores, deltas


def test_generate_proposals_shapes_and_validity(rng):
    anchors, scores, deltas = _rpn_inputs(rng)
    p = generate_proposals(scores, deltas, anchors, 160.0, 192.0,
                           pre_nms_top_n=200, post_nms_top_n=50, nms_threshold=0.7)
    assert p.rois.shape == (50, 4)
    assert p.valid.shape == (50,)
    assert int(p.valid.sum()) > 0
    rois = np.asarray(p.rois)[np.asarray(p.valid)]
    assert (rois[:, 0] >= 0).all() and (rois[:, 2] <= 192).all()
    assert (rois[:, 1] >= 0).all() and (rois[:, 3] <= 160).all()
    # Scores descending among valid.
    s = np.asarray(p.scores)[np.asarray(p.valid)]
    assert np.all(np.diff(s) <= 0)


def test_generate_proposals_respects_min_size(rng):
    anchors, scores, deltas = _rpn_inputs(rng)
    # Huge min_size: nothing survives.
    p = generate_proposals(scores, deltas, anchors, 160.0, 192.0,
                           pre_nms_top_n=100, post_nms_top_n=20, min_size=1000.0)
    assert int(p.valid.sum()) == 0
    assert np.all(np.asarray(p.rois) == 0)


def test_fpn_proposals_batched_nms_equals_per_level(rng):
    """generate_fpn_proposals' single vmapped NMS fixed point must equal
    running generate_proposals per level and concatenating (the pre-r4
    formulation): the level padding must neither keep nor suppress."""
    from mx_rcnn_tpu.ops.proposals import generate_fpn_proposals

    level_scores, level_deltas, level_anchors = {}, {}, {}
    for lvl, hw in ((3, (20, 24)), (4, (10, 12)), (5, (5, 6))):
        base = generate_base_anchors(2**lvl, (0.5, 1.0, 2.0), (8,))
        anchors = shifted_anchors(jnp.asarray(base), 2**lvl, *hw)
        a = anchors.shape[0]
        level_anchors[lvl] = anchors
        level_scores[lvl] = jnp.asarray(rng.uniform(0, 1, a), jnp.float32)
        level_deltas[lvl] = jnp.asarray(rng.normal(0, 0.1, (a, 4)), jnp.float32)

    # pre=120 truncates lvl 3 (1440 anchors) but exceeds lvl 5's 90 -> the
    # level axis mixes truncated and padded lanes, the interesting case.
    kw = dict(pre_nms_top_n=120, post_nms_top_n=60, nms_threshold=0.7)
    fused = generate_fpn_proposals(
        level_scores, level_deltas, level_anchors, 160.0, 192.0, **kw
    )

    per_level = [
        generate_proposals(
            level_scores[lvl], level_deltas[lvl], level_anchors[lvl],
            160.0, 192.0, **kw,
        )
        for lvl in sorted(level_scores)
    ]
    rois = jnp.concatenate([p.rois for p in per_level])
    scores = jnp.concatenate([p.scores for p in per_level])
    valid = jnp.concatenate([p.valid for p in per_level])
    masked = jnp.where(valid, scores, -jnp.inf)
    k = min(kw["post_nms_top_n"], rois.shape[0])
    top_scores, top_idx = jax.lax.top_k(masked, k)
    want_valid = np.isfinite(np.asarray(top_scores))
    want_rois = np.asarray(jnp.take(rois, top_idx, axis=0)) * want_valid[:, None]

    np.testing.assert_array_equal(np.asarray(fused.valid), want_valid)
    np.testing.assert_array_equal(np.asarray(fused.rois), want_rois)
    np.testing.assert_array_equal(
        np.asarray(fused.scores),
        np.where(want_valid, np.asarray(top_scores), 0.0),
    )
    assert int(fused.valid.sum()) > 0


def test_generate_proposals_topk_impl(rng):
    anchors, scores, deltas = _rpn_inputs(rng)
    exact = generate_proposals(scores, deltas, anchors, 160.0, 192.0,
                               pre_nms_top_n=200, post_nms_top_n=50)
    approx = generate_proposals(scores, deltas, anchors, 160.0, 192.0,
                                pre_nms_top_n=200, post_nms_top_n=50,
                                topk_impl="approx", topk_recall=0.95)
    # Basic contract holds under the approx selector...
    assert approx.rois.shape == (50, 4)
    assert int(approx.valid.sum()) > 0
    s = np.asarray(approx.scores)[np.asarray(approx.valid)]
    assert np.all(np.diff(s) <= 0)
    # ...and off-TPU approx_max_k lowers to an exact sort, so CPU results
    # are identical (the parity claim in RPNConfig.topk_impl).
    if jax.default_backend() == "cpu":
        np.testing.assert_array_equal(
            np.asarray(exact.rois), np.asarray(approx.rois)
        )

    with pytest.raises(ValueError, match="topk_impl"):
        generate_proposals(scores, deltas, anchors, 160.0, 192.0,
                           pre_nms_top_n=200, post_nms_top_n=50,
                           topk_impl="banana")


def test_generate_proposals_all_in_one_jit(rng):
    anchors, scores, deltas = _rpn_inputs(rng)

    @jax.jit
    def f(s, d):
        return generate_proposals(s, d, anchors, 160.0, 192.0,
                                  pre_nms_top_n=100, post_nms_top_n=20)

    p = f(scores, deltas)
    assert p.rois.shape == (20, 4)


# ---------------- assign_anchors ----------------


def test_select_random_exact_and_uniform(rng):
    from mx_rcnn_tpu.ops.sampling import _select_random

    cand = jnp.asarray(rng.rand(1000) < 0.3)
    n_cand = int(cand.sum())
    # Exactly n selected, all candidates.
    for n, quota in [(0, 64), (10, 64), (64, 64)]:
        sel = _select_random(jax.random.key(0), cand, jnp.minimum(n, n_cand), quota)
        assert int(sel.sum()) == min(n, n_cand)
        assert bool(jnp.all(~sel | cand))
    # Deterministic per key, different across keys.
    s1 = _select_random(jax.random.key(1), cand, 32, 64)
    s2 = _select_random(jax.random.key(1), cand, 32, 64)
    s3 = _select_random(jax.random.key(2), cand, 32, 64)
    assert bool(jnp.all(s1 == s2))
    assert not bool(jnp.all(s1 == s3))
    # Roughly uniform: over many keys every candidate gets picked sometimes.
    counts = np.zeros(1000)
    for k in range(200):
        counts += np.asarray(
            _select_random(jax.random.key(k), cand, 32, 64)
        )
    picked_rate = counts[np.asarray(cand)]
    assert picked_rate.min() > 0  # no candidate starved over 200 draws

    # Scarce-candidate regime: fewer candidates than quota — the top_k
    # window then contains non-candidate slots, which must never be picked
    # even when the requested n exceeds the candidate count.
    scarce = jnp.zeros(1000, bool).at[jnp.asarray(rng.choice(1000, 20, False))].set(True)
    sel = _select_random(jax.random.key(5), scarce, 64, 64)
    assert int(sel.sum()) == 20
    assert bool(jnp.all(~sel | scarce))


def test_assign_anchors_basic(rng):
    base = generate_base_anchors(16, (0.5, 1.0, 2.0), (2, 4))
    anchors = shifted_anchors(jnp.asarray(base), 16, 12, 12)
    gt = jnp.asarray([[30.0, 30.0, 80.0, 90.0], [0.0, 0.0, 0.0, 0.0]])
    gt_valid = jnp.asarray([True, False])
    t = assign_anchors(jax.random.key(0), anchors, gt, gt_valid, 192.0, 192.0,
                       batch_size=64, fg_fraction=0.5)
    labels = np.asarray(t.labels)
    assert (labels == 1).sum() >= 1
    assert (labels == 1).sum() <= 32
    assert (labels >= 0).sum() <= 64
    # All fg anchors overlap the gt box decently.
    from oracles import iou_matrix_np

    fg_anchors = np.asarray(anchors)[labels == 1]
    ious = iou_matrix_np(fg_anchors, np.asarray(gt[:1]))
    assert ious.max(axis=1).min() > 0.1


def test_assign_anchors_best_anchor_is_fg_even_below_thresh(rng):
    # One tiny gt that no anchor reaches 0.7 IoU with: its best anchor must
    # still be labeled fg (reference gt_argmax behavior).
    base = generate_base_anchors(16, (1.0,), (2,))
    anchors = shifted_anchors(jnp.asarray(base), 16, 8, 8)
    gt = jnp.asarray([[33.0, 33.0, 50.0, 52.0]])
    t = assign_anchors(jax.random.key(1), anchors, gt, jnp.asarray([True]),
                       128.0, 128.0, batch_size=32)
    assert int(t.fg_mask.sum()) >= 1


def test_assign_anchors_border_gt_still_gets_positive():
    # gt in the image corner whose globally-best anchor crosses the border:
    # the best INSIDE anchor must be fg (reference computes gt-argmax over
    # inside anchors only).
    base = generate_base_anchors(16, (1.0,), (2,))  # 32px anchors
    anchors = shifted_anchors(jnp.asarray(base), 16, 4, 4)  # 64px image
    gt = jnp.asarray([[44.0, 44.0, 63.0, 63.0]])
    t = assign_anchors(jax.random.key(0), anchors, gt, jnp.asarray([True]),
                       64.0, 64.0, batch_size=32)
    assert int(t.fg_mask.sum()) >= 1


def test_assign_anchors_outside_ignored():
    base = generate_base_anchors(16, (1.0,), (8,))  # 128px anchors on 64px image
    anchors = shifted_anchors(jnp.asarray(base), 16, 4, 4)
    gt = jnp.asarray([[10.0, 10.0, 50.0, 50.0]])
    t = assign_anchors(jax.random.key(2), anchors, gt, jnp.asarray([True]),
                       64.0, 64.0, batch_size=32)
    # Every anchor crosses the boundary -> everything ignored.
    assert int(t.valid_mask.sum()) == 0


def test_assign_anchors_no_gt_all_bg():
    base = generate_base_anchors(16, (1.0,), (1,))
    anchors = shifted_anchors(jnp.asarray(base), 16, 6, 6)
    gt = jnp.zeros((2, 4))
    t = assign_anchors(jax.random.key(3), anchors, gt, jnp.asarray([False, False]),
                       96.0, 96.0, batch_size=16)
    labels = np.asarray(t.labels)
    assert (labels == 1).sum() == 0
    assert (labels == 0).sum() == 16  # all sampled slots are bg


def test_assign_anchors_deterministic_per_key(rng):
    base = generate_base_anchors(16, (0.5, 1.0), (2, 4))
    anchors = shifted_anchors(jnp.asarray(base), 16, 10, 10)
    gt = jnp.asarray([[20.0, 20.0, 90.0, 100.0]])
    gv = jnp.asarray([True])
    t1 = assign_anchors(jax.random.key(7), anchors, gt, gv, 160.0, 160.0)
    t2 = assign_anchors(jax.random.key(7), anchors, gt, gv, 160.0, 160.0)
    np.testing.assert_array_equal(np.asarray(t1.labels), np.asarray(t2.labels))


# ---------------- sample_rois ----------------


def _roi_setup(rng, n_rois=100):
    gt = jnp.asarray([[10.0, 10.0, 50.0, 60.0], [70.0, 20.0, 120.0, 90.0],
                      [0.0, 0.0, 0.0, 0.0]])
    gt_classes = jnp.asarray([3, 7, 0], dtype=jnp.int32)
    gt_valid = jnp.asarray([True, True, False])
    rois = jnp.asarray(random_boxes(rng, n_rois, 130.0))
    roi_valid = jnp.ones(n_rois, dtype=bool)
    return rois, roi_valid, gt, gt_classes, gt_valid


def test_sample_rois_composition(rng):
    rois, rv, gt, gc, gv = _roi_setup(rng)
    s = sample_rois(jax.random.key(0), rois, rv, gt, gc, gv,
                    batch_size=64, fg_fraction=0.25)
    assert s.rois.shape == (64, 4)
    n_fg = int(s.fg_mask.sum())
    assert 1 <= n_fg <= 16
    labels = np.asarray(s.labels)
    w = np.asarray(s.label_weights)
    # fg labels are real classes; bg labels are 0.
    assert set(labels[np.asarray(s.fg_mask)]).issubset({3, 7})
    assert (labels[(w > 0) & ~np.asarray(s.fg_mask)] == 0).all()
    # fg slots come first.
    fg = np.asarray(s.fg_mask)
    assert fg[: n_fg].all() and not fg[n_fg:].any()


def test_sample_rois_gt_appended_guarantees_fg(rng):
    # Proposals nowhere near gt: the appended gt boxes still provide fg.
    gt = jnp.asarray([[10.0, 10.0, 50.0, 60.0]])
    rois = jnp.asarray([[200.0, 200.0, 250.0, 260.0]] * 10, dtype=jnp.float32)
    s = sample_rois(jax.random.key(0), rois, jnp.ones(10, bool), gt,
                    jnp.asarray([5], jnp.int32), jnp.asarray([True]),
                    batch_size=16, fg_fraction=0.5)
    assert int(s.fg_mask.sum()) == 1
    got_roi = np.asarray(s.rois)[np.asarray(s.fg_mask)][0]
    np.testing.assert_allclose(got_roi, [10, 10, 50, 60])
    assert np.asarray(s.labels)[np.asarray(s.fg_mask)][0] == 5


def test_sample_rois_bbox_targets_decode_back(rng):
    rois, rv, gt, gc, gv = _roi_setup(rng)
    w = (10.0, 10.0, 5.0, 5.0)
    s = sample_rois(jax.random.key(0), rois, rv, gt, gc, gv,
                    batch_size=64, bbox_weights=w)
    from mx_rcnn_tpu.geometry import decode_boxes

    fg = np.asarray(s.fg_mask)
    decoded = np.asarray(decode_boxes(s.bbox_targets, s.rois, weights=w))[fg]
    # Each fg decode must land on one of the gt boxes.
    gts = np.asarray(gt)[:2]
    for box in decoded:
        d = np.abs(gts - box).max(axis=1).min()
        assert d < 1e-2


def test_sample_rois_padding_zero_weight(rng):
    # Only 3 valid proposals, no bg candidates in range -> padding appears.
    gt = jnp.asarray([[10.0, 10.0, 50.0, 60.0]])
    rois = jnp.asarray([[11.0, 11.0, 50.0, 59.0]] * 3, dtype=jnp.float32)
    s = sample_rois(jax.random.key(0), rois, jnp.ones(3, bool), gt,
                    jnp.asarray([2], jnp.int32), jnp.asarray([True]),
                    batch_size=8, fg_fraction=0.5)
    w = np.asarray(s.label_weights)
    assert w.sum() <= 4  # 4 fg candidates max (3 rois + 1 gt), no bg
    assert (w[int(w.sum()):] == 0).all()


# ---------------- ignore regions (COCO crowd / VOC difficult) ----------------


def test_assign_anchors_crowd_never_bg():
    # One valid gt in a corner plus an ignore (crowd) region: anchors
    # covering the crowd (IoA >= 0.5) must never be labeled background —
    # the reference silently trained them as negatives after dropping
    # crowd annotations at roidb build.
    base = generate_base_anchors(16, (1.0,), (2,))  # 32px anchors
    anchors = shifted_anchors(jnp.asarray(base), 16, 6, 6)  # 96px image
    gt = jnp.asarray([[4.0, 4.0, 35.0, 35.0], [48.0, 48.0, 95.0, 95.0]])
    gt_valid = jnp.asarray([True, False])
    gt_ignore = jnp.asarray([False, True])
    t = assign_anchors(
        jax.random.key(0), anchors, gt, gt_valid, 96.0, 96.0,
        batch_size=256, gt_ignore=gt_ignore,
    )
    from mx_rcnn_tpu.geometry import ioa_matrix

    ioa = np.asarray(ioa_matrix(anchors, gt[1:2])).ravel()
    labels = np.asarray(t.labels)
    covered = ioa >= 0.5
    assert covered.any()  # the grid does cover the crowd
    assert (labels[covered] != 0).all()
    # Without the flag those same anchors DO become bg (the regression
    # the flag exists to prevent).
    t0 = assign_anchors(
        jax.random.key(0), anchors, gt[:1], gt_valid[:1], 96.0, 96.0,
        batch_size=256,
    )
    assert (np.asarray(t0.labels)[covered] == 0).any()


def test_sample_rois_crowd_never_bg():
    gt = jnp.asarray([[10.0, 10.0, 50.0, 60.0], [80.0, 80.0, 126.0, 126.0]])
    gc = jnp.asarray([3, 0], jnp.int32)
    gv = jnp.asarray([True, False])
    gi = jnp.asarray([False, True])
    rois = jnp.asarray(
        [[11.0, 11.0, 50.0, 59.0]] * 3          # fg
        + [[82.0, 82.0, 124.0, 124.0]] * 5      # inside the crowd
        + [[150.0, 150.0, 200.0, 200.0]] * 5,   # clean bg
        dtype=jnp.float32,
    )
    s = sample_rois(
        jax.random.key(0), rois, jnp.ones(13, bool), gt, gc, gv,
        batch_size=32, fg_fraction=0.25, gt_ignore=gi,
    )
    from mx_rcnn_tpu.geometry import ioa_matrix

    picked = np.asarray(s.label_weights) > 0
    bg = picked & ~np.asarray(s.fg_mask)
    assert bg.any()  # clean bg still sampled
    ioa = np.asarray(ioa_matrix(s.rois, gt[1:2])).ravel()
    assert (ioa[bg] < 0.5).all()


# ---------------- analytic FLOP counter ----------------


def test_flops_counter_known_shapes():
    from mx_rcnn_tpu.utils.flops import count_matmul_flops

    f = lambda x, w: jax.lax.conv_general_dilated(  # noqa: E731
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    x = jnp.zeros((2, 16, 16, 8))
    w = jnp.zeros((3, 3, 8, 32))
    assert count_matmul_flops(f, x, w) == 2 * 2 * 16 * 16 * 32 * 8 * 9
    g = lambda a, b: a @ b  # noqa: E731
    assert (
        count_matmul_flops(g, jnp.zeros((64, 128)), jnp.zeros((128, 256)))
        == 2 * 64 * 128 * 256
    )
    # scan multiplies by trip count; grad roughly triples a conv (fwd +
    # input-transpose + kernel-transpose convs).
    s = lambda c: jax.lax.scan(  # noqa: E731
        lambda carry, _: (carry @ jnp.ones((32, 32)), None), c, None, length=5
    )[0]
    assert count_matmul_flops(s, jnp.zeros((32, 32))) == 5 * 2 * 32**3
    h = lambda w_: (f(x, w_) ** 2).sum()  # noqa: E731
    fwd = count_matmul_flops(lambda w_: f(x, w_), w)
    both = count_matmul_flops(jax.grad(h), w)
    assert 2.0 <= both / fwd <= 3.2
