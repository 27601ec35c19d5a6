"""Exactness proofs for the detection-middle fast paths (PR 5).

The hierarchical proposal top-k, the blocked anchor assignment, and the
compact RPN loss are TPU-layout rewrites of exact math — every default
path must be BIT-identical to the straightforward global implementation
it replaces (the ``"exact"`` / ``assign_block=0`` / ``"dense"`` oracles
kept alongside).  These tests pin that contract on the adversarial
inputs: snapped-score ties, -inf masked lanes, non-dividing block sizes,
zero-gt and all-ignore degeneracies.  The proposal chain as a whole is held
to the one built from the dense oracle (``tests/oracles.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mx_rcnn_tpu.geometry import clip_boxes, decode_boxes, snap, valid_box_mask
from mx_rcnn_tpu.ops import assign_anchors, hierarchical_top_k
from mx_rcnn_tpu.ops.proposals import generate_fpn_proposals, generate_proposals
from mx_rcnn_tpu.ops.sampling import AnchorTargets, _select_random
from oracles import nms_mask_dense


def _assert_bitwise(a, b, msg=""):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype, msg
    np.testing.assert_array_equal(a, b, err_msg=msg)


# ---------------------------------------------------------------------------
# hierarchical_top_k == lax.top_k, bit for bit (values AND indices)


class TestHierarchicalTopK:
    @pytest.mark.parametrize("a", [100_003, 65_536, 1_000])
    @pytest.mark.parametrize("k", [1, 7, 2000])
    @pytest.mark.parametrize("block", [1024, 7777, 32768])
    def test_matches_global_topk_with_ties(self, rng, a, k, block):
        if k > a:
            pytest.skip("k > operand length is rejected by contract")
        # Heavy ties: rounded snapped scores, exactly the RPN contract
        # (proposals rank snap()ed sigmoid scores, so equal values with
        # index-stable tie-break is the common case, not the corner).
        s = snap(jnp.asarray(rng.randn(a), jnp.float32))
        s = jnp.round(s * 16) / 16  # collapse to few distinct values
        hv, hi = jax.jit(
            lambda x: hierarchical_top_k(x, k, block=block)
        )(s)
        ev, ei = lax.top_k(s, k)
        _assert_bitwise(hv, ev, f"values a={a} k={k} block={block}")
        _assert_bitwise(hi, ei, f"indices a={a} k={k} block={block}")

    def test_masked_invalid_lanes(self, rng):
        # -inf is how callers mask invalid anchors; padding uses the same
        # floor, so the test proves padding can't displace a real -inf
        # (both lose every tie to lower indices, and real -inf at smaller
        # index wins over padding at the tail).
        a, k = 9_999, 128
        s = jnp.asarray(rng.randn(a), jnp.float32)
        s = s.at[::3].set(-jnp.inf)
        hv, hi = hierarchical_top_k(s, k, block=1000)
        ev, ei = lax.top_k(s, k)
        _assert_bitwise(hv, ev)
        _assert_bitwise(hi, ei)

    def test_all_equal_scores_index_stable(self):
        a, k = 4_097, 50
        s = jnp.full((a,), 0.5, jnp.float32)
        hv, hi = hierarchical_top_k(s, k, block=512)
        _assert_bitwise(hi, jnp.arange(k, dtype=hi.dtype))
        _assert_bitwise(hv, jnp.full((k,), 0.5, jnp.float32))

    def test_k_equals_a_and_small_operand_fall_back(self, rng):
        s = jnp.asarray(rng.randn(300), jnp.float32)
        hv, hi = hierarchical_top_k(s, 300, block=128)
        ev, ei = lax.top_k(s, 300)
        _assert_bitwise(hv, ev)
        _assert_bitwise(hi, ei)
        # operand smaller than block: plain lax.top_k path
        hv, hi = hierarchical_top_k(s, 10, block=4096)
        ev, ei = lax.top_k(s, 10)
        _assert_bitwise(hv, ev)
        _assert_bitwise(hi, ei)

    def test_int_dtype(self, rng):
        s = jnp.asarray(rng.randint(-1000, 1000, 5_000), jnp.int32)
        hv, hi = hierarchical_top_k(s, 64, block=999)
        ev, ei = lax.top_k(s, 64)
        _assert_bitwise(hv, ev)
        _assert_bitwise(hi, ei)

    def test_bfloat16_scores(self, rng):
        # The dtype the bf16 presets rank (the head emits bf16 under the
        # mixed policy).  Found by PR 21's first run at recipe width on
        # the chip: the padded tail's floor value must come from jnp's
        # dtype lattice — numpy calls bfloat16 kind 'V' and refused it.
        s = jnp.asarray(rng.randn(5_000), jnp.bfloat16)
        hv, hi = hierarchical_top_k(s, 64, block=999)
        ev, ei = lax.top_k(s, 64)
        _assert_bitwise(hv, ev)
        _assert_bitwise(hi, ei)

    def test_k_larger_than_operand_raises(self):
        with pytest.raises(ValueError):
            hierarchical_top_k(jnp.zeros(10), 11)

    def test_select_random_blocked_matches_global(self, rng):
        key = jax.random.PRNGKey(3)
        cand = jnp.asarray(rng.rand(50_000) < 0.1)
        for with_idx in (False, True):
            out_b = _select_random(key, cand, 128, 256, block=4096,
                                   with_indices=with_idx)
            out_g = _select_random(key, cand, 128, 256, block=0,
                                   with_indices=with_idx)
            for x, y in zip(jax.tree_util.tree_leaves(out_b),
                            jax.tree_util.tree_leaves(out_g)):
                _assert_bitwise(x, y)


# ---------------------------------------------------------------------------
# blocked anchor assignment == dense assignment, bit for bit


def _random_anchors(rng, n, canvas=800):
    a = rng.uniform(-40, canvas + 40, (n, 4)).astype(np.float32)
    lo = np.minimum(a[:, :2], a[:, 2:])
    hi = np.maximum(a[:, :2], a[:, 2:]) + 1.0
    return jnp.asarray(np.concatenate([lo, hi], axis=1))


class TestBlockedAssignment:
    def _parity(self, key, anchors, gt, gv, block, **kw):
        t_b = assign_anchors(key, anchors, gt, gv, 800.0, 800.0,
                             assign_block=block, **kw)
        t_d = assign_anchors(key, anchors, gt, gv, 800.0, 800.0,
                             assign_block=0, **kw)
        for f in AnchorTargets._fields:
            x, y = getattr(t_b, f), getattr(t_d, f)
            if x is None:
                assert y is None
                continue
            _assert_bitwise(x, y, f"field {f} block={block}")
        return t_b

    @pytest.mark.parametrize("block", [512, 4096, 3001])
    def test_random_inputs(self, rng, block):
        anchors = _random_anchors(rng, 20_000)
        gt = jnp.asarray(
            [[10, 10, 200, 200], [300, 300, 500, 400],
             [5, 5, 790, 790], [0, 0, 0, 0]], jnp.float32)
        gv = jnp.asarray([True, True, True, False])
        t = self._parity(jax.random.PRNGKey(0), anchors, gt, gv, block)
        assert t.sel_idx is not None and t.sel_idx.dtype == jnp.int32
        # Active compact slots point at loss-contributing (labeled) anchors.
        assert bool(jnp.all(~t.sel_take | t.valid_mask[t.sel_idx]))

    def test_zero_gt(self, rng):
        anchors = _random_anchors(rng, 9_000)
        gt = jnp.zeros((5, 4), jnp.float32)
        gv = jnp.zeros((5,), bool)
        self._parity(jax.random.PRNGKey(1), anchors, gt, gv, 1024)

    def test_all_ignore(self, rng):
        anchors = _random_anchors(rng, 9_000)
        gt = jnp.asarray([[0, 0, 799, 799]] * 3, jnp.float32)
        gv = jnp.ones((3,), bool)
        gi = jnp.ones((3,), bool)
        self._parity(jax.random.PRNGKey(2), anchors, gt, gv, 1024,
                     gt_ignore=gi)

    def test_block_larger_than_anchors_is_dense(self, rng):
        # assign_block >= A dispatches to the dense pass — trivially equal,
        # but pins the dispatch predicate.
        anchors = _random_anchors(rng, 1_000)
        gt = jnp.asarray([[100, 100, 300, 300]], jnp.float32)
        gv = jnp.ones((1,), bool)
        self._parity(jax.random.PRNGKey(4), anchors, gt, gv, 4096)


# ---------------------------------------------------------------------------
# proposals: hier == exact end-to-end; the FPN chain == the oracle's chain


def _oracle_fpn_chain(level_scores, level_deltas, level_anchors, image_height,
                      image_width, pre_nms_top_n, post_nms_top_n,
                      nms_threshold, min_size):
    """``generate_fpn_proposals`` the plain way: a level at a time, the
    global ``lax.top_k``, the dense oracle's keep mask, and the ranking and
    the final top-k in numpy (stable sorts: the lower index wins a tie)."""
    rois, scores = [], []
    for lvl in sorted(level_scores):
        top_s, top_i = lax.top_k(snap(level_scores[lvl]),
                                 min(pre_nms_top_n, level_scores[lvl].shape[0]))
        boxes = decode_boxes(level_deltas[lvl][top_i], level_anchors[lvl][top_i])
        boxes = snap(clip_boxes(boxes, image_height, image_width), bits=8)
        masked = jnp.where(valid_box_mask(boxes, min_size=min_size), top_s, -jnp.inf)
        keep = np.asarray(nms_mask_dense(boxes, masked, nms_threshold))
        boxes, masked = np.asarray(boxes), np.asarray(masked)
        order = np.argsort(np.where(keep, -masked, np.inf), kind="stable")
        order = order[:min(int(keep.sum()), post_nms_top_n)]
        rois.append(boxes[order])
        scores.append(masked[order])
    rois, scores = np.concatenate(rois), np.concatenate(scores)
    best = np.argsort(-scores, kind="stable")[:post_nms_top_n]
    n = len(best)
    out_rois = np.zeros((post_nms_top_n, 4), np.float32)
    out_scores = np.zeros(post_nms_top_n, np.float32)
    out_rois[:n], out_scores[:n] = rois[best], scores[best]
    return out_rois, out_scores, np.arange(post_nms_top_n) < n


class TestProposalParity:
    def test_single_level_hier_equals_exact(self, rng):
        a = 9_000
        scores = snap(jnp.asarray(rng.rand(a), jnp.float32))
        deltas = jnp.asarray(rng.randn(a, 4) * 0.1, jnp.float32)
        anchors = _random_anchors(rng, a, canvas=700)
        kw = dict(image_height=800.0, image_width=800.0,
                  pre_nms_top_n=2000, post_nms_top_n=300,
                  nms_threshold=0.7)
        r_h = generate_proposals(scores, deltas, anchors, **kw,
                                 topk_impl="hier", topk_block=1024)
        r_e = generate_proposals(scores, deltas, anchors, **kw,
                                 topk_impl="exact")
        for x, y in zip(r_h, r_e):
            _assert_bitwise(x, y)

    def test_fpn_hier_equals_exact(self, rng):
        level_scores, level_deltas, level_anchors = {}, {}, {}
        for lvl, n in ((2, 6000), (3, 1500), (4, 400), (5, 100)):
            level_scores[lvl] = snap(jnp.asarray(rng.rand(n), jnp.float32))
            level_deltas[lvl] = jnp.asarray(rng.randn(n, 4) * 0.1, jnp.float32)
            level_anchors[lvl] = _random_anchors(rng, n, canvas=700)
        kw = dict(image_height=800.0, image_width=800.0,
                  pre_nms_top_n=1000, post_nms_top_n=500,
                  nms_threshold=0.7)
        r_h = generate_fpn_proposals(level_scores, level_deltas,
                                     level_anchors, **kw,
                                     topk_impl="hier", topk_block=1024)
        r_e = generate_fpn_proposals(level_scores, level_deltas,
                                     level_anchors, **kw, topk_impl="exact")
        for x, y in zip(r_h, r_e):
            _assert_bitwise(x, y)

    @pytest.mark.parametrize("case, overrides", [
        ("tied_padded", {}),
        ("min_size", {"min_size": 16.0}),
        ("zero_valid", {"image_height": 0.0, "image_width": 0.0}),
    ])
    def test_fpn_chain_equals_dense_oracle_chain(self, rng, case, overrides):
        # Five levels, three shorter than pre_nms_top_n (padded to the
        # widest with -inf), heavy snapped ties and -inf masked lanes.
        # "min_size" masks candidates after the decode; "zero_valid" clips
        # every box to nothing, so no candidate is valid anywhere.
        kw = dict(image_height=800.0, image_width=800.0, pre_nms_top_n=256,
                  post_nms_top_n=128, nms_threshold=0.7, min_size=0.0)
        kw.update(overrides)
        level_scores, level_deltas, level_anchors = {}, {}, {}
        for lvl, n in ((2, 3000), (3, 800), (4, 200), (5, 60), (6, 15)):
            sc = jnp.round(snap(jnp.asarray(rng.rand(n), jnp.float32)) * 16) / 16
            level_scores[lvl] = sc.at[::5].set(-jnp.inf)
            level_deltas[lvl] = jnp.asarray(rng.randn(n, 4) * 0.1, jnp.float32)
            level_anchors[lvl] = _random_anchors(rng, n, canvas=700)
        got = generate_fpn_proposals(level_scores, level_deltas,
                                     level_anchors, **kw)
        want = _oracle_fpn_chain(level_scores, level_deltas, level_anchors, **kw)
        for x, y, name in zip(got, want, got._fields):
            _assert_bitwise(x, y, f"{case}: {name}")
        if case == "zero_valid":
            assert not bool(jnp.any(got.valid))
        else:
            assert 0 < int(jnp.sum(got.valid))

    def test_bad_topk_impl_raises(self, rng):
        a = 500
        with pytest.raises(ValueError, match="topk_impl"):
            generate_proposals(
                jnp.zeros(a), jnp.zeros((a, 4)), _random_anchors(rng, a),
                image_height=800.0, image_width=800.0,
                pre_nms_top_n=100, post_nms_top_n=50, topk_impl="wrong",
            )


# ---------------------------------------------------------------------------
# compact RPN loss == dense up to summation order; accuracy exactly equal


class TestCompactRpnLoss:
    def _setup(self, rng, b=2, a=20_000):
        from mx_rcnn_tpu.detection.graph import _rpn_losses

        anchors = _random_anchors(rng, a)
        gt = jnp.asarray([[[10, 10, 200, 200], [300, 300, 500, 400]]] * b,
                         jnp.float32)
        gv = jnp.ones((b, 2), bool)
        targets = jax.vmap(
            lambda k, g, v: assign_anchors(k, anchors, g, v, 800.0, 800.0,
                                           assign_block=1024)
        )(jax.random.split(jax.random.PRNGKey(0), b), gt, gv)
        logits = jnp.asarray(rng.randn(b, a), jnp.float32)
        deltas = jnp.asarray(rng.randn(b, a, 4) * 0.1, jnp.float32)
        return _rpn_losses, logits, deltas, targets

    def test_compact_matches_dense(self, rng):
        _rpn_losses, logits, deltas, targets = self._setup(rng)
        cls_d, box_d, acc_d = _rpn_losses(logits, deltas, targets, "dense")
        cls_c, box_c, acc_c = _rpn_losses(logits, deltas, targets, "compact")
        # Same terms, different summation order: f32 round-off only.
        np.testing.assert_allclose(float(cls_c), float(cls_d), rtol=1e-5)
        np.testing.assert_allclose(float(box_c), float(box_d), rtol=1e-5)
        # Accuracy is an integer count / count ratio (<= 256 < 2^24):
        # EXACTLY equal, not just close.
        assert float(acc_c) == float(acc_d)

    def test_compact_requires_sel_indices(self, rng):
        _rpn_losses, logits, deltas, targets = self._setup(rng, a=5_000)
        stripped = targets._replace(sel_idx=None, sel_take=None, sel_fg=None)
        with pytest.raises(ValueError, match="sel_"):
            _rpn_losses(logits, deltas, stripped, "compact")

    def test_bad_loss_impl_raises(self, rng):
        _rpn_losses, logits, deltas, targets = self._setup(rng, a=5_000)
        with pytest.raises(ValueError, match="loss_impl"):
            _rpn_losses(logits, deltas, targets, "sparse")


# ---------------------------------------------------------------------------
# anchor-constant hoisting: cached, numpy-typed (tracer-leak-proof)


class TestAnchorCache:
    def test_cached_and_host_typed(self):
        from mx_rcnn_tpu.detection.graph import _cached_level_anchor

        a1 = _cached_level_anchor(16, (0.5, 1.0, 2.0), (8.0,), 4, 6)
        a2 = _cached_level_anchor(16, (0.5, 1.0, 2.0), (8.0,), 4, 6)
        assert a1 is a2  # memoized
        # numpy, NOT jnp: a cached jnp array built under a trace would be
        # a leaked tracer on the next trace.
        assert isinstance(a1, np.ndarray)
        assert a1.shape == (4 * 6 * 3, 4)

    def test_matches_direct_generation(self):
        from mx_rcnn_tpu.detection.graph import _cached_level_anchor
        from mx_rcnn_tpu.geometry import (
            generate_base_anchors,
            shifted_anchors,
        )

        got = _cached_level_anchor(8, (0.5, 1.0, 2.0), (8.0, 16.0), 3, 5)
        base = generate_base_anchors(
            base_size=8, ratios=(0.5, 1.0, 2.0), scales=(8.0, 16.0))
        want = shifted_anchors(base, 8, 3, 5)
        _assert_bitwise(got, np.asarray(want))
