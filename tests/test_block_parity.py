"""Exactness proofs for two PR-13 perf paths: the blocked ROI sampling
stats and the bucketed/overlapped gradient all-reduce.

Same discipline as test_detection_middle.py: every fast path is a
layout/schedule rewrite of exact math and must be BIT-identical to the
dense form it replaces.  The collective tests run on the 8-device fake
mesh the suite always has (conftest.py).

The one tolerance in this file is deliberate: the overlapped step's
``loss`` METRIC is a pmean of per-shard means where GSPMD sums globally
— same math, different summation order (~1 ulp).  The STATE (params,
momentum, rng — everything training consumes) is asserted bitwise.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mx_rcnn_tpu.config import get_config
from mx_rcnn_tpu.detection import Batch, TwoStageDetector
from mx_rcnn_tpu.ops.sampling import RoiSamples, sample_rois
from mx_rcnn_tpu.parallel import (
    ExecutionPlan,
    make_mesh,
    make_train_step,
    shard_batch,
)
from mx_rcnn_tpu.parallel.step import _bucketed_pmean
from mx_rcnn_tpu.train import create_train_state, make_optimizer


def _assert_bitwise(a, b, msg=""):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype, msg
    np.testing.assert_array_equal(a, b, err_msg=msg)


def _assert_trees_bitwise_equal(a, b, what=""):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(fa) == len(fb)
    for (pa, la), (pb, lb) in zip(fa, fb):
        assert pa == pb
        la, lb = np.asarray(la), np.asarray(lb)
        assert la.dtype == lb.dtype, f"{what}{pa}: {la.dtype} != {lb.dtype}"
        nan_ok = np.issubdtype(la.dtype, np.floating)
        assert np.array_equal(la, lb, equal_nan=nan_ok), (
            f"{what}{jax.tree_util.keystr(pa)} differs bitwise"
        )


def _random_anchors(rng, n, canvas=800):
    a = rng.uniform(-40, canvas + 40, (n, 4)).astype(np.float32)
    lo = np.minimum(a[:, :2], a[:, 2:])
    hi = np.maximum(a[:, :2], a[:, 2:]) + 1.0
    return jnp.asarray(np.concatenate([lo, hi], axis=1))


# ---------------------------------------------------------------------------
# Blocked ROI sampling stats == dense (R+G, G) matrices, bit for bit


class TestRoiBlockParity:
    def _parity(self, rng, roi_block, n_rois=600, n_gt=12, **kw):
        rois = _random_anchors(rng, n_rois, canvas=700)
        rv = jnp.asarray(rng.rand(n_rois) < 0.9)
        gt = _random_anchors(rng, n_gt, canvas=700)
        gc = jnp.asarray(rng.randint(1, 7, n_gt), jnp.int32)
        gv = jnp.asarray(rng.rand(n_gt) < 0.8)
        key = jax.random.PRNGKey(7)
        s_b = sample_rois(key, rois, rv, gt, gc, gv, roi_block=roi_block,
                          **kw)
        s_d = sample_rois(key, rois, rv, gt, gc, gv, roi_block=0, **kw)
        for f in RoiSamples._fields:
            x, y = getattr(s_b, f), getattr(s_d, f)
            if x is None:
                assert y is None
                continue
            _assert_bitwise(x, y, f"field {f} roi_block={roi_block}")

    @pytest.mark.parametrize("roi_block", [64, 100, 128])
    def test_random_inputs(self, rng, roi_block):
        self._parity(rng, roi_block)

    def test_block_larger_than_rois_is_dense(self, rng):
        self._parity(rng, 10_000)

    def test_with_ignore_regions(self, rng):
        gi = jnp.asarray([True] * 6 + [False] * 6)
        self._parity(rng, 100, gt_ignore=gi, ignore_ioa=0.4)

    @pytest.mark.slow  # CI perf_smoke runs the full file in interpret mode
    def test_zero_valid_gt(self, rng):
        rois = _random_anchors(rng, 200, canvas=700)
        rv = jnp.ones(200, bool)
        gt = jnp.zeros((4, 4), jnp.float32)
        gc = jnp.zeros(4, jnp.int32)
        gv = jnp.zeros(4, bool)
        key = jax.random.PRNGKey(9)
        s_b = sample_rois(key, rois, rv, gt, gc, gv, roi_block=64)
        s_d = sample_rois(key, rois, rv, gt, gc, gv)
        for f in RoiSamples._fields:
            x, y = getattr(s_b, f), getattr(s_d, f)
            if x is not None:
                _assert_bitwise(x, y, f"field {f}")


# ---------------------------------------------------------------------------
# Bucketed gradient all-reduce: exact regrouping, overlapped step parity


@pytest.fixture(scope="module")
def built():
    """Tiny model + host step-0 state (same recipe as test_plan.py's
    fixture: 64px canvas, saturated sampling quotas so loss normalizers
    are constant — the accumulation/sharding parity precondition)."""
    cfg = get_config("tiny_synthetic")
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(
            cfg.model,
            rpn=dataclasses.replace(cfg.model.rpn, allowed_border=1000.0),
        ),
        data=dataclasses.replace(
            cfg.data, image_size=(64, 64), short_side=64, max_side=64
        ),
    )
    model = TwoStageDetector(cfg=cfg.model)
    tx, schedule = make_optimizer(cfg.train, None)
    state = create_train_state(
        model, tx, jax.random.PRNGKey(0), cfg.data.image_size, batch=1
    )
    host = jax.device_get(state)
    return SimpleNamespace(
        cfg=cfg, model=model, tx=tx, schedule=schedule, host=host,
        pixel_stats=(cfg.data.pixel_mean, cfg.data.pixel_std),
    )


def _batches(cfg, n, b):
    rng = np.random.RandomState(0)
    h, w = cfg.data.image_size
    g = cfg.data.max_gt_boxes
    n_gt = min(8, g)
    total = n * b
    boxes = np.zeros((total, g, 4), np.float32)
    for i in range(total):
        bw = rng.uniform(w // 8, w // 4, n_gt)
        bh = rng.uniform(h // 8, h // 4, n_gt)
        x1 = rng.uniform(0, w - bw)
        y1 = rng.uniform(0, h - bh)
        boxes[i, :n_gt] = np.stack([x1, y1, x1 + bw, y1 + bh], axis=1)
    classes = np.zeros((total, g), np.int32)
    classes[:, :n_gt] = rng.randint(1, cfg.model.num_classes, (total, n_gt))
    valid = np.zeros((total, g), bool)
    valid[:, :n_gt] = True
    batch = Batch(
        images=rng.randint(0, 256, (total, h, w, 3), dtype=np.uint8),
        image_hw=np.tile(
            np.asarray([[float(h), float(w)]], np.float32), (total, 1)
        ),
        gt_boxes=boxes, gt_classes=classes, gt_valid=valid,
    )
    if n > 1:
        batch = Batch(*[
            None if f is None else f.reshape(n, b, *f.shape[1:])
            for f in batch
        ])
    return batch


def _mesh_step(built, **plan_kw):
    plan = ExecutionPlan.for_model(built.model, mesh=make_mesh(), **plan_kw)
    step = make_train_step(
        built.model, built.tx, built.schedule,
        pixel_stats=built.pixel_stats, plan=plan, state_template=built.host,
    )
    return plan, step


@pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device fake mesh"
)
class TestBucketedPmean:
    def test_regrouping_is_exact_and_invisible_in_the_trace(self):
        # Four 1-MiB leaves, one bucket each (bucket_mb=1) or all in one
        # (bucket_mb=0): values bitwise equal — pmean over a list reduces
        # each leaf independently, so grouping never changes numerics.
        # Under jax 0.9.0 it does not change the TRACE either: pmean of a
        # list traces to one psum PER LEAF whatever the grouping, so both
        # forms show four psum eqns, and how many all-reduce ops run is
        # the XLA all-reduce combiner's decision (one on XLA:CPU for
        # both; the on-chip count is in PERF.md, "Bring-up, PR 21").
        mesh = make_mesh()
        tree = {
            k: jnp.full((512, 512), float(i), jnp.float32)
            for i, k in enumerate("abcd")
        }

        def reduced(mb):
            return jax.shard_map(
                lambda t: _bucketed_pmean(t, mb), mesh=mesh,
                in_specs=(P(),), out_specs=P(), check_vma=False,
            )

        assert str(jax.make_jaxpr(reduced(1))(tree)).count("psum") == 4
        assert str(jax.make_jaxpr(reduced(0))(tree)).count("psum") == 4
        _assert_trees_bitwise_equal(reduced(1)(tree), reduced(0)(tree))

    def test_plan_gating(self):
        # Module construction is enough for for_model (param_families is
        # config-derived) — keeps this off the expensive `built` fixture
        # so tier-1 never pays the state init (only @slow tests do).
        model = TwoStageDetector(cfg=get_config("tiny_synthetic").model)
        mesh = make_mesh()
        p = ExecutionPlan.for_model(model, mesh=mesh, bucket_mb=64)
        assert p.overlap_grads and p.use_shard_map
        assert not ExecutionPlan.for_model(model, mesh=mesh).overlap_grads
        # Off-mesh / stacked variants keep their existing dispatch.
        assert not ExecutionPlan.for_model(model, bucket_mb=64).overlap_grads
        q = ExecutionPlan.for_model(
            model, mesh=mesh, bucket_mb=64, accum_steps=2
        )
        assert not q.overlap_grads and q.use_shard_map
        with pytest.raises(ValueError, match="bucket_mb"):
            ExecutionPlan(bucket_mb=-1)
        with pytest.raises(ValueError, match="spatial"):
            ExecutionPlan(mesh=mesh, spatial=True, bucket_mb=64)

    @pytest.mark.slow  # executes full train steps (CI multichip smoke)
    def test_overlap_step_state_bitwise_the_plain_step(self, built):
        # The headline claim: issuing the gradient all-reduce ourselves
        # (bucketed, overlapped) changes WHEN bytes move, not what the
        # optimizer applies — state after one step is bit-identical to
        # the plain GSPMD step.  Only the loss METRIC reassociates
        # (per-shard means pmean'd vs one global sum).
        flat = _batches(built.cfg, 1, 8)
        plan0, step0 = _mesh_step(built)
        s0, m0 = step0(plan0.shard_state(built.host),
                       shard_batch(flat, plan0.mesh, stacked=False))
        plan1, step1 = _mesh_step(built, bucket_mb=64)
        s1, m1 = step1(plan1.shard_state(built.host),
                       shard_batch(flat, plan1.mesh, stacked=False))
        _assert_trees_bitwise_equal(
            jax.device_get(s0), jax.device_get(s1), "state:"
        )
        m0, m1 = jax.device_get((m0, m1))
        for key in m0:
            np.testing.assert_allclose(
                m0[key], m1[key], rtol=1e-5, atol=2e-6,
                err_msg=f"metric {key!r}",
            )

    @pytest.mark.slow  # executes full train steps (CI multichip smoke)
    def test_bucketed_vs_single_bucket_bitwise_at_accum1(self, built):
        # Same overlapped structure, different grouping: ~64 MiB buckets
        # vs one bucket holding the whole tree (bucket_mb larger than
        # the params).  Bitwise everywhere, metrics included.
        flat = _batches(built.cfg, 1, 8)
        plan1, step1 = _mesh_step(built, bucket_mb=64)
        s1, m1 = step1(plan1.shard_state(built.host),
                       shard_batch(flat, plan1.mesh, stacked=False))
        plan2, step2 = _mesh_step(built, bucket_mb=1 << 20)
        s2, m2 = step2(plan2.shard_state(built.host),
                       shard_batch(flat, plan2.mesh, stacked=False))
        _assert_trees_bitwise_equal(
            jax.device_get(s1), jax.device_get(s2), "state:"
        )
        _assert_trees_bitwise_equal(
            jax.device_get(m1), jax.device_get(m2), "metrics:"
        )

    @pytest.mark.slow  # executes full train steps (CI multichip smoke)
    @pytest.mark.parametrize("accum", [2, 4])
    def test_accum_bucketed_matches_single_reduce(self, built, accum):
        # The accumulation path's all-reduce rides the same bucketing.
        # Held to f32 accumulation tolerance (the two programs compile
        # separately); in practice the per-leaf pmean identity makes
        # them land bitwise equal too.
        stacked = _batches(built.cfg, accum, 8)
        plan0, step0 = _mesh_step(built, accum_steps=accum)
        s0, m0 = step0(plan0.shard_state(built.host),
                       shard_batch(stacked, plan0.mesh, stacked=True))
        plan1, step1 = _mesh_step(built, accum_steps=accum, bucket_mb=64)
        s1, m1 = step1(plan1.shard_state(built.host),
                       shard_batch(stacked, plan1.mesh, stacked=True))
        fa = jax.tree_util.tree_flatten_with_path(
            jax.device_get(s0.params))[0]
        fb = jax.tree_util.tree_flatten_with_path(
            jax.device_get(s1.params))[0]
        for (pa, la), (_, lb) in zip(fa, fb):
            np.testing.assert_allclose(
                la, lb, rtol=1e-5, atol=2e-6,
                err_msg=f"param {jax.tree_util.keystr(pa)} (accum={accum})",
            )
        m0, m1 = jax.device_get((m0, m1))
        for key in m0:
            np.testing.assert_allclose(
                m0[key], m1[key], rtol=1e-5, atol=2e-6,
                err_msg=f"metric {key!r} (accum={accum})",
            )

    @pytest.mark.slow  # executes full train steps (CI multichip smoke)
    def test_bit_exact_resume_through_overlap_step(self, built, tmp_path):
        # PR-3's chaos guarantee extended to the overlapped step: save
        # after one overlapped step, restore into a fresh template, run
        # one more — bitwise identical to two uninterrupted steps.
        from mx_rcnn_tpu.train.checkpoint import (
            restore_checkpoint,
            save_checkpoint,
        )

        plan, step_fn = _mesh_step(built, bucket_mb=64)
        flat = _batches(built.cfg, 1, 8)

        state = plan.shard_state(built.host)
        for _ in range(2):
            state, _ = step_fn(state, shard_batch(flat, plan.mesh,
                                                  stacked=False))
        straight = jax.device_get(state)

        state = plan.shard_state(built.host)
        state, _ = step_fn(state, shard_batch(flat, plan.mesh,
                                              stacked=False))
        ckpt_dir = str(tmp_path / "ckpt")
        save_checkpoint(ckpt_dir, jax.device_get(state), wait=True)
        restored = restore_checkpoint(ckpt_dir, built.host)
        assert int(restored.step) == 1
        state = plan.shard_state(restored)
        state, _ = step_fn(state, shard_batch(flat, plan.mesh,
                                              stacked=False))
        resumed = jax.device_get(state)

        _assert_trees_bitwise_equal(straight, resumed, "resume:")
