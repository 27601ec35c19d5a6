"""Mixed-precision (r6) tests: policy resolution, bf16-vs-f32 training
parity, f32 metric accumulation, bit-exact checkpoint resume on the bf16
path, the int8/bf16 serving head, the TPU006 upcast walk, and the bench
headline-knob drift guard.

Everything runs the hermetic tiny_synthetic preset on CPU.  The bf16
variant forces ``model.backbone.dtype=bfloat16`` +
``model.precision.policy=mixed`` — on CPU bf16 matmuls emulate in f32,
so these tests prove the precision THREADING (dtypes flow where the
policy says, accumulations stay f32, nothing NaNs or degenerates), while
the numeric win is the TPU bench's job.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import apply_overrides, get_config

BF16_OVERRIDES = [
    "model.backbone.dtype=bfloat16",
    "model.precision.policy=mixed",
]


def _build(overrides=()):
    from bench import _synthetic_batch
    from mx_rcnn_tpu.train.loop import build_all

    cfg = apply_overrides(get_config("tiny_synthetic"), list(overrides))
    model, _tx, state, step, _gb = build_all(cfg, mesh=None)
    k = max(cfg.train.steps_per_call, 1)
    batch = _synthetic_batch(
        cfg, cfg.train.per_device_batch, cfg.data.image_size, k
    )
    return cfg, model, state, step, jax.device_put(batch)


@pytest.fixture(scope="module")
def f32_step_out():
    _cfg, _model, state, step, batch = _build()
    new_state, metrics = step(state, batch)
    return jax.device_get(new_state), jax.device_get(metrics)


@pytest.fixture(scope="module")
def bf16_step_out():
    _cfg, _model, state, step, batch = _build(BF16_OVERRIDES)
    new_state, metrics = step(state, batch)
    return jax.device_get(new_state), jax.device_get(metrics)


# ---------------------------------------------------------------------------
# policy resolution
# ---------------------------------------------------------------------------


class TestPolicy:
    def test_mixed_bf16(self):
        from mx_rcnn_tpu.utils.precision import resolve

        p = resolve("mixed", "bfloat16")
        assert p.compute_dtype == jnp.bfloat16
        assert p.output_dtype == jnp.bfloat16
        assert p.accum_dtype == jnp.float32
        assert p.param_dtype == jnp.float32

    def test_widen_bf16_emits_f32(self):
        from mx_rcnn_tpu.utils.precision import resolve

        p = resolve("widen", "bfloat16")
        assert p.compute_dtype == jnp.bfloat16
        assert p.output_dtype == jnp.float32

    def test_float32_policy_overrides_backbone_knob(self):
        from mx_rcnn_tpu.utils.precision import resolve

        p = resolve("float32", "bfloat16")
        assert p.compute_dtype == jnp.float32
        assert p.output_dtype == jnp.float32

    def test_mixed_on_f32_backbone_degenerates_to_f32(self):
        # tiny_synthetic's contract: mixed + f32 backbone == all-f32, so
        # the hermetic goldens are bit-identical by construction.
        from mx_rcnn_tpu.utils.precision import policy_of

        p = policy_of(get_config("tiny_synthetic").model)
        assert p.compute_dtype == jnp.float32
        assert p.output_dtype == jnp.float32

    def test_policy_of_without_precision_section_is_widen(self):
        from mx_rcnn_tpu.utils.precision import policy_of

        class OldModelCfg:
            precision = None
            backbone = get_config("tiny_synthetic").model.backbone

        p = policy_of(OldModelCfg())
        assert p.name == "widen"
        assert p.output_dtype == jnp.float32

    def test_unknown_policy_raises(self):
        from mx_rcnn_tpu.utils.precision import resolve

        with pytest.raises(ValueError, match="unknown precision policy"):
            resolve("int4", "bfloat16")

    def test_heads_take_output_dtype_from_policy(self):
        from mx_rcnn_tpu.detection import TwoStageDetector
        from mx_rcnn_tpu.detection.graph import init_detector

        cfg = apply_overrides(
            get_config("tiny_synthetic"), BF16_OVERRIDES
        )
        model = TwoStageDetector(cfg=cfg.model)
        h, w = cfg.data.image_size
        variables = init_detector(model, jax.random.PRNGKey(0), (h, w))
        feats = model.apply(
            variables,
            jnp.zeros((1, h, w, 3), jnp.float32),
            method="features",
        )
        assert all(f.dtype == jnp.bfloat16 for f in feats.values())


# ---------------------------------------------------------------------------
# bf16 train-step parity + metric accumulation (satellites 2 and 3)
# ---------------------------------------------------------------------------


class TestBf16Training:
    def test_bf16_metrics_are_f32_and_finite(self, bf16_step_out):
        _state, metrics = bf16_step_out
        for name, v in metrics.items():
            assert np.asarray(v).dtype == np.float32, name
            assert np.isfinite(v), name

    def test_bf16_params_stay_f32_masters_and_finite(self, bf16_step_out):
        state, _metrics = bf16_step_out
        for leaf in jax.tree_util.tree_leaves(state.params):
            assert np.asarray(leaf).dtype == np.float32
            assert np.all(np.isfinite(leaf))

    def test_bf16_metrics_close_to_f32(self, f32_step_out, bf16_step_out):
        # Tolerance note (docs/performance.md): bf16 proposal scores can
        # legitimately reorder the top-k / sampled-roi set, so the RCNN
        # losses see a slightly different roi sample — this guards
        # against precision-THREADING bugs (degenerate zeros, NaN, f32
        # graphs silently unchanged), not bitwise numerics.
        _s1, m32 = f32_step_out
        _s2, m16 = bf16_step_out
        assert set(m32) == set(m16)
        for name in m32:
            a, b = float(m32[name]), float(m16[name])
            assert abs(a - b) <= 0.1 + 0.05 * abs(a), (name, a, b)

    def test_bf16_loss_not_degenerate(self, bf16_step_out):
        _state, metrics = bf16_step_out
        assert float(metrics["loss"]) > 0.5
        assert float(metrics["nonfinite"]) == 0.0

    def test_bf16_checkpoint_resume_bitexact(self, tmp_path):
        # One interrupted and one uninterrupted continuation from the
        # same saved step must produce bit-identical states: the f32
        # master params are the single source of truth, and bf16 casts
        # are deterministic functions of them.
        from mx_rcnn_tpu.train.checkpoint import (
            restore_checkpoint,
            save_checkpoint,
        )

        _cfg, _model, state, step, batch = _build(BF16_OVERRIDES)
        s1, _ = step(state, batch)
        template = jax.tree_util.tree_map(jnp.copy, s1)
        save_checkpoint(str(tmp_path), s1, wait=True)
        continued, _ = step(s1, batch)

        restored = restore_checkpoint(str(tmp_path), template)
        resumed, _ = step(restored, batch)

        assert int(continued.step) == int(resumed.step)
        # rng is compared via its consequences (params below), not
        # directly — typed key arrays don't convert to numpy.
        for field in ("params", "model_state", "opt_state"):
            a = jax.tree_util.tree_leaves(
                jax.device_get(getattr(continued, field))
            )
            b = jax.tree_util.tree_leaves(
                jax.device_get(getattr(resumed, field))
            )
            assert len(a) == len(b)
            for la, lb in zip(a, b):
                np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# int8/bf16 serving head (tentpole b + satellite 3)
# ---------------------------------------------------------------------------


class TestInt8BoxHead:
    def test_quantize_roundtrip_error_bound(self):
        from mx_rcnn_tpu.utils.precision import (
            dequantize,
            quantize_per_channel,
        )

        w = np.random.RandomState(0).randn(96, 40).astype(np.float32)
        q, scale = quantize_per_channel(jnp.asarray(w))
        assert q.dtype == jnp.int8
        wd = np.asarray(dequantize(q, scale, jnp.float32))
        # Symmetric int8: error per weight <= scale/2 per channel.
        amax = np.max(np.abs(w), axis=0, keepdims=True)
        assert np.all(np.abs(wd - w) <= amax / 127.0 * 0.5 + 1e-7)

    def test_zero_channel_dequantizes_exact(self):
        from mx_rcnn_tpu.utils.precision import (
            dequantize,
            quantize_per_channel,
        )

        w = np.ones((8, 3), np.float32)
        w[:, 1] = 0.0
        q, scale = quantize_per_channel(jnp.asarray(w))
        wd = np.asarray(dequantize(q, scale, jnp.float32))
        np.testing.assert_array_equal(wd[:, 1], 0.0)
        np.testing.assert_allclose(wd, w, atol=1e-6)

    @pytest.fixture(scope="class")
    def tiny_variables(self):
        from mx_rcnn_tpu.detection import TwoStageDetector
        from mx_rcnn_tpu.detection.graph import init_detector

        cfg = get_config("tiny_synthetic")
        model = TwoStageDetector(cfg=cfg.model)
        h, w = cfg.data.image_size
        variables = init_detector(model, jax.random.PRNGKey(0), (h, w))
        return cfg, model, variables

    def test_q8_head_matches_f32_head(self, tiny_variables):
        from mx_rcnn_tpu.serve.quantize import (
            apply_box_head_q8,
            quantize_box_head,
        )

        cfg, model, variables = tiny_variables
        s = cfg.model.rcnn.pooled_size
        in_dim = variables["params"]["box_head"]["fc6"]["kernel"].shape[0]
        c = in_dim // (s * s)
        pooled = jnp.asarray(
            np.random.RandomState(1).randn(32, s, s, c), jnp.float32
        )
        ref_logits, ref_deltas = model.apply(variables, pooled, method="box")
        qtree = quantize_box_head(variables)
        got_logits, got_deltas = apply_box_head_q8(qtree, pooled)
        assert got_logits.shape == ref_logits.shape
        assert got_deltas.shape == ref_deltas.shape
        assert got_logits.dtype == jnp.float32
        # Weight-only int8 + bf16 activations vs the f32 head: the
        # documented serving tolerance (docs/performance.md).
        scale = float(np.max(np.abs(np.asarray(ref_logits)))) + 1e-3
        assert (
            float(np.max(np.abs(np.asarray(got_logits - ref_logits))))
            <= 0.05 * scale
        )
        dscale = float(np.max(np.abs(np.asarray(ref_deltas)))) + 1e-3
        assert (
            float(np.max(np.abs(np.asarray(got_deltas - ref_deltas))))
            <= 0.05 * dscale
        )

    def test_runner_q8_program_warms_and_serves(self, tiny_variables):
        from mx_rcnn_tpu.serve.engine import DetectorRunner

        cfg, _model, variables = tiny_variables
        runner = DetectorRunner(
            cfg, variables, batch_size=1, with_proposals=False,
            int8_head=True,
        )
        assert runner.levels() == ("full", "full_q8", "reduced")
        n = runner.warmup()
        assert n == 3  # full + full_q8 + reduced, one bucket
        img = np.random.RandomState(2).randint(
            0, 255, (96, 128, 3), np.uint8
        ).astype(np.float32)
        full = runner.run("full", runner.buckets[0], [img])[0]
        q8 = runner.run("full_q8", runner.buckets[0], [img])[0]
        for out in (full, q8):
            assert set(out) >= {"boxes", "scores", "classes"}
        # Same program family: identical output slots, scores in [0, 1].
        assert q8["boxes"].shape[1:] == full["boxes"].shape[1:]
        if len(q8["scores"]) and len(full["scores"]):
            assert abs(
                float(q8["scores"][0]) - float(full["scores"][0])
            ) <= 0.05

    def test_plan_level_degrades_through_q8(self):
        from mx_rcnn_tpu.serve.degrade import plan_level

        avail = ("full", "full_q8", "reduced", "proposals")
        est = {"full": 10.0, "full_q8": 5.0, "reduced": 1.0}
        assert plan_level(100.0, est, True, avail) == "full"
        assert plan_level(8.0, est, True, avail) == "full_q8"
        assert plan_level(2.0, est, True, avail) == "reduced"


# ---------------------------------------------------------------------------
# full-network int8 PTQ (r16 tentpole) + result cache
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_detector():
    from mx_rcnn_tpu.detection import TwoStageDetector
    from mx_rcnn_tpu.detection.graph import init_detector

    cfg = get_config("tiny_synthetic")
    model = TwoStageDetector(cfg=cfg.model)
    h, w = cfg.data.image_size
    variables = init_detector(model, jax.random.PRNGKey(0), (h, w))
    return cfg, model, variables


@pytest.fixture(scope="module")
def q8n_runner(tiny_detector):
    """Warmed runner with BOTH int8 surfaces: the box head (full_q8) and
    the whole network (full_q8n)."""
    from mx_rcnn_tpu.serve.engine import DetectorRunner

    cfg, _model, variables = tiny_detector
    runner = DetectorRunner(
        cfg, variables, batch_size=1, with_proposals=False,
        int8_head=True, int8_network=True,
    )
    runner.warmup()
    return runner


class TestFullNetworkQ8:
    def test_quantize_network_per_layer_budget(self, tiny_detector):
        # EVERY conv/dense kernel is quantized and reconstructs within
        # the symmetric-int8 bound (|w - deq| <= scale/2 per channel);
        # biases and BN constants pass through bit-identical.
        from mx_rcnn_tpu.serve.quantize import (
            dequantize_network,
            is_quantized_leaf,
            quantize_network,
        )
        from mx_rcnn_tpu.utils.precision import dequantize

        _cfg, _model, variables = tiny_detector
        qnet = quantize_network(variables)

        def descend(tree, path):
            node = tree
            for k in path:
                key = getattr(k, "key", None)
                if key is None:
                    key = getattr(k, "name", None)
                node = node[key]
            return node

        leaves = jax.tree_util.tree_flatten_with_path(variables)[0]
        n_quantized = 0
        for path, w in leaves:
            node = descend(qnet, path)
            if is_quantized_leaf(node):
                n_quantized += 1
                assert np.asarray(node["q"]).dtype == np.int8
                scale = np.asarray(node["scale"])
                deq = np.asarray(
                    dequantize(node["q"], node["scale"], jnp.float32)
                )
                assert np.all(
                    np.abs(deq - np.asarray(w)) <= scale / 2.0 + 1e-7
                ), [getattr(k, "key", k) for k in path]
            else:
                np.testing.assert_array_equal(
                    np.asarray(node), np.asarray(w)
                )
        # backbone + FPN + RPN + heads: a real network's worth of layers.
        assert n_quantized >= 20
        deq_tree = dequantize_network(qnet)
        assert (
            jax.tree_util.tree_structure(deq_tree)
            == jax.tree_util.tree_structure(variables)
        )

    def test_q8n_ladder_between_q8_and_reduced(self):
        from mx_rcnn_tpu.serve import LEVELS
        from mx_rcnn_tpu.serve.degrade import FULL_QUALITY_LEVELS

        i = {lvl: n for n, lvl in enumerate(LEVELS)}
        assert i["full_q8"] < i["full_q8n"] < i["reduced"]
        # q8 levels are degraded quality: the breaker must keep steering
        # half-open probes at full/small only.
        assert "full_q8" not in FULL_QUALITY_LEVELS
        assert "full_q8n" not in FULL_QUALITY_LEVELS

    def test_q8_programs_register_per_bucket(self, tiny_detector):
        # Regression: full_q8/full_q8n used to compile ONLY the smallest
        # bucket, so large images silently recompiled on the serving
        # path.  Every bucket must have its own q8 program, and the
        # LARGEST bucket must actually serve.
        from mx_rcnn_tpu.serve.engine import DetectorRunner

        cfg, _model, variables = tiny_detector
        runner = DetectorRunner(
            cfg, variables, buckets=((64, 64), (96, 128)), batch_size=1,
            with_proposals=False, int8_head=True, int8_network=True,
        )
        for b in runner.buckets:
            assert ("full_q8", b) in runner._program_keys
            assert ("full_q8n", b) in runner._program_keys
        assert runner.warmup() == len(runner._program_keys)
        big = runner.buckets[-1]
        img = np.random.RandomState(7).randint(
            0, 255, (big[0], big[1], 3), np.uint8
        ).astype(np.float32)
        out = runner.run("full_q8", big, [img])[0]
        assert set(out) >= {"boxes", "scores", "classes"}

    def test_q8n_map_parity_with_f32(self, q8n_runner):
        # The PTQ acceptance gate: score full_q8n detections against the
        # f32 program's detections as ground truth.  Weight-only int8
        # perturbs scores/boxes slightly (the per-layer budget above),
        # but detection-level agreement must stay high.
        from mx_rcnn_tpu.evalutil.voc_eval import voc_eval

        rng = np.random.RandomState(3)
        imgs = [
            rng.randint(0, 255, (96, 128, 3), np.uint8).astype(np.float32)
            for _ in range(4)
        ]
        b = q8n_runner.buckets[0]

        def detect(level):
            out = {}
            for i, im in enumerate(imgs):
                r = q8n_runner.run(level, b, [im])[0]
                out[i] = {
                    k: np.asarray(r[k])
                    for k in ("boxes", "scores", "classes")
                }
            return out

        d32, dq8 = detect("full"), detect("full_q8n")
        classes = sorted({
            int(c) for i in range(len(imgs))
            for c in d32[i]["classes"][d32[i]["scores"] > 0.05]
        })
        assert classes, "f32 reference produced no detections"
        aps = []
        for c in classes:
            det, gt = {}, {}
            for i in range(len(imgs)):
                m32 = (d32[i]["scores"] > 0.05) & (d32[i]["classes"] == c)
                mq8 = (dq8[i]["scores"] > 0.05) & (dq8[i]["classes"] == c)
                gt[str(i)] = {"boxes": d32[i]["boxes"][m32]}
                det[str(i)] = np.concatenate(
                    [dq8[i]["boxes"][mq8], dq8[i]["scores"][mq8, None]],
                    axis=1,
                )
            aps.append(voc_eval(det, gt)[0])
        assert float(np.mean(aps)) >= 0.85, aps

    def test_runner_q8n_serves_and_swaps(self, q8n_runner):
        assert q8n_runner.levels() == (
            "full", "full_q8", "full_q8n", "reduced"
        )
        img = np.random.RandomState(5).randint(
            0, 255, (96, 128, 3), np.uint8
        ).astype(np.float32)
        out = q8n_runner.run("full_q8n", q8n_runner.buckets[0], [img])[0]
        assert set(out) >= {"boxes", "scores", "classes"}
        assert out["generation"] == q8n_runner.generation


# ---------------------------------------------------------------------------
# content-addressed result cache (r16 tentpole)
# ---------------------------------------------------------------------------


class TestResultCacheServing:
    def test_cache_hit_bitwise_equals_cold_miss(self, q8n_runner):
        # A hit returns the very response a cold call latched (minus
        # per-call placement metadata), so it is bitwise-identical by
        # construction — proven here through a REAL single-replica fleet.
        from mx_rcnn_tpu.serve import (
            FleetRouter,
            InferenceEngine,
            ResultCache,
        )

        cache = ResultCache(capacity=4)
        fleet = FleetRouter(
            lambda rid: InferenceEngine(q8n_runner, replica_id=rid),
            1, supervisor_poll=0.05, result_cache=cache,
        )
        img = np.random.RandomState(11).randint(
            0, 255, (96, 128, 3), np.uint8
        ).astype(np.float32)
        with fleet:
            cold = fleet.submit(img, timeout=60).result(60)
            hit = fleet.submit(img, timeout=60).result(60)
        assert not cold.get("cached")
        assert hit["cached"] is True
        assert hit["level"] == cold["level"]
        for k in ("boxes", "scores", "classes"):
            np.testing.assert_array_equal(
                np.asarray(hit[k]), np.asarray(cold[k])
            )
        # Placement metadata describes the cold call, not the answer.
        assert "replica_id" not in hit and "latency_s" not in hit
        assert cache.stats()["hits"] == 1

    def test_coalescing_is_one_device_call(self):
        # N identical in-flight requests: one leader reaches the device,
        # followers latch its response when it settles.
        import threading

        from test_serve import FakeRunner, _img

        from mx_rcnn_tpu.serve import (
            FleetRouter,
            InferenceEngine,
            ResultCache,
        )

        gate = threading.Event()
        runner = FakeRunner(block=gate)
        cache = ResultCache(capacity=4)
        fleet = FleetRouter(
            lambda rid: InferenceEngine(runner, replica_id=rid),
            1, supervisor_poll=0.05, result_cache=cache,
        )
        with fleet:
            runs_before = len(runner.run_calls)
            reqs = [fleet.submit(_img(16, 16), timeout=30)
                    for _ in range(3)]
            gate.set()
            results = [r.result(30) for r in reqs]
        assert len(runner.run_calls) - runs_before == 1
        assert sum(1 for r in results if r.get("coalesced")) == 2
        st = cache.stats()
        assert st["coalesced"] == 2 and st["inserts"] == 1
        s = fleet.stats()
        assert s["completed"] == 3 and s["failed"] == 0

    def test_generation_roll_invalidates(self):
        from test_serve import FakeRunner, _img

        from mx_rcnn_tpu.serve import (
            FleetRouter,
            InferenceEngine,
            ResultCache,
        )

        cache = ResultCache(capacity=4)
        fleet = FleetRouter(
            lambda rid: InferenceEngine(
                FakeRunner(), replica_id=rid
            ),
            1, supervisor_poll=0.05, result_cache=cache,
        )
        with fleet:
            fleet.submit(_img(16, 16), timeout=30).result(30)
            assert fleet.submit(
                _img(16, 16), timeout=30
            ).result(30)["cached"] is True
            fleet.swap_weights({"params": {}})
            post = fleet.submit(_img(16, 16), timeout=30).result(30)
        assert not post.get("cached")
        assert cache.stats()["size"] == 1  # stale generation dropped

    def test_content_key_separates_dtype_and_shape(self):
        from mx_rcnn_tpu.serve import content_key

        a = np.zeros((4, 4, 3), np.uint8)
        assert content_key(a) == content_key(a.copy())
        assert content_key(a) != content_key(a.astype(np.float32))
        assert content_key(a) != content_key(
            np.zeros((4, 12), np.uint8)
        )
        assert content_key("not an image") is None


# ---------------------------------------------------------------------------
# TPU006 upcast walk (unit level; the full invariant runs in test_tpulint)
# ---------------------------------------------------------------------------


class TestUpcastWalk:
    def _walk(self, fn, *args):
        from mx_rcnn_tpu.analysis.jaxpr_checks import _walk_upcasts

        closed = jax.make_jaxpr(fn)(*args)
        bad, total = [], [0]
        _walk_upcasts(closed.jaxpr, "", bad, total)
        return bad, total[0]

    def test_flags_stray_upcast(self):
        def leaky(x):
            with jax.named_scope("detection_middle"):
                return x.astype(jnp.float32) * 2.0

        bad, total = self._walk(leaky, jnp.ones((4,), jnp.bfloat16))
        assert total == 1
        assert len(bad) == 1
        assert "detection_middle" in bad[0]

    def test_allows_scoped_accumulation(self):
        def fine(x):
            with jax.named_scope("rpn_loss"):
                return x.astype(jnp.float32).sum()

        bad, total = self._walk(fine, jnp.ones((4,), jnp.bfloat16))
        assert total == 1
        assert bad == []

    def test_ignores_non_bf16_converts(self):
        def casts(x):
            return x.astype(jnp.float32) + 1.0  # uint8 -> f32: fine

        bad, total = self._walk(casts, jnp.ones((4,), jnp.uint8))
        assert total == 0
        assert bad == []

    def test_walks_into_scan(self):
        def leaky_scan(x):
            def body(c, xi):
                with jax.named_scope("hot"):
                    return c + xi.astype(jnp.float32), None

            out, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), x)
            return out

        bad, total = self._walk(leaky_scan, jnp.ones((3,), jnp.bfloat16))
        assert total == 1
        assert len(bad) == 1


# ---------------------------------------------------------------------------
# bench headline knob drift guard (satellite 1)
# ---------------------------------------------------------------------------


class TestBenchKnobs:
    def _headline_cfg(self, name="r50_fpn_coco"):
        import bench

        return apply_overrides(
            get_config(name), list(bench.HEADLINE_FASTPATH)
        )

    def test_headline_preset_resolves_to_fastpath(self):
        import bench

        cfg = self._headline_cfg()
        bench.assert_headline_fastpath(cfg)  # must not raise
        knobs = bench.resolved_knobs(cfg)
        assert knobs["topk_impl"] == "hier"
        assert knobs["assign_block"] > 0
        assert knobs["loss_impl"] == "compact"
        assert knobs["packed_head"] is True
        assert knobs["roi_align_bwd_impl"] == "pallas"
        assert knobs["fold_frozen_bn"] is True
        assert knobs["precision_policy"] == "mixed"
        assert knobs["backbone_dtype"] == "bfloat16"

    def test_drifted_preset_fails_loudly(self):
        import bench

        cfg = apply_overrides(
            self._headline_cfg(), ["model.rpn.loss_impl=dense"]
        )
        with pytest.raises(SystemExit, match="loss_impl"):
            bench.assert_headline_fastpath(cfg)

    def test_widen_policy_fails_headline_guard(self):
        import bench

        cfg = apply_overrides(
            self._headline_cfg(), ["model.precision.policy=widen"]
        )
        with pytest.raises(SystemExit, match="precision_policy"):
            bench.assert_headline_fastpath(cfg)

    def test_knobs_line_is_json_serializable(self):
        import json

        import bench

        knobs = bench.resolved_knobs(self._headline_cfg())
        line = json.loads(json.dumps({"metric": "bench_knobs", "value": knobs}))
        assert line["value"]["loss_impl"] == "compact"
