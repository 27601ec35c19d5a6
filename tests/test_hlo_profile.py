"""Per-component FLOP attribution (utils/hlo_profile.py, tools/mfu_report.py).

All abstract-trace / CPU-compile only — this is the layer that must keep
working under ``JAX_PLATFORMS=cpu`` so a laptop can attribute the full
TPU-shaped recipe program."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.utils.flops import count_matmul_flops
from mx_rcnn_tpu.utils.hlo_profile import (
    attribute_flops,
    component_of,
    component_report,
    hlo_component_summary,
)


class TestComponentOf:
    @pytest.mark.parametrize(
        "stack,comp",
        [
            ("jvp(TwoStageDetector.features)/backbone/conv1", "stem"),
            ("transpose(jvp(X))/backbone/layer1_block0/conv2", "C2"),
            ("X/backbone/layer2_block3/conv1", "C3"),
            ("X/backbone/layer3_block5/conv3", "C4"),
            ("X/backbone/layer4_block0/downsample_conv", "C5"),
            ("jvp(TwoStageDetector.features)/fpn/lateral2", "FPN"),
            ("jvp(TwoStageDetector.rpn)/rpn.packed/rpn._heads/conv",
             "RPN-head"),
            ("transpose(jvp(TwoStageDetector.rpn))/rpn.packed/rpn._heads/"
             "objectness", "RPN-head"),
            ("jvp(TwoStageDetector.box)/roi_align", "ROI"),
            ("jvp(TwoStageDetector.box)/box_head/fc6", "box-head"),
            ("X/mask_head/conv0", "mask-head"),
            ("jit(train_step)/adamw_update", "other"),
        ],
    )
    def test_classifier(self, stack, comp):
        assert component_of(stack) == comp


class TestAttributeFlops:
    def _graph(self):
        from flax import linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                x = nn.Conv(8, (3, 3), name="conv1")(x)
                with jax.named_scope("roi_align"):
                    x = x @ jnp.ones((8, 8), x.dtype)
                return x.sum()

        class Wrap(nn.Module):
            @nn.compact
            def __call__(self, x):
                return Net(name="backbone")(x)

        m = Wrap()
        x = jnp.ones((1, 8, 8, 3))
        v = m.init(jax.random.PRNGKey(0), x)
        return lambda p: m.apply(p, x), v

    def test_sums_to_count_matmul_flops(self):
        fn, v = self._graph()
        grad = jax.grad(lambda p: fn(p))
        acc = attribute_flops(grad, v)
        total = sum(c["flops"] for c in acc.values())
        assert total == pytest.approx(count_matmul_flops(grad, v))
        assert total > 0

    def test_buckets_and_fwd_bwd_split(self):
        fn, v = self._graph()
        acc = attribute_flops(jax.grad(lambda p: fn(p)), v)
        assert "stem" in acc  # backbone/conv1
        assert "ROI" in acc  # the named scope
        for comp in ("stem", "ROI"):
            assert acc[comp]["fwd"] > 0
            assert acc[comp]["bwd"] > 0
            assert acc[comp]["flops"] == pytest.approx(
                acc[comp]["fwd"] + acc[comp]["bwd"]
            )

    def test_scan_trip_count_scales(self):
        w = jnp.ones((4, 4))

        def one(w):
            return (w @ w).sum()

        def scanned(w):
            def body(c, _):
                return c, (w @ w).sum()

            _, ys = jax.lax.scan(body, 0.0, None, length=5)
            return ys.sum()

        f1 = sum(c["flops"] for c in attribute_flops(one, w).values())
        f5 = sum(c["flops"] for c in attribute_flops(scanned, w).values())
        assert f5 == pytest.approx(5 * f1)

    def test_detector_train_step_components(self):
        """The real (tiny) train graph attributes to the expected
        component set and the per-component sum matches the flat count."""
        from mx_rcnn_tpu.config import get_config
        from mx_rcnn_tpu.detection import (
            Batch,
            TwoStageDetector,
            forward_train,
            init_detector,
        )

        cfg = get_config("tiny_synthetic")
        model = TwoStageDetector(cfg=cfg.model)
        variables = init_detector(
            model, jax.random.PRNGKey(0), cfg.data.image_size
        )
        h, w = cfg.data.image_size
        g = 8
        batch = Batch(
            images=jnp.zeros((1, h, w, 3), jnp.float32),
            image_hw=jnp.full((1, 2), float(h), jnp.float32),
            gt_boxes=jnp.tile(
                jnp.asarray([[10.0, 10.0, 40.0, 40.0]], jnp.float32),
                (1, g, 1),
            ).reshape(1, g, 4),
            gt_classes=jnp.ones((1, g), jnp.int32),
            gt_valid=jnp.ones((1, g), bool),
        )
        rest = {k: v for k, v in variables.items() if k != "params"}

        def loss(p):
            total, _ = forward_train(
                model, {"params": p, **rest}, jax.random.PRNGKey(1), batch
            )
            return total

        grad = jax.grad(loss)
        acc = attribute_flops(grad, variables["params"])
        for comp in ("stem", "C2", "C3", "C4", "C5", "FPN", "RPN-head",
                     "box-head"):
            assert comp in acc, f"{comp} missing from {sorted(acc)}"
            assert acc[comp]["flops"] > 0
        total = sum(c["flops"] for c in acc.values())
        assert total == pytest.approx(
            count_matmul_flops(grad, variables["params"])
        )
        # Nothing substantial should fall through to "other": the only
        # unmatched MXU work is box encode/decode-adjacent einsums.
        assert acc.get("other", {"flops": 0.0})["flops"] < 0.02 * total

    def test_component_report_shape(self):
        fn, v = self._graph()
        rep = component_report(
            jax.grad(lambda p: fn(p)), v,
            steps_per_call=2, dt_per_step=0.1, peak_flops=1e12,
        )
        assert rep["total_tflops_per_step"] >= 0
        assert "mfu_pct" in rep
        assert rep["components"]
        pcts = [c["pct_of_total"] for c in rep["components"].values()]
        assert sum(pcts) == pytest.approx(100.0, abs=0.2)


class TestHloSummary:
    def test_compiled_text_buckets(self):
        def f(x, k):
            with jax.named_scope("roi_align"):
                y = jax.lax.conv_general_dilated(
                    x, k, (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )
            return (y.reshape(-1, 8) @ jnp.ones((8, 8), y.dtype)).sum()

        txt = (
            jax.jit(f)
            .lower(jnp.ones((1, 8, 8, 3)), jnp.ones((3, 3, 3, 8)))
            .compile()
            .as_text()
        )
        summary = hlo_component_summary(txt)
        assert summary, "no kernel-forming instructions recognized"
        assert "ROI" in summary
        assert summary["ROI"].get("convolution", 0) >= 1


class TestMfuReportTool:
    def test_cpu_end_to_end(self, tmp_path, monkeypatch, capsys):
        """tools/mfu_report.py runs attribution-only under
        JAX_PLATFORMS=cpu and writes the committed-artifact schema."""
        sys.path.insert(
            0,
            os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools"),
        )
        import mfu_report

        out = str(tmp_path / "mfu.json")
        report = mfu_report.main(
            ["--config", "tiny_synthetic", "--out", out]
        )
        assert os.path.exists(out)
        with open(out) as f:
            on_disk = json.load(f)
        assert on_disk["config"] == "tiny_synthetic"
        comps = on_disk["default_layout"]["components"]
        for comp in ("C3", "C4", "FPN", "RPN-head"):
            assert comp in comps
        assert on_disk["default_layout"]["total_tflops_per_step"] > 0
        assert report["default_layout"]["layout"]["stem_s2d"] is True


class TestPeakTable:
    """utils/flops.py::PEAK_BF16_FLOPS — the one table every MFU figure
    divides by, keyed by the device_kind jax reports."""

    def test_known_kind(self):
        from mx_rcnn_tpu.utils.flops import PEAK_BF16_FLOPS, peak_bf16_flops

        for kind, peak in PEAK_BF16_FLOPS.items():
            assert peak_bf16_flops(kind) == peak > 1e12

    @pytest.mark.parametrize("kind", ["cpu", "TPU v9 imaginary", ""])
    def test_unknown_kind_is_an_error_not_a_default(self, kind):
        from mx_rcnn_tpu.utils.flops import peak_bf16_flops

        with pytest.raises(ValueError, match="no published bf16 peak"):
            peak_bf16_flops(kind)
