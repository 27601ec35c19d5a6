"""Pallas kernels vs their XLA reference implementations (interpret mode).

SURVEY.md §5: the new framework validates Pallas kernels against the XLA
impls the tests already trust; interpret mode runs the real kernel logic
(grid, DMA, scalar prefetch) on CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops.pallas.roi_align import multilevel_roi_align_pallas
from mx_rcnn_tpu.ops.roi_align import multilevel_roi_align


def _pyramid(rng, canvas=256, channels=32, levels=(2, 3, 4, 5)):
    return {
        l: jnp.asarray(
            rng.rand(canvas // (1 << l), canvas // (1 << l), channels), jnp.float32
        )
        for l in levels
    }


def _random_rois(rng, n, canvas=256):
    """Mix of scales so every FPN level gets hits."""
    ctr = rng.rand(n, 2) * canvas
    size = 2.0 ** rng.uniform(2, np.log2(canvas * 0.9), size=(n, 2))
    x1 = np.clip(ctr[:, 0] - size[:, 0] / 2, 0, canvas - 2)
    y1 = np.clip(ctr[:, 1] - size[:, 1] / 2, 0, canvas - 2)
    x2 = np.clip(x1 + size[:, 0], x1 + 1, canvas - 1)
    y2 = np.clip(y1 + size[:, 1], y1 + 1, canvas - 1)
    return jnp.asarray(np.stack([x1, y1, x2, y2], 1), jnp.float32)


class TestPallasRoiAlign:
    def test_matches_xla_reference(self, rng):
        pyr = _pyramid(rng)
        rois = _random_rois(rng, 64)
        ref = multilevel_roi_align(pyr, rois, output_size=7, sampling_ratio=2)
        out = multilevel_roi_align_pallas(
            pyr, rois, output_size=7, sampling_ratio=2, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    def test_mask_head_size(self, rng):
        pyr = _pyramid(rng, channels=16)
        rois = _random_rois(rng, 16)
        ref = multilevel_roi_align(pyr, rois, output_size=14, sampling_ratio=2)
        out = multilevel_roi_align_pallas(
            pyr, rois, output_size=14, sampling_ratio=2, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    def test_degenerate_and_edge_rois(self, rng):
        pyr = _pyramid(rng, channels=8)
        rois = jnp.asarray(
            [
                [0.0, 0.0, 0.0, 0.0],          # zero-size (padding roi)
                [0.0, 0.0, 255.0, 255.0],      # whole image -> P5
                [250.0, 250.0, 255.0, 255.0],  # corner sliver
                [-8.0, -8.0, 20.0, 20.0],      # out-of-bounds start
                [5.0, 5.0, 6.5, 6.5],          # tiny -> P2
            ],
            jnp.float32,
        )
        ref = multilevel_roi_align(pyr, rois)
        out = multilevel_roi_align_pallas(pyr, rois, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    def test_bfloat16_features(self, rng):
        pyr = {l: f.astype(jnp.bfloat16) for l, f in _pyramid(rng, channels=8).items()}
        rois = _random_rois(rng, 8)
        ref = multilevel_roi_align(pyr, rois)
        out = multilevel_roi_align_pallas(pyr, rois, interpret=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
        )


    def test_odd_width_levels_match_xla(self, rng):
        """Recipe canvases (800x1344) give coarse levels whose width is NOT
        a multiple of 8 (84/42/21 cells); the kernel zero-pads W internally
        and must still match the XLA reference bit-for-bit in masking."""
        h, w = 400, 672  # 1/2-scale stand-in for the 800x1344 canvas
        pyr = {
            l: jnp.asarray(
                rng.rand(-(-h // (1 << l)), -(-w // (1 << l)), 8), jnp.float32
            )
            for l in (2, 3, 4, 5)
        }
        assert any(f.shape[1] % 8 for f in pyr.values())  # test premise
        ctr = rng.rand(48, 2) * np.array([w, h])
        size = 2.0 ** rng.uniform(2, 8, size=(48, 2))
        x1 = np.clip(ctr[:, 0] - size[:, 0] / 2, 0, w - 2)
        y1 = np.clip(ctr[:, 1] - size[:, 1] / 2, 0, h - 2)
        rois = jnp.asarray(
            np.stack(
                [x1, y1, np.clip(x1 + size[:, 0], x1 + 1, w - 1),
                 np.clip(y1 + size[:, 1], y1 + 1, h - 1)], 1
            ),
            jnp.float32,
        )
        ref = multilevel_roi_align(pyr, rois, output_size=7, sampling_ratio=2)
        out = multilevel_roi_align_pallas(
            pyr, rois, output_size=7, sampling_ratio=2, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    def test_window_size_classes_match_xla(self, rng):
        """Rois spanning the smallest and the full window classes share one
        launch and all match the oracle — covering the per-roi conditional
        DMA + origin-select path and the stale-cells-are-zero-weighted
        argument."""
        from mx_rcnn_tpu.ops.pallas.roi_align import window_classes

        # Coarsest level = P3 of a 512 canvas (64-cell map), so a ~260 px
        # roi clamps there at ~32.5 cells of extent: beyond every small
        # class budget (full-window class) but within the 48-window's
        # exact range.  Smaller pyramids cannot produce a full-class roi
        # at all (every map fits a small corner whole).
        canvas = 512
        pyr = _pyramid(rng, canvas, levels=(2, 3))
        small = np.array(_random_rois(rng, 24, canvas))
        small[:, 2:] = small[:, :2] + np.minimum(
            small[:, 2:] - small[:, :2], 40.0
        )  # guaranteed tiny extent -> small class
        giant = np.asarray(
            [[3.0, 5.0, 263.0, 266.0], [200.0, 150.0, 462.0, 410.0]] * 4,
            np.float32,
        )  # ~260 px rois -> large class at the clamped coarsest level
        rois = jnp.asarray(np.concatenate([small, giant]), jnp.float32)
        # The class split must actually exercise BOTH branches.
        from mx_rcnn_tpu.ops.pallas.roi_align import _prep

        # Mid-extent rois (~20 cells at P2) so the MIDDLE class branch is
        # exercised too, not just the smallest and the fallback.
        mid = np.asarray(
            [[40.0, 40.0, 120.0, 118.0], [300.0, 200.0, 383.0, 270.0]] * 2,
            np.float32,
        )
        rois = jnp.asarray(
            np.concatenate([np.asarray(rois), mid]), jnp.float32
        )
        _, _, _, params, _, _, _ = _prep(pyr, rois, 7, 48)
        cls = np.asarray(params[:, 0, -1])
        n_classes = len(window_classes(48))
        assert n_classes >= 3
        # EVERY class branch (DMA origin + matmul width + interp origin)
        # must be hit — a middle-class-only bug would otherwise stay green.
        assert len(np.unique(cls)) == n_classes, np.unique(cls)
        ref = multilevel_roi_align(pyr, rois, output_size=7, sampling_ratio=2)
        out = multilevel_roi_align_pallas(
            pyr, rois, output_size=7, sampling_ratio=2, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    def test_window_size_classes_bwd_matches_xla_grad(self, rng):
        """The BACKWARD's per-class RMW path on the same mixed roi set as
        the forward test above: the origin re-select and the class branches
        in _bwd_kernel must scatter gradients into the window the class
        actually reads, or recipe-canvas (full-class) gradients silently
        land in the wrong cells while every tiny-canvas test stays
        green."""
        import jax

        from mx_rcnn_tpu.ops.pallas import roi_align as pra
        from mx_rcnn_tpu.ops.pallas.roi_align import _prep

        canvas = 512
        pyr = _pyramid(rng, canvas, levels=(2, 3))
        small = np.array(_random_rois(rng, 8, canvas))
        small[:, 2:] = small[:, :2] + np.minimum(
            small[:, 2:] - small[:, :2], 40.0
        )
        giant = np.asarray(
            [[3.0, 5.0, 263.0, 266.0], [200.0, 150.0, 462.0, 410.0]],
            np.float32,
        )
        rois = jnp.asarray(np.concatenate([small, giant]), jnp.float32)
        mid = np.asarray(
            [[40.0, 40.0, 120.0, 118.0], [300.0, 200.0, 383.0, 270.0]],
            np.float32,
        )
        rois = jnp.asarray(
            np.concatenate([np.asarray(rois), mid]), jnp.float32
        )
        _, _, _, params, _, _, _ = _prep(pyr, rois, 7, 48)
        from mx_rcnn_tpu.ops.pallas.roi_align import window_classes

        cls = np.asarray(params[:, 0, -1])
        assert len(np.unique(cls)) == len(window_classes(48)), np.unique(cls)

        def loss_ref(p):
            return (
                multilevel_roi_align(
                    p, rois, output_size=7, sampling_ratio=2
                ) ** 2
            ).sum()

        g_ref = jax.grad(loss_ref)(pyr)
        fwd = multilevel_roi_align(pyr, rois, output_size=7, sampling_ratio=2)
        g_pyr, _ = pra._fast_bwd(7, 2, 48, True, "pallas", (pyr, rois), 2.0 * fwd)
        for l in pyr:
            np.testing.assert_allclose(
                np.asarray(g_pyr[l]), np.asarray(g_ref[l]), atol=1e-4
            )

    def test_batched_matches_per_image(self, rng):
        """(B, R, 4) rois + (B, H, W, C) pyramid in ONE kernel launch equals
        the per-image calls it replaced."""
        b = 3
        pyrs = [_pyramid(rng) for _ in range(b)]
        roiss = [_random_rois(rng, 16) for _ in range(b)]
        batched_pyr = {
            l: jnp.stack([p[l] for p in pyrs]) for l in pyrs[0]
        }
        batched_rois = jnp.stack(roiss)
        out = multilevel_roi_align_pallas(
            batched_pyr, batched_rois, output_size=7, sampling_ratio=2,
            interpret=True,
        )
        assert out.shape[:2] == (b, 16)
        for i in range(b):
            ref = multilevel_roi_align_pallas(
                pyrs[i], roiss[i], output_size=7, sampling_ratio=2,
                interpret=True,
            )
            np.testing.assert_allclose(
                np.asarray(out[i]), np.asarray(ref), atol=1e-5
            )

    def test_batched_custom_vjp_matches_xla_grad(self, rng):
        b = 2
        pyr = {l: jnp.stack([_pyramid(rng)[l] for _ in range(b)])
               for l in (2, 3, 4, 5)}
        rois = jnp.stack([_random_rois(rng, 8) for _ in range(b)])

        # Gradient of the XLA reference, vmapped, vs the custom-vjp backward
        # (since r3 the default backward is the Pallas window-RMW kernel —
        # interpret mode runs its real grid/DMA/aliasing logic on CPU).
        ref_fn = lambda p: jax.vmap(
            lambda pp, rr: multilevel_roi_align(
                pp, rr, output_size=7, sampling_ratio=2, max_extent_cells=38
            )
        )(p, rois).sum()
        g_ref = jax.grad(ref_fn)(pyr)
        from mx_rcnn_tpu.ops.pallas import roi_align as pra

        out_shape = (b, 8, 7, 7, pyr[2].shape[-1])
        g = jnp.ones(out_shape, jnp.float32)
        grad_pyr, grad_rois = pra._fast_bwd(7, 2, 48, True, "pallas", (pyr, rois), g)
        for l in pyr:
            np.testing.assert_allclose(
                np.asarray(grad_pyr[l]), np.asarray(g_ref[l]), atol=1e-4
            )
        assert grad_rois.shape == rois.shape

    def test_custom_vjp_matches_xla_grad(self, rng):
        """multilevel_roi_align_fast: pallas forward + pallas window-RMW
        backward (r3) — its feature gradients must equal differentiating
        the XLA path (f32: to rounding; the kernel accumulates f32)."""
        import jax

        pyr = _pyramid(rng, canvas=128, channels=8)
        rois = _random_rois(rng, 8, canvas=128)

        def loss_ref(p):
            return (multilevel_roi_align(p, rois) ** 2).sum()

        g_ref = jax.grad(loss_ref)(pyr)
        from mx_rcnn_tpu.ops.pallas import roi_align as pra

        g_pyr, g_rois = pra._fast_bwd(
            7, 2, 48, True, "pallas", (pyr, rois),
            2.0 * multilevel_roi_align(pyr, rois)
        )
        for l in pyr:
            np.testing.assert_allclose(
                np.asarray(g_pyr[l]), np.asarray(g_ref[l]), atol=1e-4
            )
        assert float(jnp.abs(g_rois).max()) == 0.0

    def test_bwd_kernel_xla_fallback_env(self, rng, monkeypatch):
        """MX_RCNN_POOL_BWD=xla restores the autodiff backward (A/B and
        debugging escape hatch); both paths agree on f32."""
        import jax

        from mx_rcnn_tpu.ops.pallas import roi_align as pra

        pyr = _pyramid(rng, canvas=128, channels=8)
        rois = _random_rois(rng, 8, canvas=128)
        g = multilevel_roi_align(pyr, rois)
        monkeypatch.setenv("MX_RCNN_POOL_BWD", "xla")
        g_xla, _ = pra._fast_bwd(7, 2, 48, True, "pallas", (pyr, rois), g)
        monkeypatch.delenv("MX_RCNN_POOL_BWD")
        g_pal, _ = pra._fast_bwd(7, 2, 48, True, "pallas", (pyr, rois), g)
        for l in pyr:
            np.testing.assert_allclose(
                np.asarray(g_xla[l]), np.asarray(g_pal[l]), atol=1e-4
            )

    def test_bwd_kernel_odd_width_bf16(self, rng):
        """Recipe-canvas shapes (odd coarse widths, bf16 features) through
        the pallas backward kernel: gradients match the XLA vjp to bf16
        output granularity, and the padded width columns carry no grad."""
        import jax

        from mx_rcnn_tpu.ops.pallas.roi_align import (
            multilevel_roi_align_bwd_pallas,
        )

        h, w = 400, 672
        pyr = {
            l: jnp.asarray(
                rng.rand(-(-h // (1 << l)), -(-w // (1 << l)), 8), jnp.bfloat16
            )
            for l in (2, 3, 4, 5)
        }
        assert any(f.shape[1] % 8 for f in pyr.values())
        rois = _random_rois(rng, 24, canvas=384)
        g = jnp.asarray(rng.rand(24, 7, 7, 8), jnp.bfloat16)

        def ref_fn(p):
            return multilevel_roi_align(
                p, rois, output_size=7, sampling_ratio=2, max_extent_cells=38
            )

        _, vjp = jax.vjp(ref_fn, pyr)
        (g_ref,) = vjp(g)
        g_pal = multilevel_roi_align_bwd_pallas(
            pyr, rois, g, output_size=7, sampling_ratio=2, window=48,
            interpret=True,
        )
        for l in pyr:
            assert g_pal[l].dtype == jnp.bfloat16
            assert g_pal[l].shape == pyr[l].shape
            # Tolerance: the reference vjp carries exact-f32 interpolation
            # weights; the kernel's bf16-cotangent path quantizes the
            # weights to bf16 (documented in _bwd_kernel — gradient noise
            # ~2^-8 relative, below the cotangent's own granularity), so
            # per-cell diffs up to a few bf16 ULPs of the accumulated
            # magnitude (~0.1 at the ~6-8 peaks here) are expected.
            np.testing.assert_allclose(
                np.asarray(g_pal[l], np.float32),
                np.asarray(g_ref[l], np.float32),
                atol=3e-2,
                rtol=2.5e-2,
            )


class TestNoHiddenFallback:
    """detection/graph.py::_pool_rois: a Pallas request that cannot be
    honoured RAISES when the backend is a TPU, and off-TPU the XLA gather
    is the design (quiet).  The backend is mocked — the point is the
    decision, which runs at trace time in Python."""

    @staticmethod
    def _pool(channels):
        from mx_rcnn_tpu.config import get_config
        from mx_rcnn_tpu.detection import graph

        rng = np.random.RandomState(0)
        feats = {
            lvl: jnp.asarray(
                rng.randn(1, 64 >> (lvl - 2), 64 >> (lvl - 2), channels),
                jnp.float32,
            )
            for lvl in (2, 3, 4, 5)
        }
        rois = jnp.asarray([[[4.0, 4.0, 60.0, 50.0]] * 4], jnp.float32)
        cfg = get_config("tiny_synthetic").model
        assert cfg.rcnn.roi_align_impl == "pallas"  # the preset default
        graph.LAST_POOL_IMPL = None
        out = graph._pool_rois(cfg, feats, rois, 7, (2, 3, 4, 5))
        return out, graph.LAST_POOL_IMPL

    def test_unsupported_layout_raises_on_a_tpu(self, monkeypatch):
        monkeypatch.delenv("MX_RCNN_PALLAS_INTERPRET", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # 64 channels: not a multiple of the 128-lane dim the kernel's
        # window DMA slices.
        with pytest.raises(ValueError, match="roi_align_impl='pallas'"):
            self._pool(channels=64)

    def test_off_tpu_takes_the_xla_gather_quietly(self, monkeypatch, caplog):
        monkeypatch.delenv("MX_RCNN_PALLAS_INTERPRET", raising=False)
        with caplog.at_level("INFO", logger="mx_rcnn_tpu"):
            for channels in (64, 128):  # unsupported and supported layouts
                out, impl = self._pool(channels)
                assert impl == "xla"
                assert out.shape == (1, 4, 7, 7, channels)
        assert not caplog.records

    def test_the_xla_gather_can_be_asked_for_by_name_on_a_tpu(
        self, monkeypatch
    ):
        import dataclasses

        from mx_rcnn_tpu.config import get_config
        from mx_rcnn_tpu.detection import graph

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = get_config("tiny_synthetic").model
        cfg = dataclasses.replace(
            cfg, rcnn=dataclasses.replace(cfg.rcnn, roi_align_impl="xla")
        )
        feats = {
            lvl: jnp.zeros((1, 64 >> (lvl - 2), 64 >> (lvl - 2), 64))
            for lvl in (2, 3, 4, 5)
        }
        rois = jnp.asarray([[[4.0, 4.0, 60.0, 50.0]]], jnp.float32)
        graph._pool_rois(cfg, feats, rois, 7, (2, 3, 4, 5))
        assert graph.LAST_POOL_IMPL == "xla"
