"""C++ native library vs the pure-python oracles."""

import numpy as np
import pytest

from mx_rcnn_tpu.native import available, cpu_nms, letterbox_normalize
from mx_rcnn_tpu.native.lib import _py_nms
from mx_rcnn_tpu.evalutil.masks import rle_decode, rle_area, rle_encode, rle_iou

needs_native = pytest.mark.skipif(not available(), reason="native lib not built")


class TestBuild:
    def test_builds_in_this_image(self):
        # The environment ships g++; the library must build (lazy, cached).
        assert available()


@needs_native
class TestCpuNms:
    def test_matches_python_oracle(self, rng):
        for _ in range(5):
            n = 200
            ctr = rng.rand(n, 2) * 100
            wh = rng.rand(n, 2) * 30 + 1
            boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2], 1).astype(np.float32)
            scores = rng.rand(n).astype(np.float32)
            keep_c = cpu_nms(boxes, scores, 0.5)
            order = np.argsort(-scores, kind="mergesort").astype(np.int32)
            keep_py = _py_nms(boxes, order, 0.5)
            np.testing.assert_array_equal(keep_c, keep_py)

    def test_keeps_all_disjoint(self):
        boxes = np.array(
            [[0, 0, 10, 10], [20, 20, 30, 30], [40, 40, 50, 50]], np.float32
        )
        keep = cpu_nms(boxes, np.array([0.3, 0.9, 0.5]), 0.5)
        assert sorted(keep.tolist()) == [0, 1, 2]
        assert keep[0] == 1  # score order


@needs_native
class TestNativeRle:
    def test_encode_decode_roundtrip(self, rng):
        m = rng.rand(43, 31) > 0.5
        rle = rle_encode(m)  # dispatches to C++
        np.testing.assert_array_equal(rle_decode(rle), m)
        assert rle_area(rle) == int(m.sum())

    def test_iou_vs_dense(self, rng):
        ms = [rng.rand(40, 28) > t for t in (0.3, 0.55, 0.8)]
        rles = [rle_encode(m) for m in ms]
        got = rle_iou(rles[:2], rles)
        for i in range(2):
            for j in range(3):
                inter = float((ms[i] & ms[j]).sum())
                union = float((ms[i] | ms[j]).sum())
                assert np.isclose(got[i, j], inter / union), (i, j)


@needs_native
class TestLetterbox:
    def test_matches_python_path(self, rng):
        from mx_rcnn_tpu.data.transforms import letterbox, normalize_image

        img = (rng.rand(97, 143, 3) * 255).astype(np.uint8)
        canvas = (128, 128)
        mean, std = (123.675, 116.28, 103.53), (58.395, 57.12, 57.375)
        ref, _, scale, (nh, nw) = letterbox(
            img.astype(np.float32), np.zeros((0, 4), np.float32), canvas, 100, 128
        )
        ref = normalize_image(ref, mean, std)
        out = letterbox_normalize(img, canvas, nh, nw, scale, mean, std)
        assert out is not None and out.shape == ref.shape
        # Same bilinear convention as cv2 up to rounding.
        assert np.abs(out - ref).max() < 0.15
        # Padding region is normalized zeros in both.
        np.testing.assert_allclose(out[nh:], ref[nh:], atol=1e-5)

    def test_identity_scale(self, rng):
        img = (rng.rand(64, 64, 3) * 255).astype(np.uint8)
        mean, std = (0.0, 0.0, 0.0), (1.0, 1.0, 1.0)
        out = letterbox_normalize(img, (64, 64), 64, 64, 1.0, mean, std)
        np.testing.assert_allclose(out, img.astype(np.float32), atol=1e-4)


@needs_native
class TestLoaderUsesNative:
    def test_batch_statistics_sane(self):
        """Loader path with uint8 source goes through the native kernel and
        produces the same normalized statistics as the python path."""
        import dataclasses

        from mx_rcnn_tpu.config import get_config
        from mx_rcnn_tpu.data import DetectionLoader
        from mx_rcnn_tpu.data.roidb import RoiRecord

        rng = np.random.RandomState(0)
        img = (rng.rand(100, 120, 3) * 255).astype(np.uint8)
        rec_u8 = RoiRecord(
            image_id="u8", image_path="", height=100, width=120,
            boxes=np.array([[10, 10, 50, 60]], np.float32),
            gt_classes=np.array([1], np.int32), image_array=img,
        )
        rec_f32 = dataclasses.replace(
            rec_u8, image_id="f32", image_array=img.astype(np.float32)
        )
        # normalize_on_host routes the uint8 record through the native
        # fused kernel (the default ships raw uint8 and normalizes
        # in-graph — that path is covered in test_data.TestUint8Pipeline).
        cfg = dataclasses.replace(
            get_config("tiny_synthetic").data, normalize_on_host=True
        )
        loader = DetectionLoader(
            [rec_u8, rec_f32], cfg, batch_size=1, train=False
        )
        batches = list(loader)
        a = np.asarray(batches[0][0].images)
        b = np.asarray(batches[1][0].images)
        assert np.abs(a - b).max() < 0.2
        np.testing.assert_allclose(
            np.asarray(batches[0][0].gt_boxes), np.asarray(batches[1][0].gt_boxes),
            atol=1e-4,
        )


class TestLoadedLibraryIsTheCurrentBuild:
    """native/build.py names the library by its source's hash, so a stale
    ``_native*.so`` lying in the package directory (git-ignored, and
    copied along by anything that copies the tree) is rebuilt, not
    loaded."""

    def test_stale_library_is_rebuilt_not_loaded(self, tmp_path, monkeypatch):
        import os
        import shutil

        from mx_rcnn_tpu.native import build, lib

        pkg = tmp_path / "native"
        (pkg / "src").mkdir(parents=True)
        shutil.copy(build.SRC, pkg / "src" / "native.cc")
        monkeypatch.setattr(build, "PKG_DIR", str(pkg))
        monkeypatch.setattr(build, "SRC", str(pkg / "src" / "native.cc"))
        monkeypatch.setattr(lib, "_LIB", None)
        # Two stale files: the pre-hash name, and a build of some other
        # source.  Neither is a library at all — loading either would
        # raise, so a green run proves neither was opened.
        (pkg / "_native.so").write_bytes(b"not a library")
        (pkg / "_native.000000000000.so").write_bytes(b"not a library")
        assert lib.available()
        want = os.path.basename(build.so_path())
        assert want not in ("_native.so", "_native.000000000000.so")
        assert os.path.exists(pkg / want)
        # The build cleared the hash-named stale sibling.
        assert not os.path.exists(pkg / "_native.000000000000.so")
        # A changed source is another name: the old build is not reused.
        with open(pkg / "src" / "native.cc", "a") as f:
            f.write("\n// changed\n")
        assert os.path.basename(build.so_path()) != want
