"""ctypes interface to the native library, with numpy fallbacks.

Every function here has identical semantics built or un-built; tests
compare the two directly (SURVEY.md §5: native kernels validated against
the pure-python oracles, the inverse of the reference which shipped the
Cython/C versions untested).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_LIB = None  # None = not attempted; False = failed (don't retry); CDLL = loaded


def _load() -> Optional[ctypes.CDLL]:
    global _LIB
    if _LIB is not None:
        return _LIB or None  # False (cached failure) -> None
    from mx_rcnn_tpu.native.build import build, so_path

    # The library is named by its source's hash (native/build.py): only the
    # build of the current native.cc is ever opened; anything else lying in
    # the package directory is stale and gets rebuilt, not loaded.
    so = so_path()
    if not os.path.exists(so):
        # Build lazily when a toolchain is present (dev/CI convenience).
        try:
            build(verbose=False)
        except (OSError, subprocess.CalledProcessError):
            # Cache the failure: these entry points sit on the per-image
            # loader hot path — one g++ attempt per process, not per call.
            _LIB = False
            return None
    try:
        lib = ctypes.CDLL(so)
    except OSError:
        _LIB = False
        return None
    u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    lib.cpu_nms.restype = ctypes.c_int
    lib.cpu_nms.argtypes = [
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_int, ctypes.c_float,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
    ]
    lib.rle_encode.restype = ctypes.c_int
    lib.rle_encode.argtypes = [
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
        ctypes.c_int, ctypes.c_int, u32p,
    ]
    lib.rle_iou.restype = None
    lib.rle_iou.argtypes = [
        u32p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_int, ctypes.c_int,
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
    ]
    lib.letterbox_normalize.restype = None
    lib.letterbox_normalize.argtypes = [
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
        ctypes.c_int, ctypes.c_int,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_float,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
    ]
    _LIB = lib
    return lib


def available() -> bool:
    return _load() is not None


def cpu_nms(boxes: np.ndarray, scores: np.ndarray, threshold: float) -> np.ndarray:
    """Greedy NMS; returns kept indices in score order.  Semantics of the
    reference's ``cpu_nms.pyx`` (+1 pixel areas)."""
    boxes = np.ascontiguousarray(boxes, np.float32)
    order = np.argsort(-np.asarray(scores), kind="mergesort").astype(np.int32)
    n = len(boxes)
    lib = _load()
    if lib is None or n == 0:
        return _py_nms(boxes, order, threshold)
    keep = np.empty(n, np.int32)
    kept = lib.cpu_nms(boxes, order, n, float(threshold), keep)
    return keep[:kept].copy()


def _py_nms(boxes: np.ndarray, order: np.ndarray, threshold: float) -> np.ndarray:
    x1, y1, x2, y2 = boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3]
    areas = np.maximum(0, x2 - x1 + 1) * np.maximum(0, y2 - y1 + 1)
    keep = []
    suppressed = np.zeros(len(boxes), bool)
    for i in order:
        if suppressed[i]:
            continue
        keep.append(int(i))
        xx1 = np.maximum(x1[i], x1[order])
        yy1 = np.maximum(y1[i], y1[order])
        xx2 = np.minimum(x2[i], x2[order])
        yy2 = np.minimum(y2[i], y2[order])
        inter = np.maximum(0, xx2 - xx1 + 1) * np.maximum(0, yy2 - yy1 + 1)
        iou = inter / (areas[i] + areas[order] - inter)
        suppressed[order[iou > threshold]] = True
    return np.asarray(keep, np.int32)


def rle_encode_native(binary: np.ndarray) -> Optional[dict]:
    """COCO column-major RLE via C++; None when the library is unavailable
    (callers fall back to evalutil.masks.rle_encode)."""
    lib = _load()
    if lib is None:
        return None
    m = np.ascontiguousarray(binary, np.uint8)
    h, w = m.shape
    counts = np.empty(h * w + 1, np.uint32)
    n = lib.rle_encode(m, h, w, counts)
    return {"size": (h, w), "counts": counts[:n].copy()}


def rle_iou_native(dts: list, gts: list) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    alls = list(dts) + list(gts)
    lengths = np.asarray([len(r["counts"]) for r in alls], np.int32)
    offsets = np.zeros(len(alls), np.int64)
    if len(alls) > 1:
        offsets[1:] = np.cumsum(lengths[:-1])
    flat = (
        np.concatenate([np.asarray(r["counts"], np.uint32) for r in alls])
        if alls else np.zeros(0, np.uint32)
    )
    out = np.zeros((len(dts), len(gts)), np.float64)
    if len(dts) and len(gts):
        lib.rle_iou(
            np.ascontiguousarray(flat), offsets, lengths, len(dts), len(gts), out
        )
    return out


def letterbox_normalize(
    image: np.ndarray,
    canvas_hw: tuple[int, int],
    nh: int,
    nw: int,
    scale: float,
    mean: tuple[float, float, float],
    std: tuple[float, float, float],
) -> Optional[np.ndarray]:
    """Fused resize-into-canvas + normalize for uint8 RGB inputs; None when
    the native library is unavailable."""
    lib = _load()
    if lib is None or image.dtype != np.uint8 or image.ndim != 3:
        return None
    sh, sw = image.shape[:2]
    dh, dw = canvas_hw
    dst = np.empty((dh, dw, 3), np.float32)
    lib.letterbox_normalize(
        np.ascontiguousarray(image), sh, sw, dst, dh, dw, int(nh), int(nw),
        float(scale),
        np.asarray(mean, np.float32), np.asarray(std, np.float32),
    )
    return dst
