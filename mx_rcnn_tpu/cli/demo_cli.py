"""Single-image demo: checkpoint → detections → visualization.

Parity with ``demo.py`` (SURVEY.md §4.4): load an image, run the jitted
inference graph, print detections, draw labeled boxes to an output file
(``rcnn/core/tester.py::vis_all_detection`` equivalent, headless).
"""

from __future__ import annotations

import argparse
import logging

import numpy as np

from mx_rcnn_tpu.cli.common import add_config_args, config_from_args, setup_logging
from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.evalutil.vis import draw_detections

log = logging.getLogger("mx_rcnn_tpu.demo")


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__)
    add_config_args(p)
    p.add_argument("image", help="input image path")
    p.add_argument("--ckpt", default=None, help="checkpoint dir (default: workdir)")
    p.add_argument("--step", type=int, default=None)
    p.add_argument("--out", default=None, help="output visualization path (png)")
    p.add_argument("--threshold", type=float, default=0.5, help="vis score cutoff")
    p.add_argument(
        "--random-params", action="store_true",
        help="skip checkpoint load (smoke-test the graph with random weights)",
    )
    return p.parse_args(argv)


def detect_image(cfg: Config, variables, image: np.ndarray,
                 mask_threshold: float = 0.0):
    """Run inference on one RGB uint8/float image; detections in original
    image coordinates (the reference's ``im_detect`` + unscale).

    Masks are pasted to image resolution only for detections scoring at
    least ``mask_threshold`` (others get None — pasting is the expensive
    part and the demo discards sub-threshold entries anyway)."""
    import jax

    from mx_rcnn_tpu.data.transforms import letterbox, normalize_image
    from mx_rcnn_tpu.detection import Batch, TwoStageDetector, forward_inference

    model = TwoStageDetector(cfg=cfg.model)
    h, w = image.shape[:2]
    canvas, _, scale, (nh, nw) = letterbox(
        image.astype(np.float32),
        np.zeros((0, 4), np.float32),
        cfg.data.image_size,
        cfg.data.short_side,
        cfg.data.max_side,
    )
    canvas = normalize_image(canvas, cfg.data.pixel_mean, cfg.data.pixel_std)
    g = cfg.data.max_gt_boxes
    batch = Batch(
        images=canvas[None],
        image_hw=np.array([[nh, nw]], np.float32),
        gt_boxes=np.zeros((1, g, 4), np.float32),
        gt_classes=np.zeros((1, g), np.int32),
        gt_valid=np.zeros((1, g), bool),
    )
    infer = jax.jit(lambda v, b: forward_inference(model, v, b))
    dets = jax.device_get(infer(variables, batch))
    from mx_rcnn_tpu.evalutil.postprocess import unletterbox_detections

    d = unletterbox_detections(
        dets.boxes[0], dets.scores[0], dets.classes[0], dets.valid[0],
        scale, h, w,
        masks=dets.masks[0] if dets.masks is not None else None,
        mask_threshold=mask_threshold,
    )
    return d["boxes"], d["scores"], d["classes"], d.get("masks")


def load_demo_image(path: str) -> np.ndarray:
    """Read one RGB image or raise SystemExit with a one-line diagnosis.

    A missing path, a directory, or bytes PIL cannot decode are operator
    errors, not bugs — the CLI reports them cleanly (nonzero exit, no
    traceback) instead of dumping PIL internals."""
    import os

    from PIL import Image, UnidentifiedImageError

    if not os.path.exists(path):
        raise SystemExit(f"error: input image not found: {path}")
    try:
        with Image.open(path) as im:
            return np.asarray(im.convert("RGB"))
    except UnidentifiedImageError:
        raise SystemExit(
            f"error: {path} is not a decodable image (corrupt or "
            "unsupported format)"
        ) from None
    except OSError as e:
        raise SystemExit(f"error: could not read image {path}: {e}") from None


def main(argv=None):
    args = parse_args(argv)
    setup_logging(args.verbose)
    cfg = config_from_args(args)

    image = load_demo_image(args.image)

    import jax

    from mx_rcnn_tpu.parallel.step import eval_variables
    from mx_rcnn_tpu.utils.compile_cache import configure_cache

    configure_cache()
    if args.random_params:
        from mx_rcnn_tpu.detection import TwoStageDetector, init_detector

        variables = init_detector(
            TwoStageDetector(cfg=cfg.model), jax.random.PRNGKey(0), cfg.data.image_size
        )
    else:
        from mx_rcnn_tpu.cli.eval_cli import _restored_state

        variables = jax.device_put(
            eval_variables(_restored_state(cfg, args.ckpt, args.step))
        )

    # The demo serves through the same engine production traffic uses
    # (docs/serving.md): warmup-compiled programs, watchdog, typed errors.
    from mx_rcnn_tpu.serve import ServeError, build_engine

    try:
        with build_engine(cfg, variables) as engine:
            result = engine.infer(image)
    except ServeError as e:
        raise SystemExit(f"error: inference failed: {e}") from None
    log.info(
        "served at level %r in %.3fs", result["level"], result["latency_s"]
    )
    boxes, scores, classes = (
        result["boxes"], result["scores"], result["classes"],
    )
    masks = result.get("masks")
    class_names = None
    if cfg.data.dataset == "voc":
        from mx_rcnn_tpu.data.datasets import VOC_CLASSES

        class_names = ("__background__",) + VOC_CLASSES
    for box, score, cls in zip(boxes, scores, classes):
        if score >= args.threshold:
            name = class_names[int(cls)] if class_names else str(int(cls))
            log.info("%s %.3f [%.1f %.1f %.1f %.1f]", name, score, *box)
    out = args.out or (args.image.rsplit(".", 1)[0] + "_det.png")
    n = draw_detections(
        image, boxes, scores, classes, class_names, out, args.threshold,
        masks=masks,
    )
    log.info("drew %d detections -> %s", n, out)
    return boxes, scores, classes, masks


def cli(argv=None) -> int:
    """Console-script entry point ([project.scripts]).  ``main`` returns
    its result dict for programmatic callers; returning that from a
    console script would make ``sys.exit`` treat the truthy dict as a
    FAILURE exit status, so discard it and return 0 explicitly."""
    main(argv)
    return 0


if __name__ == "__main__":
    main()
