"""Probe: eval-path roi window-class distribution.

Runs forward_proposals at bench eval shapes (random weights — the same
distribution bench.py --eval measures on), then classifies each roi by the
smallest (Ty, Tx) window whose taps it fits under the kernel's origin and
8-alignment rules, per FPN level.  The r4 numbers this produced drive
ops/pallas/roi_align.py::window_classes — re-run it if the proposal
distribution changes (e.g. trained weights, new canvas).

Run from anywhere: the repo path is inserted below.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.config import get_config
from mx_rcnn_tpu.detection import Batch, TwoStageDetector
from mx_rcnn_tpu.detection.graph import forward_proposals, init_detector
from mx_rcnn_tpu.ops.roi_align import fpn_level_assignment

cfg = get_config("r50_fpn_coco")
h, w, b = 800, 1344, 8
model = TwoStageDetector(cfg=cfg.model)
variables = jax.device_put(init_detector(model, jax.random.PRNGKey(0), (h, w)))
rng = np.random.RandomState(0)
g = 32
batch = Batch(
    images=jnp.asarray(rng.randint(0, 256, (b, h, w, 3), dtype=np.uint8)),
    image_hw=jnp.asarray([[float(h), float(w)]] * b, jnp.float32),
    gt_boxes=jnp.zeros((b, g, 4), jnp.float32),
    gt_classes=jnp.zeros((b, g), jnp.int32),
    gt_valid=jnp.zeros((b, g), bool),
)
stats = (cfg.data.pixel_mean, cfg.data.pixel_std)
props = jax.device_get(
    jax.jit(lambda v, bt: forward_proposals(model, v, bt, pixel_stats=stats))(
        variables, batch
    )
)
rois = props.rois.reshape(-1, 4)
valid = props.valid.reshape(-1)
rois = rois[valid]
print(f"{len(rois)} valid rois of {b}x{props.rois.shape[1]}", file=sys.stderr)

# P2-P5 only: detector.roi_levels clamps pooling at 5 (P6 is RPN-only),
# and _prep assigns within the POOLING levels — max_level=6 here would
# count the biggest rois at a scale production never pools them at.
assign = np.asarray(fpn_level_assignment(jnp.asarray(rois), 2, 5, max_extent_cells=38))
scale = 1.0 / (1 << assign)
x1 = rois[:, 0] * scale
y1 = rois[:, 1] * scale
ex = np.maximum(rois[:, 2] * scale - x1, 1.0)
ey = np.maximum(rois[:, 3] * scale - y1, 1.0)
# Same bound as _prep: oy_s = clip(floor(y1)-1, ...); needs y_hi - oy <= T-1.
# Worst case (ignoring map-edge clamps helping): y span floor(y1+ey)+2 - (floor(y1)-1)
y_need = np.floor(y1 + ey) + 2 - (np.floor(y1) - 1) + 1  # cells incl. endpoints
# x: origin clips into the map (as _prep does) then floors to a
# multiple of 8 -> up to +7 slack; an unclamped left-edge origin would
# anchor at -8 and inflate x_need.
ox = (np.clip(np.floor(x1) - 1, 0, None) // 8) * 8
x_need = np.floor(x1 + ex) + 2 - ox + 1

print("extent percentiles (cells): ey", np.percentile(ey, [50, 90, 99]).round(1),
      "ex", np.percentile(ex, [50, 90, 99]).round(1))
print("need percentiles: y", np.percentile(y_need, [50, 90, 99]).round(1),
      "x", np.percentile(x_need, [50, 90, 99]).round(1))
for ty, tx in [(16, 16), (16, 24), (24, 24), (24, 32), (32, 32), (48, 48)]:
    fit = (y_need <= ty) & (x_need <= tx)
    print(f"fits ({ty:2d},{tx:2d}): {fit.mean()*100:5.1f}%")
for lvl in sorted(set(assign)):
    m = assign == lvl
    print(f"level {lvl}: {m.mean()*100:5.1f}% of rois, "
          f"median need y {np.median(y_need[m]):.0f} x {np.median(x_need[m]):.0f}")
