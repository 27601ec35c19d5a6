"""The one traffic generator: a data file of parameters under
``perfbench/traffic/`` plus ``--seed`` -> the images and boxes a cell is fed.

A traffic file fixes the multiset of image sizes and of objects per image, so
every seed carries the same amount of work in another order with other pixels;
a later PR adds a mix by adding a file, never code.  Keys:

- ``pool``: distinct images made per run (the feed cycles over them, shuffled
  by the loader, as an epoch over a small dataset);
- ``sizes``: [[height, width, count], ...] summing to ``pool``, all landscape
  or square (one canvas orientation, so one compiled program);
- ``objects``: objects per image, cycled over the pool;
- ``box_frac``: [lo, hi] side of a box as a share of the image's short side.

Pixels are uniform noise under filled, striped rectangles (one per box), the
boxes are the ground truth.  uint8, as a decoded JPEG would be.
"""

from __future__ import annotations

import numpy as np


def make_images(traffic: dict, num_classes: int, seed: int):
    """-> (images [uint8 (h, w, 3)], boxes [(n, 4) float32], classes [(n,) int32])."""
    rng = np.random.default_rng([seed, 0x7261])
    sizes = [(h, w) for h, w, count in traffic["sizes"] for _ in range(count)]
    if len(sizes) != traffic["pool"]:
        raise ValueError(f"sizes sum to {len(sizes)}, pool is {traffic['pool']}")
    if any(h > w for h, w in sizes):
        raise ValueError("traffic sizes must be landscape or square (one canvas)")
    counts = [traffic["objects"][i % len(traffic["objects"])] for i in range(len(sizes))]
    order = rng.permutation(len(sizes))
    lo, hi = traffic["box_frac"]
    images, boxes, classes = [], [], []
    for j in order:
        h, w = sizes[j]
        img = rng.integers(0, 64, (h, w, 3), dtype=np.uint8)
        n = counts[j]
        bs, cs = [], []
        for _ in range(n):
            bw = int(rng.uniform(lo, hi) * h)
            bh = int(rng.uniform(lo, hi) * h)
            x1 = int(rng.integers(0, w - bw))
            y1 = int(rng.integers(0, h - bh))
            c = int(rng.integers(1, num_classes))
            color = rng.integers(64, 256, 3)
            stripe = ((np.arange(bw) // (2 + c % 7)) % 2).astype(np.uint8)
            patch = (color[None, None, :] * (0.6 + 0.4 * stripe[None, :, None])).astype(np.uint8)
            img[y1:y1 + bh, x1:x1 + bw] = patch
            bs.append([x1, y1, x1 + bw - 1, y1 + bh - 1])
            cs.append(c)
        images.append(img)
        boxes.append(np.asarray(bs, np.float32).reshape(-1, 4))
        classes.append(np.asarray(cs, np.int32))
    return images, boxes, classes
