"""Tensor-cache prewarm + LRU eviction for COCO-scale datasets.

A cold :class:`~mx_rcnn_tpu.data.cache.TensorCache` makes the first
training epoch pay full decode+letterbox cost per image; this tool pays
it up front, in parallel, through the SAME path production uses — the
process input service (data/service.py) with the cache directory shared
between workers.  Records whose blobs already exist are skipped (the
assembly path's ``cache.key``/``get`` hit short-circuits the decode), so
re-running after an interrupted warm only fills the holes.

With ``--max-bytes`` the tool then trims the cache directory to a byte
budget by evicting the least-recently-used blobs (mtime order — reads
via the loader touch blobs through the OS, and a warm rewrites them), and
emits one journaled ``cache_evict`` event so the obs plane records what
was dropped and why (tools/obs_report.py lists it in the incident
timeline).  Eviction is safe against concurrent readers: a reader that
loses a blob sees a plain cache miss and rebuilds from source.

Prints diagnostics to stderr and exactly one JSON summary as the LAST
line on stdout:

    {"metric": "cache_warm", "value": {"records": 64, "blobs": 128,
     "already_cached": 0, "warmed_s": 3.2, "evicted": 10,
     "freed_bytes": 81920, "used_bytes": 524288}, ...}

Usage:
    python tools/cache_warm.py --cache-dir /data/cache --images 64 \\
        --workers 4 --epochs 2 --max-bytes 268435456
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

log = logging.getLogger("cache_warm")


def _blobs(cache_dir_root: str) -> list[tuple[str, int, float]]:
    """Every blob under the cache root (all transform fingerprints):
    (path, size, mtime) — eviction order is mtime-LRU across the lot."""
    out = []
    tensors = os.path.join(cache_dir_root, "tensors")
    for dirpath, _dirnames, filenames in os.walk(tensors):
        for fn in filenames:
            if not fn.endswith(".blob"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                st = os.stat(path)
            except OSError:
                continue  # a concurrent evict/replace won the race
            out.append((path, st.st_size, st.st_mtime))
    return out


def warm(args) -> dict:
    """Drive --epochs of the train stream through the input service with
    the cache attached; every assembled batch populates the shared disk
    cache as a side effect.  Returns warm-phase stats."""
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.data import DetectionLoader
    from mx_rcnn_tpu.data.cache import TensorCache
    from train_soak import make_roidb

    cfg = get_config(args.config)
    data_cfg = dataclasses.replace(
        cfg.data, dataset="synthetic", cache_dir=args.cache_dir
    )
    roidb = make_roidb(cfg, args.images, seed=args.seed)
    cache = TensorCache(args.cache_dir, data_cfg)
    already = sum(
        1
        for rec in roidb
        for flip in (False, True)
        if os.path.exists(cache._path(cache.key(rec, flip)))
    )
    loader = DetectionLoader(
        roidb, data_cfg, batch_size=args.batch_size, train=True,
        seed=args.seed, prefetch=False, num_workers=0,
        service_workers=args.workers,
    )
    t0 = time.monotonic()
    batches = 0
    for _ in loader._raw_train_batches(0, epochs=args.epochs):
        batches += 1  # batches populate the cache; content is discarded
    warmed_s = time.monotonic() - t0
    blobs = _blobs(args.cache_dir)
    return {
        "records": len(roidb),
        "epochs": args.epochs,
        "batches": batches,
        "already_cached": already,
        "blobs": len(blobs),
        "used_bytes": sum(s for _, s, _ in blobs),
        "warmed_s": round(warmed_s, 3),
    }


def evict(cache_dir_root: str, max_bytes: int) -> dict:
    """Trim the cache to ``max_bytes`` by deleting blobs oldest-mtime
    first; one journaled ``cache_evict`` event summarizes the sweep."""
    from mx_rcnn_tpu import obs

    blobs = sorted(_blobs(cache_dir_root), key=lambda b: b[2])  # LRU first
    used = sum(s for _, s, _ in blobs)
    evicted = 0
    freed = 0
    for path, size, _mtime in blobs:
        if used - freed <= max_bytes:
            break
        try:
            os.unlink(path)
        except OSError:
            continue  # reader/rewarm race: it no longer counts anyway
        evicted += 1
        freed += size
    if evicted:
        obs.emit("data", "cache_evict", {
            "evicted": evicted,
            "freed_bytes": freed,
            "used_bytes": used - freed,
            "max_bytes": max_bytes,
        }, logger=log)
    return {
        "evicted": evicted,
        "freed_bytes": freed,
        "used_bytes": used - freed,
        "max_bytes": max_bytes,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="tiny_synthetic")
    p.add_argument("--cache-dir", required=True,
                   help="TensorCache root (data.cache_dir)")
    p.add_argument("--images", type=int, default=64,
                   help="synthetic dataset size to warm")
    p.add_argument("--batch-size", type=int, default=2)
    p.add_argument("--epochs", type=int, default=2,
                   help="train-stream epochs to run (flip augmentation "
                        "means later epochs fill the other flip variants)")
    p.add_argument("--workers", type=int, default=2,
                   help="input-service decode workers (0 = in-process)")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--max-bytes", type=int, default=0,
                   help="evict LRU blobs until the cache fits this "
                        "budget (0 = no eviction)")
    p.add_argument("--obs-dir", default=None,
                   help="journal cache_evict events here")
    args = p.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    from mx_rcnn_tpu import obs

    obs_on = bool(args.obs_dir)
    if obs_on:
        obs.configure(args.obs_dir, flush_s=5.0)

    os.makedirs(args.cache_dir, exist_ok=True)
    stats = warm(args)
    log.info(
        "warmed %d record(s) x %d epoch(s) in %.2fs: %d blob(s), %dB "
        "(%d already cached)",
        stats["records"], stats["epochs"], stats["warmed_s"],
        stats["blobs"], stats["used_bytes"], stats["already_cached"],
    )
    if args.max_bytes > 0:
        ev = evict(args.cache_dir, args.max_bytes)
        log.info(
            "evicted %d blob(s), freed %dB -> %dB used (budget %dB)",
            ev["evicted"], ev["freed_bytes"], ev["used_bytes"],
            ev["max_bytes"],
        )
        stats.update(ev)
    if obs_on:
        obs.close()
    print(json.dumps({
        "metric": "cache_warm",
        "value": stats,
        "cache_dir": os.path.abspath(args.cache_dir),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
