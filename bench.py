"""Headline benchmark: training throughput, images/sec/chip.

Measures the flagship Faster R-CNN FPN full train step (forward + backward +
optimizer) at COCO resolution on the available accelerator and reports
images/sec/chip against BASELINE.json's >=20 img/s/chip north star.
Synthetic pixels (no dataset download in this environment) — the compute
path is identical to real training; input pipeline is benchmarked
separately (see --loader).

A device benchmark: unless ``JAX_PLATFORMS`` names a CPU explicitly (the
CI wiring check, whose line is named as such and carries no baseline
ratio), any platform other than a TPU is an error.  Shapes never depend
on the platform.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline"} (plus
diagnostics on stderr: per-step percentiles, analytic FLOPs/step, achieved
TFLOP/s and MFU when XLA cost analysis is available).

Flags (default invocation is the driver's headline r50 run):
  --config NAME   preset to bench (default r50_fpn_coco; r101_fpn_coco is
                  the north-star model)
  --loader        ALSO measure loader-fed throughput: real DetectionLoader
                  batches shipped host->device through the train loop's
                  device_prefetch.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

BASELINE_IMG_S_CHIP = 20.0
# The reference's GPU-era inference speed (~5 fps, Ren et al. / upstream
# README) — the --eval metric's vs_baseline denominator.  NOTE the two
# modes' vs_baseline fields are ratios against DIFFERENT anchors: train is
# "fraction of the >=20 img/s/chip north star", eval is "speedup over the
# reference's published inference fps".
BASELINE_EVAL_IMG_S = 5.0
# The detection-middle fast paths plus the r6 precision policy that the
# headline number is defined over.  Applied as bench DEFAULTS (user --set
# overrides win — A/B probes must be able to turn any of these off); the
# no-override invocation is asserted below to resolve to exactly the
# fast-path set, so preset drift can never silently re-benchmark a slow
# path.  That drift is how the r5 wins leaked out of the r5 headline:
# the preset gained topk_impl="hier"/assign_block/pallas-bwd defaults,
# but loss_impl stayed "dense" and fold_frozen_bn stayed off, and the
# headline run inherited whatever the preset happened to say.
HEADLINE_FASTPATH = (
    "model.rpn.loss_impl=compact",
    "model.backbone.fold_frozen_bn=true",
    "model.precision.policy=mixed",
)


def resolved_knobs(cfg) -> dict:
    """The perf-relevant knob set a bench run actually resolved to.

    Emitted into the BENCH artifact as the ``bench_knobs`` JSON line so
    every headline number carries its own provenance — a regression
    triages by diffing two artifacts' knob lines before anyone re-runs
    anything."""
    m = cfg.model
    return {
        "backbone_dtype": m.backbone.dtype,
        "precision_policy": m.precision.policy,
        "fold_frozen_bn": m.backbone.fold_frozen_bn,
        "stem_s2d": m.backbone.stem_s2d,
        "stem_pool_fold": m.backbone.stem_pool_fold,
        "c2_pad": m.backbone.c2_pad,
        "remat": m.backbone.remat,
        "topk_impl": m.rpn.topk_impl,
        "topk_block": m.rpn.topk_block,
        "assign_block": m.rpn.assign_block,
        "loss_impl": m.rpn.loss_impl,
        "packed_head": m.rpn.packed_head,
        "roi_align_impl": m.rcnn.roi_align_impl,
        "roi_align_bwd_impl": m.rcnn.roi_align_bwd_impl,
        "roi_block": m.rcnn.roi_block,
        "steps_per_call": cfg.train.steps_per_call,
        "accum_steps": cfg.train.accum_steps,
        "bucket_mb": cfg.train.bucket_mb,
        "per_device_batch": cfg.train.per_device_batch,
    }


def assert_headline_fastpath(cfg) -> None:
    """Hard-fail the NO-override invocation when any fast path resolved
    off.  Only the default (driver/headline) invocation is guarded —
    ``--set`` runs are A/B probes and may disable anything."""
    knobs = resolved_knobs(cfg)
    want = {
        "topk_impl": "hier",
        "loss_impl": "compact",
        "packed_head": True,
        "roi_align_bwd_impl": "pallas",
        "precision_policy": "mixed",
    }
    bad = {
        k: (knobs[k], v) for k, v in want.items() if knobs[k] != v
    }
    if knobs["assign_block"] <= 0:
        bad["assign_block"] = (knobs["assign_block"], "> 0")
    if cfg.model.backbone.name.startswith("resnet") and not knobs[
        "fold_frozen_bn"
    ]:
        bad["fold_frozen_bn"] = (False, True)
    if bad:
        raise SystemExit(
            "headline bench config drifted off the fast-path set: "
            + "; ".join(
                f"{k}={got!r} (want {need!r})"
                for k, (got, need) in sorted(bad.items())
            )
            + " — fix the preset/HEADLINE_FASTPATH or make this an "
            "explicit --set A/B probe"
        )


def _synthetic_batch(cfg, batch, image_size, k):
    from mx_rcnn_tpu.detection import Batch

    rng = np.random.RandomState(0)
    g = cfg.data.max_gt_boxes
    h, w = image_size
    n_gt = 8
    # K DISTINCT batches for the scan loop (a single batch broadcast K
    # times would let every post-warmup step re-read hot pixels/boxes and
    # slightly flatter cache locality vs real training).
    n = batch * k
    boxes = np.zeros((n, g, 4), np.float32)
    for b in range(n):
        x1 = rng.uniform(0, w - 64, n_gt)
        y1 = rng.uniform(0, h - 64, n_gt)
        bw = rng.uniform(16, 64, n_gt)
        bh = rng.uniform(16, 64, n_gt)
        boxes[b, :n_gt] = np.stack([x1, y1, x1 + bw, y1 + bh], axis=1)
    classes = np.zeros((n, g), np.int32)
    classes[:, :n_gt] = rng.randint(1, cfg.model.num_classes, (n, n_gt))
    valid = np.zeros((n, g), bool)
    valid[:, :n_gt] = True
    # uint8 pixels: the production loader ships raw letterboxed uint8 and
    # the step normalizes in-graph (graph.py::prep_images), so the timed
    # program must be that one.  Also 1/4 the device_put bytes.
    images = rng.randint(0, 256, (n, h, w, 3), dtype=np.uint8)
    masks = None
    if cfg.model.mask.enabled:
        # Box-relative gt masks, the loader's rasterized contract
        # (data/loader.py::GT_MASK_SIZE); blobby half-coverage shapes so
        # the mask loss sees both classes.
        from mx_rcnn_tpu.data.loader import GT_MASK_SIZE

        masks = np.zeros((n, g, GT_MASK_SIZE, GT_MASK_SIZE), np.float32)
        yy, xx = np.mgrid[0:GT_MASK_SIZE, 0:GT_MASK_SIZE]
        for b in range(n):
            cy = rng.uniform(0.3, 0.7, n_gt) * GT_MASK_SIZE
            cx = rng.uniform(0.3, 0.7, n_gt) * GT_MASK_SIZE
            r = rng.uniform(0.2, 0.45, n_gt) * GT_MASK_SIZE
            for j in range(n_gt):
                masks[b, j] = (
                    (yy - cy[j]) ** 2 + (xx - cx[j]) ** 2 <= r[j] ** 2
                ).astype(np.float32)
    data = Batch(
        images=images,
        image_hw=np.tile(
            np.asarray([[float(h), float(w)]], np.float32), (n, 1)
        ),
        gt_boxes=boxes,
        gt_classes=classes,
        gt_valid=valid,
        gt_masks=masks,
    )
    if k > 1:
        # Stacked (K, B, ...) layout consumed by the device-side lax.scan.
        data = Batch(*[
            None if f is None else f.reshape(k, batch, *f.shape[1:])
            for f in data
        ])
    return data


def _cost_analysis(step_fn, state, data, k, dt_per_call):
    """FLOPs/step + achieved TFLOP/s + (on a TPU) MFU.

    Primary count: an analytic jaxpr walk over conv/dot primitives
    (mx_rcnn_tpu.utils.flops) — XLA's ``compiled.cost_analysis()`` counts a
    ``lax.scan`` body once, so it is printed only as a per-step
    cross-check."""
    import jax

    from mx_rcnn_tpu.utils.flops import count_matmul_flops, peak_bf16_flops
    from mx_rcnn_tpu.utils.hlo_profile import attribute_flops

    flops = count_matmul_flops(step_fn, state, data)
    per_step = flops / k
    achieved = flops / dt_per_call
    dev = jax.devices()[0]
    line = (
        f"analytic (conv+matmul jaxpr walk): {per_step/1e12:.2f} TFLOP/step, "
        f"achieved {achieved/1e12:.1f} TFLOP/s"
    )
    if dev.platform == "tpu":
        peak = peak_bf16_flops(dev.device_kind)
        line += f", MFU {achieved/peak*100:.1f}% of {dev.device_kind} bf16 peak"
    print(line, file=sys.stderr)
    comps = attribute_flops(step_fn, state, data)
    total = sum(c["flops"] for c in comps.values()) or 1.0
    ranked = sorted(
        comps.items(), key=lambda kv: kv[1]["flops"], reverse=True
    )
    print(
        "per-component: " + ", ".join(
            f"{name} {c['flops']/k/1e9:.0f}GF ({c['flops']/total*100:.0f}%)"
            for name, c in ranked if c["flops"] / total >= 0.01
        ) + "  [full table: tools/mfu_report.py]",
        file=sys.stderr,
    )
    ca = step_fn.lower(state, data).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    xla_flops = float((ca or {}).get("flops", 0.0))
    if xla_flops > 0:
        # cost_analysis counts the lax.scan body ONCE (no trip-count
        # multiply), i.e. it is already a per-step figure here.
        print(
            f"(xla cost_analysis per-step cross-check: "
            f"{xla_flops/1e12:.2f} TFLOP/step)",
            file=sys.stderr,
        )


def _loader_fed(cfg, step_fn, state, global_batch, n_steps=20):
    """Throughput with real loader batches shipped host->device through
    device_prefetch (the production train path)."""
    import jax

    from mx_rcnn_tpu.data import DetectionLoader, SyntheticDataset
    from mx_rcnn_tpu.parallel.prefetch import PrefetchStats, device_prefetch
    from mx_rcnn_tpu.train.loop import _stacked_batches

    k = max(cfg.train.steps_per_call, 1)
    accum = max(cfg.train.accum_steps, 1)
    stack = max(k, accum)
    # uint8 synthetic pixels: same batch dtype as the main phase's program
    # (no recompile) and the production transfer size — 3 MB/image at the
    # recipe canvas instead of the f32 path's 12.
    roidb = SyntheticDataset(
        num_images=max(global_batch * 2, 8), image_hw=cfg.data.image_size,
        dtype="uint8",
    ).roidb()
    loader = DetectionLoader(
        roidb, cfg.data, batch_size=global_batch // accum, prefetch=False
    )
    host_it = iter(loader)
    if stack > 1:
        host_it = _stacked_batches(host_it, stack)
    stats = PrefetchStats()
    it = device_prefetch(
        host_it, mesh=None, depth=2, stacked=stack > 1, host_depth=1,
        stats=stats,
    )
    # Warm (program is already compiled from the synthetic phase).
    state, metrics = step_fn(state, next(it))
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    jax.device_get((metrics["loss"], leaf.ravel()[0]))
    stats.take()  # warmup stall is compile wait, not loader speed
    n_calls = max(n_steps // k, 2)
    t0 = time.perf_counter()
    for _ in range(n_calls):
        state, metrics = step_fn(state, next(it))
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    jax.device_get((metrics["loss"], leaf.ravel()[0]))
    dt = time.perf_counter() - t0
    n_steps_done = n_calls * k
    img_s = n_calls * k * global_batch / dt
    stall_s, _ = stats.take()
    # Tear the pipeline down promptly: closing the device_prefetch
    # generator closes the host prefetch thread, the stacking generator,
    # and the loader iterator under it — including input-service worker
    # processes when the run was configured with data.num_workers > 0.
    it.close()
    h, w = cfg.data.image_size
    platform = jax.default_backend()
    # Data-starvation stage line (satellite of the train_stage_ms
    # breakdown): ms/step the consumer blocked in next(loader) PAST the
    # prefetch double buffer.  ~0 means the step hides the loader; a
    # value near the step time means the run is input-bound and device
    # optimizations will not move the headline.
    print(
        json.dumps(
            {
                "metric": (
                    f"train_stage_ms[data_stall@{h}x{w},"
                    f"b{global_batch},{platform}]"
                ),
                "value": round(stall_s * 1e3 / n_steps_done, 3),
                "unit": "ms/step",
                "stalled_frac": round(stall_s / dt, 4),
            }
        )
    )
    print(
        f"loader-fed (host->device each step): {img_s:.2f} img/s "
        f"({n_steps_done} steps in {dt:.1f}s, "
        f"data stall {stall_s:.2f}s)",
        file=sys.stderr,
    )
    return img_s


def _eval_bench(cfg, image_size):
    """Inference throughput: forward_inference at test.per_device_batch.

    The timed graph is the PRODUCTION one: uint8 images normalized
    in-graph (graph.py::prep_images), exactly what eval_cli runs on real
    loader batches.

    Timing method: N per-dispatch chained executions with ONE final fetch
    — each dispatch provably executes the full forward, nothing can be
    hoisted.  The chain rides the PARAMS (v_{i+1} = v_i + 1e-20 * f(v_i,
    images), f32 leaves, buffers donated) because uint8 images cannot
    absorb an infinitesimal perturbation; the r3 form chained through the
    float images.  A scan-with-perturbed-carry form measured 7x slower on
    the same graph (an XLA scan pathology with a 100 MB changing carry,
    r3 finding), so eval numbers use the per-dispatch chain; it agrees
    with the 0-carry scan form to ~3%."""
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.detection import Batch, TwoStageDetector, forward_inference
    from mx_rcnn_tpu.detection.graph import init_detector

    b = max(cfg.model.test.per_device_batch, 1)
    h, w = image_size
    model = TwoStageDetector(cfg=cfg.model)
    variables = init_detector(model, jax.random.PRNGKey(0), (h, w))
    rng = np.random.RandomState(0)
    g = cfg.data.max_gt_boxes
    stats = (cfg.data.pixel_mean, cfg.data.pixel_std)
    batch = Batch(
        images=jnp.asarray(rng.randint(0, 256, (b, h, w, 3), dtype=np.uint8)),
        image_hw=jnp.asarray([[float(h), float(w)]] * b, jnp.float32),
        gt_boxes=jnp.zeros((b, g, 4), jnp.float32),
        gt_classes=jnp.zeros((b, g), jnp.int32),
        gt_valid=jnp.zeros((b, g), bool),
    )

    # Params ride as a jit ARGUMENT (device buffers), not a closure: closed-
    # over arrays embed as constants in the HLO module — VGG-16's ~0.5 GB
    # fc6/fc7 would be baked into the program the compiler must fold and
    # the executable the cache must store — and the chain below donates
    # them, which a constant cannot be.
    variables = jax.device_put(variables)

    def run(v, imgs):
        dets = forward_inference(
            model, v, batch._replace(images=imgs), pixel_stats=stats
        )
        return jnp.sum(dets.boxes) + jnp.sum(dets.scores)

    def chain(v, im):
        eps = 1e-20 * run(v, im)
        return jax.tree_util.tree_map(lambda p: p + eps.astype(p.dtype), v)

    step = jax.jit(chain, donate_argnums=(0,))
    variables = step(variables, batch.images)
    jax.device_get(jax.tree_util.tree_leaves(variables)[0].ravel()[0])
    n = 10
    t0 = time.perf_counter()
    for _ in range(n):
        variables = step(variables, batch.images)
    jax.device_get(jax.tree_util.tree_leaves(variables)[0].ravel()[0])
    dt = (time.perf_counter() - t0) / n
    print(
        f"eval: {dt * 1e3:.1f} ms/batch-of-{b} ({b / dt:.1f} img/s/chip)",
        file=sys.stderr,
    )
    return b / dt, b


def _stage_breakdown(cfg, model, state, image_size, batch, platform):
    """One JSON line per train-step stage into the BENCH artifact.

    Same prefix-ablation stage list as tools/perf_breakdown.py (shared in
    mx_rcnn_tpu/utils/stage_bench.py) so future BENCH_r*.json files carry
    their own regression localization: a throughput drop shows up as a
    specific stage's delta growing, not as an unattributed headline number.
    Stage lines print BEFORE the headline metric line so "last JSON line =
    headline" keeps holding for existing consumers."""
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.detection import Batch
    from mx_rcnn_tpu.train.loop import FREEZE_PREFIXES
    from mx_rcnn_tpu.train.optim import frozen_mask
    from mx_rcnn_tpu.utils.stage_bench import time_train_stages, train_stage_fns

    h, w = image_size
    b = batch
    rng = np.random.RandomState(0)
    g = cfg.data.max_gt_boxes
    boxes = np.zeros((b, g, 4), np.float32)
    boxes[:, :8] = [100.0, 100.0, 300.0, 300.0]
    bt = Batch(
        images=jnp.asarray(rng.randn(b, h, w, 3), jnp.float32),
        image_hw=jnp.asarray([[float(h), float(w)]] * b, jnp.float32),
        gt_boxes=jnp.asarray(boxes),
        gt_classes=jnp.ones((b, g), jnp.int32),
        gt_valid=jnp.asarray(np.tile(np.arange(g)[None] < 8, (b, 1))),
    )
    params = state.params
    rest = state.model_state
    if cfg.model.backbone.freeze_stages > 0:
        mask = frozen_mask(
            params, FREEZE_PREFIXES.get(cfg.model.backbone.name, ())
        )

        def masked(p):
            return jax.tree_util.tree_map(
                lambda x, t: x if t else jax.lax.stop_gradient(x), p, mask
            )
    else:
        masked = None

    stages = train_stage_fns(
        model, params, rest, bt, jax.random.PRNGKey(1), masked=masked
    )
    results = time_train_stages(stages, params, steps=10, calls=2)
    label = f"@{h}x{w},b{b},{platform}"
    prev = 0.0
    for name, dt in results:
        print(
            json.dumps(
                {
                    "metric": f"train_stage_ms[{name}{label}]",
                    "value": round(dt * 1e3, 3),
                    "unit": "ms/step",
                    "delta_ms": round((dt - prev) * 1e3, 3),
                }
            )
        )
        prev = dt


def _metric(kind, config, image_size, batch, platform, img_s, baseline):
    """The headline JSON line.  Only a TPU run carries the device metric's
    name and a ratio to the baseline; a (named) CPU run is a wiring check
    and says so in its name — a rate taken there is not a speed."""
    label = (
        f"{config.replace('_coco', '')}@{image_size[0]}x{image_size[1]},"
        f"b{batch},{platform}"
    )
    if platform != "tpu":
        return {
            "metric": f"{kind}_wiring_check[{label}]",
            "value": round(img_s, 3),
            "unit": "img/s on a CPU (not a device metric)",
        }
    return {
        "metric": f"{kind}_images_per_sec_per_chip[{label}]",
        "value": round(img_s, 3),
        "unit": "img/s/chip",
        "vs_baseline": round(img_s / baseline, 4),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="r50_fpn_coco")
    ap.add_argument("--loader", action="store_true")
    ap.add_argument(
        "--eval", action="store_true",
        help="bench forward_inference (proposals -> heads -> per-class NMS) "
        "instead of the train step",
    )
    ap.add_argument(
        "--set", dest="overrides", action="append", default=[],
        metavar="KEY.PATH=VALUE",
        help="config overrides for A/B probes (same syntax as train.py)",
    )
    ap.add_argument(
        "--breakdown", action=argparse.BooleanOptionalAction, default=True,
        help="ALSO emit one JSON line per train-step stage (the "
        "tools/perf_breakdown.py prefix ablation, shared via "
        "mx_rcnn_tpu/utils/stage_bench.py) so the BENCH artifact localizes "
        "regressions without a separate tool run (each stage is its own "
        "compile).",
    )
    args = ap.parse_args()
    if args.eval and args.loader:
        ap.error("--loader applies to the train bench only, not --eval")

    import os

    import jax

    # A device benchmark: no accelerator is an error, not a smaller run.
    # Only a CPU the caller NAMED (JAX_PLATFORMS=cpu — the CI wiring
    # check) may stand in, and its line says so (see _metric).
    platform = jax.devices()[0].platform
    wanted = os.environ.get("JAX_PLATFORMS", "")
    if platform != "tpu" and (not wanted or "tpu" in wanted.split(",")):
        raise SystemExit(
            f"bench.py wants a TPU and found platform {platform!r} "
            f"(JAX_PLATFORMS={wanted!r}); a CPU run must be asked for by "
            "name and is a wiring check, never a benchmark"
        )

    # Persistent compile cache (utils/compile_cache.py's one rule): repeat
    # bench invocations skip the multi-minute compile of the K-step scan.
    from mx_rcnn_tpu.utils.compile_cache import configure_cache

    configure_cache()

    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.train.loop import build_all

    # Full recipe resolution: the preset's own landscape canvas (COCO
    # presets: 800x1344 per the 800-short/1333-max Detectron rule;
    # vgg16_voc07: 608x1024 per the 600/1000 VOC rule).
    cfg = get_config(args.config)
    image_size = cfg.data.image_size
    # 2 images per chip: the Detectron-recipe per-device batch (the
    # BASELINE north-star mAP presumes that recipe).  lr scales linearly
    # via build_all.
    batch = 2

    # steps_per_call: the host-side loop is a lax.scan on device — one
    # dispatch per K steps, so the timed window holds K steps of device
    # work per host round trip.  K = 10 was chosen when one dispatch cost
    # tens of ms; on the local runtime a dispatch costs 0.2 ms (PR 21 chip
    # run, tests/_kernels_tpu_worker.py::runtime_facts), so that reason no
    # longer holds and none has replaced it — whether K stays is ROADMAP
    # S8/D2's to decide.
    k = 10
    cfg = dataclasses.replace(
        cfg,
        data=dataclasses.replace(cfg.data, image_size=image_size, max_gt_boxes=32),
        train=dataclasses.replace(
            cfg.train, steps_per_call=k, per_device_batch=batch
        ),
    )
    # Fast-path headline preset (see HEADLINE_FASTPATH): bench defaults,
    # below user overrides in precedence.
    cfg = apply_overrides(cfg, list(HEADLINE_FASTPATH))
    if args.overrides:
        # Overrides win over the bench defaults above — and the locals the
        # synthetic batch / metric label derive from must follow them, or
        # an overridden canvas/batch would silently bench stale shapes.
        cfg = apply_overrides(cfg, args.overrides)
        image_size = cfg.data.image_size
        batch = cfg.train.per_device_batch
        k = max(cfg.train.steps_per_call, 1)
        if cfg.train.accum_steps > 1 and k > 1:
            # The plan forbids the combination; surface it as a CLI error
            # instead of a trace-time ValueError.
            ap.error("train.accum_steps and train.steps_per_call are "
                     "mutually exclusive (both stack the leading axis)")
    else:
        assert_headline_fastpath(cfg)
    # Leading-axis stack: K scanned optimizer steps OR N accumulated
    # microbatches (mutually exclusive; plan-validated).
    accum = max(cfg.train.accum_steps, 1)
    stack = max(k, accum)
    # Knob provenance line, FIRST json line of the artifact (the headline
    # metric stays the last — existing consumers key off that).
    print(json.dumps({"metric": "bench_knobs", "value": resolved_knobs(cfg)}))

    if args.eval:
        img_s, eb = _eval_bench(cfg, image_size)
        print(json.dumps(_metric(
            "eval", args.config, image_size, eb, platform, img_s,
            BASELINE_EVAL_IMG_S,
        )))
        return
    model, tx, state, step_fn, global_batch = build_all(cfg, mesh=None)
    data = _synthetic_batch(cfg, batch, image_size, stack)

    # Device-resident batch: the metric is the train step (fwd+bwd+update);
    # input delivery is measured separately (--loader).
    data = jax.device_put(data)

    def sync(s, m):
        # The barrier is a device->host fetch of values that depend on the
        # whole step: the UPDATED params (forward+backward+optimizer) and
        # the loss, one fetch per timed window.  On the local runtime
        # block_until_ready is a true barrier too (PR 21 chip run: 50
        # chained 4096^3 bf16 matmuls took 38.3 ms under it — 179 TFLOP/s,
        # the chip's roofline, so it cannot have returned at dispatch);
        # the fetch is kept because it is a barrier on any runtime.
        leaf = jax.tree_util.tree_leaves(s.params)[0]
        jax.device_get((m["loss"], leaf.ravel()[0]))

    # Warmup (compile) + timed steps.
    for _ in range(2):
        state, metrics = step_fn(state, data)
    sync(state, metrics)
    n_calls = 6
    # Images processed per call: K steps x batch, or batch x N
    # microbatches per accumulated step — `stack * batch` either way.
    n_steps = n_calls * stack
    t0 = time.perf_counter()
    for _ in range(n_calls):
        state, metrics = step_fn(state, data)
    sync(state, metrics)
    dt = time.perf_counter() - t0

    _cost_analysis(step_fn, state, data, stack, dt / n_calls)

    # Per-step percentiles (sync per call — includes one device->host
    # fetch per call, an upper bound) on stderr.
    from mx_rcnn_tpu.utils import StepTimer

    timer = StepTimer(warmup=2)
    for _ in range(8):
        with timer:
            state, metrics = step_fn(state, data)
            sync(state, metrics)
    per_call = timer.summary()
    per_step = {key: v / stack if key != "steps" else v for key, v in per_call.items()}
    print(
        f"per-call (K={k} steps x N={accum} microbatches, synced upper "
        f"bound): {per_call}\n"
        f"per-step equivalent: {per_step}",
        file=sys.stderr,
    )

    if args.loader:
        _loader_fed(cfg, step_fn, state, global_batch)

    if args.breakdown:
        _stage_breakdown(cfg, model, state, image_size, batch, platform)

    line = _metric(
        "train", args.config, image_size, batch, platform,
        n_steps * batch / dt, BASELINE_IMG_S_CHIP,
    )
    # Per-step wall-clock tail (StepTimer, synced upper bound):
    # mean/p50/p90/p99/max in ms — a throughput headline can hide a
    # straggler step; these cannot.
    line["step_ms"] = {
        key: round(v, 3) for key, v in per_step.items() if key != "steps"
    }
    print(json.dumps(line))


if __name__ == "__main__":
    sys.exit(main())
