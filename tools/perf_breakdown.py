"""Ablation timing of the train step's sub-graphs on the real chip.

Times successively larger prefixes of the full train computation
(backbone -> +RPN head -> +anchor assignment/RPN losses -> +proposals ->
+sampling+ROIAlign -> full step) so hotspots can be localized without a
device trace (ROADMAP D4: a reduction of the profiler's trace is what
should replace this).  Every timing is N queued executions ended by ONE
device->host fetch of a dependent value, which waits on the whole queue.

Also times the full optimizer step (make_train_step minus the ablation
grad — optimizer/update overhead) and standalone micro-benches of the
usual non-MXU suspects (per-level proposal NMS fixed point, the big
anchor top_k) so the largest delta line can be attributed inside itself.

Usage: python tools/perf_breakdown.py [--hw 800x1344] [--batch 2] [--steps 20]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# Persistent compile cache (utils/compile_cache.py's one rule): iterating
# on one stage should not recompile the other seven.
from mx_rcnn_tpu.utils.compile_cache import configure_cache
from mx_rcnn_tpu.utils.flops import peak_bf16_flops
from mx_rcnn_tpu.utils.stage_bench import (  # noqa: F401  (timed: re-export)
    time_train_stages,
    timed,
    train_stage_fns,
)

configure_cache()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--hw", default="800x1344",
        help="canvas as HxW (recipe default) or one square int",
    )
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--config", default="r50_fpn_coco")
    ap.add_argument(
        "--infer", action="store_true",
        help="break down forward_inference (eval path) instead of the "
        "train step: features -> +proposals -> +box head -> full "
        "(per-class NMS + top-D)",
    )
    ap.add_argument(
        "--backbone", action="store_true",
        help="break down the backbone wall one level further: per-stage "
        "trunk fwd+bwd (stem, +C2.., production freeze), the FrozenBN-vs-"
        "identity fusion A/B, the FPN neck delta, and the per-level RPN "
        "head cost",
    )
    ap.add_argument(
        "--set", dest="overrides", action="append", default=[],
        metavar="KEY.PATH=VALUE",
    )
    ap.add_argument(
        "--only", default=None, metavar="SUBSTR",
        help="run only train-breakdown stages whose name contains this "
        "substring (skips the optimizer row and micro-benches too)",
    )
    ap.add_argument(
        "--freeze", action=argparse.BooleanOptionalAction, default=None,
        help="apply the production freeze (stop-grad conv1/bn1/layer1 — "
        "their backward is DCE'd exactly as in the real step).  Default: "
        "follow the config (freeze_stages > 0), matching build_all.  The "
        "r3 tables were recorded with --no-freeze semantics and overstate "
        "the backbone wall by the frozen stages' backward (~20 ms on "
        "R101-FPN at recipe shapes)",
    )
    args = ap.parse_args()

    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.detection import Batch, TwoStageDetector
    from mx_rcnn_tpu.detection.graph import init_detector, level_anchors

    if "x" in args.hw:
        h, w = (int(t) for t in args.hw.split("x"))
    else:
        h = w = int(args.hw)
    b = args.batch
    cfg = get_config(args.config)
    cfg = apply_overrides(
        cfg,
        [f"data.image_size=({h},{w})", "data.max_gt_boxes=32"]
        + args.overrides,
    )
    model = TwoStageDetector(cfg=cfg.model)
    variables = init_detector(model, jax.random.PRNGKey(0), (h, w))
    params = variables["params"]
    rest = {k: v for k, v in variables.items() if k != "params"}

    rng = np.random.RandomState(0)
    g = cfg.data.max_gt_boxes
    boxes = np.zeros((b, g, 4), np.float32)
    boxes[:, :8] = [100.0, 100.0, 300.0, 300.0]
    batch = Batch(
        images=jnp.asarray(rng.randn(b, h, w, 3), jnp.float32),
        image_hw=jnp.asarray([[float(h), float(w)]] * b, jnp.float32),
        gt_boxes=jnp.asarray(boxes),
        gt_classes=jnp.ones((b, g), jnp.int32),
        gt_valid=jnp.asarray(np.tile(np.arange(g)[None] < 8, (b, 1))),
    )
    key = jax.random.PRNGKey(1)
    mcfg = cfg.model

    if args.backbone:
        _backbone_breakdown(args, cfg, model, params, rest, batch)
        return
    if args.infer:
        _infer_breakdown(args, model, params, rest, batch, mcfg)
        return

    freeze_on = (
        args.freeze
        if args.freeze is not None
        else cfg.model.backbone.freeze_stages > 0
    )
    if freeze_on:
        from mx_rcnn_tpu.train.loop import FREEZE_PREFIXES
        from mx_rcnn_tpu.train.optim import frozen_mask

        _mask = frozen_mask(
            params, FREEZE_PREFIXES.get(cfg.model.backbone.name, ())
        )

        def masked(p):
            return jax.tree_util.tree_map(
                lambda x, t: x if t else jax.lax.stop_gradient(x), p, _mask
            )
    else:
        def masked(p):
            return p

    # Stage list shared with bench.py --breakdown
    # (mx_rcnn_tpu/utils/stage_bench.py): each stage is "everything before
    # it" + one more piece of forward_train; all keep the RPN loss term so
    # the backbone backward exists in every variant.
    stages = train_stage_fns(model, params, rest, batch, key, masked=masked)
    if args.only:
        stages = [s for s in stages if args.only in s[0]]
    results = time_train_stages(
        stages, params, args.steps,
        report=lambda name, dt: print(
            f"{name:32s} {dt * 1e3:8.2f} ms/step", flush=True
        ),
    )

    if args.only:
        _print_deltas(results, filtered=True)
        return

    # Full production step incl. optimizer (delta vs the grad-only full
    # stage = clip + wd + sgd + state bookkeeping).
    from mx_rcnn_tpu.parallel.step import make_train_step
    from mx_rcnn_tpu.train.loop import FREEZE_PREFIXES
    from mx_rcnn_tpu.train.optim import frozen_mask, make_optimizer
    from mx_rcnn_tpu.train.state import create_train_state

    freeze = FREEZE_PREFIXES.get(cfg.model.backbone.name, ())
    tx, schedule = make_optimizer(cfg.train, params, freeze_prefixes=freeze)
    state = create_train_state(model, tx, jax.random.PRNGKey(0), (h, w), batch=1)
    state = state.replace(params=params, model_state=rest)
    step_fn = make_train_step(
        model, tx, schedule,
        trainable_mask=frozen_mask(params, freeze) if freeze else None,
    )

    # timed() feeds fn its own output; close over state and chain via params.
    def opt_fn(p):
        new_s, _ = step_fn(state.replace(params=p), batch)
        return new_s.params

    dt = timed(jax.jit(opt_fn), params, args.steps)
    results.append(("full step + optimizer", dt))
    print(f"{'full step + optimizer':32s} {dt * 1e3:8.2f} ms/step", flush=True)

    _print_deltas(results)

    # ---- standalone micro-benches of the usual non-MXU suspects ---------
    print("\nisolated micro-benches (forward only, per step):")
    from mx_rcnn_tpu.ops.nms import nms_indices

    feats = model.apply({"params": params, **rest}, batch.images,
                        method="features")
    anchors = level_anchors(mcfg, feats)
    n_anchors = int(sum(a.shape[0] for a in anchors.values()))

    # timed() chains fn's output back into its argument, so each micro fn
    # returns an argument-shaped value that depends on the measured op.
    pre = mcfg.rpn.train_pre_nms_top_n

    # The big per-image objectness top_k over all anchors.
    scores_all = jnp.asarray(rng.rand(b, n_anchors), jnp.float32)
    topk = jax.jit(
        lambda s: s + 0.0 * jax.lax.top_k(s, pre)[0].sum()
    )
    dt = timed(topk, scores_all, args.steps)
    print(f"  top_k({n_anchors} anchors -> {pre}) x{b}   {dt*1e3:8.2f} ms")

    # One per-level NMS fixed point at the proposal count (the train path
    # runs one of these per FPN level per image).
    k = pre
    bx = jnp.asarray(rng.rand(b, k, 4) * 800, jnp.float32)
    bx = bx.at[..., 2:].set(bx[..., :2] + 8 + 120 * rng.rand(b, k, 2))
    post = mcfg.rpn.train_post_nms_top_n
    nms1 = jax.jit(
        lambda s: s + 0.0 * jax.vmap(
            lambda bb, ss: nms_indices(
                bb, ss, mcfg.rpn.nms_threshold, post
            )[0].astype(jnp.float32).sum()
        )(bx, s)[:, None]
    )
    sc = jnp.asarray(rng.rand(b, k), jnp.float32)
    dt = timed(nms1, sc, args.steps)
    n_lvl = len(model.feature_levels)
    print(
        f"  NMS fixed point ({k} boxes) x{b} imgs  {dt*1e3:8.2f} ms"
        f"  (train path runs {n_lvl} levels/img)"
    )


def _print_deltas(results, filtered: bool = False) -> None:
    """``filtered``: a --only run — the first surviving row has no
    predecessor, so its cumulative time is printed as an absolute (a
    '+delta' there would mislabel everything upstream of the filter as
    this stage's cost), and later rows may skip stages in between."""
    print(
        "\ndeltas vs previous stage"
        + (" (filtered: first row is ABSOLUTE; gaps possible):" if filtered else ":")
    )
    prev = None
    for name, dt in results:
        if prev is None and filtered:
            print(f"{name:32s} ={dt * 1e3:8.2f} ms (cumulative)")
        else:
            d = dt - (prev if prev is not None else 0.0)
            print(f"{name:32s} +{d * 1e3:7.2f} ms")
        prev = dt


def _backbone_breakdown(args, cfg, model, params, rest, batch) -> None:
    """One level below the step breakdown (VERDICT r4 #2): WHERE inside
    the backbone wall the time goes.

    - Trunk truncations (stem, +C2, +C3, +C4, +C5): fwd+bwd of a ResNet cut
      after each stage, with the production freeze (conv1/bn1/layer1
      stop-grad — their backward is DCE'd exactly as in the real step).
      Fresh random inits: stage timing is value-independent.
    - FrozenBN fusion A/B: the same full trunk with norm="none" (identity).
      Equal times = the multiply-add fuses into the convs (the claim in
      models/norm.py); a gap = each BN costs an HBM round trip.
    - FPN neck delta: detector.features (trunk+FPN) minus trunk alone, on
      the real variables.
    - RPN head per level: the weight-shared head applied to each pyramid
      level separately (activation bytes halve per level; P2 is the
      prime suspect).
    """
    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    from mx_rcnn_tpu.models.resnet import STAGE_BLOCKS, ResNet

    name = cfg.model.backbone.name
    if name not in STAGE_BLOCKS:
        raise SystemExit(f"--backbone supports ResNets, not {name}")
    blocks = STAGE_BLOCKS[name]
    dtype = jnp.bfloat16
    imgs = batch.images
    key = jax.random.PRNGKey(0)
    b = imgs.shape[0]

    def frozen_stopgrad(p):
        """Production freeze inside a bare trunk tree (FREEZE_PREFIXES
        minus the 'backbone/' scope)."""
        flat = traverse_util.flatten_dict(p)
        out = {
            k: (
                jax.lax.stop_gradient(v)
                if k[0] in ("conv1", "bn1") or k[0].startswith("layer1_")
                else v
            )
            for k, v in flat.items()
        }
        return traverse_util.unflatten_dict(out)

    def time_trunk(m, label):
        vs = m.init(key, imgs)
        p0 = vs["params"]
        r0 = {k: v for k, v in vs.items() if k != "params"}

        def loss(p, im):
            out = m.apply({"params": frozen_stopgrad(p), **r0}, im)
            return sum(jnp.sum(f.astype(jnp.float32) ** 2) for f in out.values())

        def grad_plus(p, im):
            # value_and_grad, with the VALUE folded into the output: the
            # stem+C2 truncation has every param frozen, so its grad is
            # constant zeros and grad alone would let XLA DCE the whole
            # forward — the row would time nothing (0.0 * val survives
            # XLA's IEEE rules like the timing chain's 0.0 * g does).
            val, g = jax.value_and_grad(loss)(p, im)
            return jax.tree_util.tree_map(
                lambda x: x + 0.0 * val.astype(x.dtype), g
            )

        dt = timed(jax.jit(grad_plus), p0, args.steps, extra=imgs)
        from mx_rcnn_tpu.utils.flops import count_matmul_flops

        fl = count_matmul_flops(grad_plus, p0, imgs)
        print(
            f"{label:34s} {dt * 1e3:8.2f} ms/step fwd+bwd"
            f"  ({fl / 1e12:5.2f} TF, {fl / dt / 1e12:5.1f} TF/s)",
            flush=True,
        )
        return dt, fl

    print(f"trunk truncations ({name}, batch {b}, {imgs.shape[1]}x{imgs.shape[2]}):")
    rows = []
    for j, label in ((1, "stem+C2"), (2, "+C3"), (3, "+C4"), (4, "+C5 (full trunk)")):
        m = ResNet(
            blocks=blocks[:j], out_levels=tuple(range(2, j + 2)),
            norm="frozen_bn", dtype=dtype,
        )
        rows.append((label, *time_trunk(m, label)))
    kind = jax.devices()[0].device_kind
    peak = peak_bf16_flops(kind)
    print(f"\nper-stage deltas (delta-MFU of {kind} bf16 peak "
          f"{peak / 1e12:.0f} TF/s):")
    prev_t = prev_f = 0.0
    for label, dt, fl in rows:
        ddt, dfl = dt - prev_t, fl - prev_f
        mfu = dfl / max(ddt, 1e-9) / peak * 100
        print(f"{label:34s} +{ddt * 1e3:7.2f} ms  ({dfl/1e12:5.2f} TF, {mfu:4.1f}% MFU)")
        prev_t, prev_f = dt, fl

    # FrozenBN fusion A/B on the full trunk.
    m_none = ResNet(blocks=blocks, out_levels=(2, 3, 4, 5), norm="none", dtype=dtype)
    dt_none, _ = time_trunk(m_none, "full trunk, norm=none (A/B)")
    dt_bn = rows[-1][1]
    print(
        f"FrozenBN cost across the trunk: {(dt_bn - dt_none) * 1e3:+.2f} ms "
        f"({'fused/free' if abs(dt_bn - dt_none) < 0.05 * dt_bn else 'NOT free'})"
    )
    m_fold = ResNet(
        blocks=blocks, out_levels=(2, 3, 4, 5), norm="frozen_bn",
        fold_bn=True, dtype=dtype,
    )
    dt_fold, _ = time_trunk(m_fold, "full trunk, fold_bn=true (A/B)")
    print(f"fold_bn recovers: {(dt_bn - dt_fold) * 1e3:+.2f} ms of the BN cost")

    # FPN neck + per-level RPN head on the real model/variables.
    v = {"params": params, **rest}
    feats = jax.jit(
        lambda vv, im: model.apply(vv, im, method="features")
    )(v, imgs)
    feats = jax.device_put(feats)

    def feats_loss(p, im):
        out = model.apply({"params": p, **rest}, im, method="features")
        return sum(jnp.sum(f.astype(jnp.float32) ** 2) for f in out.values())

    # Freeze via the production mask: loop.FREEZE_PREFIXES paths.
    from mx_rcnn_tpu.train.loop import FREEZE_PREFIXES
    from mx_rcnn_tpu.train.optim import frozen_mask

    mask = frozen_mask(params, FREEZE_PREFIXES.get(name, ()))

    def masked(p):
        return jax.tree_util.tree_map(
            lambda x, t: x if t else jax.lax.stop_gradient(x), p, mask
        )

    grad_feats = jax.jit(lambda p, im: jax.grad(
        lambda pp, i: feats_loss(masked(pp), i)
    )(p, im))
    dt_feats = timed(grad_feats, params, args.steps, extra=imgs)
    print(
        f"\n{'features (trunk+FPN neck)':34s} {dt_feats * 1e3:8.2f} ms/step"
        f"  (FPN delta vs trunk: {(dt_feats - dt_bn) * 1e3:+.2f} ms)"
    )

    levels = sorted(feats)
    for lvls in [levels] + [[l] for l in levels]:
        sub = {l: feats[l] for l in lvls}

        def rpn_loss(p, ft):
            out = model.apply({"params": p, **rest}, ft, method="rpn")
            return sum(
                jnp.sum(o.astype(jnp.float32) ** 2)
                for pair in out.values() for o in pair
            )

        grad_rpn = jax.jit(lambda p, ft: jax.grad(rpn_loss)(p, ft))
        dt = timed(grad_rpn, params, args.steps, extra=sub)
        tag = "all levels" if len(lvls) > 1 else f"P{lvls[0]} only"
        print(f"{'rpn head ' + tag:34s} {dt * 1e3:8.2f} ms/step fwd+bwd")


def _infer_breakdown(args, model, params, rest, batch, mcfg) -> None:
    """Ablation timing of forward_inference (the eval path), forward only.

    Stages: backbone features -> +RPN/proposal gen -> +ROIAlign+box head ->
    full inference (softmax, per-class decode, per-class NMS, global
    top-D).  The chain carry is the image tensor (every stage reads it), so
    each scanned step provably depends on the previous one."""
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.detection import forward_inference
    from mx_rcnn_tpu.detection.graph import (
        _pool_rois,
        _postprocess_one,
        _propose_on_features,
    )

    v = {"params": params, **rest}

    def front(imgs, upto: str):
        bt = batch._replace(images=imgs)
        feats = model.apply(v, imgs, method="features")
        if upto == "features":
            s = sum(jnp.sum(f.astype(jnp.float32) ** 2) for f in feats.values())
            return imgs * 0.0 + s
        props = _propose_on_features(model, v, feats, bt)
        if upto == "proposals":
            return imgs * 0.0 + (jnp.sum(props.rois) + jnp.sum(props.scores))
        pooled = _pool_rois(
            mcfg, feats, props.rois, mcfg.rcnn.pooled_size, model.roi_levels
        )
        ps = mcfg.rcnn.pooled_size
        cls_logits, box_deltas = model.apply(
            v, pooled.reshape(-1, ps, ps, pooled.shape[-1]), method="box"
        )
        if upto == "boxhead":
            s = jnp.sum(cls_logits.astype(jnp.float32) ** 2) + jnp.sum(
                box_deltas.astype(jnp.float32) ** 2
            )
            return imgs * 0.0 + s
        raise ValueError(upto)

    def full(imgs):
        dets = forward_inference(model, v, batch._replace(images=imgs))
        return imgs * 0.0 + (jnp.sum(dets.boxes) + jnp.sum(dets.scores))

    b = batch.images.shape[0]
    stages = [
        ("backbone features", lambda im: front(im, "features")),
        ("+rpn + proposal gen", lambda im: front(im, "proposals")),
        ("+roialign + box head", lambda im: front(im, "boxhead")),
        ("full inference (+postprocess)", full),
    ]
    results = []
    for name, fn in stages:
        dt = timed(jax.jit(fn), batch.images, args.steps)
        results.append((name, dt))
        print(
            f"{name:32s} {dt * 1e3:8.2f} ms/batch  "
            f"({b / dt:6.1f} img/s)", flush=True
        )
    print("\ndeltas vs previous stage:")
    prev = None
    for name, dt in results:
        d = dt - (prev if prev is not None else 0.0)
        print(f"{name:32s} +{d * 1e3:7.2f} ms")
        prev = dt

    # Standalone postprocess at eval shapes: R rois x (C-1) classes, NMS per
    # class, global top-D — vmapped over the batch like the real path.
    import numpy as np

    rng = np.random.RandomState(7)
    r = mcfg.rpn.test_post_nms_top_n
    c = mcfg.num_classes
    rois = np.asarray(rng.rand(b, r, 4) * 700, np.float32)
    rois[..., 2:] += 16 + 150 * rng.rand(b, r, 2).astype(np.float32)
    probs = jnp.asarray(rng.dirichlet(np.ones(c), size=(b, r)), jnp.float32)
    deltas = jnp.asarray(
        rng.randn(b, r, 1 if mcfg.rcnn.class_agnostic else c, 4) * 0.1,
        jnp.float32,
    )
    rv = jnp.ones((b, r), bool)
    hw = batch.image_hw

    from mx_rcnn_tpu.detection.graph import _postprocess_one_fused

    for mode, fn in (
        ("per_class", _postprocess_one),
        ("fused", _postprocess_one_fused),
    ):
        def post(pr, fn=fn):
            out = jax.vmap(
                lambda ro, rv_, p, d, hw_: fn(mcfg, ro, rv_, p, d, hw_)
            )(jnp.asarray(rois), rv, pr, deltas, hw)
            return pr * 0.0 + (jnp.sum(out[0]) + jnp.sum(out[1]))

        dt = timed(jax.jit(post), probs, args.steps)
        star = " <- config default" if mcfg.test.nms_mode == mode else ""
        print(
            f"\nstandalone postprocess[{mode}] ({r} rois x {c - 1} classes) "
            f"x{b}: {dt * 1e3:8.2f} ms{star}"
        )


if __name__ == "__main__":
    main()
