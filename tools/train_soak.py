"""Long-run training soak on real hardware (VERDICT r4 #1).

Trains the flagship R50-FPN at the recipe canvas (800x1344) on synthetic
uint8 data for thousands of optimizer steps — through warmup and two
lr-decay boundaries, with a mid-run stop + checkpoint resume — then
evaluates the final state.  This exercises exactly the paths no short
bench or test touches as one continuous run (the reference's analog is
``MutableModule.fit``'s epoch loop over a real schedule, SURVEY.md §3.7):

- schedule dynamics at scale (warmup -> plateau -> two decays);
- bf16 numerical stability over thousands of optimizer steps;
- the checkpoint-every-N branch of the production train loop;
- loader epoch wraparound under run_length grouping (hundreds of images,
  many epochs);
- resume continuity mid-run (phase B restores phase A's checkpoint and
  fast-forwards the data schedule);
- the train -> eval handoff at recipe resolution.

The dataset is the 81-class synthetic renderer in uint8 form, so the
trained program is bit-for-bit the flagship r50_fpn_coco train step
(same class count, same canvas, same dtype path as real COCO training).
Since r4 the renderer uses the "wheel" palette (all 80 classes visually
distinct); the first r4 soak ran the "classic" ramp, whose color
saturation above class ~8 capped absolute AP at 0.128 by construction.
The gates are "loss decreased substantially", "every logged metric
finite", "lr boundaries visible", and "eval AP clears an
untrained-model floor".

Usage:  python tools/train_soak.py [--steps 3000] [--resume-at 1600]
                             [--images 400] [--workdir runs/soak]
Prints one JSON summary line on stdout; diagnostics on stderr.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_soak_config(steps: int, workdir: str, preset: str = "r50_fpn_coco"):
    from mx_rcnn_tpu.config import ScheduleConfig, get_config

    cfg = get_config(preset)
    # Absolute step schedule (reference_batch=0: no epoch rescale — the
    # soak pins exact boundaries) with warmup and two decays inside the
    # run.  lr still scales by global_batch/16 = 2/16, i.e. base 0.02 ->
    # 0.0025 at per-chip batch 2, the linear-scaling value real training
    # would use on one chip.
    sched = ScheduleConfig(
        base_lr=0.02,
        warmup_steps=500,
        warmup_factor=1.0 / 3.0,
        decay_steps=(steps // 2, steps * 5 // 6),
        factor=0.1,
        total_steps=steps,
        reference_batch=0,
    )
    return dataclasses.replace(
        cfg,
        name=f"{preset}_soak",
        workdir=workdir,
        data=dataclasses.replace(cfg.data, dataset="synthetic", max_gt_boxes=32),
        train=dataclasses.replace(
            cfg.train,
            per_device_batch=2,
            steps_per_call=10,
            schedule=sched,
            checkpoint_every=1000,
            log_every=20,
        ),
    )


def make_roidb(cfg, num_images: int, seed: int = 1):
    from mx_rcnn_tpu.data import SyntheticDataset

    return SyntheticDataset(
        num_images=num_images,
        image_hw=cfg.data.image_size,
        num_classes=cfg.model.num_classes,
        max_objects=8,
        seed=seed,
        dtype="uint8",
        # All 80 classes visually distinct (golden-ratio hue + texture
        # combos) — the classic ramp saturates above class ~8 and capped
        # the r4 soak's absolute AP at 0.128 by renderer design, not by
        # anything the detector did.
        palette="wheel",
    ).roidb()


def make_loader(cfg, roidb, batch_size: int):
    from mx_rcnn_tpu.data import DetectionLoader

    return DetectionLoader(
        roidb,
        cfg.data,
        batch_size=batch_size,
        train=True,
        seed=cfg.train.seed,
        run_length=max(cfg.train.steps_per_call, 1),
        # Mask presets need gt masks rasterized (the synthetic roidb
        # carries octagon polygons) — same wiring train/loop.py uses.
        with_masks=cfg.model.mask.enabled,
    )


def final_eval(cfg, state, roidb):
    """Evaluate the trained state over a slice of the soak set (train-set
    AP: the learning signal the soak gates on).  Mirrors run_eval's body
    with an explicit loader because build_dataset's synthetic default is
    the 5-class float set, not the soak's 81-class uint8 one."""
    import jax

    from mx_rcnn_tpu.data import DetectionLoader
    from mx_rcnn_tpu.detection import TwoStageDetector
    from mx_rcnn_tpu.evalutil import pred_eval
    from mx_rcnn_tpu.parallel.step import eval_variables, make_eval_step

    model = TwoStageDetector(cfg=cfg.model)
    eval_step = make_eval_step(
        model, mesh=None,
        pixel_stats=(cfg.data.pixel_mean, cfg.data.pixel_std),
    )
    variables = jax.device_put(eval_variables(jax.device_get(state)))
    loader = DetectionLoader(
        roidb, cfg.data,
        batch_size=max(cfg.model.test.per_device_batch, 1),
        train=False,
    )
    return pred_eval(
        eval_step, variables, loader, roidb, cfg.model.num_classes,
        style="coco",
    )


def summarize_metrics(path: str, decay_steps) -> dict:
    """Parse metrics.jsonl: finiteness, loss trajectory, lr boundaries."""
    rows = []
    with open(path) as f:
        for line in f:
            rows.append(json.loads(line))
    assert rows, f"{path} is empty"
    nonfinite = []
    for r in rows:
        for k, v in r.items():
            if isinstance(v, float) and not math.isfinite(v):
                nonfinite.append((r.get("step"), k, v))
    by_step = {r["step"]: r for r in rows}

    def lr_near(step, side):
        """lr at the last log <= step (side=before) / first > (after)."""
        steps_logged = sorted(by_step)
        cands = [s for s in steps_logged if (s <= step if side == "before" else s > step)]
        if not cands:
            return None
        s = cands[-1] if side == "before" else cands[0]
        return by_step[s].get("lr")

    losses = [r["loss"] for r in rows if "loss" in r]
    k = max(len(losses) // 20, 1)
    return {
        "logged_rows": len(rows),
        "nonfinite_count": len(nonfinite),
        "nonfinite_first": nonfinite[:3],
        "first_loss": losses[0],
        "mean_first_5pct": sum(losses[:k]) / k,
        "mean_last_5pct": sum(losses[-k:]) / k,
        "last_loss": losses[-1],
        "lr_around_decays": {
            str(d): (lr_near(d, "before"), lr_near(d, "after"))
            for d in decay_steps
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=3000)
    ap.add_argument(
        "--resume-at", type=int, default=1600,
        help="stop phase A here; phase B restores the checkpoint and "
        "continues to --steps (0 disables the resume exercise)",
    )
    ap.add_argument("--images", type=int, default=400)
    ap.add_argument("--workdir", default="runs/soak")
    ap.add_argument("--eval-images", type=int, default=96)
    ap.add_argument(
        "--config", default="r50_fpn_coco",
        help="config preset to soak (e.g. mask_r50_fpn_coco — the mask "
        "branch then trains and checkpoints through the whole run)",
    )
    args = ap.parse_args()
    if args.resume_at and not 0 < args.resume_at < args.steps:
        # Catch this up front: phase A training past the schedule would
        # only surface as an assert after the whole run's chip time.
        ap.error(
            f"--resume-at {args.resume_at} must lie strictly inside "
            f"(0, --steps {args.steps}); pass --resume-at 0 to disable "
            "the resume exercise"
        )

    import jax

    # Persistent compile cache: repeat soak invocations (smoke run, then
    # the real run) skip the multi-minute step compile.
    from mx_rcnn_tpu.utils.compile_cache import configure_cache

    configure_cache()

    from mx_rcnn_tpu.cli.common import setup_logging
    from mx_rcnn_tpu.train.loop import train

    setup_logging(True)
    cfg = build_soak_config(args.steps, args.workdir, preset=args.config)
    # A previous run's checkpoints would hijack phase B's resume (it
    # restores the LATEST step — a stale step-3000 checkpoint makes phase
    # B a no-op and the PASS gate score the old params).  Refuse rather
    # than silently wipe.
    from mx_rcnn_tpu.train.checkpoint import latest_step

    ckpt_dir = os.path.join(args.workdir, cfg.name, "ckpt")
    stale = latest_step(ckpt_dir)
    if stale is not None:
        raise SystemExit(
            f"{ckpt_dir} already holds a run (latest step {stale}); delete "
            "it or pass a fresh --workdir — phase B's resume would restore "
            "it instead of this run's phase A"
        )
    global_batch = cfg.train.per_device_batch  # single chip
    t0 = time.perf_counter()
    print(
        f"rendering {args.images} synthetic {cfg.data.image_size} uint8 "
        f"images ({cfg.model.num_classes} classes)...",
        file=sys.stderr,
    )
    roidb = make_roidb(cfg, args.images)
    print(f"rendered in {time.perf_counter() - t0:.0f}s", file=sys.stderr)

    epochs = args.steps * global_batch / args.images
    print(
        f"soak: {args.steps} steps x batch {global_batch} over "
        f"{args.images} images = {epochs:.1f} epochs; decays at "
        f"{cfg.train.schedule.decay_steps}, resume exercise at "
        f"{args.resume_at}, checkpoints every "
        f"{cfg.train.checkpoint_every}",
        file=sys.stderr,
    )

    t_train0 = time.perf_counter()
    if args.resume_at:
        train(
            cfg, total_steps=args.resume_at, workdir=args.workdir,
            loader=make_loader(cfg, roidb, global_batch),
        )
        print(
            f"phase A done at step {args.resume_at} "
            f"({time.perf_counter() - t_train0:.0f}s); resuming...",
            file=sys.stderr,
        )
    state = train(
        cfg, total_steps=args.steps, workdir=args.workdir, resume=True,
        loader=make_loader(cfg, roidb, global_batch),
    )
    t_train = time.perf_counter() - t_train0
    assert int(jax.device_get(state.step)) == args.steps

    metrics = final_eval(cfg, state, roidb[: args.eval_images])
    summary = summarize_metrics(
        os.path.join(args.workdir, cfg.name, "metrics.jsonl"),
        cfg.train.schedule.decay_steps,
    )
    ckpts = sorted(
        os.listdir(os.path.join(args.workdir, cfg.name, "ckpt"))
    )
    out = {
        "steps": args.steps,
        "resume_at": args.resume_at,
        "images": args.images,
        "epochs": round(epochs, 1),
        "train_seconds": round(t_train, 1),
        "img_per_sec": round(args.steps * global_batch / t_train, 2),
        "checkpoints": ckpts,
        "eval": {k: round(float(v), 4) for k, v in metrics.items()},
        **summary,
    }
    print(json.dumps(out))
    # Loss gate against the FIRST logged loss, not the first-5% mean: the
    # steepest descent happens inside the first log window (r4 run: 2.11
    # at step 10, ~1.0 by step 150), so a windowed-mean ratio understates
    # a perfectly healthy curve.  AP floor: see the inline rationale on
    # the gate below (untrained is < 0.001).
    ok = (
        summary["nonfinite_count"] == 0
        and summary["mean_last_5pct"] < 0.6 * summary["first_loss"]
        # Wheel-palette floor: the r4b run read AP 0.556 (classic-ramp
        # runs read 0.128 — renderer-capped); 0.25 catches a real
        # learning regression without pinning a chaotic synthetic value.
        and metrics.get("AP", 0.0) > 0.25
        # Mask presets must also gate the mask head: a segm regression to
        # zero with a healthy box head would otherwise still PASS.  Floor
        # is below the r4b run's 0.2573 by the same margin logic as box.
        and (
            not cfg.model.mask.enabled
            or metrics.get("segm/AP", 0.0) > 0.12
        )
    )
    print(f"SOAK {'PASS' if ok else 'FAIL'}", file=sys.stderr)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
