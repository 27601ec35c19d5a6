"""Evaluation driver.

Parity with ``test.py`` → ``rcnn/core/tester.py::pred_eval`` (SURVEY.md
§4.3): restore checkpoint, run the jitted inference graph over the val
split, score with the dataset evaluator (COCO mAP@[.5:.95] or VOC AP).
``--proposals`` runs the RPN-only path and dumps proposals instead
(``rcnn/tools/test_rpn.py`` parity).
"""

from __future__ import annotations

import argparse
import logging
import pickle
from typing import Optional

from mx_rcnn_tpu.cli.common import (
    add_config_args,
    config_from_args,
    setup_logging,
    submission_imageset,
)
from mx_rcnn_tpu.config import Config

log = logging.getLogger("mx_rcnn_tpu.eval")

# Mirrored from train.preemption.RESUMABLE_EXIT_CODE without importing it
# at module scope (parse_args must not drag in jax).
_RESUMABLE_CODE = 75


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__)
    add_config_args(p)
    p.add_argument("--ckpt", default=None, help="checkpoint dir (default: workdir)")
    p.add_argument("--step", type=int, default=None, help="checkpoint step")
    p.add_argument(
        "--dump", default=None, help="write raw detections here (reeval input)"
    )
    p.add_argument(
        "--dump-coco", default=None, metavar="RESULTS.JSON",
        help="also write a COCO results json in ORIGINAL (sparse 91-space) "
        "category ids — the format the COCO server and stock pycocotools "
        "loadRes score (reference coco.py evaluate_detections parity)",
    )
    p.add_argument(
        "--dump-voc", default=None, metavar="DIR",
        help="also write VOC comp4 per-class det files into DIR "
        "(reference pascal_voc.py det-file-writer parity)",
    )
    p.add_argument(
        "--proposals",
        default=None,
        metavar="OUT.PKL",
        help="dump RPN proposals per image instead of evaluating (test_rpn parity)",
    )
    p.add_argument(
        "--from-proposals",
        default=None,
        metavar="IN.PKL",
        help="score this external proposal pkl instead of running the RPN "
        "(Fast R-CNN testing; reference: test_rcnn --has_rpn false)",
    )
    p.add_argument(
        "--proposals-split",
        choices=("train", "val"),
        default=None,
        help="which split --proposals dumps (default val; train: the Fast "
        "R-CNN training input; reference rpn.generate over TRAIN.dataset)",
    )
    p.add_argument(
        "--use-07-metric",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="VOC 11-point AP metric (default: on for VOC2007 test splits, "
        "matching the reference's use_07_metric choice; off otherwise)",
    )
    p.add_argument(
        "--vis", type=int, default=0, metavar="N",
        help="draw the first N evaluated images with detections into "
        "<workdir>/<config>/vis (reference pred_eval vis=True parity)",
    )
    p.add_argument(
        "--resumable", action="store_true",
        help="preemption-safe evaluation: per-shard detection checkpoints "
        "under --shard-dir, SIGTERM flushes the in-flight shard and exits "
        f"{_RESUMABLE_CODE} for the supervisor to re-run with --resume",
    )
    p.add_argument(
        "--shard-dir", default=None, metavar="DIR",
        help="where shard files + manifest live (implies --resumable; "
        "default <workdir>/<config>/eval_shards)",
    )
    p.add_argument(
        "--shard-size", type=int, default=8, metavar="N",
        help="eval batches per shard checkpoint (default 8)",
    )
    p.add_argument(
        "--shard-retries", type=int, default=1, metavar="N",
        help="retries per failed shard before giving up (default 1)",
    )
    p.add_argument(
        "--resume", action="store_true",
        help="skip shards already on disk (schedule fingerprint checked)",
    )
    p.add_argument(
        "--limit", type=int, default=None, metavar="N",
        help="evaluate only the first N images (smoke/chaos runs)",
    )
    return p.parse_args(argv)


def _eval_loader(
    cfg: Config,
    batch_size: int = 1,
    with_masks: bool = False,
    proposals_path: Optional[str] = None,
    limit: Optional[int] = None,
):
    from mx_rcnn_tpu.data import DetectionLoader, build_dataset, load_proposals

    import jax

    proposals = load_proposals(proposals_path) if proposals_path else None
    dataset = build_dataset(cfg.data, train=False)
    roidb = dataset.roidb()
    if limit is not None:
        # Smoke/chaos runs: evaluate a prefix of the split.  The metric
        # roidb is sliced identically so absent images don't score as
        # misses.
        roidb = roidb[:limit]
    loader = DetectionLoader(
        roidb, cfg.data, batch_size=batch_size, train=False,
        with_masks=with_masks,
        proposals=proposals,
        num_proposals=cfg.model.rpn.test_post_nms_top_n,
        # Eval keeps the full roidb everywhere; rank/world shard each
        # global batch for lockstep multi-host iteration (loader docs).
        rank=jax.process_index(),
        world=jax.process_count(),
        # Same rot-tolerance contract as training: unreadable images are
        # quarantined + blank-substituted, never a crashed eval.
        num_classes=cfg.model.num_classes,
        quarantine_path=(
            f"{cfg.workdir}/{cfg.name}/quarantine.jsonl"
            if cfg.workdir else None
        ),
    )
    return dataset, roidb, loader


def _restored_state(cfg: Config, ckpt_dir: Optional[str], step: Optional[int]):
    import jax

    from mx_rcnn_tpu.train.checkpoint import restore_checkpoint
    from mx_rcnn_tpu.train.loop import build_all

    # restore_checkpoint only needs the target's tree structure and
    # shapes/dtypes, so build it under eval_shape: no parameter is ever
    # initialised on device just to be thrown away.
    def make_state():
        _, _, state, _, _ = build_all(cfg, mesh=None)
        return state

    abstract = jax.eval_shape(make_state)
    ckpt = ckpt_dir or f"{cfg.workdir}/{cfg.name}/ckpt"
    return restore_checkpoint(ckpt, abstract, step=step)


def run_eval(
    cfg: Config,
    state=None,
    ckpt_dir: Optional[str] = None,
    step: Optional[int] = None,
    dump_path: Optional[str] = None,
    use_07_metric: Optional[bool] = None,
    vis_count: int = 0,
    proposals_path: Optional[str] = None,
    coco_results_path: Optional[str] = None,
    voc_dets_dir: Optional[str] = None,
    shard_dir: Optional[str] = None,
    shard_size: int = 8,
    resume: bool = False,
    shard_retries: int = 1,
    limit: Optional[int] = None,
) -> dict:
    """Evaluate a state (or a restored checkpoint) on the config's val split.

    ``use_07_metric`` None = auto: the 11-point metric for VOC2007 test
    splits (the reference evaluates VOC07 with use_07_metric=True), the
    area metric otherwise.

    ``proposals_path``: score an external proposal pkl instead of running
    the RPN (reference ``test_rcnn --has_rpn false`` Fast R-CNN testing).

    ``shard_dir`` switches to preemption-safe sharded evaluation
    (docs/serving.md): per-shard detection checkpoints, ``resume`` skipping
    completed shards, SIGTERM/SIGINT draining the in-flight shard and
    raising ``Preempted`` (the CLI maps it to exit 75).  Single-process
    only."""
    import jax

    from mx_rcnn_tpu.cli.common import default_use_07_metric

    if use_07_metric is None:
        use_07_metric = default_use_07_metric(cfg)

    from mx_rcnn_tpu.detection import TwoStageDetector
    from mx_rcnn_tpu.evalutil import pred_eval
    from mx_rcnn_tpu.parallel import make_mesh, replicated
    from mx_rcnn_tpu.parallel.step import eval_variables, make_eval_step

    if state is None:
        state = _restored_state(cfg, ckpt_dir, step)
    state = jax.device_get(state)
    # ALL visible chips evaluate in data parallel, test.per_device_batch
    # images per chip per step (the reference's test path is strictly
    # single-device, one image at a time).  Multi-host runs shard each
    # GLOBAL batch by process rank in the loader (lockstep schedule from
    # the full roidb), assemble global arrays via shard_batch, and gather
    # the tiny Detections to every host so each computes the full metric
    # (artifacts are written by process 0 only — see pred_eval).
    mesh = make_mesh() if jax.device_count() > 1 else None
    multiproc = jax.process_count() > 1
    model = TwoStageDetector(cfg=cfg.model)
    eval_step = make_eval_step(
        model, mesh=mesh, gather_outputs=multiproc,
        pixel_stats=(cfg.data.pixel_mean, cfg.data.pixel_std),
    )
    # Pin the inference params on device ONCE.  Feeding the numpy pytree
    # into the jitted step would re-upload every parameter (~100 MB) on
    # every call (the r1 CLI had exactly this bug).
    variables = eval_variables(state)
    variables = (
        jax.device_put(variables, replicated(mesh))
        if mesh is not None
        else jax.device_put(variables)
    )
    per_chip = max(cfg.model.test.per_device_batch, 1)
    dataset, roidb, loader = _eval_loader(
        cfg,
        batch_size=(mesh.size if mesh is not None else 1) * per_chip,
        proposals_path=proposals_path,
        limit=limit,
    )
    style = "voc" if cfg.data.dataset == "voc" else "coco"
    class_names = None
    if cfg.data.dataset == "voc":
        from mx_rcnn_tpu.data.datasets import VOC_CLASSES

        class_names = ("__background__",) + VOC_CLASSES
    elif voc_dets_dir:
        # comp4 files are per-class-NAME; non-VOC datasets use their own.
        class_names = tuple(getattr(dataset, "classes", ()))
    if voc_dets_dir and len(class_names or ()) <= 1:
        # write_submission_artifacts raises the same complaint, but only
        # AFTER pred_eval's full inference pass (and only on the artifact-
        # writing process) — minutes of eval discarded by an error that is
        # knowable right here.  Fail up-front, on every host.
        raise ValueError(
            "--dump-voc needs foreground class names; the dataset "
            f"exposes {tuple(class_names or ())!r} — comp4 det files "
            "are per-class-NAME"
        )
    # COCO submissions must carry the ORIGINAL sparse category ids; only
    # the real CocoDataset has the mapping (synthetic/custom ids are
    # already dense → identity).
    label_to_cat = (
        getattr(dataset, "label_to_cat", None) if coco_results_path else None
    )
    import contextlib

    from mx_rcnn_tpu.train.preemption import PreemptionGuard

    # The guard turns SIGTERM/SIGINT into a shard-boundary drain; without
    # sharding there is no safe boundary to drain to, so don't install it.
    guard_cm = PreemptionGuard() if shard_dir else contextlib.nullcontext()
    with guard_cm as guard:
        metrics = pred_eval(
            eval_step,
            variables,
            loader,
            roidb,
            cfg.model.num_classes,
            style=style,
            class_names=class_names,
            use_07_metric=use_07_metric,
            dump_path=dump_path,
            vis_dir=f"{cfg.workdir}/{cfg.name}/vis" if vis_count > 0 else None,
            vis_count=vis_count,
            mesh=mesh,
            coco_results_path=coco_results_path,
            label_to_cat=label_to_cat,
            voc_dets_dir=voc_dets_dir,
            voc_imageset=submission_imageset(cfg),
            shard_dir=shard_dir,
            shard_size=shard_size,
            resume=resume,
            shard_retries=shard_retries,
            guard=guard,
        )
    for k, v in sorted(metrics.items()):
        log.info("%s = %.4f", k, v)
    return metrics


def dump_proposals(
    cfg: Config,
    out_path: str,
    state=None,
    ckpt_dir: Optional[str] = None,
    step: Optional[int] = None,
    train_split: bool = True,
    use_train_counts: Optional[bool] = None,
) -> dict:
    """Run the RPN over a split and dump per-image proposal boxes+scores.

    The alternate-training bridge: phase N's RPN writes the proposal roidb
    consumed by phase N+1's Fast R-CNN training (SURVEY.md §4.2 steps 2/5).

    ``use_train_counts`` (default: follows ``train_split``): generate the
    TRAIN-config proposal counts (pre/post-NMS top-n, e.g. 2000) instead of
    the test counts (e.g. 300) — proposals destined for Fast R-CNN
    *training* must match the reference's TRAIN.RPN_POST_NMS_TOP_N pool,
    not the test pool.

    Runs batched over every visible chip (the same loader/mesh machinery
    as ``run_eval``, ``test.per_device_batch`` images per chip per step):
    a COCO train-split dump is minutes, not the hours the old
    one-image-one-chip loop took (VERDICT r2 #7).
    """
    import dataclasses

    import jax
    import numpy as np

    from mx_rcnn_tpu.data import DetectionLoader, build_dataset
    from mx_rcnn_tpu.detection import TwoStageDetector, forward_proposals
    from mx_rcnn_tpu.evalutil.pred_eval import device_eval_batches
    from mx_rcnn_tpu.parallel import make_mesh, replicated
    from mx_rcnn_tpu.parallel.step import eval_variables, make_sharded_infer

    if state is None:
        state = _restored_state(cfg, ckpt_dir, step)
    state = jax.device_get(state)
    if use_train_counts is None:
        use_train_counts = train_split
    if use_train_counts:
        # forward_proposals runs the test-config proposal path; give it the
        # train counts so the dumped pool matches what training samples.
        cfg = dataclasses.replace(
            cfg,
            model=dataclasses.replace(
                cfg.model,
                rpn=dataclasses.replace(
                    cfg.model.rpn,
                    test_pre_nms_top_n=cfg.model.rpn.train_pre_nms_top_n,
                    test_post_nms_top_n=cfg.model.rpn.train_post_nms_top_n,
                ),
            ),
        )
    model = TwoStageDetector(cfg=cfg.model)
    mesh = make_mesh() if jax.device_count() > 1 else None
    multiproc = jax.process_count() > 1
    # Device-resident params: see run_eval — numpy params re-upload per call.
    variables = eval_variables(state)
    variables = (
        jax.device_put(variables, replicated(mesh))
        if mesh is not None
        else jax.device_put(variables)
    )
    stats = (cfg.data.pixel_mean, cfg.data.pixel_std)
    prop_step = make_sharded_infer(
        lambda v, b: forward_proposals(model, v, b, pixel_stats=stats),
        mesh, gather_outputs=multiproc,
    )

    per_chip = max(cfg.model.test.per_device_batch, 1)
    data_cfg = cfg.data
    split = data_cfg.train_split if train_split else data_cfg.val_split
    roidb = build_dataset(dataclasses.replace(data_cfg, val_split=split), train=False).roidb()
    loader = DetectionLoader(
        roidb, data_cfg,
        batch_size=(mesh.size if mesh is not None else 1) * per_chip,
        train=False,
        rank=jax.process_index(),
        world=jax.process_count(),
    )
    out: dict[str, dict] = {}
    for batch, recs in device_eval_batches(loader, mesh):
        props = jax.device_get(prop_step(variables, batch))
        for i, rec in enumerate(recs):
            scale = loader.record_scale(rec)
            valid = np.asarray(props.valid[i])
            out[rec.image_id] = {
                "boxes": np.asarray(props.rois[i])[valid] / scale,
                "scores": np.asarray(props.scores[i])[valid],
            }
    from mx_rcnn_tpu.parallel.distributed import is_primary

    if is_primary():
        with open(out_path, "wb") as f:
            pickle.dump(out, f)
        log.info("wrote %d images' proposals to %s", len(out), out_path)
    return out


def main(argv=None) -> dict:
    args = parse_args(argv)
    setup_logging(args.verbose)
    cfg = config_from_args(args)
    if args.proposals and args.from_proposals:
        raise SystemExit(
            "--proposals (dump) and --from-proposals (score) are exclusive"
        )
    if args.proposals_split and not args.proposals:
        raise SystemExit("--proposals-split only applies with --proposals")
    if args.proposals and (args.resumable or args.shard_dir or args.resume):
        raise SystemExit("--proposals does not support sharded/resumable mode")
    if args.resume and not (args.resumable or args.shard_dir):
        raise SystemExit("--resume requires --resumable (or --shard-dir)")
    from mx_rcnn_tpu.utils.compile_cache import configure_cache

    configure_cache()
    if args.proposals:
        return dump_proposals(
            cfg, args.proposals, ckpt_dir=args.ckpt, step=args.step,
            train_split=args.proposals_split == "train",
        )
    shard_dir = args.shard_dir
    if args.resumable and not shard_dir:
        shard_dir = f"{cfg.workdir}/{cfg.name}/eval_shards"
    return run_eval(
        cfg,
        ckpt_dir=args.ckpt,
        step=args.step,
        dump_path=args.dump,
        use_07_metric=args.use_07_metric,
        vis_count=args.vis,
        proposals_path=args.from_proposals,
        coco_results_path=args.dump_coco,
        voc_dets_dir=args.dump_voc,
        shard_dir=shard_dir,
        shard_size=args.shard_size,
        resume=args.resume,
        shard_retries=args.shard_retries,
        limit=args.limit,
    )


def cli(argv=None) -> int:
    """Console-script entry point ([project.scripts]).  ``main`` returns
    its result dict for programmatic callers; returning that from a
    console script would make ``sys.exit`` treat the truthy dict as a
    FAILURE exit status, so discard it and return 0 explicitly.

    A preemption during --resumable eval exits with the distinct
    RESUMABLE_EXIT_CODE after the in-flight shard lands, so supervisors
    can tell "requeue with --resume" from a real failure."""
    from mx_rcnn_tpu.train.preemption import RESUMABLE_EXIT_CODE, Preempted

    try:
        main(argv)
    except Preempted as p:
        log.warning(
            "eval preempted after shard %d (shards in %s); exiting %d — "
            "requeue with --resume", p.step, p.ckpt_dir, RESUMABLE_EXIT_CODE,
        )
        return RESUMABLE_EXIT_CODE
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(cli())
