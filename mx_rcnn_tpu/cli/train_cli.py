"""End-to-end training driver.

Parity with ``train_end2end.py`` (SURVEY.md §3.1/§4.1): config + overrides →
mesh → train loop with metrics/checkpoints, optional resume, optional final
evaluation pass.  The kvstore/ctx-list plumbing of the reference is replaced
by the device mesh (all visible chips by default).
"""

from __future__ import annotations

import argparse
import logging

from mx_rcnn_tpu.cli.common import add_config_args, config_from_args, setup_logging

log = logging.getLogger("mx_rcnn_tpu.train")


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__)
    add_config_args(p)
    p.add_argument("--resume", action="store_true", help="resume from workdir ckpt")
    p.add_argument(
        "--strict-resume", action="store_true",
        help="fail (instead of warn) when the resumed config drifts from "
        "the workdir's recorded config.json",
    )
    p.add_argument(
        "--steps", type=int, default=None, help="override schedule total_steps"
    )
    p.add_argument(
        "--no-eval", action="store_true", help="skip the final evaluation pass"
    )
    p.add_argument(
        "--profile", default=None, metavar="DIR",
        help="write a jax.profiler trace of steps 10-15 to DIR",
    )
    p.add_argument(
        "--pretrained", default=None, metavar="PTH",
        help="torchvision-style ResNet .pth to seed the backbone "
        "(reference: --pretrained imagenet params)",
    )
    p.add_argument(
        "--proposals", default=None, metavar="PKL",
        help="train the box head on this external proposal pkl (from "
        "test.py --proposals) instead of in-graph RPN proposals — Fast "
        "R-CNN mode (reference: train_rcnn.py/ROIIter).  Pair with --set "
        "model.rpn.loss_weight=0 to drop the RPN from the graph entirely",
    )
    return p.parse_args(argv)


def main(argv=None) -> dict:
    args = parse_args(argv)
    setup_logging(args.verbose)
    cfg = config_from_args(args)

    import jax

    from mx_rcnn_tpu.parallel import initialize, make_mesh
    from mx_rcnn_tpu.train.loop import train
    from mx_rcnn_tpu.utils.compile_cache import configure_cache

    initialize()  # multi-host runtime (no-op single-process)
    configure_cache()  # after initialize(): this starts the backend
    mesh = (
        make_mesh(model_parallel=cfg.train.spatial_partition)
        if jax.device_count() > 1
        else None
    )
    n_dev = mesh.size if mesh is not None else 1
    log.info(
        "config=%s devices=%d backend=%s", cfg.name, n_dev, jax.default_backend()
    )
    state = train(
        cfg,
        mesh=mesh,
        total_steps=args.steps,
        workdir=cfg.workdir,
        resume=args.resume,
        profile_dir=args.profile,
        pretrained=args.pretrained,
        proposals_path=args.proposals,
        strict_resume=args.strict_resume,
    )
    metrics: dict = {"final_step": int(jax.device_get(state.step))}
    if not args.no_eval:
        from mx_rcnn_tpu.cli.eval_cli import run_eval

        metrics.update(run_eval(cfg, state=state))
    return metrics


def cli(argv=None) -> int:
    """Console-script entry point ([project.scripts]).  ``main`` returns
    its result dict for programmatic callers; returning that from a
    console script would make ``sys.exit`` treat the truthy dict as a
    FAILURE exit status, so discard it and return 0 explicitly.

    A preemption (SIGTERM/SIGINT mid-run) exits with the distinct
    RESUMABLE_EXIT_CODE after the emergency checkpoint lands, so
    schedulers can tell "requeue with --resume" from a real failure."""
    from mx_rcnn_tpu.train.preemption import RESUMABLE_EXIT_CODE, Preempted

    try:
        main(argv)
    except Preempted as p:
        log.warning(
            "preempted at step %d (checkpoint: %s); exiting %d — requeue "
            "with --resume", p.step, p.ckpt_dir, RESUMABLE_EXIT_CODE,
        )
        return RESUMABLE_EXIT_CODE
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(cli())
