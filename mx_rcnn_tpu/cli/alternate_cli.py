"""4-step alternate training (Ren et al. 2015) driver.

Parity with ``train_alternate.py`` (SURVEY.md §4.2).  The reference runs
four separate processes over four separate symbol graphs
(``rcnn/tools/train_rpn.py`` / ``test_rpn.py`` / ``train_rcnn.py``) and
merges the two resulting param files with ``combine_model``.  Here every
phase reuses the SAME jitted train graph and the SAME loop — phases differ
only in loss weights (rpn vs rcnn) and freeze prefixes, and "combine" is a
no-op because all parameters already live in one pytree:

  1. train RPN          (rcnn loss off;   box head frozen)
  2. dump proposals     (forward_proposals over the train split → pkl)
  3. train Fast R-CNN   (rpn loss off;    rpn head frozen — its frozen
                         weights generate the in-graph proposals, which is
                         exactly "train on phase-1's proposals")
  4. retrain RPN        (rcnn loss off;   shared conv + box head frozen)
  5. dump proposals again
  6. retrain Fast R-CNN (rpn loss off;    shared conv + rpn head frozen)

Proposal dumps are written for artifact parity (the reference's rpn pkl);
training itself consumes proposals in-graph from the frozen RPN, which keeps
every phase a single statically-shaped jitted step.

Two schedules are offered:

- default (in-graph): the rcnn phases keep the frozen RPN in the graph and
  sample from its live proposals.  Deviation from the reference: phases
  continue from the previous phase's weights (an in-graph frozen RPN only
  matches the trunk it was trained on), so ``--pretrained`` seeds phase 1
  only.
- ``--external-proposals``: the reference-faithful Ren et al. schedule.
  Each rcnn phase consumes the PRECOMPUTED pkl dumped by the preceding rpn
  phase (Fast R-CNN mode — the RPN drops out of the graph), which makes
  per-phase re-initialization safe: rcnn1 restarts from the ImageNet seed
  exactly as the reference's ``train_rcnn.py`` does.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import os

from mx_rcnn_tpu.cli.common import add_config_args, config_from_args, setup_logging
from mx_rcnn_tpu.config import Config

log = logging.getLogger("mx_rcnn_tpu.alternate")


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__)
    add_config_args(p, default="vgg16_voc07")
    p.add_argument(
        "--phase-steps", type=int, default=None,
        help="steps per phase (default: schedule total_steps per phase)",
    )
    p.add_argument(
        "--no-proposal-dump", action="store_true",
        help="skip the pkl artifact dumps between phases",
    )
    p.add_argument(
        "--pretrained", default=None, metavar="PTH",
        help="torchvision backbone .pth. Default schedule: seeds phase 1 "
        "only (see module docstring); with --external-proposals it also "
        "re-seeds the rcnn1 phase, as the reference does",
    )
    p.add_argument(
        "--strict-resume", action="store_true",
        help="fail (instead of warn) when a phase's config drifts from "
        "the workdir's recorded config.json",
    )
    p.add_argument(
        "--external-proposals", action="store_true",
        help="reference-faithful schedule: rcnn phases train on the pkl "
        "dumped by the preceding rpn phase (Fast R-CNN mode, RPN out of "
        "the graph) instead of in-graph frozen-RPN proposals",
    )
    return p.parse_args(argv)


def _phase_cfg(cfg: Config, name: str, rpn_on: bool, rcnn_on: bool) -> Config:
    model = dataclasses.replace(
        cfg.model,
        rpn=dataclasses.replace(cfg.model.rpn, loss_weight=1.0 if rpn_on else 0.0),
        rcnn=dataclasses.replace(cfg.model.rcnn, loss_weight=1.0 if rcnn_on else 0.0),
    )
    return dataclasses.replace(cfg, name=f"{cfg.name}_{name}", model=model)


def alternate_train(
    cfg: Config,
    mesh=None,
    phase_steps=None,
    workdir=None,
    dump_proposals_pkl: bool = True,
    num_phases: int = 4,
    pretrained=None,
    external_proposals: bool = False,
    strict_resume: bool = False,
):
    """Run the 6-step schedule; returns the final combined TrainState.

    ``num_phases`` < 4 truncates the schedule (tests exercise the phase
    transition without paying for four full compiles).
    ``external_proposals``: reference-faithful mode — rcnn phases train on
    the preceding rpn phase's pkl dump (see module docstring).
    """
    import jax

    from mx_rcnn_tpu.cli.eval_cli import dump_proposals
    from mx_rcnn_tpu.train.loop import train

    workdir = workdir or cfg.workdir
    # Backbone trunk freeze prefixes come from the shared-conv set; the
    # conv1/res2-style early freeze stays active in every phase via
    # build_all's default behavior.
    shared_conv = ("backbone", "fpn")

    phases = [
        ("rpn1", dict(rpn=True, rcnn=False), ("box_head",), None),
        ("rcnn1", dict(rpn=False, rcnn=True), ("rpn",), "proposals_rpn1.pkl"),
        ("rpn2", dict(rpn=True, rcnn=False), shared_conv + ("box_head",), None),
        ("rcnn2", dict(rpn=False, rcnn=True), shared_conv + ("rpn",), "proposals_rpn2.pkl"),
    ]
    if external_proposals and not dump_proposals_pkl:
        raise ValueError("--external-proposals requires the proposal dumps")
    state = None
    for name, losses, freeze, dump_before in phases[:num_phases]:
        pcfg = _phase_cfg(cfg, name, losses["rpn"], losses["rcnn"])
        proposals_path = None
        if dump_before and dump_proposals_pkl and state is not None:
            path = os.path.join(workdir, cfg.name, dump_before)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            dump_proposals(cfg, path, state=state)
            if external_proposals:
                proposals_path = path
        # Reference-faithful mode: rcnn1 restarts from the ImageNet seed
        # and trains on the dumped pkl (Fast R-CNN, RPN out of the graph) —
        # safe because the proposals are precomputed, exactly like
        # rcnn/tools/train_rcnn.py.  rcnn2 keeps rpn2's weights (its trunk
        # is frozen-shared by then, per the 4-step schedule).
        reseed = external_proposals and name == "rcnn1"
        if reseed and not pretrained:
            # Hermetic/synthetic runs may legitimately lack a .pth, but the
            # reference schedule presumes the ImageNet seed — be loud.
            log.warning(
                "--external-proposals without --pretrained: rcnn1 restarts "
                "from RANDOM init (the reference re-seeds it from ImageNet)"
            )
        log.info(
            "=== alternate phase %s (freeze: %s%s) ===",
            name, ",".join(freeze),
            ", external proposals" if proposals_path else "",
        )
        state = train(
            pcfg,
            mesh=mesh,
            total_steps=phase_steps,
            workdir=workdir,
            state=(
                jax.device_get(state)
                if state is not None and not reseed
                else None
            ),
            extra_freeze=tuple(freeze),
            # ImageNet seed applies to fresh states only: phase 1, and the
            # re-seeded rcnn1 of the reference-faithful schedule.
            pretrained=pretrained if (state is None or reseed) else None,
            proposals_path=proposals_path,
            strict_resume=strict_resume,
        )
    # combine_model parity: nothing to merge — one pytree holds RPN + RCNN.
    # Save the combined result under the BASE config name so eval/demo find
    # it at the same path an end-to-end run would use (the reference's
    # combine_model writes the merged `final` param file).
    from mx_rcnn_tpu.train.checkpoint import save_checkpoint

    state = jax.device_get(state)
    save_checkpoint(f"{workdir}/{cfg.name}/ckpt", state, wait=True)
    return state


def main(argv=None):
    args = parse_args(argv)
    setup_logging(args.verbose)
    cfg = config_from_args(args)

    import jax

    from mx_rcnn_tpu.parallel import make_mesh
    from mx_rcnn_tpu.utils.compile_cache import configure_cache

    configure_cache()
    mesh = (
        make_mesh(model_parallel=cfg.train.spatial_partition)
        if jax.device_count() > 1
        else None
    )
    state = alternate_train(
        cfg,
        mesh=mesh,
        phase_steps=args.phase_steps,
        workdir=cfg.workdir,
        dump_proposals_pkl=not args.no_proposal_dump,
        pretrained=args.pretrained,
        external_proposals=args.external_proposals,
        strict_resume=args.strict_resume,
    )
    from mx_rcnn_tpu.cli.eval_cli import run_eval

    return run_eval(cfg, state=state)


def cli(argv=None) -> int:
    """Console-script entry point ([project.scripts]).  ``main`` returns
    its result dict for programmatic callers; returning that from a
    console script would make ``sys.exit`` treat the truthy dict as a
    FAILURE exit status, so discard it and return 0 explicitly.

    A preemption mid-phase exits with RESUMABLE_EXIT_CODE after the
    emergency checkpoint lands (see train_cli.cli)."""
    from mx_rcnn_tpu.train.preemption import RESUMABLE_EXIT_CODE, Preempted

    try:
        main(argv)
    except Preempted as p:
        log.warning(
            "preempted at step %d (checkpoint: %s); exiting %d",
            p.step, p.ckpt_dir, RESUMABLE_EXIT_CODE,
        )
        return RESUMABLE_EXIT_CODE
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(cli())
