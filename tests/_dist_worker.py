"""Subprocess body for the 2-process distributed test.

Launched by tests/test_distributed.py with JAX_COORDINATOR_ADDRESS /
JAX_NUM_PROCESSES / JAX_PROCESS_ID and a 4-device fake-CPU platform in
the environment.  Joins the runtime via parallel.distributed.initialize()
(the production entry point — this is its only end-to-end exercise), then
runs :func:`run_steps` over the global 8-device mesh and prints the
metrics as one RESULT json line for the parent to compare across
processes and against its own single-process 8-device run (the parent
calls run_steps directly — same code, world of 1).
"""

from __future__ import annotations

import dataclasses
import json


def run_steps() -> dict:
    """One sharded train step + one sharded eval batch on the global mesh
    of whatever runtime this process is part of (1x8 or 2x4 devices).
    The loader's global-schedule design means any (rank, world) split of
    the same roidb yields the same global batch content."""
    import jax
    import numpy as np

    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.data import DetectionLoader, SyntheticDataset
    from mx_rcnn_tpu.parallel import make_mesh, replicated, shard_batch
    from mx_rcnn_tpu.parallel.step import eval_variables, make_eval_step
    from mx_rcnn_tpu.train.loop import build_all

    cfg = get_config("tiny_synthetic")
    # XLA ROIAlign: bit-identical oracle of the Pallas kernel, without the
    # minutes of interpret-mode execution on a timeshared CPU host.
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(
            cfg.model,
            rcnn=dataclasses.replace(cfg.model.rcnn, roi_align_impl="xla"),
        ),
    )
    mesh = make_mesh()  # all global devices
    model, tx, state, step_fn, global_batch = build_all(cfg, mesh)

    roidb = SyntheticDataset(
        num_images=max(global_batch, 2), image_hw=cfg.data.image_size
    ).roidb()
    rank, world = jax.process_index(), jax.process_count()
    loader = DetectionLoader(
        roidb, cfg.data, batch_size=global_batch, prefetch=False,
        rank=rank, world=world,
    )
    state = jax.device_put(state, replicated(mesh))
    batch = shard_batch(next(iter(loader)), mesh)
    state, metrics = step_fn(state, batch)
    out = {k: float(v) for k, v in jax.device_get(metrics).items()}
    assert int(jax.device_get(state.step)) == 1

    # One sharded eval batch, detections gathered to every host (the
    # multi-host eval path run_eval uses).
    eval_loader = DetectionLoader(
        roidb, cfg.data, batch_size=global_batch, train=False,
        rank=rank, world=world,
    )
    eval_step = make_eval_step(model, mesh=mesh, gather_outputs=True)
    variables = jax.device_put(eval_variables(state), replicated(mesh))
    eval_batch, recs = next(iter(eval_loader))
    dets = jax.device_get(eval_step(variables, shard_batch(eval_batch, mesh)))
    out["eval_n_valid"] = int(np.sum(dets.valid))
    out["eval_scores_sum"] = float(
        np.sum(np.where(dets.valid, dets.scores, 0.0))
    )
    out["eval_n_images"] = len(recs)
    return out


def main() -> None:
    import jax

    # The parent exports JAX_PLATFORMS=cpu; pin through the config API too
    # so a jax imported earlier cannot have captured another platform —
    # two processes that both resolved to the one real chip would each
    # see a separate 1-device world.
    jax.config.update("jax_platforms", "cpu")

    from mx_rcnn_tpu.utils.compile_cache import configure_cache

    configure_cache()

    from mx_rcnn_tpu.parallel import distributed

    distributed.initialize()
    assert jax.process_count() == 2, jax.process_count()
    assert jax.local_device_count() == 4, jax.local_device_count()
    assert jax.device_count() == 8, jax.device_count()
    print("RESULT " + json.dumps(run_steps()), flush=True)


if __name__ == "__main__":
    main()
