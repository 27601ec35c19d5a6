"""Subprocess body for the opt-in TPU overfit golden.

Runs the tiny_synthetic overfit recipe (the same one
tests/test_overfit.py pins on CPU) on jax's default platform — on the
chip machine that is the TPU; the parent refuses any other.  Prints one
RESULT json line with the eval metrics, the platform/device count and the
jax/libtpu versions so the parent can gate on them and a re-recorded
golden carries its provenance.

Run directly: python tests/_overfit_tpu_worker.py
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from mx_rcnn_tpu.cli.eval_cli import run_eval
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.train.loop import train
    from mx_rcnn_tpu.utils.compile_cache import configure_cache
    from mx_rcnn_tpu.utils.runtime import device_record, runtime_versions

    configure_cache()
    cfg = get_config("tiny_synthetic")
    sched = dataclasses.replace(
        cfg.train.schedule, base_lr=0.02, warmup_steps=20,
        decay_steps=(300,), total_steps=400,
    )
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, schedule=sched, log_every=100)
    )
    state = train(cfg, mesh=None)
    metrics = run_eval(cfg, state=state)
    out = {
        **device_record(),
        **runtime_versions(),
        "AP": float(metrics["AP"]),
        "AP50": float(metrics["AP50"]),
    }
    print("RESULT " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
