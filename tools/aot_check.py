"""Compile the train and eval steps for a TPU topology — without a TPU.

The installed libtpu can describe a topology (``v5e:2x2``) and compile for
it with no chip attached, so everything the COMPILER decides can be read
from a CPU-only sandbox before any chip time is spent:

- whether the programs lower and compile at all — Mosaic's verdict on the
  Pallas kernels included, under the real mesh (the interpret-mode CPU
  tests have no Mosaic call, so they cannot see a refusal);
- which ROIAlign path the trace took (``LAST_POOL_IMPL``);
- how many all-reduce ops the compiled train step holds — the number
  ``train.bucket_mb`` was written to change;
- the compiler's memory analysis per device (argument / output / temp /
  code bytes).

What it cannot give is anything measured: it executes nothing, so no
time, no rate, no utilization.  The seconds it prints are this sandbox's
CPU compiling, labelled as such.  The run on real chips is
``chip_smoke.py``.

Usage:
    JAX_PLATFORMS=cpu python tools/aot_check.py [--config r50_fpn_coco]
        [--chips 4] [--set train.bucket_mb=64 ...]

Prints one JSON line (last on stdout).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The process runs on the CPU; only the COMPILE targets a TPU.
os.environ["JAX_PLATFORMS"] = "cpu"


def _memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes",
        )
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="r50_fpn_coco")
    ap.add_argument("--chips", type=int, default=4, choices=(1, 4))
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument(
        "--set", dest="overrides", action="append", default=[],
        metavar="KEY.PATH=VALUE",
    )
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.detection import Batch, graph
    from mx_rcnn_tpu.parallel import make_mesh
    from mx_rcnn_tpu.parallel.step import eval_variables, make_eval_step
    from mx_rcnn_tpu.train.loop import build_all

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=args.topology
    )
    devices = list(topo.devices)[: args.chips]
    cfg = apply_overrides(
        get_config(args.config), ["data.dataset=synthetic"] + args.overrides
    )
    # The CLIs' rule: a mesh only with more than one chip.
    mesh = make_mesh(devices) if args.chips > 1 else None
    one = SingleDeviceSharding(devices[0])
    model, _, state, step_fn, global_batch = build_all(cfg, mesh=mesh)
    h, w = cfg.data.image_size
    g = cfg.data.max_gt_boxes

    def spec(shape, dtype):
        # Under a mesh the jitted steps carry their own shardings; a
        # single-chip program needs its arguments to name the TPU.
        if mesh is not None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def batch(b):
        return Batch(
            images=spec((b, h, w, 3), jnp.float32),
            image_hw=spec((b, 2), jnp.float32),
            gt_boxes=spec((b, g, 4), jnp.float32),
            gt_classes=spec((b, g), jnp.int32),
            gt_valid=spec((b, g), jnp.bool_),
        )

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: spec(np.shape(x), x.dtype), tree
        )

    def build(fn, *fn_args):
        t0 = time.perf_counter()
        graph.LAST_POOL_IMPL = None
        compiled = (
            fn.trace(*fn_args).lower(lowering_platforms=("tpu",)).compile()
        )
        text = compiled.as_text()
        return {
            "pool_impl": graph.LAST_POOL_IMPL,
            "all_reduce_ops": text.count(" all-reduce(")
            + text.count(" all-reduce-start("),
            "mosaic_kernels": text.count("tpu_custom_call"),
            "memory_analysis": _memory(compiled),
            "sandbox_cpu_compile_s": round(time.perf_counter() - t0, 1),
        }

    # The graph picks its Pallas paths from jax.default_backend() at trace
    # time; this process's backend is the CPU, the compile's is not.
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        train = build(step_fn, abstract(state), batch(global_batch))
        eval_step = make_eval_step(
            model, mesh=mesh,
            pixel_stats=(cfg.data.pixel_mean, cfg.data.pixel_std),
        )
        per_step = max(cfg.model.test.per_device_batch, 1) * args.chips
        evaluate = build(
            eval_step, abstract(eval_variables(state)), batch(per_step)
        )
    finally:
        jax.default_backend = real_backend
    rec = {
        "tool": "aot_check",
        "executed": False,
        "config": cfg.name,
        "overrides": args.overrides,
        "topology": args.topology,
        "chips": args.chips,
        "device_kind": devices[0].device_kind,
        "global_batch": global_batch,
        "train": train,
        "eval": {"images_per_step": per_step, **evaluate},
    }
    print(json.dumps(rec))
    return rec


if __name__ == "__main__":
    main()
