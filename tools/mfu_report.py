"""Per-component MFU/FLOP attribution report for the train step.

Walks the traced train program (utils/hlo_profile.py) and writes a JSON
report attributing every MXU FLOP to a model component — stem, C2..C5,
FPN, RPN-head, ROI, box-head — so "20.6% MFU" decomposes into per-region
shares instead of one opaque number.  The attribution is an abstract
trace: it runs under ``JAX_PLATFORMS=cpu`` for the full TPU-shaped recipe
program (no execution, no device).  Timing and the post-fusion HLO
instruction summary are optional extras for hosts that can afford to
execute/compile the program.

Usage:
  python tools/mfu_report.py [--config r50_fpn_coco] [--set K=V ...]
      [--out artifacts/mfu_report.json]
      [--compare-legacy]   also attribute the pre-PR dense layout
                           (stem_s2d/stem_pool_fold/c2_pad/packed_head off)
                           so the report shows WHERE the restructured
                           components moved the FLOP mix
      [--hlo]              compile and add per-component instruction counts
      [--time N]           execute N timed steps and add measured ms/step,
                           achieved TFLOP/s and MFU vs the v5e bf16 peak
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LEGACY_LAYOUT_OVERRIDES = (
    "model.backbone.stem_s2d=false",
    "model.backbone.stem_pool_fold=false",
    "model.backbone.c2_pad=false",
    "model.rpn.packed_head=false",
)


def _variant(cfg, args, label: str) -> dict:
    import jax

    from bench import _synthetic_batch
    from mx_rcnn_tpu.utils.flops import peak_bf16_flops
    from mx_rcnn_tpu.train.loop import build_all
    from mx_rcnn_tpu.utils.hlo_profile import (
        component_report,
        hlo_component_summary,
    )

    k = max(cfg.train.steps_per_call, 1)
    batch = cfg.train.per_device_batch
    image_size = cfg.data.image_size
    print(
        f"[{label}] tracing {args.config} @ {image_size[0]}x{image_size[1]} "
        f"b{batch} k{k} ...",
        file=sys.stderr,
    )
    model, tx, state, step_fn, global_batch = build_all(cfg, mesh=None)
    data = _synthetic_batch(cfg, batch, image_size, k)

    dt_per_step = None
    if args.time:
        data = jax.device_put(data)
        state, metrics = step_fn(state, data)  # compile + warm
        leaf = jax.tree_util.tree_leaves(state.params)[0]
        jax.device_get((metrics["loss"], leaf.ravel()[0]))
        t0 = time.perf_counter()
        for _ in range(args.time):
            state, metrics = step_fn(state, data)
        leaf = jax.tree_util.tree_leaves(state.params)[0]
        jax.device_get((metrics["loss"], leaf.ravel()[0]))
        dt_per_step = (time.perf_counter() - t0) / (args.time * k)

    report = component_report(
        step_fn,
        state,
        data,
        steps_per_call=k,
        dt_per_step=dt_per_step,
        # MFU needs a measured time on a chip whose peak is published;
        # an unknown device_kind (a CPU included) raises.
        peak_flops=(
            peak_bf16_flops(jax.devices()[0].device_kind)
            if dt_per_step is not None else None
        ),
    )
    report["layout"] = {
        "stem_s2d": cfg.model.backbone.stem_s2d,
        "stem_pool_fold": cfg.model.backbone.stem_pool_fold,
        "c2_pad": cfg.model.backbone.c2_pad,
        "rpn_packed_head": cfg.model.rpn.packed_head,
    }
    if args.hlo:
        print(f"[{label}] compiling for the HLO summary ...", file=sys.stderr)
        txt = step_fn.lower(state, data).compile().as_text()
        report["hlo_instructions"] = hlo_component_summary(txt)
    return report


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="r50_fpn_coco")
    ap.add_argument(
        "--set", dest="overrides", action="append", default=[],
        metavar="KEY.PATH=VALUE",
    )
    ap.add_argument("--out", default=os.path.join("artifacts", "mfu_report.json"))
    ap.add_argument("--compare-legacy", action="store_true")
    ap.add_argument("--hlo", action="store_true")
    ap.add_argument("--time", type=int, default=0, metavar="N")
    args = ap.parse_args(argv)

    import jax

    from mx_rcnn_tpu.config import apply_overrides, get_config

    cfg = get_config(args.config)
    # Attribution-only runs never execute the program, so the full recipe
    # canvas is free even on CPU; k=1 keeps the jaxpr small (the K-step
    # scan scales every component linearly).
    cfg = dataclasses.replace(
        cfg,
        data=dataclasses.replace(cfg.data, max_gt_boxes=32),
        train=dataclasses.replace(
            cfg.train, steps_per_call=1, per_device_batch=2
        ),
    )
    if args.overrides:
        cfg = apply_overrides(cfg, args.overrides)

    report = {
        "config": args.config,
        "overrides": list(args.overrides),
        "platform": jax.default_backend(),
        "image_size": list(cfg.data.image_size),
        "per_device_batch": cfg.train.per_device_batch,
        "attribution": "analytic conv+dot jaxpr walk per name-stack component"
        " (mx_rcnn_tpu.utils.hlo_profile); timing "
        + ("measured" if args.time else "not measured on this host"),
        "default_layout": _variant(cfg, args, "default"),
    }
    if args.compare_legacy:
        legacy = apply_overrides(cfg, list(LEGACY_LAYOUT_OVERRIDES))
        report["legacy_layout"] = _variant(legacy, args, "legacy")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}", file=sys.stderr)
    print(json.dumps({
        "metric": "mfu_report_total_tflops_per_step",
        "value": report["default_layout"]["total_tflops_per_step"],
    }))
    return report


if __name__ == "__main__":
    main()
