"""Readings for a cell's limits, many seeds in one process (set-up is long):

    python3 perfbench/readings.py --workload <cell> --seeds 1,2,3,... \
        [--control int8,fp8 --faults half_batch,unchanged --side-seeds 3] [--seconds 2]

For every seed: the cell's system reseeded, its first steps through the timed
call and feed, a short window at the cell's own load, then the plain
reference -> the numbers ``compare.py`` would judge (the LOWER readings).
For the first ``--side-seeds`` seeds also the UPPER readings, each against
the same float32 reference on the same batches:

- ``--control int8,fp8``: the reference put in the program's place with every
  matmul operand rounded to eight bits (reference/lowprec.py);
- ``--faults half_batch,unchanged``: the reference put in the program's place
  with half of each batch left out, the mean taken over the rest; with every
  step returning its state as it got it.

One JSON line per reading on standard output and in
``chiprun_out/readings_<cell>.jsonl``.  A benchmark run never calls this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def half_batches(batches):
    return [{k: v[: v.shape[0] // 2] for k, v in b.items()} for b in batches]


def side_reading(cell, kind: str, ref_res: dict, runners: dict) -> tuple[dict, dict]:
    """(numbers, {leaf: [gradient norm, change norm]}) of one control or fault
    against the float32 reference.  ``runners`` keeps one ``Reference`` per
    rounding over the seeds: a new one compiles anew (two minutes a reading)."""
    from perfbench import compare
    from perfbench.entries.train import reference_side
    from perfbench.reference.lowprec import ROUNDINGS
    from perfbench.reference.train import Reference

    import jax

    devices = jax.devices()[: cell.cell["chips"]]
    rounding = kind if kind in ROUNDINGS else None
    if rounding not in runners:
        runners[rounding] = Reference(cell.ref_run, matmul=ROUNDINGS.get(rounding), devices=devices)
    w0 = jax.device_put(cell.w0, devices[0])
    batches = half_batches(cell.followed) if kind == "half_batch" else cell.followed
    run = runners[rounding].run(
        w0, batches, cell.rng, cell.follow_steps, steady=cell.steady,
        unchanged=(kind == "unchanged"),
    )
    leaves = {p: [run["grad1"][p], run["change"][p]] for p in run["grad1"]}
    return compare.train_numbers(reference_side(run), ref_res), leaves


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--side-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from perfbench import compare, program
    from perfbench.entries.train import TrainCell
    from perfbench.run import Context, require_chips
    from perfbench.spec import Spec

    spec = Spec(REPO_ROOT)
    require_chips(spec.cell(args.workload)["chips"])
    program.configure_cache()
    ctx = Context(spec, args.workload, seeds[0], args.seconds, 0, time.perf_counter())
    out_dir = os.path.join(REPO_ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, f"readings_{args.workload}.jsonl"), "a")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    cell = TrainCell(ctx)
    sides = [k for k in (args.control.split(",") + args.faults.split(",")) if k]
    runners: dict = {}
    for i, seed in enumerate(seeds):
        if i:
            cell.reseed(seed)
        t0 = time.perf_counter()
        prog = cell.follow()
        counters = cell.window(args.seconds)
        t1 = time.perf_counter()
        ref_res = cell.reference()
        t2 = time.perf_counter()
        numbers = compare.train_numbers(prog, ref_res)
        leaves = {
            p: [prog["grad1"][p], ref_res["grad1"][p], prog["change"][p], ref_res["change"][p]]
            for p in ref_res["grad1"]
        }
        emit({
            "workload": args.workload, "seed": seed, "kind": "program", "numbers": numbers,
            "loss": [[p["loss"], r["loss"]] for p, r in zip(prog["steps"], ref_res["steps"])],
            "grad_norm_ref": [s["grad_norm"] for s in ref_res["steps"]],
            "img_s": counters["steps"] * counters["global_batch"] / counters["window_s"],
            "program_s": t1 - t0, "reference_s": t2 - t1,
            "built_in_window": len(ctx.built_in_window), "memory": ctx.memory(),
            "leaves": leaves,
        })
        if i < args.side_seeds:
            for kind in sides:
                t3 = time.perf_counter()
                numbers, leaves = side_reading(cell, kind, ref_res, runners)
                emit({
                    "workload": args.workload, "seed": seed, "kind": kind, "numbers": numbers,
                    "seconds": time.perf_counter() - t3, "leaves": leaves,
                })
    cell.close()
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
