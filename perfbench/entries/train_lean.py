"""The training cells whose state fills the chip: ``entries/train.py``'s run
(the same loop, feed, followed steps, window and comparison) with the
benchmark's own copies of the weights kept on the HOST.

``TrainCell`` holds the seed's weights, a copy of the first parameters and the
program's state on the device together, and its reference holds seven copies
at its peak (reference/train.py): fine for 0.14 G parameters, impossible for
0.8 G at 12 bytes each on 16 GB.  Here the seed's weights go to the host once
they are made; the first gradient's and the change's leaf norms are taken a
leaf at a time against the host's copy; and the plain reference is
``LeanReference``.  ``compare.py`` and the numbers are the same but for the
held experts' leaves, which ``grad1`` and ``change`` take together
(:func:`numbers_of`; the cell's file lists the departures under ``entry_why``).

The window also keeps each step's routing counters and fetches them once it
has closed, and the step's FLOPs come from the configuration's need
functions (``ling_need.py``): the plain reference recomputes its blocks and
computes every held expert over every token, so its traced count is not what
a step NEEDS.

Run as a script it takes the readings a cell's limits are set from:

    python3 perfbench/entries/train_lean.py --workload <cell> --seeds 1,2 \
        [--sides fp8,half_batch,unchanged,no_experts] [--seconds 2]
"""

from __future__ import annotations

import math
import os
import re
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import jax
import jax.numpy as jnp

from perfbench import compare, program
from perfbench import weights as W
from perfbench.entries.train import FOLLOW, TrainCell, reference_side
from perfbench.reference import detector as D
from perfbench.reference.train_lean import LeanReference

_T0 = time.perf_counter()


def _log(what: str):
    """A phase's end on standard error (the run's result stays the last line
    of standard output): set-up here is minutes, and a cut run should say where."""
    print(f"train_lean: {what} at {time.perf_counter() - _T0:.1f} s", file=sys.stderr, flush=True)


COUNTERS = ("moe_slots_here", "moe_load_max_over_mean", "moe_dropped_slots",
            "moe_tokens_without_held_expert")


_EXPERT = re.compile(r"/(l\d+)/moe/experts/e\d+/")
AS_ONE, BY_LAYER = "/moe/experts/all/", r"/\1/moe/experts/all/"


def experts_merged(side: dict, to: str) -> dict:
    """A side's ``grad1`` and ``change`` with held experts taken together, one
    leaf per matrix: all of them (``AS_ONE``) or one layer's (``BY_LAYER``).
    An image's patch tokens look alike, so where rounding moves a router's
    logit a whole cluster of them changes expert at once: a single little-used
    expert's norm then differs by up to 81 % between the bfloat16 program and
    the float32 reference on a sound run, which no limit could tell from
    experts left out (they read 1 under either merge).  One layer's experts
    together read up to 33 %, all of them up to 20 % (PERF.md section 2)."""
    def merged(norms):
        out = {}
        for path, norm in norms.items():
            key = _EXPERT.sub(to, path)
            out[key] = math.hypot(out.get(key, 0.0), norm)
        return out

    return dict(side, grad1=merged(side["grad1"]), change=merged(side["change"]))


def numbers_of(prog: dict, ref_res: dict) -> dict:
    """What the cell judges - ``compare.train_numbers`` with all the held
    experts as one leaf (``grad1``, ``change``) and with one layer's as one
    (``grad1_layer``, ``change_layer``: a fault in one layer's experts does
    not vanish into the other five) - and beside them the same two numbers
    expert by expert, which nothing judges."""
    out = compare.train_numbers(experts_merged(prog, AS_ONE), experts_merged(ref_res, AS_ONE))
    layer = compare.train_numbers(experts_merged(prog, BY_LAYER), experts_merged(ref_res, BY_LAYER))
    each = compare.train_numbers(prog, ref_res)
    for k in ("grad1", "grad1_leaf", "change", "change_leaf"):
        out[f"{k}_layer"], out[f"{k}_per_expert"] = layer[k], each[k]
    return out


class LeanTrainCell(TrainCell):
    def __init__(self, ctx):
        self.ctx = ctx
        self.cell, self.conf = ctx.cell, ctx.config
        self.ref = ctx.config["reference"]
        self.cfg = program.load_config(self.conf, self.cell)
        self.mesh = program.make_mesh(self.cell["chips"])
        self.specs = D.all_specs(self.ref)
        self.follow_steps = FOLLOW
        self.steady = self.cell.get("steady", "")
        self.seed = ctx.seed
        made = W.make_weights(self.seed, self.specs)
        self.w0 = jax.device_get(made)          # the benchmark's copy: on the host
        del made
        _log("weights made")
        self.rng = W.seed_key(self.seed, 2)
        self.state, self.step_fn, self.plan, self.global_batch = program.build_train(
            self.cfg, self.mesh, self.w0, self.rng
        )
        self.ref_run = dict(self.ref, global_batch=self.global_batch)
        self.feed = None
        self._open_feed()
        wd = self.ref["optimizer"]["weight_decay"]
        self._grad_norm = jax.jit(lambda t, p0, decay: jnp.linalg.norm(t - decay * wd * p0))
        self._gap = jax.jit(lambda a, b: jnp.linalg.norm(a - b))

    def _first(self, path):
        return jax.device_put(self.w0[path])

    def follow(self) -> dict:
        """``TrainCell.follow`` against the host's copy of the first weights."""
        ctx, wd = self.ctx, self.ref["optimizer"]["weight_decay"]
        held = lambda p: self.steady and p.startswith(self.steady)
        prog = {"steps": [], "steady_grad1": {}, "steady_change": {}}
        for t in range(self.follow_steps):
            self.state, m = ctx.guarded(self.step_fn, self.state, next(self.feed), first=(t == 0))
            m = jax.device_get(m)
            prog["steps"].append({
                "loss": float(m["loss"]),
                "rpn": float(m["RPNLogLoss"] + m["RPNL1Loss"]),
                "rcnn": float(m["RCNNLogLoss"] + m["RCNNL1Loss"]),
            })
            if t == 0:
                if "moe_slots_here" in m:
                    prog["moe_slots1"] = float(m["moe_slots_here"])
                trace = program.momentum_trace(self.state.opt_state)
                norms = {
                    p: self._grad_norm(v, self._first(p), float(D.decayed(p)))
                    for p, v in trace.items()
                }
                prog["grad1"] = {p: float(v) for p, v in jax.device_get(norms).items()}
                prog["steady_grad1"] = {
                    p: jax.device_get(v) - (wd * self.w0[p] if D.decayed(p) else 0.0)
                    for p, v in trace.items() if held(p)
                }
                del trace
        params = W.flatten(self.state.params, "params")
        gaps = {p: self._gap(v, self._first(p)) for p, v in params.items()
                if D.trainable(self.ref, p)}
        prog["change"] = {p: float(v) for p, v in jax.device_get(gaps).items()}
        prog["steady_change"] = {
            p: jax.device_get(v) - self.w0[p] for p, v in params.items()
            if D.trainable(self.ref, p) and held(p)
        }
        return prog

    def window(self, seconds: float) -> dict:
        """``TrainCell.window`` with every step's routing counters kept where
        the step left them, on the device, and fetched once the window has
        closed: nothing is added between its barriers."""
        kept, step_fn = [], self.step_fn

        def counting(state, batch):
            state, m = step_fn(state, batch)
            kept.append({k: m[k] for k in COUNTERS if k in m})
            return state, m

        self.step_fn = counting
        try:
            out = super().window(seconds)
        finally:
            self.step_fn = step_fn
        fetched = jax.device_get(kept)
        for name in COUNTERS:
            values = [float(f[name]) for f in fetched if name in f]
            if values:
                worst = name in ("moe_load_max_over_mean", "moe_dropped_slots")
                out[name] = max(values) if worst else sum(values) / len(values)
        return out

    def reference(self, matmul=None, batches=None, unchanged=False) -> dict:
        devices = jax.devices()[: self.cell["chips"]]
        out = LeanReference(self.ref_run, matmul=matmul, devices=devices).run(
            self.w0, self.followed if batches is None else batches, self.rng,
            self.follow_steps, steady=self.steady, unchanged=unchanged,
        )
        return reference_side(out)

    def reference_slots(self) -> float:
        """Token-slots the float32 reference routes to the held experts over
        the first followed batch: the other side of the program's first
        ``moe_slots_here``.  Their gap counts the picks that rounding flipped
        across the share's edge (a flip between two absent or two held experts
        does not show)."""
        from perfbench.reference import backbone_ling3_flash_vl as B

        w = jax.device_put(self.w0)
        count = jax.jit(lambda w, image: B.slots_here(self.ref, w, D.normalize(self.ref, image[None])))
        return sum(float(count(w, jnp.asarray(image))) for image in self.followed[0]["images"])

    def step_flops(self, counters=None) -> float:
        from perfbench.ling_need import step_flops

        slots = (counters or {}).get("moe_slots_here")
        return step_flops(self.ref_run, self.global_batch, slots)


def run(ctx) -> dict:
    """One benchmark run of a training cell.  -> result fields (run.py)."""
    cell = LeanTrainCell(ctx)
    _log("built")
    prog = cell.follow()
    ctx.setup_done()
    _log("followed")
    counters = cell.window(ctx.seconds)
    _log(f"window closed after {counters['steps']} steps")
    memory = ctx.memory()
    scopes = program.op_scopes(cell.step_fn, cell.state, next(cell.feed)) if ctx.trace else None
    pool_impl = program.pool_impl()
    cell.close()

    t_ref = time.perf_counter()
    ref_res = cell.reference()
    _log("reference done")
    numbers = numbers_of(prog, ref_res)
    extra = {
        "reference_s": time.perf_counter() - t_ref, "pool_impl": pool_impl,
        "loss1": [prog["steps"][0]["loss"], ref_res["steps"][0]["loss"]],
        "grad_norm1_ref": ref_res["steps"][0]["grad_norm"],
    }
    if "moe_slots1" in prog:  # [program, reference]: the picks flipped across the share's edge
        extra["moe_slots1"] = [prog["moe_slots1"], cell.reference_slots()]
        _log("reference's routing counted")
    images_done = counters["steps"] * counters["global_batch"]
    return {
        "attempted": images_done, "failed": 0,
        "end_to_end": {
            "train_img_s_chip": images_done / counters["window_s"] / ctx.cell["chips"]
        },
        "counters": counters, "memory": memory, "numbers": numbers, "extra": extra,
        "step_flops": lambda: cell.step_flops(counters), "program_name": "jit_step",
        "scopes": scopes,
    }


# -- readings for the limits ---------------------------------------------------


def side_reading(cell, kind: str, ref_res: dict) -> dict:
    """The numbers of one control or planted fault put in the program's place,
    against the float32 reference ``ref_res`` on the same batches."""
    from perfbench.readings import half_batches
    from perfbench.reference import backbone_ling3_flash_vl as B
    from perfbench.reference.lowprec import ROUNDINGS

    if kind in ROUNDINGS:
        side = cell.reference(matmul=ROUNDINGS[kind])
    elif kind == "half_batch":
        side = cell.reference(batches=half_batches(cell.followed))
    elif kind == "unchanged":
        side = cell.reference(unchanged=True)
    elif kind == "no_experts":  # the held experts' part left out of the layer
        real, B.held = B.held, lambda dc: range(0)
        try:
            side = cell.reference()
        finally:
            B.held = real
    else:
        raise ValueError(f"unknown side {kind!r}")
    return numbers_of(side, ref_res)


def main(argv=None) -> int:
    import argparse
    import json

    from perfbench.run import Context, require_chips
    from perfbench.spec import Spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sides", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--no-chip", action="store_true", help="tests only")
    args = ap.parse_args(argv)
    spec = Spec(REPO_ROOT)
    if not args.no_chip:
        require_chips(spec.cell(args.workload)["chips"])
        program.configure_cache()
    os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
    sink = open(os.path.join(REPO_ROOT, "chiprun_out", f"readings_{args.workload}.jsonl"), "a")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = Context(spec, args.workload, seed, args.seconds, 0, time.perf_counter())
        cell = LeanTrainCell(ctx)
        prog = cell.follow()
        counters = cell.window(args.seconds)
        memory = ctx.memory()
        cell.close()
        t0 = time.perf_counter()
        ref_res = cell.reference()
        limits = dict(ctx.cell["limits"], built_in_window=0)

        def judged(numbers):  # as run.py judges a run
            correct, rows = compare.judge(
                dict(numbers, built_in_window=len(ctx.built_in_window)), limits
            )
            return {"correct": correct, "compared": rows, "numbers": numbers}

        emit({
            "workload": args.workload, "seed": seed, "kind": "program",
            **judged(numbers_of(prog, ref_res)), "counters": counters,
            "loss": [[p["loss"], r["loss"]] for p, r in zip(prog["steps"], ref_res["steps"])],
            "grad_norm_ref": [s["grad_norm"] for s in ref_res["steps"]],
            "reference_s": time.perf_counter() - t0, "memory": memory,
            "built_in_window": len(ctx.built_in_window),
            "leaves": {p: [prog["grad1"][p], ref_res["grad1"][p], prog["change"][p],
                           ref_res["change"][p]] for p in ref_res["grad1"]},
        })
        for kind in (k for k in args.sides.split(",") if k):
            t0 = time.perf_counter()
            emit({"workload": args.workload, "seed": seed, "kind": kind,
                  **judged(side_reading(cell, kind, ref_res)),
                  "seconds": time.perf_counter() - t0})
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
