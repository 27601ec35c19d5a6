"""The training cells' run: ``train/loop.py``'s step path (``build_all`` ->
``step_fn`` fed by ``DetectionLoader`` -> ``device_prefetch``) driven for a
fixed time.  Set-up builds ONE state and compiled step, drives it through its
first ``FOLLOW`` steps on the window's own feed (the plain reference follows
the same steps afterwards) and hands the same object to the window.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp

from perfbench import compare, program, traffic
from perfbench import weights as W
from perfbench.reference import detector as D
from perfbench.reference.train import Reference, step_keys

FOLLOW = 3  # steps the reference follows


def _leaf_norms(tree: dict) -> dict:
    return {p: jnp.linalg.norm(v.astype(jnp.float32)) for p, v in tree.items()}


class TrainCell:
    """One built training system: state, compiled step, feed."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.cell, self.conf = ctx.cell, ctx.config
        self.ref = ctx.config["reference"]
        self.cfg = program.load_config(self.conf, self.cell)
        self.mesh = program.make_mesh(self.cell["chips"])
        self.specs = D.all_specs(self.ref)
        self.follow_steps = FOLLOW
        # Leaves whose gradient does not pass through the proposal picks
        # (compare.py::direction_gap); "" = none.
        self.steady = self.cell.get("steady", "")
        self.seed = ctx.seed
        self.w0 = W.make_weights(self.seed, self.specs)
        self.rng = W.seed_key(self.seed, 2)
        self.state, self.step_fn, self.plan, self.global_batch = program.build_train(
            self.cfg, self.mesh, self.w0, self.rng
        )
        self.ref_run = dict(self.ref, global_batch=self.global_batch)
        self.feed = None
        self._open_feed()
        ref, wd = self.ref, self.ref["optimizer"]["weight_decay"]

        @jax.jit
        def grad_norms(trace, p0):
            p0 = W.flatten(p0, "params")
            return _leaf_norms({
                p: t - (wd * p0[p] if D.decayed(p) else 0.0) for p, t in trace.items()
            })

        @jax.jit
        def change_norms(params, p0):
            a, b = W.flatten(params, "params"), W.flatten(p0, "params")
            return _leaf_norms({p: a[p] - b[p] for p in a if D.trainable(ref, p)})

        steady = self.steady

        @jax.jit
        def steady_grads(trace, p0):
            p0 = W.flatten(p0, "params")
            return {
                p: t - (wd * p0[p] if D.decayed(p) else 0.0)
                for p, t in trace.items() if p.startswith(steady)
            }

        @jax.jit
        def steady_change(params, p0):
            a, b = W.flatten(params, "params"), W.flatten(p0, "params")
            return {p: a[p] - b[p] for p in a if D.trainable(ref, p) and p.startswith(steady)}

        self._grad_norms, self._change_norms = grad_norms, change_norms
        self._steady_grads, self._steady_change = steady_grads, steady_change

    def _open_feed(self):
        images, boxes, classes = traffic.make_images(
            self.ctx.spec.traffic(self.cell["traffic"]), self.ref["num_classes"], self.seed
        )
        self.followed: list[dict] = []

        def tap(host_batch):
            if len(self.followed) < self.follow_steps:
                self.followed.append(program.host_batch_dict(host_batch))

        self.stats = program.prefetch_stats()
        self.feed = program.train_feed(
            self.cfg, self.plan, self.mesh, program.records(images, boxes, classes),
            self.global_batch, self.seed % (2**31), self.stats, tap,
        )

    def reseed(self, seed: int):
        """The same built system with another seed's weights, key and feed:
        what a fresh build from that seed holds (for reading many seeds in
        one process; a benchmark run never calls it)."""
        self.feed.close()
        self.seed = seed
        self.w0 = W.make_weights(seed, self.specs)
        self.rng = W.seed_key(seed, 2)
        self.state = program.reset_state(self.state, self.plan, self.w0, self.rng)
        self._open_feed()

    def follow(self) -> dict:
        """The first steps through the window's own call and feed: each
        step's loss, the first gradient's leaf norms as the optimizer applied
        it (momentum after one step less the decay), the change's leaf norms,
        and both themselves over the ``steady`` leaves."""
        ctx = self.ctx
        p0 = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), self.state.params)
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
                trace = program.momentum_trace(self.state.opt_state)
                prog["grad1"] = {
                    p: float(v) for p, v in jax.device_get(self._grad_norms(trace, p0)).items()
                }
                if self.steady:
                    prog["steady_grad1"] = jax.device_get(self._steady_grads(trace, p0))
        prog["change"] = {
            p: float(v)
            for p, v in jax.device_get(self._change_norms(self.state.params, p0)).items()
        }
        if self.steady:
            prog["steady_change"] = jax.device_get(self._steady_change(self.state.params, p0))
        return prog

    def window(self, seconds: float) -> dict:
        ctx = self.ctx
        sync_every = int(self.cell.get("sync_every", 8))
        self.stats.take()
        steps, pending = 0, []
        ctx.window_open()
        t0 = time.perf_counter()
        while True:
            with ctx.span("next_batch"):
                batch = next(self.feed)
            with ctx.span("dispatch"):
                self.state, m = ctx.guarded(self.step_fn, self.state, batch)
            pending.append(m["loss"])
            steps += 1
            if steps % sync_every == 0:
                with ctx.span("sync"):
                    jax.device_get(pending)  # the loop's log-interval fetch
                pending.clear()
                ctx.at_sync(time.perf_counter() - t0)
                if time.perf_counter() - t0 >= seconds:
                    break
        t1 = time.perf_counter()
        ctx.window_close()
        stall_s, _ = self.stats.take()
        return {
            "steps": steps, "global_batch": self.global_batch, "window_s": t1 - t0,
            "data_stall_s": stall_s, "sync_every": sync_every,
        }

    def close(self):
        self.feed.close()
        self.state = None
        gc.collect()

    def reference(self, matmul=None) -> dict:
        """The plain reference over the followed batches, from the benchmark's
        own weights and key."""
        devices = jax.devices()[: self.cell["chips"]]
        w0 = jax.device_put(self.w0, devices[0])
        out = Reference(self.ref_run, matmul=matmul, devices=devices).run(
            w0, self.followed, self.rng, self.follow_steps, steady=self.steady
        )
        return reference_side(out)

    def step_flops(self) -> float:
        """Matmul+conv FLOPs one optimizer step NEEDS: the reference's forward
        and backward of one image (frozen layers get no weight gradient,
        nothing is recomputed), times the images of a step."""
        from perfbench.flops import count_matmul_flops

        ref, batch = self.ref_run, self.followed[0]
        ka, ks = step_keys(self.rng, 0, 1)
        args = (
            jnp.asarray(batch["images"][0]), jnp.asarray(batch["gt_boxes"][0], jnp.float32),
            jnp.asarray(batch["gt_classes"][0], jnp.int32), jnp.asarray(batch["gt_valid"][0]),
            jnp.asarray(batch["image_hw"][0], jnp.float32), ka[0], ks[0],
        )
        wt = {p: v for p, v in self.w0.items() if D.trainable(ref, p)}
        wf = {p: v for p, v in self.w0.items() if p not in wt}

        def one(wt):
            return jax.grad(lambda wt: D.image_loss(ref, {**wf, **wt}, 1.0, 1.0, *args)[0])(wt)

        return count_matmul_flops(one, wt) * self.global_batch


def reference_side(out: dict) -> dict:
    """``Reference.run``'s result as ``compare.train_numbers`` takes a side."""
    return dict(out, steps=[
        {"loss": s["loss"], "rpn": s["rpn_cls"] + s["rpn_box"],
         "rcnn": s["rcnn_cls"] + s["rcnn_box"], "grad_norm": s["grad_norm"]}
        for s in out["steps"]
    ])


def run(ctx) -> dict:
    """One benchmark run of a training cell.  -> result fields (run.py)."""
    cell = TrainCell(ctx)
    prog = cell.follow()
    ctx.setup_done()
    counters = cell.window(ctx.seconds)
    memory = ctx.memory()
    scopes = program.op_scopes(cell.step_fn, cell.state, next(cell.feed)) if ctx.trace else None
    pool_impl = program.pool_impl()
    cell.close()

    t_ref = time.perf_counter()
    ref_res = cell.reference()
    numbers = compare.train_numbers(prog, ref_res)
    extra = {
        "reference_s": time.perf_counter() - t_ref, "pool_impl": pool_impl,
        "loss1": [prog["steps"][0]["loss"], ref_res["steps"][0]["loss"]],
        "grad_norm1_ref": ref_res["steps"][0]["grad_norm"],
    }
    images_done = counters["steps"] * counters["global_batch"]
    return {
        "attempted": images_done, "failed": 0,
        "end_to_end": {
            "train_img_s_chip": images_done / counters["window_s"] / ctx.cell["chips"]
        },
        "counters": counters, "memory": memory, "numbers": numbers, "extra": extra,
        "step_flops": cell.step_flops, "program_name": "jit_step", "scopes": scopes,
    }
