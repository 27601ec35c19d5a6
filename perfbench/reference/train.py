"""The reference's training steps: batch loss, gradients, and the stated
optimizer (SGD with momentum, weight decay off biases, global-norm clip,
linear warm-up), image by image so that the float32 activations of one image
are all that is alive.  ``matmul`` swaps in the control's operand rounding.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.reference import detector as D


def learning_rate(opt, step, global_batch):
    ref_batch = opt["reference_batch"] or 16
    warm = opt["warmup_factor"] + (1.0 - opt["warmup_factor"]) * min(
        step / max(opt["warmup_steps"], 1), 1.0
    )
    return opt["base_lr"] * (global_batch / ref_batch) * warm


def step_keys(rng, step, batch):
    """Per-image (assign, sample) keys of one step, from the state's key."""
    k = jax.random.fold_in(rng, step)
    ka, ks = jax.random.split(k)
    return jax.random.split(ka, batch), jax.random.split(ks, batch)


class Reference:
    """Holds the jitted per-image functions of one configuration."""

    def __init__(self, ref, matmul=None, devices=None):
        """``devices``: the chips to spread a batch's images over (each image
        is still one plain program on one chip; only the sum of their
        gradients crosses chips).  None = the default device."""
        self.ref = ref
        self.matmul = matmul
        self.devices = list(devices) if devices else [jax.devices()[0]]

        def grad(wt, wf, n_rpn, n_rcnn, *args):
            def loss(wt):
                return D.image_loss(ref, {**wf, **wt}, n_rpn, n_rcnn, *args, matmul=matmul)

            (_, s), g = jax.value_and_grad(loss, has_aux=True)(wt)
            return g, s

        self._grad = jax.jit(grad)
        self._update = jax.jit(partial(self._apply, ref["optimizer"]))

    @staticmethod
    def _apply(opt, wt, trace, grads, lr):
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-30))
        new_w, new_t, seen = {}, {}, {}
        for p in wt:
            g = grads[p] * scale
            seen[p] = g
            if D.decayed(p):
                g = g + opt["weight_decay"] * wt[p]
            t = g + opt["momentum"] * trace[p]
            new_t[p] = t
            new_w[p] = wt[p] - lr * t
        return new_w, new_t, seen, gnorm

    def batch_step(self, w, trace, batch, rng, step):
        """One optimizer step.  ``batch``: dict of host arrays (images uint8
        (B, H, W, 3), gt_boxes, gt_classes, gt_valid, image_hw).
        -> (w, trace, report) with the step's losses and the clipped gradient."""
        ref = self.ref
        b = batch["images"].shape[0]
        ka, ks = step_keys(rng, step, b)

        devs = self.devices[: max(1, min(len(self.devices), b))]

        def args(i):
            return jax.device_put((
                jnp.asarray(batch["images"][i]), jnp.asarray(batch["gt_boxes"][i], jnp.float32),
                jnp.asarray(batch["gt_classes"][i], jnp.int32), jnp.asarray(batch["gt_valid"][i]),
                jnp.asarray(batch["image_hw"][i], jnp.float32), ka[i], ks[i],
            ), devs[i % len(devs)])

        wt = {p: v for p, v in w.items() if D.trainable(ref, p)}
        wf = {p: v for p, v in w.items() if p not in wt}
        copies = [(jax.device_put(wt, d), jax.device_put(wf, d)) for d in devs]

        def sweep(n_rpn, n_rcnn):
            parts = [None] * len(devs)
            for i in range(b):
                k = i % len(devs)
                out = self._grad(copies[k][0], copies[k][1], n_rpn, n_rcnn, *args(i))
                parts[k] = out if parts[k] is None else jax.tree_util.tree_map(jnp.add, parts[k], out)
            total = None
            for part in parts:
                part = jax.device_put(part, devs[0])
                total = part if total is None else jax.tree_util.tree_map(jnp.add, total, part)
            return total

        # The batch's normalizers are its sampled counts, known only once
        # every image has been through; the quotas are full in all but
        # degenerate batches, so sweep with them and sweep again if not.
        n_rpn = float(b * ref["rpn"]["batch_size"])
        n_rcnn = float(b * ref["rcnn"]["roi_batch_size"])
        grads, tot = sweep(n_rpn, n_rcnn)
        true = (float(tot["n_rpn"]), float(tot["n_rcnn"]))
        if true != (n_rpn, n_rcnn):
            n_rpn, n_rcnn = true
            grads, tot = sweep(n_rpn, n_rcnn)
        lr = learning_rate(ref["optimizer"], step, ref["global_batch"])
        new_wt, trace, seen, gnorm = self._update(wt, trace, grads, lr)
        parts = {
            "rpn_cls": tot["rpn_cls"] / jnp.maximum(n_rpn, 1.0),
            "rpn_box": tot["rpn_box"] / jnp.maximum(n_rpn, 1.0),
            "rcnn_cls": tot["rcnn_cls"] / jnp.maximum(n_rcnn, 1.0),
            "rcnn_box": tot["rcnn_box"] / jnp.maximum(n_rcnn, 1.0),
        }
        report = {k: float(v) for k, v in parts.items()}
        report["loss"] = sum(report.values())
        report["grad_norm"] = float(gnorm)
        return {**wf, **new_wt}, trace, report, seen

    def run(self, w0, batches, rng, n_steps, steady="", unchanged=False):
        """Follow the first ``n_steps`` from ``w0``.  -> dict with the losses
        of each step, the leaf norms of the first gradient as the optimizer
        applied it (after the clip) and of the parameters' change, and, for
        the leaves under the ``steady`` prefix, the first gradient and the
        change themselves.  ``unchanged`` plants the fault of a step that
        returns its state as it got it (readings.py)."""
        ref = self.ref
        w = dict(w0)
        trace = {p: jnp.zeros_like(v) for p, v in w.items() if D.trainable(ref, p)}
        held = [p for p in trace if steady and p.startswith(steady)]
        reports, first, first_held = [], None, {}
        for t in range(n_steps):
            # An unchanged state keeps its step count too: key and rate of step 0.
            new_w, new_trace, rep, seen = self.batch_step(
                w, trace, batches[t], rng, 0 if unchanged else t
            )
            if unchanged:  # ... and its optimizer state holds no gradient
                seen = {p: jnp.zeros_like(g) for p, g in seen.items()}
            else:
                w, trace = new_w, new_trace
            reports.append(rep)
            if t == 0:
                first = {p: float(jnp.linalg.norm(g)) for p, g in seen.items()}
                first_held = {p: jax.device_get(seen[p]) for p in held}
        change = {
            p: float(jnp.linalg.norm(w[p] - w0[p])) for p in trace
        }
        return {
            "steps": reports, "grad1": first, "change": change,
            "steady_grad1": first_held,
            "steady_change": {p: jax.device_get(w[p] - w0[p]) for p in held},
        }
