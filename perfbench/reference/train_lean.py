"""The reference's training steps for a state that fills the chip: the same
arithmetic as ``train.py`` (it IS ``Reference``'s per-image gradient and the
same optimizer formula), with nothing held twice.

``Reference.run`` keeps the first weights, the weights, the momentum, the sum
of gradients, one image's gradients and the update's three results on the
device at once: seven float32 copies at its peak, which 0.8 G parameters
cannot have on a 16 GB chip.  Here the weights start from the HOST's copy and
are updated in place; each image's gradient is added into one accumulator in
place; the momentum waits on the host while gradients are taken; the update's
norms are taken inside the update; and the change is measured against the
host's copy a leaf at a time.  At most three copies and one image's
activations are alive.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import detector as D
from perfbench.reference.train import Reference, learning_rate, step_keys


class LeanReference(Reference):
    def __init__(self, ref, matmul=None, devices=None):
        super().__init__(ref, matmul=matmul, devices=devices)
        self._add = jax.jit(
            lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=(0,)
        )
        self._update_lean = jax.jit(
            partial(self._apply_lean, ref["optimizer"]), donate_argnums=(0, 1),
            static_argnums=(4,),
        )
        self._gap = jax.jit(lambda a, b: jnp.linalg.norm(a - b))

    @staticmethod
    def _apply_lean(opt, wt, trace, grads, lr, steady):
        """``Reference._apply`` with the clipped gradient's leaf norms (and the
        ``steady`` leaves themselves) in place of the whole clipped gradient."""
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-30))
        new_w, new_t, norms, held = {}, {}, {}, {}
        for p in wt:
            g = grads[p] * scale
            norms[p] = jnp.linalg.norm(g)
            if steady and p.startswith(steady):
                held[p] = g
            if D.decayed(p):
                g = g + opt["weight_decay"] * wt[p]
            t = g + opt["momentum"] * trace[p]
            new_t[p] = t
            new_w[p] = wt[p] - lr * t
        return new_w, new_t, norms, held, gnorm

    def batch_grads(self, wt, wf, batch, rng, step):
        """The batch's summed gradient and loss sums, one image at a time."""
        ref, dev = self.ref, self.devices[0]
        b = batch["images"].shape[0]
        ka, ks = step_keys(rng, step, b)

        def sweep(n_rpn, n_rcnn):
            acc = tot = None
            for i in range(b):
                args = jax.device_put((
                    jnp.asarray(batch["images"][i]), jnp.asarray(batch["gt_boxes"][i], jnp.float32),
                    jnp.asarray(batch["gt_classes"][i], jnp.int32),
                    jnp.asarray(batch["gt_valid"][i]),
                    jnp.asarray(batch["image_hw"][i], jnp.float32), ka[i], ks[i],
                ), dev)
                g, s = self._grad(wt, wf, n_rpn, n_rcnn, *args)
                acc = g if acc is None else self._add(acc, g)
                tot = s if tot is None else jax.tree_util.tree_map(jnp.add, tot, s)
                del g
            return acc, tot

        # As ``Reference.batch_step``: the quotas are full in all but
        # degenerate batches; sweep again with the true counts if not.
        n_rpn = float(b * ref["rpn"]["batch_size"])
        n_rcnn = float(b * ref["rcnn"]["roi_batch_size"])
        grads, tot = sweep(n_rpn, n_rcnn)
        true = (float(tot["n_rpn"]), float(tot["n_rcnn"]))
        if true != (n_rpn, n_rcnn):
            del grads
            n_rpn, n_rcnn = true
            grads, tot = sweep(n_rpn, n_rcnn)
        report = {
            "rpn_cls": float(tot["rpn_cls"]) / max(n_rpn, 1.0),
            "rpn_box": float(tot["rpn_box"]) / max(n_rpn, 1.0),
            "rcnn_cls": float(tot["rcnn_cls"]) / max(n_rcnn, 1.0),
            "rcnn_box": float(tot["rcnn_box"]) / max(n_rcnn, 1.0),
        }
        report["loss"] = sum(report.values())
        return grads, report

    def run(self, w0, batches, rng, n_steps, steady="", unchanged=False):
        """``Reference.run``'s result from ``w0`` = {path: HOST array}."""
        ref, dev = self.ref, self.devices[0]
        w = jax.device_put(w0, dev)
        wt = {p: v for p, v in w.items() if D.trainable(ref, p)}
        wf = {p: v for p, v in w.items() if p not in wt}
        del w
        trace_host = {p: np.zeros(v.shape, np.float32) for p, v in wt.items()}
        reports, first, first_held = [], None, {}
        for t in range(n_steps):
            # An unchanged state keeps its step count too: key and rate of step 0.
            step = 0 if unchanged else t
            grads, rep = self.batch_grads(wt, wf, batches[t], rng, step)
            if unchanged:  # nothing applied: the optimizer state holds no gradient
                norms = {p: 0.0 for p in wt}
                held = {p: np.zeros(v.shape, np.float32) for p, v in wt.items()
                        if steady and p.startswith(steady)}
                rep["grad_norm"] = 0.0
                del grads
            else:
                lr = learning_rate(ref["optimizer"], step, ref["global_batch"])
                trace = jax.device_put(trace_host, dev)
                wt, trace, norms, held, gnorm = self._update_lean(wt, trace, grads, lr, steady)
                del grads
                trace_host = jax.device_get(trace)
                del trace
                rep["grad_norm"] = float(gnorm)
            reports.append(rep)
            if t == 0:
                first = {p: float(v) for p, v in jax.device_get(norms).items()}
                first_held = jax.device_get(held)
        change = {p: float(self._gap(wt[p], jax.device_put(w0[p], dev))) for p in wt}
        return {
            "steps": reports, "grad1": first, "change": change,
            "steady_grad1": first_held,
            "steady_change": {
                p: jax.device_get(wt[p]) - w0[p] for p in wt if steady and p.startswith(steady)
            },
        }
