"""Shared per-stage train-step ablation timing.

One implementation of the "successively larger prefixes of forward_train"
breakdown (backbone -> +RPN head -> +assign/RPN losses -> +proposals ->
+sampling -> +ROIAlign -> full step), used by BOTH
``tools/perf_breakdown.py`` (the interactive drill-down tool) and
``bench.py --breakdown`` (which emits one JSON line per stage into the
BENCH artifact so a regression in future BENCH_r*.json files localizes
itself without a separate tool run).

Timing method is the repo-wide rule (BASELINE.md): n dependency-chained
executions inside one ``lax.scan`` dispatch ended by ONE device->host
fetch of a dependent value — a barrier on any runtime, with the per-call
dispatch cost paid once per n steps instead of once per step.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def timed(fn, arg, n, calls=3, extra=None):
    """Time n dependency-chained executions of ``fn`` per device call.

    The chain lives INSIDE a ``lax.scan`` (one dispatch per n steps): each
    scan iteration perturbs the carry with 0 * the step's output, so step
    i+1 provably depends on step i and the single final fetch waits for the
    whole chain (BASELINE.md timing rule).  The scan matches bench.py's
    step loop, so stage times and the headline are taken the same way;
    on the local runtime one dispatch costs 0.2 ms (PR 21 chip run), so
    the scan no longer protects short stages from a large per-call cost —
    whether it stays is ROADMAP D4's to decide.

    ``extra``: a pytree of large scan-invariant inputs (feature maps,
    params) passed as a jit ARGUMENT — closing over device arrays would
    embed them as constants in the HLO module, which the compiler then
    has to fold and every cached executable has to carry."""

    def chain(carry, ex):
        def body(c, _):
            out = fn(c) if ex is None else fn(c, ex)
            c2 = jax.tree_util.tree_map(lambda x, g: x + 0.0 * g, c, out)
            return c2, ()

        return jax.lax.scan(body, carry, None, length=n)[0]

    chained = jax.jit(chain)
    carry = chained(arg, extra)  # compile + warm
    jax.device_get(jax.tree_util.tree_leaves(carry)[0].ravel()[0])
    t0 = time.perf_counter()
    for _ in range(calls):
        carry = chained(carry, extra)
    jax.device_get(jax.tree_util.tree_leaves(carry)[0].ravel()[0])
    return (time.perf_counter() - t0) / (n * calls)


def train_stage_fns(model, params, rest, batch, key, masked=None):
    """The train breakdown's (name, loss_fn(params)) stage list.

    Each stage is "everything before it" + one more piece of the train
    graph; all stages keep the RPN loss term so the backbone backward
    exists in every variant (in the real graph proposals/sampling are
    stop-grad side computations).  ``masked`` applies the production
    freeze (stop-grad on frozen prefixes); identity when None.
    """
    from mx_rcnn_tpu.detection import forward_train
    from mx_rcnn_tpu.detection.graph import (
        _pool_rois,
        _propose_one,
        _rpn_losses,
        _slice_levels,
        assign_anchors_cfg,
        level_anchors,
    )
    from mx_rcnn_tpu.ops import sample_rois

    mcfg = model.cfg
    b = batch.images.shape[0]
    if masked is None:
        def masked(p):
            return p

    def front(p, upto: str):
        v = {"params": masked(p), **rest}
        feats = model.apply(v, batch.images, method="features")
        if upto == "backbone":
            return sum(jnp.sum(f.astype(jnp.float32) ** 2) for f in feats.values())
        rpn_out = model.apply(v, feats, method="rpn")
        anchors = level_anchors(mcfg, feats)
        levels = sorted(rpn_out)
        logits = jnp.concatenate([rpn_out[l][0] for l in levels], axis=1)
        deltas = jnp.concatenate([rpn_out[l][1] for l in levels], axis=1)
        if upto == "rpn":
            return sum(
                jnp.sum(o.astype(jnp.float32) ** 2)
                for pair in rpn_out.values() for o in pair
            )
        anchors_cat = jnp.concatenate([anchors[l] for l in levels], axis=0)
        targets = jax.vmap(
            lambda k, gt, gv, hw_: assign_anchors_cfg(
                mcfg, k, anchors_cat, gt, gv, hw_[0], hw_[1]
            )
        )(jax.random.split(key, b), batch.gt_boxes, batch.gt_valid, batch.image_hw)
        rpn_cls, rpn_box, _ = _rpn_losses(
            logits, deltas, targets, mcfg.rpn.loss_impl
        )
        loss = rpn_cls + rpn_box
        if upto == "rpnloss":
            return loss
        scores = jax.nn.sigmoid(jax.lax.stop_gradient(logits))
        propose = _propose_one(mcfg, train=True)
        props = jax.vmap(
            lambda s, d, hw_: propose(*_slice_levels(levels, anchors, s, d), hw_)
        )(scores, jax.lax.stop_gradient(deltas), batch.image_hw)
        if upto == "proposals":
            return loss + (jnp.sum(props.rois) + jnp.sum(props.scores)) * 1e-30
        samples = jax.vmap(
            lambda k, rois, rv, gt, gc, gv: sample_rois(
                k, rois, rv, gt, gc, gv,
                batch_size=mcfg.rcnn.roi_batch_size,
                fg_fraction=mcfg.rcnn.fg_fraction,
                fg_iou=mcfg.rcnn.fg_iou,
                bg_iou_hi=mcfg.rcnn.bg_iou_hi,
                bg_iou_lo=mcfg.rcnn.bg_iou_lo,
                bbox_weights=mcfg.rcnn.bbox_weights,
            )
        )(jax.random.split(key, b), props.rois, props.valid, batch.gt_boxes,
          batch.gt_classes, batch.gt_valid)
        if upto == "sample":
            return loss + jnp.sum(samples.rois) * 1e-30
        if upto == "pool_fwd":
            # Forward-only pooling: cut the feature cotangent so the delta
            # vs "sample" isolates the kernel FORWARD in-graph, and the
            # "pool" - "pool_fwd" gap isolates backward + the cost of
            # merging a second cotangent into the shared trunk backward.
            pooled = _pool_rois(
                mcfg,
                jax.tree_util.tree_map(jax.lax.stop_gradient, feats),
                samples.rois, mcfg.rcnn.pooled_size, model.roi_levels,
            )
            return loss + jnp.sum(pooled.astype(jnp.float32) ** 2) * 1e-30
        pooled = _pool_rois(
            mcfg, feats, samples.rois, mcfg.rcnn.pooled_size, model.roi_levels
        )
        if upto == "pool":
            return loss + jnp.sum(pooled.astype(jnp.float32) ** 2) * 1e-30
        raise ValueError(upto)

    def stage_full(p):
        loss, _ = forward_train(model, {"params": masked(p), **rest}, key, batch)
        return loss

    return [
        ("backbone fwd+bwd", lambda p: front(p, "backbone")),
        ("+rpn head", lambda p: front(p, "rpn")),
        ("+assign+rpn losses", lambda p: front(p, "rpnloss")),
        ("+proposal gen (stop-grad)", lambda p: front(p, "proposals")),
        ("+sample_rois (stop-grad)", lambda p: front(p, "sample")),
        ("+roialign fwd only", lambda p: front(p, "pool_fwd")),
        ("+roialign fwd+bwd", lambda p: front(p, "pool")),
        ("full forward_train+bwd", stage_full),
    ]


def grad_stage(fn):
    """jit'd fwd+bwd of a stage loss, shaped for :func:`timed`'s chain.

    value_and_grad with the VALUE folded into the output: value-only side
    branches (the pool_fwd stage's stop-grad pooling) otherwise get DCE'd
    under jax.grad and time as 0."""

    def grad_plus(p):
        val, g = jax.value_and_grad(fn)(p)
        return jax.tree_util.tree_map(
            lambda x: x + 0.0 * val.astype(x.dtype), g
        )

    return jax.jit(grad_plus)


def time_train_stages(stages, params, steps, calls=3, report=None):
    """Time each (name, loss_fn) stage; returns [(name, seconds/step)].

    ``report``: optional callback ``report(name, dt)`` invoked as each
    stage lands (both callers stream progress)."""
    results = []
    for name, fn in stages:
        dt = timed(grad_stage(fn), params, steps, calls=calls)
        results.append((name, dt))
        if report is not None:
            report(name, dt)
    return results
