"""Jitted, sharded train and eval steps, compiled through the execution plan.

Replaces the reference's per-iteration runtime (SURVEY.md §4.1 hot loop):
``MutableModule.forward/backward/update`` + KVStore push/pull per parameter.
One compiled XLA program does forward, backward, gradient all-reduce (ICI)
and the optimizer update; there is no per-parameter communication schedule
to manage because XLA fuses the collectives.

All sharding/donation decisions live in :class:`~mx_rcnn_tpu.parallel.plan.
ExecutionPlan` (parallel/plan.py) — train, eval, and serving compiles go
through the same plan.  This module owns only the step BODIES: the fused
fwd+bwd+update, the steps-per-call scan, and the gradient-accumulation
loop (``shard_map`` over the data axis: grads accumulate locally in f32
across microbatches and all-reduce ONCE per optimizer step).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from mx_rcnn_tpu.detection.detector import TwoStageDetector
from mx_rcnn_tpu.detection.graph import Batch, forward_inference, forward_train
from mx_rcnn_tpu.parallel.mesh import DATA_AXIS, spatial_sharding
from mx_rcnn_tpu.parallel.plan import ExecutionPlan
from mx_rcnn_tpu.train.state import TrainState, state_variables
from mx_rcnn_tpu.utils.precision import policy_of


def _bucketed_pmean(grads, bucket_mb: int):
    """All-reduce a gradient pytree in ~``bucket_mb``-MiB buckets.

    Leaves are grouped in REVERSE flatten order — the backbone's deep
    layers flatten first and backward produces gradients output-to-input,
    so reversed order is (approximately) completion order.  Each bucket
    rides its own ``pmean``, so the scheduler can launch the first
    buckets' collectives while backward is still computing the last —
    the overlap a single whole-tree reduce structurally forbids (it
    depends on EVERY leaf).

    Exact: ``pmean`` over a list reduces each leaf independently, so a
    leaf's value is bit-identical whatever bucket it rides in —
    bucketed vs single differ only in schedule, never in numerics.

    On the installed jax (0.9.0) they do not differ in the TRACE either:
    ``pmean`` of a list traces to one ``psum`` per leaf whatever the
    grouping, so the bucket size never reaches the compiler — the
    shard_map step compiles to the same 2 all-reduce ops at 64 MiB and at
    8 MiB on four chips (PERF.md, "Bring-up, PR 21").  What ``bucket_mb >
    0`` still changes is which STEP compiles (shard_map vs GSPMD's 4
    all-reduces); whether either schedule is faster is unmeasured
    (ROADMAP S7), and the grouping below stays until that verdict (D2).
    """
    if bucket_mb <= 0:
        return jax.lax.pmean(grads, DATA_AXIS)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    budget = bucket_mb * (1 << 20)
    buckets, cur, cur_bytes = [], [], 0
    for idx in reversed(range(len(leaves))):
        leaf = leaves[idx]
        nbytes = leaf.size * jnp.dtype(leaf.dtype).itemsize
        if cur and cur_bytes + nbytes > budget:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(idx)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    out = [None] * len(leaves)
    for bucket in buckets:
        reduced = jax.lax.pmean([leaves[i] for i in bucket], DATA_AXIS)
        for i, r in zip(bucket, reduced):
            out[i] = r
    return jax.tree_util.tree_unflatten(treedef, out)


def make_train_step(
    model: TwoStageDetector,
    tx: optax.GradientTransformation,
    schedule=None,
    mesh: Optional[Mesh] = None,
    spatial: bool = False,
    trainable_mask=None,
    steps_per_call: int = 1,
    pixel_stats=None,
    accum_steps: int = 1,
    plan: Optional[ExecutionPlan] = None,
    state_template: Optional[TrainState] = None,
):
    """Build ``step(state, batch) -> (state, metrics)``.

    With a mesh: state placed per the plan's partition rules (pure DP:
    replicated), batch sharded over the data axis; the gradient all-reduce
    is implicit in XLA's SPMD partitioning.  Without: plain single-device
    jit.  State buffers are donated — params update in place in HBM.

    ``spatial``: additionally shard the image height over the mesh's model
    axis (parallel/mesh.py::spatial_sharding) — XLA partitions the
    backbone convs with halo exchange; the detection head's flatten/top-k
    ops re-gather where profitable (XLA's choice).

    ``trainable_mask``: optional params-shaped bool pytree (True =
    trainable).  Frozen leaves enter the loss under ``stop_gradient`` so
    XLA deletes their whole backward computation — the reference likewise
    never runs backward for ``fixed_param`` layers; the optimizer's
    set_to_zero on the same mask alone would still compute (then discard)
    those gradients.  Freezing the stem+stage1 is ~40% of the R50
    backbone's forward FLOPs whose weight-gradient pass disappears.

    ``accum_steps`` > 1: the batch arrives STACKED (N, B, ...) and one
    optimizer step accumulates gradients over the N microbatches
    (``lax.scan``, f32 accumulators per utils/precision.py) — the
    large-minibatch lever (Goyal et al. 2017) when the target global
    batch exceeds what the chips hold.  ``accum_steps=1`` is bit-identical
    to the plain step (it IS the plain step — same trace), so the chaos
    harness's bit-exact-resume proof carries over unchanged.  Per-image
    rng keys are derived for the FULL (N*B) global batch and sliced per
    microbatch, so an accumulated step samples the same anchors/rois per
    image as one monolithic (N*B,) batch would — the parity oracle
    tests/test_plan.py asserts.

    ``plan`` / ``state_template``: an explicit ExecutionPlan (otherwise
    built from the model's family vocabulary) and a state whose structure
    resolves the per-leaf in/out shardings (otherwise a broadcast
    replicated spec — identical program while every rule is ``P()``).
    """
    if plan is None:
        plan = ExecutionPlan.for_model(
            model, mesh=mesh, spatial=spatial, accum_steps=accum_steps,
            steps_per_call=steps_per_call,
        )
    mesh, spatial = plan.mesh, plan.spatial
    spatial_spec = (
        spatial_sharding(mesh) if spatial and mesh is not None else None
    )
    # The Pallas ROIAlign shard_map wrap needs the mesh at trace time.
    # Spatial partitioning shards feature heights over the model axis — a
    # layout the per-shard kernel contract doesn't cover — so those runs
    # keep mesh=None here and the XLA path (see mesh_safe_model_cfg).
    # Inside the accumulation shard_map the step is ALREADY per-shard, so
    # the kernel runs its single-device form there too.
    roi_mesh = mesh if (mesh is not None and not spatial) else None

    def _finish(state: TrainState, grads, metrics):
        with jax.named_scope("guardian"):
            # On-device finiteness reduction (train/guardian.py): ONE 0/1
            # scalar covering the gradient global norm (inf/NaN anywhere
            # in the grad tree makes the norm non-finite) and every loss
            # metric.  It rides the metric dict the loop already fetches
            # once per log interval — no per-step host sync is added, so
            # the hot loop stays transfer_guard-clean (tools/tpulint.py).
            finite = jnp.isfinite(optax.global_norm(grads))
            for key in sorted(metrics):
                finite &= jnp.all(jnp.isfinite(metrics[key]))
            nonfinite = 1.0 - finite.astype(jnp.float32)
        with jax.named_scope("optimizer"):
            new_state = state.apply_gradients(grads, tx)
        metrics = dict(metrics, nonfinite=nonfinite)
        if schedule is not None:
            with jax.named_scope("schedule"):
                metrics["lr"] = schedule(state.step)
        return new_state, metrics

    def _masked(params):
        if trainable_mask is None:
            return params
        return jax.tree_util.tree_map(
            lambda p, t: p if t else jax.lax.stop_gradient(p),
            params,
            trainable_mask,
        )

    def step(state: TrainState, batch: Batch):
        if spatial_spec is not None:
            batch = batch._replace(
                images=jax.lax.with_sharding_constraint(
                    batch.images, spatial_spec
                )
            )
        with jax.named_scope("rng"):
            rng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(params):
            variables = {"params": _masked(params), **state.model_state}
            total, metrics = forward_train(
                model, variables, rng, batch, mesh=roi_mesh,
                pixel_stats=pixel_stats,
            )
            return total, metrics

        grads, metrics = jax.grad(loss_fn, has_aux=True)(state.params)
        return _finish(state, grads, metrics)

    def multi_step(state: TrainState, batches: Batch):
        # The host-side step loop, moved on-device: scan over the leading
        # (K, B, ...) axis.  One dispatch per K optimizer steps — the
        # per-call dispatch cost amortizes K-fold.  rng/schedule stay
        # per-step correct because `step` keys everything off state.step.
        new_state, mets = jax.lax.scan(step, state, batches)
        # Per-call metrics: mean over the K steps (lr: the last step's).
        # The f32 cast is the metric-accumulation contract (a no-op today
        # — every loss/metric upcasts inside its accumulation scope — but
        # it pins the K-step mean to f32 even if a future metric leaf
        # arrives in bf16).
        metrics = jax.tree_util.tree_map(
            lambda m: jnp.mean(m.astype(jnp.float32), axis=0), mets
        )
        if schedule is not None:
            metrics["lr"] = mets["lr"][-1]
        return new_state, metrics

    # --- gradient accumulation (accum_steps > 1) -------------------------
    # f32 accumulators: grads are cast to the precision policy's accum
    # dtype before summing, divided by N, then cast back to the param
    # dtype for the optimizer (a no-op with f32 masters).
    acc_dtype = policy_of(model.cfg).accum_dtype

    def _accum_local(params, model_state, batches, a_keys, s_keys):
        """Mean grads/metrics over the N stacked microbatches.

        Runs per-shard inside the accumulation shard_map when a mesh is
        present (batches/keys then hold this shard's rows), or on the
        whole batch off-mesh.  Losses normalize by each microbatch's own
        sampled-anchor/roi count, so the mean over microbatches equals
        the monolithic big-batch loss exactly when every image meets its
        sampling quota (the usual case) and to normalizer-weighting
        round-off otherwise — the documented accumulation contract
        (docs/scaling.md).
        """
        n = batches.images.shape[0]

        def loss_fn(p, mb, ak, sk):
            variables = {"params": _masked(p), **model_state}
            return forward_train(
                model, variables, None, mb, mesh=None,
                pixel_stats=pixel_stats, rngs=(ak, sk),
            )

        def body(g_acc, xs):
            mb, ak, sk = xs
            grads, metrics = jax.grad(loss_fn, has_aux=True)(
                params, mb, ak, sk
            )
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(acc_dtype), g_acc, grads
            )
            return g_acc, metrics

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, acc_dtype), params
        )
        g_sum, mets = jax.lax.scan(body, g0, (batches, a_keys, s_keys))
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / n).astype(p.dtype), g_sum, params
        )
        metrics = jax.tree_util.tree_map(
            lambda m: jnp.mean(m.astype(jnp.float32), axis=0), mets
        )
        return grads, metrics

    def _accum_psum(params, model_state, batches, a_keys, s_keys):
        # Per-shard local means, ONE all-reduce pass per optimizer step
        # (bucketed when plan.bucket_mb > 0) — the reason this is
        # shard_map and not jit+GSPMD (which would all-reduce the
        # replicated scan carry every microbatch).
        grads, metrics = _accum_local(
            params, model_state, batches, a_keys, s_keys
        )
        grads = _bucketed_pmean(grads, plan.bucket_mb)
        metrics = jax.lax.pmean(metrics, DATA_AXIS)
        return grads, metrics

    def accum_step(state: TrainState, batches: Batch):
        rng = jax.random.fold_in(state.rng, state.step)
        rng_assign, rng_sample = jax.random.split(rng)
        n, b = batches.images.shape[0], batches.images.shape[1]
        if b % plan.data_shards:
            raise ValueError(
                f"microbatch size {b} not divisible by the data axis "
                f"({plan.data_shards} shards)"
            )
        # Keys for the FULL global batch, sliced (N, B): microbatch j gets
        # the rows a monolithic (N*B,) batch would hand images jB..jB+B-1.
        a_keys = jax.random.split(rng_assign, n * b).reshape(n, b, -1)
        s_keys = jax.random.split(rng_sample, n * b).reshape(n, b, -1)
        if mesh is None:
            grads, metrics = _accum_local(
                state.params, state.model_state, batches, a_keys, s_keys
            )
        else:
            kspec = P(None, DATA_AXIS)
            grads, metrics = jax.shard_map(
                _accum_psum,
                mesh=mesh,
                in_specs=(P(), P(), plan.batch_specs(), kspec, kspec),
                out_specs=(P(), P()),
                check_vma=False,
            )(state.params, state.model_state, batches, a_keys, s_keys)
        return _finish(state, grads, metrics)

    # --- overlapped non-accumulated step (plan.bucket_mb > 0, mesh) -----
    # The plain jitted step leaves the gradient all-reduce to GSPMD: one
    # whole-tree collective that depends on every leaf, so nothing moves
    # over ICI until backward fully finishes.  This variant takes the
    # per-shard view explicitly (shard_map, like the accumulation path)
    # and issues _bucketed_pmean's schedule instead — the first buckets'
    # collectives overlap the rest of backward.  Keys are derived for the
    # FULL global batch exactly as forward_train's internal split would
    # (fold_in -> split -> per-image split) and handed in via the rngs
    # override, so every image samples identically to the plain step.

    def _overlap_psum(params, model_state, batch, a_keys, s_keys):
        def loss_fn(p):
            variables = {"params": _masked(p), **model_state}
            return forward_train(
                model, variables, None, batch, mesh=None,
                pixel_stats=pixel_stats, rngs=(a_keys, s_keys),
            )

        grads, metrics = jax.grad(loss_fn, has_aux=True)(params)
        grads = _bucketed_pmean(grads, plan.bucket_mb)
        metrics = jax.lax.pmean(metrics, DATA_AXIS)
        return grads, metrics

    def overlap_step(state: TrainState, batch: Batch):
        rng = jax.random.fold_in(state.rng, state.step)
        rng_assign, rng_sample = jax.random.split(rng)
        b = batch.images.shape[0]
        if b % plan.data_shards:
            raise ValueError(
                f"batch size {b} not divisible by the data axis "
                f"({plan.data_shards} shards)"
            )
        a_keys = jax.random.split(rng_assign, b)
        s_keys = jax.random.split(rng_sample, b)
        kspec = P(DATA_AXIS)
        grads, metrics = jax.shard_map(
            _overlap_psum,
            mesh=mesh,
            in_specs=(P(), P(), plan.batch_specs(), kspec, kspec),
            out_specs=(P(), P()),
            check_vma=False,
        )(state.params, state.model_state, batch, a_keys, s_keys)
        return _finish(state, grads, metrics)

    if plan.accum_steps > 1:
        fn = accum_step
    elif plan.steps_per_call > 1:
        fn = multi_step
    elif plan.overlap_grads:
        fn = overlap_step
    else:
        fn = step
    return plan.compile_step(fn, state_template=state_template)


def mesh_safe_model_cfg(model_cfg, mesh, spatial: bool = False):
    """Model config adjusted for spatially-partitioned meshes.

    Pure data-parallel meshes run the Pallas ROIAlign per-shard via
    ``shard_map`` (graph.py::_pool_rois) — no downgrade.  Spatial
    partitioning (model axis > 1) shards feature-map heights across chips,
    which the per-shard kernel contract doesn't cover, so those runs use
    the XLA form (identical numerics — it is the kernel's oracle).

    The TPU layout forms revert to their dense equivalents under spatial
    partitioning for the same reason — each reshapes or concatenates along
    the sharded height axis (s2d stem halves H, the packed RPN head stacks
    levels along H), turning an exact local rewrite into a cross-shard
    shuffle.  All are exact either way, so only the compiled program
    changes.  C2 lane padding widens channels, not height, and stays.
    """
    if not (spatial and mesh is not None and mesh.size > 1):
        return model_cfg
    import dataclasses

    changed = {}
    if model_cfg.rcnn.roi_align_impl == "pallas":
        changed["rcnn"] = dataclasses.replace(
            model_cfg.rcnn, roi_align_impl="xla"
        )
    if model_cfg.rpn.packed_head:
        changed["rpn"] = dataclasses.replace(model_cfg.rpn, packed_head=False)
    bb = model_cfg.backbone
    if bb.stem_s2d or bb.stem_pool_fold:
        changed["backbone"] = dataclasses.replace(
            bb, stem_s2d=False, stem_pool_fold=False
        )
    return dataclasses.replace(model_cfg, **changed) if changed else model_cfg


def make_sharded_infer(
    fn, mesh: Optional[Mesh] = None, gather_outputs: bool = False,
    plan: Optional[ExecutionPlan] = None,
):
    """Jit an inference-shaped ``fn(variables, batch)`` for the mesh:
    replicated params, data-sharded batch.  The one scaffolding shared by
    eval, proposal dumps, and any future read-only pass — all via
    :meth:`ExecutionPlan.compile_infer`, the same plan the train step
    compiles through.

    ``gather_outputs``: replicate the outputs across the mesh (an XLA
    all-gather at the step's end).  Multi-host runs need it — a host can
    only ``device_get`` what it addresses, and detection/proposal outputs
    are tiny next to the step's compute."""
    if plan is None:
        plan = ExecutionPlan(mesh=mesh)
    return plan.compile_infer(fn, gather_outputs=gather_outputs)


def make_eval_step(
    model: TwoStageDetector,
    mesh: Optional[Mesh] = None,
    gather_outputs: bool = False,
    pixel_stats=None,
    plan: Optional[ExecutionPlan] = None,
):
    """Build ``eval_step(variables, batch) -> Detections`` (jitted)."""

    def step(variables, batch: Batch):
        return forward_inference(
            model, variables, batch, mesh=mesh, pixel_stats=pixel_stats
        )

    return make_sharded_infer(step, mesh, gather_outputs, plan=plan)


def eval_variables(state: TrainState) -> dict:
    """Inference variables from a train state (no weight folding needed —
    see train/checkpoint.py docstring)."""
    return state_variables(state)
