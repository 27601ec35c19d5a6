"""The training loop.

Replaces ``MutableModule.fit`` + the driver body of ``train_end2end.py``
(SURVEY.md §4.1): one function that wires loader → sharded step → metrics →
checkpoints.  Reused verbatim by every training mode — end-to-end, the
RPN/RCNN phases of alternate training (phase behavior is expressed through
the config's loss weights and freeze prefixes, not separate code paths) —
where the reference re-implements the loop per tool
(``rcnn/tools/train_rpn.py``, ``train_rcnn.py``).
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Optional

import jax
import numpy as np

from mx_rcnn_tpu import obs
from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.data import DetectionLoader, build_dataset, filter_roidb
from mx_rcnn_tpu.detection import TwoStageDetector
from mx_rcnn_tpu.parallel import (
    PrefetchStats,
    device_prefetch,
    is_primary,
    make_mesh,
    make_train_step,
)
from mx_rcnn_tpu.parallel.mesh import MODEL_AXIS
from mx_rcnn_tpu.train.checkpoint import (
    delete_steps_after,
    finite_state,
    flush_checkpoints,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from mx_rcnn_tpu.train.guardian import Guardian
from mx_rcnn_tpu.train.metrics import (
    ScalarWriter,
    Speedometer,
    host_interval_metrics,
)
from mx_rcnn_tpu.train.preemption import Preempted, PreemptionGuard
from mx_rcnn_tpu.train.optim import frozen_mask, make_optimizer
from mx_rcnn_tpu.train.state import TrainState, create_train_state
from mx_rcnn_tpu.utils import ProfileWindow
from mx_rcnn_tpu.utils.compile_cache import compile_totals

log = logging.getLogger("mx_rcnn_tpu")

# fixed_param_prefix equivalents per backbone (reference: conv1/res2 frozen
# for ResNet, conv1_/conv2_ for VGG — train_end2end.py arg defaults).
FREEZE_PREFIXES = {
    "resnet50": ("backbone/conv1", "backbone/bn1", "backbone/layer1"),
    "resnet101": ("backbone/conv1", "backbone/bn1", "backbone/layer1"),
    # VGG groups 1-2 = conv1_x/conv2_x (reference: fixed conv1_/conv2_).
    "vgg16": ("backbone/group1", "backbone/group2"),
}


def scale_schedule_steps(sched, global_batch: int):
    """Rescale step-denominated schedule fields by
    ``reference_batch / global_batch`` (the step half of the linear-scaling
    rule; see ScheduleConfig).  Identity when ``reference_batch`` is 0
    (absolute steps) or already matches."""
    import dataclasses as _dc

    ref = sched.reference_batch
    if not ref or global_batch == ref:
        return sched
    f = ref / global_batch
    return _dc.replace(
        sched,
        decay_steps=tuple(max(1, round(s * f)) for s in sched.decay_steps),
        total_steps=max(1, round(sched.total_steps * f)),
    )


@contextlib.contextmanager
def _setup_span(name: str):
    """One phase of :func:`build_all` on the timeline (subsystem ``train``):
    its seconds are the span's; ``programs`` / ``compile_s`` are what the
    compile listener (utils/compile_cache.py) counted inside it."""
    n0, s0 = compile_totals()
    with obs.span(name, subsystem="train") as sp:
        yield sp
        n1, s1 = compile_totals()
        sp.set(programs=n1 - n0, compile_s=round(s1 - s0, 3))


def build_all(cfg: Config, mesh=None, freeze_backbone: bool = True,
              extra_freeze: tuple[str, ...] = (),
              pretrained: Optional[str] = None):
    """Model + optimizer + fresh state + sharded step for a config.

    ``pretrained``: path to a torchvision-style ResNet ``.pth`` whose
    weights+BN stats seed the backbone (reference: ``load_param`` on the
    ImageNet ``.params`` file before training)."""
    from mx_rcnn_tpu.parallel.step import mesh_safe_model_cfg

    model_cfg = mesh_safe_model_cfg(
        cfg.model, mesh, spatial=cfg.train.spatial_partition > 1
    )
    if model_cfg is not cfg.model:
        log.info(
            "spatial partitioning: using the XLA ROIAlign and dense "
            "stem/RPN-head forms (the Pallas kernel's shard_map wrap and "
            "the height-axis layout rewrites cover unsharded heights only)"
        )
    model = TwoStageDetector(cfg=model_cfg)
    rng = jax.random.PRNGKey(cfg.train.seed)
    n_dev = mesh.size if mesh is not None else 1
    sp = cfg.train.spatial_partition
    if sp > 1:
        if mesh is None:
            raise ValueError(
                f"spatial_partition={sp} needs a device mesh "
                "(single-device runs cannot shard the height axis)"
            )
        if mesh.shape[MODEL_AXIS] != sp:
            raise ValueError(
                f"mesh model axis is {mesh.shape[MODEL_AXIS]} but "
                f"spatial_partition={sp}; build the mesh with "
                f"make_mesh(model_parallel={sp})"
            )
    # With spatial partitioning, `sp` chips cooperate on each image: the
    # data axis shrinks by sp, and so does the global batch.  Gradient
    # accumulation multiplies it back up: one optimizer step sees
    # accum_steps microbatches, so the EFFECTIVE global batch (what the
    # linear-scaling rule and the img/s meter care about) includes it.
    accum = cfg.train.accum_steps
    global_batch = cfg.train.per_device_batch * (n_dev // sp) * accum
    # Linear-scaling rule, both halves: lr scales UP by global_batch/ref
    # and the step-denominated schedule scales DOWN by ref/global_batch,
    # so any pod size trains the same epochs (reference drivers:
    # ``lr * len(ctx) * kv.num_workers`` with epoch schedules).
    sched = scale_schedule_steps(cfg.train.schedule, global_batch)
    train_cfg = cfg.train
    if sched is not cfg.train.schedule:
        import dataclasses as _dc

        log.info(
            "schedule rescaled for global batch %d (reference %d): "
            "decay %s -> %s, total %d -> %d",
            global_batch, cfg.train.schedule.reference_batch,
            cfg.train.schedule.decay_steps, sched.decay_steps,
            cfg.train.schedule.total_steps, sched.total_steps,
        )
        train_cfg = _dc.replace(cfg.train, schedule=sched)
    lr_scale = global_batch / (sched.reference_batch or 16)
    freeze = ()
    if freeze_backbone and cfg.model.backbone.freeze_stages > 0:
        freeze = FREEZE_PREFIXES.get(cfg.model.backbone.name, ())
    freeze = tuple(freeze) + tuple(extra_freeze)

    # Init params first (on host) so the freeze mask can see the tree.
    probe_tx, schedule = make_optimizer(train_cfg, None, lr_scale=lr_scale)
    with _setup_span("setup.init_state"):
        state = create_train_state(
            model, probe_tx, rng, cfg.data.image_size, batch=1
        )
    if pretrained:
        from mx_rcnn_tpu.train.import_torch import load_pretrained_backbone
        from mx_rcnn_tpu.train.state import state_variables

        variables = load_pretrained_backbone(state_variables(state), pretrained)
        state = state.replace(
            params=variables["params"],
            model_state={k: v for k, v in variables.items() if k != "params"},
        )
    trainable = None
    with _setup_span("setup.optimizer"):
        if freeze:
            tx, schedule = make_optimizer(
                train_cfg, state.params, lr_scale=lr_scale,
                freeze_prefixes=freeze,
            )
            state = state.replace(opt_state=tx.init(state.params))
            # Same mask the optimizer uses: frozen leaves are
            # stop-gradient'd inside the step so their backward is
            # eliminated, not just zeroed.
            trainable = frozen_mask(state.params, freeze)
        else:
            tx = probe_tx
    # The execution plan (parallel/plan.py) owns every sharding decision
    # from here on: it validates the knob combination, resolves the
    # partition rules against the real state (unmatched leaf = hard error
    # at build time), and compiles the step.  train() rebuilds the same
    # plan (pure function of cfg+mesh) for state placement and restore.
    with _setup_span("setup.plan"):
        plan = build_plan(cfg, mesh, model=model)
    with _setup_span("setup.step"):
        step_fn = make_train_step(
            model, tx, schedule, trainable_mask=trainable,
            pixel_stats=(cfg.data.pixel_mean, cfg.data.pixel_std),
            plan=plan, state_template=state,
        )
    return model, tx, state, step_fn, global_batch


def build_plan(cfg: Config, mesh=None, model: Optional[TwoStageDetector] = None):
    """The config's ExecutionPlan — shared by build_all and train()."""
    from mx_rcnn_tpu.parallel.plan import ExecutionPlan
    from mx_rcnn_tpu.parallel.step import mesh_safe_model_cfg

    if model is None:
        model_cfg = mesh_safe_model_cfg(
            cfg.model, mesh, spatial=cfg.train.spatial_partition > 1
        )
        model = TwoStageDetector(cfg=model_cfg)
    return ExecutionPlan.for_model(
        model,
        mesh=mesh,
        spatial=cfg.train.spatial_partition > 1,
        accum_steps=cfg.train.accum_steps,
        steps_per_call=cfg.train.steps_per_call,
        bucket_mb=cfg.train.bucket_mb,
    )


def _flat_config(d: dict, prefix: str = "") -> dict:
    out = {}
    for key, v in d.items():
        path = f"{prefix}{key}"
        if isinstance(v, dict):
            out.update(_flat_config(v, path + "."))
        else:
            out[path] = v
    return out


class ConfigDriftError(RuntimeError):
    """--strict-resume: the resumed config differs from the run-start one."""


def _warn_config_drift(
    cfg: Config, config_json_path: str, strict: bool = False
) -> None:
    """Resuming under a different config than the run was started with
    silently changes the training trajectory — the global batch / lr scale
    shift the schedule, and the loader's fast-forward replays a different
    data order.  The run directory's config.json records the original; log
    every differing field loudly (intentional overrides on resume are
    legitimate), or — ``strict`` (the ``--strict-resume`` flag, production
    runs) — fail hard with the full drift list."""
    import dataclasses as _dc
    import json as _json
    import os as _os

    if not _os.path.exists(config_json_path):
        return
    try:
        with open(config_json_path) as f:
            saved = _flat_config(_json.load(f))
    except (OSError, ValueError):  # unreadable/corrupt — nothing to compare
        return
    current = _flat_config(_dc.asdict(cfg))

    def norm(v):
        return list(v) if isinstance(v, tuple) else v

    drift: list[str] = []
    for key in sorted(set(saved) | set(current)):
        a, b = saved.get(key), norm(current.get(key))
        if a != b:
            drift.append(f"{key}: {a!r} -> {b!r}")
            log.warning(
                "resume config drift: %s was %r at run start, now %r — "
                "schedule/data continuity is NOT guaranteed across this "
                "change", key, a, b,
            )
    if strict and drift:
        raise ConfigDriftError(
            "--strict-resume: config drifted from the run-start "
            f"config.json ({config_json_path}):\n  " + "\n  ".join(drift)
        )


def _stacked_batches(it, k: int):
    """Group k consecutive host batches into one (k, B, ...) stacked Batch
    for a steps_per_call>1 device loop.  Closing this generator closes its
    source — the teardown chain (device_prefetch → here → loader iterator
    → input-service workers) must reach the bottom or worker processes and
    prefetch threads outlive the run."""
    buf = []
    try:
        for b in it:
            buf.append(b)
            if len(buf) == k:
                yield type(b)(
                    *[
                        None if fields[0] is None else np.stack(fields)
                        for fields in zip(*buf)
                    ]
                )
                buf = []
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()


def _save(ckpt_dir: str, state: TrainState, step: int,
          wait: bool = False) -> None:
    """One checkpoint save: a ``checkpoint`` span on the timeline (the
    device_get and the write the loop waits for) and its journal event."""
    with obs.span("checkpoint", subsystem="train", attrs={"step": step}):
        save_checkpoint(ckpt_dir, jax.device_get(state), wait=wait)
    obs.emit("train", "checkpoint_saved", {"step": step}, logger=log)


def _report_recompiles(since_ns: int, upto_step: int) -> None:
    """``train/recompiled`` for every program built since ``since_ns``: in a
    steady loop nothing compiles after the first interval, so each is a
    shape, dtype or static argument that changed under the loop.  ``step``
    is the one whose ``train_step`` span holds the compile's end."""
    compiles = [
        c for c in obs.tracer().recent(since_ns, "jit")
        if c.name == "jit.compile"
    ]
    if not compiles:
        return
    steps = [
        s for s in obs.tracer().recent(since_ns, "train")
        if s.name == "train_step"
    ]
    for c in compiles:
        step = next(
            (s.attrs["step"] for s in steps
             if s.start_ns <= c.end_ns <= s.end_ns),
            upto_step,
        )
        obs.emit("train", "recompiled", {
            "step": step, "fun_name": c.attrs.get("fun_name", "?"),
            "seconds": c.dur_ns / 1e9,
        }, logger=log)


def train(
    cfg: Config,
    mesh=None,
    total_steps: Optional[int] = None,
    workdir: Optional[str] = None,
    resume: bool = False,
    state: Optional[TrainState] = None,
    extra_freeze: tuple[str, ...] = (),
    loader: Optional[DetectionLoader] = None,
    profile_dir: Optional[str] = None,
    profile_steps: tuple[int, int] = (10, 15),
    pretrained: Optional[str] = None,
    proposals_path: Optional[str] = None,
    strict_resume: bool = False,
) -> TrainState:
    """Train for ``total_steps`` (default: cfg schedule length); returns the
    final state (host-fetchable).  Pass ``state`` to continue from an earlier
    phase (alternate training), ``resume`` to restore from workdir;
    ``profile_dir`` traces steps ``profile_steps`` into it (jax.profiler);
    ``proposals_path`` trains the box head on an external proposal pkl
    (Fast R-CNN mode — reference ``rcnn/tools/train_rcnn.py``);
    ``strict_resume`` escalates resume config drift to a hard error.

    Fault tolerance (docs/robustness.md): SIGTERM/SIGINT drain the
    in-flight step, write a synchronous emergency checkpoint and raise
    :class:`~mx_rcnn_tpu.train.preemption.Preempted` (the CLIs map it to
    the resumable exit code); non-finite metrics trigger the guardian's
    bounded rollback-and-skip, then :class:`TrainingDiverged`."""
    if cfg.obs.enabled and is_primary():
        # Durable observability (docs/observability.md): journal + spans
        # + flight dumps under the run directory (or cfg.obs.dir), plus
        # the optional /metrics endpoint.  Idempotent — a caller that
        # configured the plane itself keeps its setup only if it also
        # left cfg.obs.enabled off.
        obs.configure(
            cfg.obs.dir or f"{workdir or cfg.workdir}/{cfg.name}/obs",
            metrics_port=(
                cfg.obs.metrics_port if cfg.obs.metrics_port >= 0 else None
            ),
            spans=cfg.obs.spans,
            flight_size=cfg.obs.flight_size,
            flush_s=cfg.obs.flush_s,
        )
        obs.install_crash_handler()
    if mesh is None and jax.device_count() > 1:
        mesh = make_mesh(model_parallel=cfg.train.spatial_partition)
    model, tx, fresh_state, step_fn, global_batch = build_all(
        cfg, mesh, extra_freeze=extra_freeze, pretrained=pretrained
    )
    plan = build_plan(cfg, mesh, model=model)
    accum = cfg.train.accum_steps
    from mx_rcnn_tpu.parallel.distributed import describe_plan

    log.info(describe_plan(plan))
    if state is None:
        state = fresh_state
    else:
        # Continuation from an earlier phase (alternate training): keep the
        # learned params + BN stats, but take this phase's optimizer state
        # (freeze masks change its pytree) and restart step/schedule —
        # matching the reference, where each phase is a fresh fit() over
        # params loaded from the previous phase's checkpoint.
        state = fresh_state.replace(
            params=state.params, model_state=state.model_state
        )
    # Explicit total_steps is absolute (alternate phases, tests); the
    # preset default is batch-scaled to keep epochs constant across pods.
    steps = (
        total_steps
        if total_steps is not None
        else scale_schedule_steps(cfg.train.schedule, global_batch).total_steps
    )
    ckpt_dir = f"{workdir or cfg.workdir}/{cfg.name}/ckpt"
    if resume and latest_step(ckpt_dir) is not None:
        # Restore validates finiteness and falls back past a truncated or
        # corrupt latest checkpoint (a kill mid-write costs one checkpoint
        # interval, not the run).
        state = restore_checkpoint(
            ckpt_dir, state, validate=finite_state,
            shardings=plan.state_shardings(state),
        )
        obs.emit(
            "train", "checkpoint_restored", {"step": int(state.step)},
            logger=log,
        )
        log.info("resumed from %s at step %d", ckpt_dir, int(state.step))
        _warn_config_drift(
            cfg, f"{workdir or cfg.workdir}/{cfg.name}/config.json",
            strict=strict_resume,
        )

    if loader is None:
        from mx_rcnn_tpu.data import load_proposals

        proposals = load_proposals(proposals_path) if proposals_path else None
        roidb = filter_roidb(build_dataset(cfg.data, train=True).roidb())
        loader = DetectionLoader(
            roidb,
            cfg.data,
            # Host batches are MICROBATCHES under gradient accumulation:
            # one optimizer step consumes `accum` consecutive loader
            # batches (stacked on the leading axis by _stacked_batches).
            batch_size=global_batch // accum,
            train=True,
            seed=cfg.train.seed,
            rank=jax.process_index(),
            world=jax.process_count(),
            with_masks=cfg.model.mask.enabled,
            proposals=proposals,
            num_proposals=cfg.model.rpn.train_post_nms_top_n,
            # Stacked steps_per_call / accum_steps calls scan K (or N)
            # batches in one device program — the loader must emit that
            # many same-canvas batches per run.
            run_length=max(cfg.train.steps_per_call, accum, 1),
            # Unreadable images are retried, then quarantined to this jsonl
            # and deterministically substituted instead of killing the run.
            quarantine_path=(
                f"{workdir}/{cfg.name}/quarantine.jsonl" if workdir else None
            ),
        )
    # Plan-directed placement (today: every rule is P() — replicated, the
    # same layout `device_put(state, replicated(mesh))` produced).
    state = plan.shard_state(state)

    speedo = Speedometer(global_batch)
    start = int(state.step)
    writer = None
    if workdir and is_primary():
        # resume_step truncates rows ahead of the restored step — a crash
        # between checkpoint and metrics flush (or a guardian rollback of a
        # previous run) must not leave duplicate/contradictory rows.
        writer = ScalarWriter(
            f"{workdir}/{cfg.name}/metrics.jsonl", resume=start > 0,
            resume_step=start,
        )
        # Reproducibility: the exact resolved config next to its artifacts
        # (the reference leaves hyperparameters scattered across argparse
        # defaults, the global config, and shell scripts).  Written on
        # fresh starts only, so every resume's drift check compares against
        # the run-start original, not the previous resume's overrides —
        # while a new run reusing the directory still replaces a stale one.
        import dataclasses as _dc
        import json as _json

        if start == 0:
            with open(f"{workdir}/{cfg.name}/config.json", "w") as f:
                _json.dump(_dc.asdict(cfg), f, indent=1)
    # Device prefetch: the host->device copy of batch k+1 overlaps batch
    # k's step (12MB/image at 1024^2 — unhidden it costs more than the
    # fwd+bwd compute on a v5e).  Resumed runs fast-forward the loader so
    # the data schedule matches an uninterrupted run.
    k = max(cfg.train.steps_per_call, 1)
    if (steps - start) % k:
        raise ValueError(
            f"total steps {steps - start} not divisible by "
            f"train.steps_per_call={k}"
        )
    spatial = cfg.train.spatial_partition > 1

    def data_iter(from_step: int, extra_skip: int):
        # Rebuilt after a guardian rollback: ``extra_skip`` optimizer
        # steps' worth of the global schedule are dropped so the retried
        # steps see FRESH data (the offending window is skipped, not
        # replayed).  Both counts are in optimizer steps; an accumulated
        # step consumes `accum` host microbatches, hence the scaling.
        host_it = loader.iter_from(
            skip_batches=(from_step + extra_skip) * accum
        )
        if k > 1:
            host_it = _stacked_batches(host_it, k)
        elif accum > 1:
            host_it = _stacked_batches(host_it, accum)
        # host_depth=1: the one-step host double buffer — decode/augment/
        # stack for batch k+1 runs on a background thread while batch k's
        # step occupies the device, on top of the async device_put depth.
        # Batch ORDER is untouched, so the data schedule (and chaos
        # bit-exact resume) is identical to the synchronous pipeline.
        return device_prefetch(
            host_it, mesh, depth=2, spatial=spatial, stacked=plan.stacked,
            host_depth=1, stats=prefetch_stats,
        )

    # Rollback safety net: make sure SOME checkpoint exists before the
    # first cadence save — a NaN (or preemption) inside the first
    # checkpoint interval then rolls back to/resumes from the start state
    # instead of aborting the run.
    if workdir and latest_step(ckpt_dir) is None:
        _save(ckpt_dir, state, int(state.step))
    # Quantize the profile window to the loop stride so it still opens
    # when i advances k at a time.  Round UP: the default (10, 15) window
    # exists to skip the compile step, so the start must never be pulled
    # back to 0.
    p0, p1 = profile_steps
    p0 += -p0 % k
    p1 = max(p1 + (-p1 % k), p0 + k)
    profiler = ProfileWindow(profile_dir, p0, p1)
    # Hot-path hygiene, machine-enforced (tools/tpulint.py checks the same
    # invariant on the isolated step): after the first iteration compiles
    # the program (trace-time constant transfers are expected then), every
    # step runs under transfer_guard — any implicit host sync that creeps
    # into the loop raises instead of silently serializing the pipeline.
    # Metrics stay on device in `pending`; ONE device_get per drain (log
    # points, checkpoint boundaries, preemption) — the guardian's
    # finiteness verdict rides that same transfer (train/guardian.py).
    guard_mode = os.environ.get("MX_RCNN_TRANSFER_GUARD", "disallow")
    # Rollback needs checkpoints; without a workdir the guardian can only
    # detect-and-raise.
    guardian = Guardian(
        max_rollbacks=cfg.train.guardian_rollbacks if workdir else 0,
        spike_zscore=cfg.train.guardian_spike_z,
    )
    pending: list[dict] = []
    # Data-starvation meter: time the consumer blocked in next(loader)
    # past the prefetch double buffer, and time it spent inside the
    # device_put of a later batch, logged per interval as data_stall_ms /
    # data_put_ms (per optimizer step) alongside the device metrics.
    prefetch_stats = PrefetchStats()
    last_drain = start
    compiles_seen_ns: Optional[int] = None  # set at the first drain
    it = data_iter(start, 0)
    data_skip = 0      # batches the guardian skipped ahead of the schedule
    last_good = start  # newest boundary whose drained metrics were finite
    i = start
    first_call = True
    with PreemptionGuard() as preempt:
        while i < steps:
            profiler.step(i, sync=state.params)
            guard = (
                jax.transfer_guard(guard_mode)
                if not first_call and guard_mode != "off"
                else contextlib.nullcontext()
            )
            first_call = False
            # The step's spans (docs/observability.md): ``data`` is the
            # host's part of the feed — the wait past the prefetch buffer
            # and the device_put of a later batch, its children
            # ``feed.wait`` / ``feed.put`` — and ``step`` the asynchronous
            # dispatch of the device program.  Always recorded: three
            # appends to a buffer against a step of milliseconds.
            tspan = obs.span(
                "train_step", subsystem="train", attrs={"step": i}
            )
            with guard:
                with tspan.child("data", attrs={"step": i}) as dspan:
                    prefetch_stats.parent = dspan
                    batch = next(it)
                with tspan.child("step", attrs={"step": i}):
                    state, metrics = step_fn(state, batch)
            tspan.end()
            pending.append(metrics)
            done = i + k
            at_log = done % cfg.train.log_every < k or i == start
            at_ckpt = bool(workdir) and done % cfg.train.checkpoint_every < k
            if at_log or at_ckpt or preempt.triggered:
                # Checkpoint boundaries drain too: a checkpoint is only
                # written after its whole interval validated finite, so
                # every on-disk step is a sound rollback target.
                with obs.span("drain", subsystem="train",
                              attrs={"step": done}):
                    means, per_step = host_interval_metrics(pending)
                pending.clear()
                # Host-side metrics, appended AFTER the guardian sees the
                # interval (a slow disk must never look like divergence).
                stall_s, _ = prefetch_stats.take()
                put_s = prefetch_stats.take_put()
                per_ms = 1000.0 / max(done - last_drain, 1)
                stall_ms, put_ms = stall_s * per_ms, put_s * per_ms
                last_drain = done
                if compiles_seen_ns is not None:
                    _report_recompiles(compiles_seen_ns, done)
                compiles_seen_ns = time.monotonic_ns()
                rollback = guardian.observe(done, means, per_step)
                if rollback is not None:
                    target = jax.device_get(state)
                    state = restore_checkpoint(
                        ckpt_dir, target, max_step=last_good,
                        validate=finite_state,
                        shardings=plan.state_shardings(target),
                    )
                    restored = int(state.step)
                    # A poisoned checkpoint newer than the rollback target
                    # must not shadow its retrained replacement (orbax
                    # no-ops saves whose step already exists).
                    delete_steps_after(ckpt_dir, restored)
                    # Explicit placement: restored leaves can arrive as
                    # host arrays, and the next step runs under
                    # transfer_guard('disallow') — implicit transfer would
                    # raise there.
                    state = plan.shard_state(state)
                    # The retried window consumes the batches AFTER the
                    # offending one — skip forward, never replay poison.
                    data_skip += done - restored
                    it.close()  # stop the superseded host-prefetch thread
                    it = data_iter(restored, data_skip)
                    if writer:
                        writer.truncate(restored)
                    speedo = Speedometer(global_batch)
                    last_drain = restored
                    obs.emit("train", "rollback_restored", {
                        "restored_step": restored,
                        "skipped": done - restored,
                        "total_skipped": data_skip,
                    }, logger=log)
                    i = restored
                    continue
                last_good = done
                means.pop("nonfinite", None)
                means["data_stall_ms"] = stall_ms
                means["data_put_ms"] = put_ms
                if at_log:
                    speedo(done, means)
                    if writer:
                        writer.write(done, means)
                if at_ckpt:
                    _save(ckpt_dir, state, done)
            if preempt.triggered:
                # Drain complete; persist synchronously and exit resumable.
                obs.emit(
                    "train", "preempt_drain", {"step": done}, logger=log
                )
                if workdir:
                    _save(ckpt_dir, state, done, wait=True)
                if writer:
                    writer.close()
                it.close()
                obs.flight_dump("preempt_drain")
                raise Preempted(done, ckpt_dir if workdir else None)
            i = done
    # Stop the host-prefetch thread (generator close -> _HostPrefetcher
    # close); GC would get there eventually, but be prompt about it.
    it.close()
    profiler.close(sync=state.params)
    if writer:
        writer.close()
    if workdir:
        _save(ckpt_dir, state, int(steps), wait=True)
        flush_checkpoints(ckpt_dir)
    return state
