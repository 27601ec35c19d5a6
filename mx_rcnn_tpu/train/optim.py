"""Optimizer and LR schedule.

Replaces the reference drivers' optimizer setup (``train_end2end.py``: SGD
with momentum 0.9, wd 5e-4, ``clip_gradient``, a ``MultiFactorScheduler``
at epoch boundaries, and per-param ``lr_mult`` dicts that freeze the early
backbone via ``fixed_param_prefix``).  Here the same semantics are an optax
chain: frozen params are masked out of the update entirely (exactly
lr_mult=0), the schedule is a warmup + piecewise-constant-decay function of
the global step, and clipping is by global norm.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax

from mx_rcnn_tpu.config import ScheduleConfig, TrainConfig


def make_schedule(cfg: ScheduleConfig, scale: float = 1.0) -> Callable:
    """Warmup + MultiFactor decay.

    ``scale`` is the data-parallel linear-scaling factor (the reference
    multiplies lr by ``len(ctx) * kv.num_workers`` in its drivers); pass
    ``global_batch / 16`` or similar.
    """
    base = cfg.base_lr * scale

    def schedule(step: jnp.ndarray) -> jnp.ndarray:
        step = jnp.asarray(step, jnp.float32)
        warm = cfg.warmup_factor + (1.0 - cfg.warmup_factor) * jnp.minimum(
            step / max(cfg.warmup_steps, 1), 1.0
        )
        decay = jnp.ones((), jnp.float32)
        for boundary in cfg.decay_steps:
            decay = decay * jnp.where(step >= boundary, cfg.factor, 1.0)
        return base * warm * decay

    return schedule


def frozen_mask(params, freeze_prefixes: tuple[str, ...]) -> dict:
    """True = trainable.  Each freeze prefix is a ``/``-separated module
    path anchored at the tree root, its last component matched as a string
    prefix: ``"box_head"`` freezes the whole box head, ``"backbone/layer1"``
    freezes every ``backbone/layer1_block*`` (reference:
    ``fixed_param_prefix``, e.g. ('conv1', 'res2') / ('conv1_', 'conv2_')).
    Anchoring is what keeps same-named inner modules trainable — ResNet
    bottlenecks and the mask head both contain a ``conv1`` that must NOT be
    caught by freezing the backbone stem's ``backbone/conv1``."""

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    prefixes = [p.split("/") for p in freeze_prefixes]

    def trainable(path) -> bool:
        names = [getattr(part, "key", None) for part in path]
        for parts in prefixes:
            if len(names) < len(parts):
                continue
            head, last = parts[:-1], parts[-1]
            if all(isinstance(n, str) for n in names[: len(parts)]) and (
                names[: len(head)] == head
                and names[len(head)].startswith(last)
            ):
                return False
        return True

    masks = {jax.tree_util.keystr(p): trainable(p) for p, _ in flat}
    return jax.tree_util.tree_map_with_path(
        lambda p, _: masks[jax.tree_util.keystr(p)], params
    )


# Leaf names (anywhere on a leaf's path) that weight decay skips.
NO_DECAY = ("bias", "scale", "A_log", "dt_bias", "D", "lambda")


def make_optimizer(
    cfg: TrainConfig,
    params,
    lr_scale: float = 1.0,
    freeze_prefixes: tuple[str, ...] = (),
) -> tuple[optax.GradientTransformation, Callable]:
    """SGD + momentum + wd + global-norm clip, with frozen-param masking.

    Weight decay skips biases and norm scales (standard detection recipe;
    the reference applies wd uniformly but modern schedules that hit the
    BASELINE north star do not) and a state-space mixer's ``A_log``,
    ``dt_bias`` and ``D`` (``NO_DECAY``: the Mamba family's recipe) and
    differential attention's ``lambda`` vectors.
    """
    schedule = make_schedule(cfg.schedule, lr_scale)

    def decay_mask(p):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: not any(getattr(k, "key", None) in NO_DECAY for k in path), p,
        )

    tx = optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip),
        optax.add_decayed_weights(cfg.weight_decay, mask=decay_mask),
        optax.sgd(learning_rate=schedule, momentum=cfg.momentum),
    )
    if freeze_prefixes:
        # multi_transform, not optax.masked: masked() passes the raw gradient
        # through for masked-out leaves; frozen params must get a zero update.
        labels = jax.tree_util.tree_map(
            lambda t: "trainable" if t else "frozen",
            frozen_mask(params, freeze_prefixes),
        )
        tx = optax.multi_transform(
            {"trainable": tx, "frozen": optax.set_to_zero()}, labels
        )
    return tx, schedule
