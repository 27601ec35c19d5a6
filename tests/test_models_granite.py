"""Granite 4.0-H Micro's hybrid decoder (``granite4_h_micro_det``) on the
program's normal path at tiny widths on the CPU: the preset and its layer rule
from the published ``layer_types``, the muP fields, the ONE jitted step
through ``build_all``, the CLI, which kernels its shapes take on a TPU.  And
that the three decoder families accepted before it trace the parent commit's
programs: the new fields at their defaults add nothing to them."""

import dataclasses
import hashlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "perfbench"))

import _granite_tiny  # noqa: E402
import _ling_tiny  # noqa: E402
import _sambay_tiny  # noqa: E402
import _ssm_tiny  # noqa: E402

from mx_rcnn_tpu.config import (  # noqa: E402
    GRANITE4_H_MICRO, DecoderConfig, apply_overrides, available_configs, get_config)
from mx_rcnn_tpu.detection.graph import Batch  # noqa: E402
from mx_rcnn_tpu.models.build import build_backbone  # noqa: E402
from mx_rcnn_tpu.models.decoder import DecoderBackbone, layer_kinds, sublayers  # noqa: E402

NEW_FIELDS = ("layer_types", "residual_multiplier", "embedding_multiplier", "attention_multiplier")


def tiny_overrides(*extra):
    conf = _granite_tiny.tiny_config()
    return conf["overrides"] + ["train.per_device_batch=2", *extra]


def tiny_cfg(*extra):
    return apply_overrides(get_config("granite4_h_micro_det"), tiny_overrides(*extra))


def tiny_batch(b=2):
    rng = np.random.default_rng(0)
    boxes = np.zeros((b, 8, 4), np.float32)
    boxes[:, 0], boxes[:, 1] = [10, 10, 60, 60], [30, 40, 100, 120]
    valid = np.zeros((b, 8), bool)
    valid[:, :2] = True
    return Batch(
        images=jnp.asarray(rng.integers(0, 255, (b, 128, 128, 3), dtype=np.uint8)),
        image_hw=jnp.full((b, 2), 128.0), gt_boxes=jnp.asarray(boxes),
        gt_classes=jnp.ones((b, 8), jnp.int32), gt_valid=jnp.asarray(valid),
    )


def test_the_preset_holds_the_published_widths_and_one_period():
    cfg = get_config("granite4_h_micro_det")
    d = cfg.model.backbone.decoder
    assert "granite4_h_micro_det" in available_configs()
    assert cfg.model.backbone.name == "granite4_h_micro" and d == GRANITE4_H_MICRO
    assert (d.hidden_size, d.num_heads, d.num_kv_heads, d.head_dim) == (2048, 32, 8, 64)
    assert (d.ssm_heads, d.ssm_head_dim, d.ssm_groups, d.ssm_state) == (64, 64, 1, 128)
    assert (d.short_conv_kernel, d.intermediate_size, d.rms_norm_eps) == (4, 8192, 1e-5)
    assert (d.residual_multiplier, d.embedding_multiplier, d.attention_multiplier) == (
        0.22, 12.0, 0.015625)
    assert d.layers == tuple(range(10)) and not d.pattern and not d.mb_per_layer
    with open(os.path.join(_granite_tiny.REPO, "perfbench", "configs",
                           "granite4_h_micro_det.json")) as f:
        published = json.load(f)["layer_types"]
    assert list(d.layer_types) == published and len(published) == 40


def test_the_layer_rule_reads_the_published_layer_types():
    """A mixer by the published word, then the SwiGLU, in every layer: the
    attention layers at 5, 15, 25 and 35, norms ``norm1`` and ``norm2``."""
    d = GRANITE4_H_MICRO
    kinds = [layer_kinds(d, l) for l in range(40)]
    assert [l for l, k in enumerate(kinds) if k == ("gqa", "ffn")] == [5, 15, 25, 35]
    assert all(k in (("gqa", "ffn"), ("ssm", "ffn")) for k in kinds)
    assert sublayers(d, 5) == (("norm1", "gqa"), ("norm2", "ffn"))
    assert sublayers(d, 0) == (("norm1", "ssm"), ("norm2", "ffn"))
    other = dataclasses.replace(d, layer_types=("attention", "mamba"), layers=(0, 1))
    assert [layer_kinds(other, l) for l in other.layers] == [("gqa", "ffn"), ("ssm", "ffn")]


def test_the_preset_s_leaves_are_the_published_sizes():
    """76.2 M a Mamba-2 layer (in_proj 2048 -> 8512, one group), 60.8 M the
    attention layer, 749 M the ten and the patchify and neck."""
    m = DecoderBackbone(cfg=GRANITE4_H_MICRO)
    v = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3))))
    p = v["params"]
    count = lambda t: sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(t))
    assert p["l0"]["ssm"]["in_proj"]["kernel"].shape == (2048, 2 * 4096 + 2 * 128 + 64)
    assert p["l0"]["ssm"]["norm"]["scale"].shape == (4096,)
    assert p["l0"]["ssm"]["conv"]["kernel"].shape == (4, 4096 + 2 * 128)
    assert p["l5"]["gqa"]["k"]["kernel"].shape == (2048, 8 * 64)
    assert p["l5"]["ffn"]["gate"]["kernel"].shape == (2048, 8192)
    assert count(p["l0"]) / 1e6 == pytest.approx(76.18, abs=0.01)
    assert count(p["l5"]) / 1e6 == pytest.approx(60.82, abs=0.01)
    assert count(p) / 1e6 == pytest.approx(749.16, abs=0.01)
    assert "constants" not in v                          # nothing is routed


def test_on_a_tpu_the_scan_takes_its_kernel_and_the_attention_the_blocked_form():
    """One group of 64 heads is a shape the SSD kernel pair is written for;
    64-wide value heads are not the attention pair's (``dv % 128``): the
    cell measures the blocked XLA form there."""
    from mx_rcnn_tpu.ops.pallas import attention as attention_kernel
    from mx_rcnn_tpu.ops.pallas import ssd as ssd_kernel

    d = GRANITE4_H_MICRO
    assert ssd_kernel.supported(4200, d.ssm_heads, d.ssm_head_dim, d.ssm_groups, d.ssm_state, 128)
    assert not attention_kernel.supported(4200, d.num_heads, d.num_kv_heads, d.head_dim,
                                          d.head_dim, jnp.bfloat16)


@pytest.mark.parametrize("preset", ["ling3_flash_vl_det", "nemotron_twotower_det",
                                    "phi4_mini_flash_det"])
def test_the_other_families_hold_the_default_fields(preset):
    d = get_config(preset).model.backbone.decoder
    defaults = DecoderConfig()
    for name in NEW_FIELDS:
        assert getattr(d, name) == getattr(defaults, name), name


# The three accepted decoder families at tiny size on the CPU, as the parent
# of the PR that added this family traced them (this file's ``_jaxprs`` run
# from a ``git archive`` of it): the SHA-256 (first 16 hex digits) of the
# jaxpr text of the features of two images and of the gradient of a loss over
# them.  Equal digests are the same program, equation by equation: the new
# fields at their defaults multiply nothing and branch nowhere.
PARENTS_JAXPRS = {
    "ling3_flash_vl_det": {"features": "b002a91f486197ac", "gradients": "9ab4a5ecf32e0504"},
    "nemotron_twotower_det": {"features": "18a21a436c93fbf8", "gradients": "4136d4b260b8bdba"},
    "phi4_mini_flash_det": {"features": "cc78bab0783b9a4f", "gradients": "39bdf7792ef2512b"},
}
TINY = {"ling3_flash_vl_det": _ling_tiny, "nemotron_twotower_det": _ssm_tiny,
        "phi4_mini_flash_det": _sambay_tiny}


@pytest.fixture(scope="module")
def jaxprs():
    out = {}
    for preset, tiny in TINY.items():
        conf = tiny.tiny_config()
        cfg = apply_overrides(get_config(conf["preset"]), conf["overrides"])
        with tiny.small_program_choices():
            bb = build_backbone(cfg.model.backbone, out_levels=(4,), dtype=jnp.float32)
            x = jax.ShapeDtypeStruct((2, 128, 128, 3), jnp.float32)
            v = jax.eval_shape(bb.init, jax.random.PRNGKey(0), x)
            f = lambda v, x: bb.apply(v, x, mutable=["counters"])[0][4]
            grad = jax.grad(lambda v, x: jnp.sum(jnp.sin(f(v, x))))
            out[preset] = {"features": str(jax.make_jaxpr(f)(v, x)),
                           "gradients": str(jax.make_jaxpr(grad)(v, x))}
    return out


@pytest.mark.parametrize("what", ["features", "gradients"])
@pytest.mark.parametrize("preset", sorted(PARENTS_JAXPRS))
def test_the_other_families_trace_the_parent_s_program(jaxprs, preset, what):
    digest = hashlib.sha256(jaxprs[preset][what].encode()).hexdigest()[:16]
    assert digest == PARENTS_JAXPRS[preset][what]


@pytest.fixture(scope="module")
def built():
    from mx_rcnn_tpu.train.loop import build_all

    with _granite_tiny.small_program_choices():
        cfg = tiny_cfg()
        model, tx, state, step_fn, global_batch = build_all(cfg, None)
        yield cfg, model, state, step_fn, global_batch


def test_the_train_step_runs_through_build_all(built):
    _, _, state, step_fn, global_batch = built
    assert global_batch == 2
    params = state.params["backbone"]
    assert sorted(params["l5"]) == ["ffn", "gqa", "norm1", "norm2"]
    assert sorted(params["l3"]) == ["ffn", "norm1", "norm2", "ssm"]
    losses = []
    for _ in range(2):
        state, metrics = step_fn(state, tiny_batch())
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))


def test_the_train_step_s_flops_leave_no_other_bucket(built):
    from mx_rcnn_tpu.utils.hlo_profile import attribute_flops

    _, _, state, step_fn, _ = built
    acc = attribute_flops(step_fn, state, tiny_batch())
    total = sum(v["flops"] for v in acc.values())
    assert acc.get("other", {"flops": 0.0})["flops"] <= 0.01 * total
    assert {"SSM", "GQA", "dense-FFN", "patchify", "neck"} <= set(acc)
    assert not {"KDA", "MLA", "MoE", "Mamba", "SWA", "GMU"} & set(acc)


def test_the_cli_trains_the_preset(tmp_path):
    """``train.py --config granite4_h_micro_det`` at tiny overrides: the normal
    path, no option of its own."""
    from mx_rcnn_tpu.cli import train_cli

    sets = []
    for o in tiny_overrides("train.log_every=1"):
        sets += ["--set", o]
    with _granite_tiny.small_program_choices():
        train_cli.main(["--config", "granite4_h_micro_det", "--workdir", str(tmp_path),
                        "--no-eval", "--steps", "2"] + sets)
    with open(tmp_path / "granite4_h_micro_det" / "metrics.jsonl") as f:
        rows = [json.loads(line) for line in f]
    assert [r["step"] for r in rows] == [1, 2]
    assert all(np.isfinite(r["loss"]) for r in rows)
