"""The decoder backbones on the program's normal path at tiny widths on the
CPU: the presets, the execution plan, the optimizer's decay rule, the ONE
jitted step through ``build_all``, inference, the CLI, and what the serving
quantizer and the factory say when they cannot.  Ling's family first, then
(``ssm_*``, ``nemotron``) the state-space family's."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "perfbench"))

import _ssm_tiny  # noqa: E402
from _ling_tiny import TINY_OVERRIDES, decoder_overrides, small_program_choices  # noqa: E402

from mx_rcnn_tpu.config import BackboneConfig, apply_overrides, available_configs, get_config
from mx_rcnn_tpu.detection.graph import Batch
from mx_rcnn_tpu.models.build import build_backbone
from mx_rcnn_tpu.models.decoder import layer_kinds, leaf_spec
from mx_rcnn_tpu.train.state import leaf_paths


@pytest.fixture(scope="module", autouse=True)
def _seams_at_tiny_size():
    with small_program_choices():
        yield


def tiny_cfg(*extra):
    return apply_overrides(
        get_config("ling3_flash_vl_det"),
        TINY_OVERRIDES + decoder_overrides() + ["train.per_device_batch=2", *extra],
    )


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


@pytest.fixture(scope="module")
def built():
    from mx_rcnn_tpu.train.loop import build_all

    cfg = tiny_cfg()
    model, tx, state, step_fn, global_batch = build_all(cfg, None)
    return cfg, model, state, step_fn, global_batch


def test_the_preset_holds_the_published_widths_and_the_chips_share():
    cfg = get_config("ling3_flash_vl_det")
    d = cfg.model.backbone.decoder
    assert "ling3_flash_vl_det" in available_configs()
    assert (d.hidden_size, d.num_heads, d.head_dim) == (2560, 32, 128)
    assert (d.num_experts, d.num_experts_per_tok, d.n_group, d.topk_group) == (512, 8, 8, 4)
    assert (d.experts_first, d.experts_count, d.routed_scaling_factor) == (0, 8, 2.5)
    assert (d.kv_lora_rank, d.qk_nope_head_dim, d.qk_rope_head_dim, d.v_head_dim) == (512, 128, 64, 128)
    assert (d.intermediate_size, d.moe_intermediate_size) == (6144, 768)
    assert d.layers == (0, 6, 7, 8, 9, 10, 11)
    kinds = [layer_kinds(d, l) for l in d.layers]
    assert kinds == [("kda", "ffn")] + [("kda", "moe")] * 5 + [("mla", "moe")]
    assert cfg.model.backbone.remat and cfg.model.backbone.freeze_stages == 0
    assert not cfg.model.fpn.enabled and cfg.model.rpn.channels == 256
    assert cfg.train.per_device_batch == 2 and cfg.data.image_size == (800, 1344)


def test_the_published_layers_count_their_parameters():
    d = get_config("ling3_flash_vl_det").model.backbone.decoder

    def count(spec):
        return sum(count(s) if isinstance(s[0], tuple) else int(np.prod(s)) for _, s in spec)

    by_layer = {name: count(sub) for name, sub in leaf_spec(d)}
    kda = dict(dict(leaf_spec(d))["l6"])["kda"]
    mla = dict(dict(leaf_spec(d))["l11"])["mla"]
    assert count(kda) / 1e6 == pytest.approx(63.1, abs=0.1)    # ISSUE 27's reckoning
    assert count(mla) / 1e6 == pytest.approx(32.0, abs=0.1)
    assert by_layer["l0"] / 1e6 == pytest.approx(110, abs=1)
    layers = sum(v for k, v in by_layer.items() if k.startswith("l"))
    assert layers / 1e6 == pytest.approx(784, abs=1)
    assert sum(by_layer.values()) / 1e6 == pytest.approx(787, abs=1)  # + patchify, neck


def test_every_leaf_resolves_in_the_plan_and_nothing_is_frozen(built):
    from mx_rcnn_tpu.parallel.plan import ExecutionPlan

    cfg, model, state, _, _ = built
    assert model.param_families() == ("backbone", "rpn", "box_head")
    specs = ExecutionPlan.for_model(model).state_specs(state)   # raises on an unmatched leaf
    assert len(jax.tree_util.tree_leaves(specs, is_leaf=lambda x: x is not None)) > 0
    names = [n for n, _ in leaf_paths(state.params)]
    assert any("l4/moe/experts/e3/down/kernel" in n for n in names)
    assert not any("e_bias" in n for n in names)               # a constant, not a parameter
    consts = [n for n, _ in leaf_paths(state.model_state)]
    assert consts == ["constants/backbone/l4/moe/router/e_bias",
                      "constants/backbone/l5/moe/router/e_bias"]
    # every parameter has a momentum buffer: nothing frozen
    from perfbench.program import momentum_trace

    assert len(momentum_trace(state.opt_state)) == len(names)


def test_norm_scales_biases_and_the_decay_s_rate_do_not_decay(built):
    import optax

    from mx_rcnn_tpu.train.optim import make_optimizer

    cfg, _, state, _, _ = built
    zero = jax.tree_util.tree_map(jnp.zeros_like, state.params)
    sched = dataclasses.replace(cfg.train.schedule, warmup_steps=0, warmup_factor=1.0)
    tx, _ = make_optimizer(dataclasses.replace(cfg.train, schedule=sched), state.params)
    updates, _ = tx.update(zero, tx.init(state.params), state.params)
    moved = {n: float(jnp.abs(u).max()) > 0 for n, u in leaf_paths(updates)}
    for name, did in moved.items():
        plain = name.rsplit("/", 1)[1] not in ("scale", "bias")
        assert did == plain, name
    assert not moved["backbone/l0/kda/decay/scale"] and not moved["backbone/l0/kda/decay/bias"]
    assert moved["backbone/l4/moe/router/kernel"] and moved["backbone/l0/kda/conv_q/kernel"]
    assert isinstance(tx, optax.GradientTransformation)


def test_the_one_jitted_step_trains_and_reports_its_routing(built):
    _, _, state, step_fn, global_batch = built
    assert global_batch == 2
    state = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), state)
    losses = []
    for _ in range(3):
        state, m = step_fn(state, tiny_batch())
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)) and float(m["nonfinite"]) == 0.0
    assert step_fn._cache_size() == 1                          # one program, no retrace
    assert float(m["moe_dropped_slots"]) == 0.0
    tokens, k, layers = 2 * 64, 4, 2
    assert 0 < float(m["moe_slots_here"]) <= tokens * k * layers
    assert float(m["moe_load_max_over_mean"]) >= 1.0
    assert 0.0 <= float(m["moe_tokens_without_held_expert"]) < 1.0


def test_inference_runs_the_same_backbone(built):
    from mx_rcnn_tpu.parallel.step import eval_variables, make_eval_step

    cfg, model, state, _, _ = built
    step = make_eval_step(model, pixel_stats=(cfg.data.pixel_mean, cfg.data.pixel_std))
    det = step(eval_variables(state), tiny_batch())
    assert det.boxes.shape[0] == 2 and bool(jnp.isfinite(det.scores).all())


def test_an_image_s_features_do_not_depend_on_its_batch_mates():
    cfg = tiny_cfg()
    bb = build_backbone(cfg.model.backbone, out_levels=(4,), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 128, 3))
    variables = bb.init(jax.random.PRNGKey(1), x[:1])
    assert "counters" not in variables
    both = bb.apply(variables, x)[4]
    alone = bb.apply(variables, x[1:])[4]
    np.testing.assert_allclose(both[1:], alone, atol=1e-5)
    assert both.shape == (2, 8, 8, 32)


def test_remat_changes_nothing_but_the_memory():
    from mx_rcnn_tpu.models import decoder

    cfg = tiny_cfg().model.backbone
    bb = build_backbone(cfg, out_levels=(4,), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 128, 3))
    variables = bb.init(jax.random.PRNGKey(1), x)
    leaves = {**variables["params"]}
    for layer, c in variables["constants"].items():
        leaves[layer] = {**leaves[layer], "moe": {**leaves[layer]["moe"], "router": {
            **leaves[layer]["moe"]["router"], **c["moe"]["router"]}}}
    f = lambda remat: lambda l: jnp.sum(jnp.sin(
        decoder.features(cfg.decoder, l, x, jnp.float32, remat)[0][4]))
    a = jax.grad(f(True))(leaves)
    b = jax.grad(f(False))(leaves)
    for (n, u), (_, v) in zip(leaf_paths(a), leaf_paths(b)):
        np.testing.assert_allclose(u, v, atol=1e-5, err_msg=n)


def test_the_factory_names_what_it_knows():
    with pytest.raises(ValueError, match="ling3_flash_vl.*resnet50|resnet50.*ling3_flash_vl"):
        build_backbone(BackboneConfig(name="vit_b"))
    with pytest.raises(ValueError, match="patchifies"):
        build_backbone(BackboneConfig(name="ling3_flash_vl", stem_s2d=True))


def test_the_serving_quantizer_refuses_the_family_in_words(built):
    from mx_rcnn_tpu.parallel.step import eval_variables
    from mx_rcnn_tpu.serve.quantize import quantize_network

    with pytest.raises(NotImplementedError, match="decoder backbone.*router|KDA"):
        quantize_network(eval_variables(built[2]))


@pytest.mark.parametrize("scope,component", [
    ("jit(step)/jvp(TwoStageDetector.features)/backbone/l6/kda/proj/dot_general", "KDA"),
    ("transpose(jvp(backbone))/l11/checkpoint/mla/attn/dot_general", "MLA"),
    ("backbone/l7/moe/experts/ragged_dot", "MoE"),
    ("backbone/l0/ffn/dot_general", "dense-FFN"),
    ("backbone/patchify/conv_general_dilated", "patchify"),
    ("backbone/neck/conv_general_dilated", "neck"),
    ("jit(step)/jvp(TwoStageDetector.features)/backbone/l0/ssm/proj/dot_general", "SSM"),
    ("backbone/l2/ssm/conv/mul", "SSM"),
    ("transpose(jvp(backbone))/l4/checkpoint/ssm/scan/intra/dot_general", "SSM"),
    ("backbone/l4/ssm/scan/inter/while/body/mul", "SSM"),
    ("backbone/l4/ssm/norm/rsqrt", "SSM"),
    ("backbone/l5/gqa/proj/dot_general", "GQA"),
    ("transpose(jvp(backbone))/l12/checkpoint/gqa/attn/rows/dot_general", "GQA"),
    ("backbone/l1/moe/router/scores/dot_general", "MoE"),
    ("backbone/l1/moe/dispatch/gather", "MoE"),
    ("backbone/l1/moe/combine/scatter-add", "MoE"),
    ("backbone/l1/moe/shared/dense/dot_general", "MoE"),
])
def test_the_new_scopes_have_a_component(scope, component):
    from mx_rcnn_tpu.utils.hlo_profile import component_of

    assert component_of(scope) == component


def test_the_train_step_s_flops_leave_no_other_bucket(built):
    from mx_rcnn_tpu.utils.hlo_profile import attribute_flops

    _, _, state, step_fn, _ = built
    acc = attribute_flops(step_fn, state, tiny_batch())
    total = sum(v["flops"] for v in acc.values())
    assert acc.get("other", {"flops": 0.0})["flops"] <= 0.01 * total
    assert {"KDA", "MLA", "MoE", "dense-FFN", "patchify", "neck"} <= set(acc)


def test_the_cli_trains_checkpoints_and_resumes(tmp_path):
    """``train.py --config ling3_flash_vl_det`` at tiny overrides: the loader,
    the ONE jitted step, a checkpoint, a resume from it; the routing counters
    land in ``metrics.jsonl`` beside the losses."""
    import json

    from mx_rcnn_tpu.cli import train_cli

    sets = []
    for o in TINY_OVERRIDES + decoder_overrides() + [
        "train.per_device_batch=2", "train.checkpoint_every=2", "train.log_every=1",
    ]:
        sets += ["--set", o]
    common = ["--config", "ling3_flash_vl_det", "--workdir", str(tmp_path), "--no-eval"] + sets
    train_cli.main(common + ["--steps", "2"])
    train_cli.main(common + ["--steps", "3", "--resume"])
    with open(tmp_path / "ling3_flash_vl_det" / "metrics.jsonl") as f:
        rows = [json.loads(line) for line in f]
    assert [r["step"] for r in rows] == [1, 2, 3]
    for r in rows:
        assert np.isfinite(r["loss"]) and r["moe_dropped_slots"] == 0.0 and r["moe_slots_here"] > 0


# -- the state-space family (preset nemotron_twotower_det) ---------------------


def ssm_tiny_cfg(*extra):
    return apply_overrides(
        get_config("nemotron_twotower_det"),
        _ssm_tiny.TINY_OVERRIDES + _ssm_tiny.decoder_overrides()
        + ["train.per_device_batch=2", *extra],
    )


@pytest.fixture(scope="module")
def ssm_built():
    from mx_rcnn_tpu.train.loop import build_all

    with _ssm_tiny.small_program_choices():
        cfg = ssm_tiny_cfg()
        model, tx, state, step_fn, global_batch = build_all(cfg, None)
        yield cfg, model, state, step_fn, global_batch


def test_the_nemotron_preset_holds_the_published_widths_and_the_chips_share():
    cfg = get_config("nemotron_twotower_det")
    d = cfg.model.backbone.decoder
    assert "nemotron_twotower_det" in available_configs()
    assert cfg.model.backbone.name == "nemotron_twotower"
    assert (d.hidden_size, d.num_heads, d.head_dim, d.num_kv_heads) == (2688, 32, 128, 2)
    assert (d.ssm_heads, d.ssm_head_dim, d.ssm_groups, d.ssm_state) == (64, 64, 8, 128)
    assert d.short_conv_kernel == 4 and d.rms_norm_eps == 1e-5
    assert (d.num_experts, d.num_experts_per_tok, d.n_group, d.topk_group) == (128, 6, 1, 1)
    assert (d.experts_first, d.experts_count, d.routed_scaling_factor) == (0, 8, 2.5)
    assert (d.moe_intermediate_size, d.shared_intermediate_size, d.expert_act) == (1856, 3712, "relu2")
    assert len(d.pattern) == 52 and d.layers == tuple(range(13))
    kinds = [layer_kinds(d, l) for l in d.layers]
    assert "".join({"ssm": "M", "moe": "E", "gqa": "*"}[k] for (k,) in kinds) == "MEMEM*EMEMEM*"
    assert cfg.model.backbone.remat and cfg.model.backbone.freeze_stages == 0
    assert not cfg.model.fpn.enabled and cfg.model.rpn.channels == 256
    assert cfg.train.per_device_batch == 2 and cfg.data.image_size == (800, 1344)
    # the other family's preset still holds its own blocks
    assert get_config("ling3_flash_vl_det").model.backbone.decoder.pattern == ""


def test_the_nemotron_layers_count_their_parameters():
    d = get_config("nemotron_twotower_det").model.backbone.decoder

    def count(spec):
        return sum(count(s) if isinstance(s[0], tuple) else int(np.prod(s)) for _, s in spec)

    spec = dict(leaf_spec(d))
    assert count(spec["l0"]) / 1e6 == pytest.approx(38.7, abs=0.05)     # Mamba-2, ISSUE 32
    assert count(spec["l5"]) / 1e6 == pytest.approx(23.4, abs=0.05)     # attention, 2 KV heads
    moe = dict(spec["l1"])["moe"]
    expert = dict(dict(moe)["experts"])["e0"]
    assert count(expert) / 1e6 == pytest.approx(9.98, abs=0.01)
    assert (count(spec["l1"]) - 8 * count(expert)) / 1e6 == pytest.approx(20.3, abs=0.05)
    layers = sum(count(v) for k, v in spec.items() if k.startswith("l"))
    assert layers / 1e6 == pytest.approx(779.9, abs=0.5)
    assert sum(count(v) for v in spec.values()) / 1e6 == pytest.approx(783, abs=1)


def test_ssm_every_leaf_resolves_in_the_plan_and_nothing_is_frozen(ssm_built):
    from mx_rcnn_tpu.parallel.plan import ExecutionPlan
    from perfbench.program import momentum_trace

    cfg, model, state, _, _ = ssm_built
    ExecutionPlan.for_model(model).state_specs(state)   # raises on an unmatched leaf
    names = [n for n, _ in leaf_paths(state.params)]
    for leaf in ("l0/ssm/A_log", "l0/ssm/dt_bias", "l0/ssm/D", "l0/ssm/conv/bias",
                 "l0/ssm/norm/scale", "l3/gqa/k/kernel", "l1/moe/experts/e3/down/kernel"):
        assert f"backbone/{leaf}" in names
    assert not any("/gate/" in n for n in names)           # two matrices an expert
    assert len(momentum_trace(state.opt_state)) == len(names)
    a = np.exp(np.asarray(state.params["backbone"]["l0"]["ssm"]["A_log"]))
    dt = np.log1p(np.exp(np.asarray(state.params["backbone"]["l0"]["ssm"]["dt_bias"])))
    assert a.min() >= 1.0 and a.max() <= 16.0 and dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1001


def test_the_scan_s_leaves_and_the_grouped_norm_do_not_decay(ssm_built):
    from mx_rcnn_tpu.train.optim import NO_DECAY, make_optimizer

    cfg, _, state, _, _ = ssm_built
    zero = jax.tree_util.tree_map(jnp.zeros_like, state.params)
    sched = dataclasses.replace(cfg.train.schedule, warmup_steps=0, warmup_factor=1.0)
    tx, _ = make_optimizer(dataclasses.replace(cfg.train, schedule=sched), state.params)
    updates, _ = tx.update(zero, tx.init(state.params), state.params)
    moved = {n: float(jnp.abs(u).max()) > 0 for n, u in leaf_paths(updates)}
    for name, did in moved.items():
        assert did == (name.rsplit("/", 1)[1] not in NO_DECAY), name
    for leaf in ("A_log", "dt_bias", "D", "norm/scale", "conv/bias"):
        assert not moved[f"backbone/l0/ssm/{leaf}"]
    assert moved["backbone/l0/ssm/conv/kernel"] and moved["backbone/l0/ssm/in_proj/kernel"]


def test_the_one_jitted_step_trains_the_nemotron_preset(ssm_built):
    _, _, state, step_fn, global_batch = ssm_built
    assert global_batch == 2
    state = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), state)
    first = jax.tree_util.tree_map(np.asarray, state.params["backbone"]["l0"]["ssm"])
    losses = []
    for _ in range(3):
        state, m = step_fn(state, tiny_batch())
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)) and float(m["nonfinite"]) == 0.0
    assert step_fn._cache_size() == 1                          # one program, no retrace
    assert float(m["moe_dropped_slots"]) == 0.0
    tokens, k, layers = 2 * 64, 3, 2
    assert 0 < float(m["moe_slots_here"]) <= tokens * k * layers
    for leaf in ("A_log", "dt_bias", "D"):                     # the scan's leaves train
        assert float(np.abs(np.asarray(state.params["backbone"]["l0"]["ssm"][leaf]) - first[leaf]).max()) > 0


def test_an_image_s_ssm_features_do_not_depend_on_its_batch_mates():
    with _ssm_tiny.small_program_choices():
        bb = build_backbone(ssm_tiny_cfg().model.backbone, out_levels=(4,), dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 128, 3))
        variables = bb.init(jax.random.PRNGKey(1), x[:1])
        both = bb.apply(variables, x)[4]
        alone = bb.apply(variables, x[1:])[4]
    np.testing.assert_allclose(both[1:], alone, atol=1e-5)
    assert both.shape == (2, 8, 8, 32)


def test_the_serving_quantizer_refuses_the_scan_s_leaves_in_words(ssm_built):
    from mx_rcnn_tpu.parallel.step import eval_variables
    from mx_rcnn_tpu.serve.quantize import quantize_network

    with pytest.raises(NotImplementedError, match="state-space.*A_log, dt_bias, D"):
        quantize_network(eval_variables(ssm_built[2]))


def test_the_nemotron_step_s_flops_leave_no_other_bucket(ssm_built):
    from mx_rcnn_tpu.utils.hlo_profile import attribute_flops

    _, _, state, step_fn, _ = ssm_built
    acc = attribute_flops(step_fn, state, tiny_batch())
    total = sum(v["flops"] for v in acc.values())
    assert acc.get("other", {"flops": 0.0})["flops"] <= 0.01 * total
    assert {"SSM", "GQA", "MoE", "patchify", "neck"} <= set(acc)
    assert not {"KDA", "MLA", "dense-FFN"} & set(acc)


def test_the_cli_trains_the_nemotron_preset(tmp_path):
    """``train.py --config nemotron_twotower_det`` at tiny overrides: the
    normal path, no option of its own."""
    import json

    from mx_rcnn_tpu.cli import train_cli

    sets = []
    for o in _ssm_tiny.TINY_OVERRIDES + _ssm_tiny.decoder_overrides() + [
        "train.per_device_batch=2", "train.log_every=1",
    ]:
        sets += ["--set", o]
    with _ssm_tiny.small_program_choices():
        train_cli.main(["--config", "nemotron_twotower_det", "--workdir", str(tmp_path),
                        "--no-eval", "--steps", "2"] + sets)
    with open(tmp_path / "nemotron_twotower_det" / "metrics.jsonl") as f:
        rows = [json.loads(line) for line in f]
    assert [r["step"] for r in rows] == [1, 2]
    for r in rows:
        assert np.isfinite(r["loss"]) and r["moe_dropped_slots"] == 0.0 and r["moe_slots_here"] > 0
