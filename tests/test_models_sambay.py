"""The SambaY decoder backbone (``phi4_mini_flash_det``) on the program's
normal path at tiny widths on the CPU: the preset and its layer rule, the
execution plan, the optimizer's decay rule, the ONE jitted step through
``build_all``, the tensors that pass between blocks and their gradients, the
CLI, and what the serving quantizer says when it cannot.  And that the two
decoder families accepted before it still lower to the parent commit's
programs."""

import dataclasses
import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "perfbench"))

import _ling_tiny  # noqa: E402
import _sambay_tiny  # noqa: E402
import _ssm_tiny  # noqa: E402

from mx_rcnn_tpu.config import PHI4_MINI_FLASH, apply_overrides, available_configs, get_config
from mx_rcnn_tpu.detection.graph import Batch
from mx_rcnn_tpu.models import decoder
from mx_rcnn_tpu.models.build import build_backbone
from mx_rcnn_tpu.models.decoder import layer_kinds, leaf_spec
from mx_rcnn_tpu.train.state import leaf_paths


@pytest.fixture(scope="module", autouse=True)
def _seams_at_tiny_size():
    with _sambay_tiny.small_program_choices():
        yield


def tiny_cfg(*extra):
    return apply_overrides(
        get_config("phi4_mini_flash_det"),
        _sambay_tiny.TINY_OVERRIDES + _sambay_tiny.decoder_overrides()
        + ["train.per_device_batch=2", *extra],
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


def test_the_preset_holds_the_published_widths_and_the_eight_layers():
    cfg = get_config("phi4_mini_flash_det")
    d = cfg.model.backbone.decoder
    assert "phi4_mini_flash_det" in available_configs()
    assert cfg.model.backbone.name == "phi4_mini_flash" and d == PHI4_MINI_FLASH
    assert (d.hidden_size, d.num_heads, d.num_kv_heads, d.head_dim) == (2560, 40, 20, 64)
    assert (d.sliding_window, d.intermediate_size, d.rms_norm_eps) == (512, 10240, 1e-5)
    assert (d.mamba_expand, d.mamba_d_state, d.mamba_dt_rank, d.short_conv_kernel) == (2, 16, 160, 4)
    assert (d.num_hidden_layers, d.mb_per_layer) == (32, 2)
    assert d.layers == (0, 1, 2, 3, 16, 17, 18, 19)
    assert [layer_kinds(d, l) for l in d.layers] == [
        (k, "ffn") for k in ("mamba", "swa", "mamba", "swa", "mamba", "full", "gmu", "xattn")]
    assert cfg.model.backbone.remat and cfg.model.backbone.freeze_stages == 0
    assert not cfg.model.fpn.enabled and cfg.model.rpn.channels == 256
    assert cfg.train.per_device_batch == 2 and cfg.data.image_size == (800, 1344)
    # the other families' presets still hold their own blocks
    assert get_config("ling3_flash_vl_det").model.backbone.decoder.mb_per_layer == 0
    assert get_config("nemotron_twotower_det").model.backbone.decoder.mb_per_layer == 0


@pytest.mark.parametrize("depth,kinds", [
    (8, "MWMWMFGX"), (12, "MWMWMWMFGXGX"), (32, "MW" * 8 + "MF" + "GX" * 7),
])
def test_the_layer_rule_follows_the_published_depth_s_middle(depth, kinds):
    d = dataclasses.replace(PHI4_MINI_FLASH, num_hidden_layers=depth)
    letter = {"mamba": "M", "swa": "W", "full": "F", "gmu": "G", "xattn": "X"}
    got = [layer_kinds(d, l) for l in range(depth)]
    assert "".join(letter[mixer] for mixer, _ in got) == kinds
    assert {ffn for _, ffn in got} == {"ffn"}           # a dense SwiGLU in EVERY layer
    assert kinds.count("F") == 1 and kinds.index("F") == depth // 2 + 1
    assert kinds[depth // 2] == "M"                     # the layer that hands on the memory


def test_the_published_layers_count_their_parameters():
    def count(spec):
        return sum(count(s) if isinstance(s[0], tuple) else int(np.prod(s)) for _, s in spec)

    spec = dict(leaf_spec(PHI4_MINI_FLASH))
    norms = 2 * 2 * 2560 / 1e6                          # ISSUE 34 counts the matrices and biases
    mixer = lambda l, k: count(dict(spec[l])[k]) / 1e6
    assert mixer("l0", "mamba") == pytest.approx(41.24, abs=0.01)
    assert mixer("l1", "swa") == pytest.approx(19.67, abs=0.01)
    assert mixer("l17", "full") == pytest.approx(19.67, abs=0.01)
    assert mixer("l19", "xattn") == pytest.approx(13.11, abs=0.01)
    assert mixer("l18", "gmu") == pytest.approx(26.21, abs=0.01)
    assert mixer("l0", "ffn") == pytest.approx(78.64, abs=0.01)
    for l, want in (("l0", 119.9), ("l1", 98.3), ("l18", 104.9), ("l19", 91.8)):
        assert count(spec[l]) / 1e6 - norms == pytest.approx(want, abs=0.05)
    layers = sum(count(v) for k, v in spec.items() if k.startswith("l"))
    assert layers / 1e6 == pytest.approx(851.3, abs=0.2)


def test_every_leaf_resolves_in_the_plan_and_nothing_is_frozen(built):
    from mx_rcnn_tpu.parallel.plan import ExecutionPlan
    from perfbench.program import momentum_trace

    cfg, model, state, _, _ = built
    ExecutionPlan.for_model(model).state_specs(state)   # raises on an unmatched leaf
    names = [n for n, _ in leaf_paths(state.params)]
    for leaf in ("l0/mamba/A_log", "l0/mamba/dt_bias", "l0/mamba/D", "l0/mamba/conv/bias",
                 "l0/mamba/x_proj/kernel", "l0/mamba/dt_proj/kernel", "l0/norm1/bias",
                 "l1/swa/Wqkv/bias", "l1/swa/lambda", "l1/swa/subln/scale", "l5/full/out_proj/bias",
                 "l6/gmu/in_proj/kernel", "l7/xattn/Wqkv/kernel", "l7/ffn/gate/kernel",
                 "final_norm/bias"):
        assert f"backbone/{leaf}" in names, leaf
    assert len(momentum_trace(state.opt_state)) == len(names)
    p = state.params["backbone"]
    assert p["l7"]["xattn"]["Wqkv"]["kernel"].shape == (32, 32)     # the queries alone
    assert p["l5"]["full"]["Wqkv"]["kernel"].shape == (32, 64)
    a = np.exp(np.asarray(p["l0"]["mamba"]["A_log"]))
    dt = np.log1p(np.exp(np.asarray(p["l0"]["mamba"]["dt_bias"])))
    assert a.shape == (64, 4) and a.min() >= 1.0 and a.max() <= 16.0
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1001
    assert 0.03 < float(np.std(np.asarray(p["l1"]["swa"]["lambda"]))) < 0.3


def test_the_scan_s_leaves_and_lambda_do_not_decay(built):
    from mx_rcnn_tpu.train.optim import NO_DECAY, make_optimizer

    cfg, _, state, _, _ = built
    zero = jax.tree_util.tree_map(jnp.zeros_like, state.params)
    sched = dataclasses.replace(cfg.train.schedule, warmup_steps=0, warmup_factor=1.0)
    tx, _ = make_optimizer(dataclasses.replace(cfg.train, schedule=sched), state.params)
    updates, _ = tx.update(zero, tx.init(state.params), state.params)
    moved = {n: float(jnp.abs(u).max()) > 0 for n, u in leaf_paths(updates)}
    for name, did in moved.items():
        assert did == (name.rsplit("/", 1)[1] not in NO_DECAY), name
    for leaf in ("l0/mamba/A_log", "l0/mamba/dt_bias", "l0/mamba/D", "l0/mamba/conv/bias",
                 "l1/swa/lambda", "l7/xattn/lambda", "l1/swa/subln/scale", "l0/norm1/bias"):
        assert not moved[f"backbone/{leaf}"], leaf
    for leaf in ("l0/mamba/conv/kernel", "l0/mamba/dt_proj/kernel", "l1/swa/Wqkv/kernel"):
        assert moved[f"backbone/{leaf}"], leaf


def test_the_one_jitted_step_trains_the_preset(built):
    _, _, state, step_fn, global_batch = built
    assert global_batch == 2
    state = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), state)
    first = jax.tree_util.tree_map(np.asarray, state.params["backbone"])
    losses = []
    for _ in range(3):
        state, m = step_fn(state, tiny_batch())
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)) and float(m["nonfinite"]) == 0.0
    assert step_fn._cache_size() == 1                          # one program, no retrace
    assert "moe_slots_here" not in m                           # nothing is routed
    now = state.params["backbone"]
    for layer, kind, leaf in (("l0", "mamba", "A_log"), ("l4", "mamba", "D"), ("l1", "swa", "lambda"),
                              ("l5", "full", "lambda"), ("l7", "xattn", "lambda")):
        assert float(np.abs(np.asarray(now[layer][kind][leaf]) - first[layer][kind][leaf]).max()) > 0


def _tiny_backbone(remat=True):
    cfg = dataclasses.replace(tiny_cfg().model.backbone, remat=remat)
    return build_backbone(cfg, out_levels=(4,), dtype=jnp.float32)


def test_an_image_s_features_do_not_depend_on_its_batch_mates():
    bb = _tiny_backbone()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 128, 3))
    variables = bb.init(jax.random.PRNGKey(1), x[:1])
    both = bb.apply(variables, x)[4]
    alone = bb.apply(variables, x[1:])[4]
    np.testing.assert_allclose(both[1:], alone, atol=1e-5)
    assert both.shape == (2, 8, 8, 32)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "stored"])
def test_gradients_reach_the_layers_that_hand_their_tensors_on(remat, monkeypatch):
    """The middle Mamba layer's scan is read by ITS OWN gate and by the Gated
    Memory Unit two blocks on; the full layer's k and v by its own attention
    and by the cross layer's.  With the own reader's path cut (the gate's and
    the attention's results zeroed where they are used in their own layer), a
    gradient still reaches ``A_log`` and the k, v columns of ``Wqkv`` - across
    the blocks' ``jax.checkpoint`` boundaries or without them - and equals the
    part the later readers add to the whole."""
    bb = _tiny_backbone(remat)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 128, 3))
    params = bb.init(jax.random.PRNGKey(1), x)["params"]
    cot = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8, 32))
    loss = lambda p: jnp.sum(bb.apply({"params": p}, x)[4] * cot)
    whole = jax.grad(loss)(params)

    def own_path_cut(real, at):
        def mixer(cfg, layer, p, x, dtype, shared):
            y, shared = real(cfg, layer, p, x, dtype, shared)
            return (jnp.zeros_like(y) if layer == at else y), shared
        return mixer

    monkeypatch.setitem(decoder.MIXERS, "mamba", own_path_cut(decoder.MIXERS["mamba"], 4))
    monkeypatch.setitem(decoder.MIXERS, "full", own_path_cut(decoder.MIXERS["full"], 5))
    through = jax.grad(loss)(params)
    scan = lambda g: float(jnp.linalg.norm(g["l4"]["mamba"]["A_log"]))
    kv = lambda g: float(jnp.linalg.norm(g["l5"]["full"]["Wqkv"]["kernel"][:, 32:]))
    assert scan(through) > 1e-3 * scan(whole) > 0 and kv(through) > 1e-3 * kv(whole) > 0
    # the full layer's queries are read by nobody else, the earlier Mamba layers' scans neither
    assert float(jnp.linalg.norm(through["l5"]["full"]["Wqkv"]["kernel"][:, :32])) == 0.0
    assert float(jnp.linalg.norm(through["l5"]["full"]["lambda"])) == 0.0
    assert scan(whole) != scan(through) and kv(whole) != kv(through)


def test_remat_changes_nothing_but_the_memory():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 128, 3))
    on, off = _tiny_backbone(True), _tiny_backbone(False)
    params = on.init(jax.random.PRNGKey(1), x)["params"]
    loss = lambda bb: lambda p: jnp.sum(jnp.square(bb.apply({"params": p}, x)[4]))
    g_on, g_off = jax.grad(loss(on))(params), jax.grad(loss(off))(params)
    for (name, a), (_, b) in zip(leaf_paths(g_on), leaf_paths(g_off)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6 * float(jnp.abs(b).max()), err_msg=name)
        assert float(jnp.abs(b).max()) > 0, name            # every leaf is read, dt_bias too


def test_the_serving_quantizer_refuses_the_scan_s_leaves_and_lambda_in_words(built):
    from mx_rcnn_tpu.parallel.step import eval_variables
    from mx_rcnn_tpu.serve.quantize import quantize_network

    with pytest.raises(NotImplementedError, match="mamba.*x_proj, dt_proj"):
        quantize_network(eval_variables(built[2]))
    attention_alone = {"params": {"backbone": {"l1": {"swa": {"lambda": jnp.zeros((4, 8))}}}}}
    with pytest.raises(NotImplementedError, match="l1/swa/lambda.*differential"):
        quantize_network(attention_alone)


@pytest.mark.parametrize("scope,component", [
    ("jit(step)/jvp(TwoStageDetector.features)/backbone/l0/mamba/proj/dense/dot_general", "Mamba"),
    ("backbone/l2/mamba/conv/mul", "Mamba"),
    ("transpose(jvp(backbone))/l16/checkpoint/mamba/scan/intra/while/body/mul", "Mamba"),
    ("backbone/l16/mamba/scan/inter/while/body/exp", "Mamba"),
    ("backbone/l1/swa/attn/flash_attention_fwd", "SWA"),
    ("backbone/l3/swa/diff/rsqrt", "SWA"),
    ("transpose(jvp(backbone))/l17/checkpoint/full/attn/flash_attention_bwd", "full-attn"),
    ("backbone/l17/full/proj/dense/dot_general", "full-attn"),
    ("backbone/l19/xattn/attn/rows/dot_general", "cross-attn"),
    ("backbone/l19/xattn/diff/exp", "cross-attn"),
    ("backbone/l18/gmu/proj/dense/dot_general", "GMU"),
    ("backbone/l18/ffn/dense/dot_general", "dense-FFN"),
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
    assert {"Mamba", "SWA", "full-attn", "cross-attn", "GMU", "dense-FFN", "patchify", "neck"} <= set(acc)
    assert not {"KDA", "MLA", "SSM", "GQA", "MoE"} & set(acc)


def test_the_cli_trains_the_preset(tmp_path):
    """``train.py --config phi4_mini_flash_det`` at tiny overrides: the normal
    path, no option of its own."""
    import json

    from mx_rcnn_tpu.cli import train_cli

    sets = []
    for o in _sambay_tiny.TINY_OVERRIDES + _sambay_tiny.decoder_overrides() + [
        "train.per_device_batch=2", "train.log_every=1",
    ]:
        sets += ["--set", o]
    train_cli.main(["--config", "phi4_mini_flash_det", "--workdir", str(tmp_path),
                    "--no-eval", "--steps", "2"] + sets)
    with open(tmp_path / "phi4_mini_flash_det" / "metrics.jsonl") as f:
        rows = [json.loads(line) for line in f]
    assert [r["step"] for r in rows] == [1, 2]
    assert all(np.isfinite(r["loss"]) for r in rows)


# The two accepted decoder families at tiny size on the CPU, VALUES from the
# parent commit of the PR that added SambaY (405fd60, this file's ``_values``
# run from a ``git archive`` of it): the SHA-256 of every leaf's bytes, in path
# order, of the seeded parameters, of the features of two seeded images, and of
# the gradient of a loss over them.  Equal digests are the parent's parameters
# and features bit for bit, whatever the compiler's text looks like; a PR that
# means to change what one of these families computes updates its lines.
PARENTS_VALUES = {
    "ling3_flash_vl_det": {
        "parameters": "0c1bebaed06ab4093e7776b356c2f309742a229de2268490d0a2b3fd6dae8611",
        "features": "07c85c9d4dbc5a88bc3d7e25770449a95265353d4f1a2b1fe7abbdf480abbf7f",
        "gradients": "ebd74a70052c74bf3ffe578961ef53ae1672e67642c0e63377d19607eb2540d8",
    },
    "nemotron_twotower_det": {
        "parameters": "6b9f11abfb80b55c2b9a3371fb76fea14572e73f66550c48a72e7d6f43aa39ac",
        "features": "3451d45007156fc01534fc724c5e21efbb7906f4d16d505fc98680e436120c7f",
        "gradients": "bad162047876158f949ea2ea75f1cbf717ac0f66f9638776c8e978a37d100644",
    },
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@functools.lru_cache(maxsize=None)
def _values(preset: str) -> dict:
    tiny = {"ling3_flash_vl_det": _ling_tiny, "nemotron_twotower_det": _ssm_tiny}[preset]
    conf = tiny.tiny_config()
    cfg = apply_overrides(get_config(conf["preset"]), conf["overrides"])
    with tiny.small_program_choices():
        bb = build_backbone(cfg.model.backbone, out_levels=(4,), dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 128, 3))
        variables = bb.init(jax.random.PRNGKey(0), x)
        features = lambda v: bb.apply(v, x, mutable=["counters"])[0][4]
        return {"parameters": _digest(variables), "features": _digest(features(variables)),
                "gradients": _digest(jax.grad(lambda v: jnp.sum(jnp.sin(features(v))))(variables))}


@pytest.mark.parametrize("what", ["parameters", "features", "gradients"])
@pytest.mark.parametrize("preset", sorted(PARENTS_VALUES))
def test_the_accepted_families_compute_the_parent_s_values_bit_for_bit(preset, what):
    assert _values(preset)[what] == PARENTS_VALUES[preset][what]
