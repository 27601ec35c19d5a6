"""The pass grammar of ``perfbench/step_parts.py`` on the program's own
paths: the three decoder families' backbones at tiny widths, their gradient
lowered on the CPU with ``remat=True`` and read BEFORE XLA's passes (the
module jax hands the compiler, so nothing is merged or dropped by XLA).  Every
layer has ops of all three passes, and the recomputed forward replays the
forward's matmuls but for what jax itself leaves out of a recomputation: the
block's LAST matmul (its last sub-layer's output projection; the experts' and
the shared expert's where both end it), whose result only the residual stream
reads and no backward does."""

import collections
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _ling_tiny  # noqa: E402
import _sambay_tiny  # noqa: E402
import _ssm_tiny  # noqa: E402
from _hlo_fragment import field  # noqa: E402

from perfbench import hlo_module, step_parts  # noqa: E402

FAMILIES = {
    "ling3_flash_vl_det": _ling_tiny,
    "nemotron_twotower_det": _ssm_tiny,
    "phi4_mini_flash_det": _sambay_tiny,
}


def lowered_module(preset: str) -> dict:
    """The gradient of the tiny backbone's features, as ``hlo_module`` parses
    the module jax lowers it to."""
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.models.build import build_backbone

    tiny = FAMILIES[preset]
    with tiny.small_program_choices():
        cfg = apply_overrides(get_config(preset), tiny.TINY_OVERRIDES + tiny.decoder_overrides())
        assert cfg.model.backbone.remat
        backbone = build_backbone(cfg.model.backbone, out_levels=(4,), dtype=jnp.float32)
        x = jnp.zeros((1, 128, 128, 3))
        variables = jax.eval_shape(backbone.init, jax.random.PRNGKey(1), x)
        params = variables["params"]
        rest = {k: v for k, v in variables.items() if k != "params"}

        def loss(params, rest, x):
            return jnp.sum(backbone.apply({"params": params, **rest}, x)[4])

        lowered = jax.jit(jax.grad(loss)).lower(params, rest, x)
    proto = lowered.compiler_ir(dialect="hlo").as_serialized_hlo_module_proto()
    return hlo_module.parse_module(field(1, proto))


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def by_pass(request):
    """{layer: {pass: (ops, Counter of the dots' paths inside the layer)}}."""
    out: dict = {}
    for instrs in lowered_module(request.param)["computations"].values():
        for ins in instrs:
            layer, _ = step_parts.place(ins["op_name"])
            if not layer:
                continue
            passes = out.setdefault(layer, {p: [0, collections.Counter()] for p in step_parts.PASSES})
            ops = passes[step_parts.pass_of(ins["op_name"])]
            ops[0] += 1
            if ins["opcode"] == "dot":
                inside = ins["op_name"].split(f"/{layer}/", 1)[1]
                # a glue function's own checkpoint is inlined into the block's recomputation
                ops[1]["/".join(p for p in inside.split("/") if p != "checkpoint")] += 1
    return out


def test_every_layer_has_ops_of_all_three_passes(by_pass):
    assert len(by_pass) >= 3
    for layer, passes in by_pass.items():
        for name, (ops, dots) in passes.items():
            assert ops > 0 and sum(dots.values()) > 0, (layer, name)
        # the backward's two matmuls for each of the forward's
        assert sum(passes["bwd"][1].values()) > sum(passes["fwd"][1].values())


def test_the_recomputation_replays_the_forward_s_matmuls(by_pass):
    for layer, passes in by_pass.items():
        fwd, remat = passes["fwd"][1], passes["remat"][1]
        assert not remat - fwd, (layer, remat - fwd)          # nothing the forward has not
        left_out = fwd - remat
        # the block's last matmul (two where experts and a shared expert end it)
        assert 1 <= sum(left_out.values()) <= 3 and set(left_out.values()) == {1}, (layer, left_out)
        assert all("scan" not in p and "attn" not in p for p in left_out), (layer, left_out)
