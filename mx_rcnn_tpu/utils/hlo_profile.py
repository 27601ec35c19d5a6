"""Per-component MXU-FLOP attribution for the compiled train step.

The driver's last record of the old round (2026-08-02) put the full train
step at 20.6% MFU, but a single MFU number
can't say WHERE the other 79% went — and the per-region numbers that drove
this PR's layout work (stem+C2 at 5.5% MFU, P2's RPN head alone 6.6
ms/step) came from one-off manual HLO spelunking.  This module makes that
attribution a first-class, repeatable artifact:

* ``attribute_flops(fn, *args)`` walks the traced jaxpr exactly like
  utils/flops.py (same conv/dot formulas, same scan trip-count scaling,
  same cond-max convention — the per-component totals sum to
  ``count_matmul_flops`` by construction) and buckets every MXU op into a
  model component classified from its ``name_stack``: flax module scopes
  land there for free (``backbone/layer1_block0/...``), and graph.py adds
  ``jax.named_scope`` for the parameter-free stages (roi_align).  Forward
  and backward are split by the ``transpose(...)`` decoration jax's AD
  leaves on backward-pass stacks.

* ``hlo_component_summary(hlo_text)`` reads the COMPILED program's
  instruction stream — the same stacks survive into HLO ``op_name``
  metadata — and counts instructions per component.  This is the
  post-fusion texture (how many kernels each component became), not a cost
  model; it's the map one reads next to a real profile.

Both run from an abstract trace / compile only — no execution, so the
whole report works under ``JAX_PLATFORMS=cpu`` for a TPU-shaped program.
"""

from __future__ import annotations

import re

import jax

from mx_rcnn_tpu.utils.flops import _conv_flops, _dot_flops

# First match wins.  Patterns are substrings of the (decoration-stripped)
# name stack; the stack for a module op looks like
# ``TwoStageDetector.features/backbone/layer1_block0/.../conv1`` and for a
# named-scope op like ``TwoStageDetector.box/roi_align``.
COMPONENT_PATTERNS: tuple[tuple[str, tuple[str, ...]], ...] = (
    ("stem", ("backbone/conv1", "backbone/bn1", "backbone/stem")),
    ("C2", ("backbone/layer1_",)),
    ("C3", ("backbone/layer2_",)),
    ("C4", ("backbone/layer3_",)),
    ("C5", ("backbone/layer4_",)),
    # The decoder backbone's scopes (models/decoder.py): each block's mixer
    # and feed-forward under ``backbone/l<k>/``.
    ("patchify", ("backbone/patchify",)),
    ("KDA", ("/kda/",)),
    ("MLA", ("/mla/",)),
    ("SSM", ("/ssm/",)),
    ("GQA", ("/gqa/",)),
    ("Mamba", ("/mamba/",)),
    ("SWA", ("/swa/",)),
    ("full-attn", ("/full/",)),
    ("cross-attn", ("/xattn/",)),
    ("GMU", ("/gmu/",)),
    ("MoE", ("/moe/",)),
    ("dense-FFN", ("/ffn/",)),
    ("neck", ("backbone/neck",)),
    ("FPN", ("/fpn/", "fpn/lateral", "fpn/output", "fpn_topdown")),
    ("RPN-head", ("rpn.packed", "rpn._heads", "/rpn/", ".rpn)")),
    ("ROI", ("roi_align",)),
    ("box-head", ("box_head",)),
    ("mask-head", ("mask_head",)),
    # Parameter-free stages, tagged via jax.named_scope in graph.py /
    # parallel/step.py so tools/tpulint.py's flop_attribution invariant
    # (and the HLO texture) has no silent "other" bucket.
    ("RPN-loss", ("rpn_loss",)),
    ("RCNN-loss", ("rcnn_loss",)),
    ("mask-loss", ("mask_loss",)),
    # Before proposals/sampling: the hierarchical top-k scope nests inside
    # both (proposal pre-NMS candidates, assign_anchors' _select_random),
    # and first-match-wins gives it its own bucket for A/B attribution.
    ("topk-hier", ("topk_hier",)),
    ("proposals", ("proposals",)),
    ("sampling", ("sample_rois", "assign_anchors")),
    ("preprocess", ("prep_images",)),
    ("guardian", ("guardian",)),
    ("optimizer", ("optimizer",)),
)

_DECORATIONS = re.compile(
    r"\b(?:jvp|transpose|vmap|pjit|jit|remat|checkpoint|custom_vjp)\("
)


def component_of(name_stack: str) -> str:
    """Model component for a jaxpr/HLO name stack; ``other`` if unmatched
    (everything FLOP-bearing is scoped — ``other`` should stay ~empty;
    tools/tpulint.py enforces >=99% attribution on the train step)."""
    # A closing "/" so that a pattern "/x/" also finds the stack's last scope.
    s = _DECORATIONS.sub("", str(name_stack)).replace(")", "") + "/"
    for comp, pats in COMPONENT_PATTERNS:
        if any(p in s for p in pats):
            return comp
    return "other"


def _is_backward(name_stack: str) -> bool:
    return "transpose(" in str(name_stack)


def _bucket(acc: dict, comp: str) -> dict:
    return acc.setdefault(comp, {"flops": 0.0, "fwd": 0.0, "bwd": 0.0, "ops": 0})


def _walk(jaxpr, scale: float, acc: dict, outer_stack: str) -> None:
    for eqn in jaxpr.eqns:
        # A sub-jaxpr's name stacks are relative to the equation that holds
        # it (a checkpoint inside a checkpoint, a scan's body): keep both.
        inner = str(eqn.source_info.name_stack)
        stack = f"{outer_stack}/{inner}" if outer_stack and inner else inner or outer_stack
        prim = eqn.primitive.name
        if prim in ("conv_general_dilated", "dot_general"):
            f = (_conv_flops if prim == "conv_general_dilated" else _dot_flops)(eqn)
            b = _bucket(acc, component_of(stack))
            b["flops"] += scale * f
            b["bwd" if _is_backward(stack) else "fwd"] += scale * f
            b["ops"] += 1
        elif prim == "scan":
            _walk(eqn.params["jaxpr"].jaxpr, scale * eqn.params["length"], acc, stack)
        elif prim == "while":
            # Trip count is data-dependent; one iteration, matching
            # flops.py's documented lower bound.
            _walk(eqn.params["body_jaxpr"].jaxpr, scale, acc, stack)
        elif prim == "cond":
            # flops.py charges the most expensive branch; attribute that
            # same branch so the per-component sum matches the total.
            best, best_total = None, -1.0
            for br in eqn.params["branches"]:
                trial: dict = {}
                _walk(br.jaxpr, scale, trial, stack)
                total = sum(v["flops"] for v in trial.values())
                if total > best_total:
                    best, best_total = trial, total
            for comp, v in (best or {}).items():
                b = _bucket(acc, comp)
                for key in ("flops", "fwd", "bwd"):
                    b[key] += v[key]
                b["ops"] += v["ops"]
        else:
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    _walk(sub.jaxpr if hasattr(sub, "jaxpr") else sub, scale, acc, stack)
                    break


def attribute_flops(fn, *args, **kwargs) -> dict[str, dict[str, float]]:
    """Per-component matmul+conv FLOPs of one ``fn(*args)`` call.

    Returns ``{component: {"flops", "fwd", "bwd", "ops"}}``; the flops
    values sum to ``count_matmul_flops(fn, *args)`` (same walk, same
    conventions).  Abstract trace only — no device, no execution.
    """
    jaxpr = jax.make_jaxpr(fn, **kwargs)(*args)
    acc: dict = {}
    _walk(jaxpr.jaxpr, 1.0, acc, "")
    return acc


def component_report(
    fn,
    *args,
    steps_per_call: int = 1,
    dt_per_step: float | None = None,
    peak_flops: float | None = None,
) -> dict:
    """Assemble the per-component attribution table for one traced program.

    Normalizes ``attribute_flops`` to per-step figures (the K-step scan
    program divides by ``steps_per_call``), adds percentage shares, and —
    when a measured ``dt_per_step`` and a ``peak_flops`` are supplied —
    overall MFU plus each component's share of it (flops-proportional: the
    component's ceiling contribution, not a per-op timing — that is the
    device trace's to give).
    """
    per_call = attribute_flops(fn, *args)
    k = max(steps_per_call, 1)
    total = sum(v["flops"] for v in per_call.values()) / k
    components = {}
    for comp, v in sorted(
        per_call.items(), key=lambda item: -item[1]["flops"]
    ):
        flops = v["flops"] / k
        components[comp] = {
            "gflops_per_step": round(flops / 1e9, 3),
            "pct_of_total": round(100.0 * flops / total, 2) if total else 0.0,
            "fwd_gflops": round(v["fwd"] / k / 1e9, 3),
            "bwd_gflops": round(v["bwd"] / k / 1e9, 3),
            "mxu_ops_in_jaxpr": v["ops"],
        }
    report = {
        "total_tflops_per_step": round(total / 1e12, 4),
        "components": components,
    }
    if dt_per_step is not None and dt_per_step > 0:
        achieved = total / dt_per_step
        report["ms_per_step"] = round(dt_per_step * 1e3, 3)
        report["achieved_tflops"] = round(achieved / 1e12, 3)
        if peak_flops:
            mfu = achieved / peak_flops
            report["mfu_pct"] = round(100.0 * mfu, 2)
            for comp, v in components.items():
                v["mfu_share_pct"] = round(
                    mfu * v["pct_of_total"], 2
                )
    return report


_OP_NAME = re.compile(r'op_name="([^"]+)"')
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([a-z][\w\-]*)\(")

# Opcodes worth counting in the post-fusion texture.  Raw elementwise ops
# inside fusion bodies are deliberately excluded (they're not dispatches);
# these are the instruction kinds that become kernels.
_KERNEL_OPS = frozenset(
    {
        "fusion",
        "convolution",
        "dot",
        "custom-call",
        "reduce-window",
        "select-and-scatter",
        "all-reduce",
        "all-gather",
        "reduce-scatter",
        "gather",
        "scatter",
        "sort",
        "while",
    }
)


def hlo_component_summary(hlo_text: str) -> dict[str, dict[str, int]]:
    """Instruction counts per component from compiled HLO text.

    Counts kernel-forming opcodes (fusions, convolutions, dots,
    custom-calls, ...) bucketed by the ``op_name`` metadata's name stack.
    A texture map of what each component compiled into, not a cost model.
    """
    out: dict[str, dict[str, int]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None or m.group(1) not in _KERNEL_OPS:
            continue
        op = m.group(1)
        name = _OP_NAME.search(line)
        comp = component_of(name.group(1)) if name else "other"
        bucket = out.setdefault(comp, {})
        bucket[op] = bucket.get(op, 0) + 1
        bucket["total"] = bucket.get("total", 0) + 1
    return out
