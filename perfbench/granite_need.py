"""What the ``granite4_h_micro`` backbone's two ops NEED in one training step,
and the matmul FLOPs the whole step needs, from the configuration's sizes: the
work of the MATHEMATICS, whatever implements it, so that a share of a roofline
or of the peak reads the same work after a rewrite and cannot pass 100 %.
The two ops' needs are ``ssm_need.py``'s, read through the reference's
Mamba-2 key mapping (:func:`_as_nemotron`); the step's FLOPs are this
family's own, with a SwiGLU after every mixer.

- State-space scan: the token-by-token recurrence's FLOPs per head (decay the
  state, the rank-one update, S C: 5 P N a token) and x, y (bfloat16), dt
  (float32) moved once a head, B and C (bfloat16) once a GROUP - one group
  here, so once for all 64 heads.  Bytes-bound; the chunked form does more
  FLOPs, so nothing can pass 100 %.
- Grouped-query attention: the causal half of the scores and of the
  probabilities times v; q and o moved once a query head, k and v once a KEY
  head.

The backward counts twice the forward's FLOPs, and moves the forward's bytes
twice (its inputs read again, a gradient written for each); what a
rematerialising program computes again is not needed and not counted.
"""

from __future__ import annotations

from perfbench import ssm_need
from perfbench.reference.backbone_granite4_h_micro import _mamba2, kind

PATTERN = {"mamba": "M", "attention": "*"}   # nemotron_h's letters for the same two mixers


def _sizes(ref):
    dc = ref["decoder"]
    h, w = ref["canvas"]
    tokens = (h // dc["patch"]) * (w // dc["patch"])
    kinds = [kind(dc, l) for l in dc["layers"]]
    return dc, tokens, kinds


def _as_nemotron(ref):
    """The configuration under the names ``ssm_need.py`` reads: the Mamba-2
    sizes through the reference's own mapping, ``layer_types`` as
    ``nemotron_h``'s pattern letters."""
    dc = ref["decoder"]
    pattern = "".join(PATTERN[t] for t in dc["layer_types"])
    return {**ref, "decoder": {**dc, **_mamba2(dc), "pattern": pattern}}


def ssm_scan_need(ref, images: int) -> dict:
    return ssm_need.ssm_scan_need(_as_nemotron(ref), images)


def attn_need(ref, images: int) -> dict:
    return ssm_need.gqa_attn_need(_as_nemotron(ref), images)


def step_flops(ref, images: int, slots_per_step=None) -> float:
    """Matmul + conv FLOPs one optimizer step over ``images`` needs: every
    projection, the SwiGLU after every mixer, the two ops above, neck, RPN head
    and box head, forward and backward (every leaf trains), nothing
    recomputed.  ``slots_per_step`` is the expert families' and is not read:
    nothing here is routed."""
    dc, tokens, kinds = _sizes(ref)
    d, h, kv, hd = (dc["hidden_size"], dc["num_attention_heads"], dc["num_key_value_heads"],
                    dc["head_dim"])
    inner = dc["mamba_n_heads"] * dc["mamba_d_head"]
    in_proj = 2 * inner + 2 * dc["mamba_n_groups"] * dc["mamba_d_state"] + dc["mamba_n_heads"]
    per_kind = {"ssm": d * in_proj + inner * d, "gqa": d * (h + 2 * kv) * hd + h * hd * d}
    per_token = dc["patch"] ** 2 * 3 * d + sum(
        per_kind[k] + 3 * d * dc["intermediate_size"] for k in kinds)
    c, rc = ref["feature_channels"], ref["rpn"]["channels"]
    k = len(ref["anchor_scales"]) * len(ref["anchor_ratios"])
    per_token += d * c + 9 * c * c + 9 * c * rc + rc * 5 * k
    rc_ = ref["rcnn"]
    hd_ = rc_["hidden_dim"]
    per_roi = rc_["pooled_size"] ** 2 * c * hd_ + hd_ * hd_ + hd_ * 5 * ref["num_classes"]
    forward = 2.0 * images * (tokens * per_token + rc_["roi_batch_size"] * per_roi)
    return 3.0 * forward + ssm_scan_need(ref, images)["flops"] + attn_need(ref, images)["flops"]
