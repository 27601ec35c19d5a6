"""What the ``nemotron_twotower`` backbone's three ops NEED in one training
step, and the matmul FLOPs the whole step needs, from the configuration's
sizes: the work of the MATHEMATICS, whatever implements it, so that a share
of a roofline or of the peak reads the same work after a rewrite and cannot
pass 100 % (``ling_need.py`` does the same for the other decoder family).

- State-space scan: the token-by-token recurrence's FLOPs per head (decay the
  state, the rank-one update, S C: 5 P N a token) and x, y (bfloat16), dt
  (float32) moved once a head, B and C (bfloat16) once a GROUP.  Bytes-bound;
  the chunked form does more FLOPs, so nothing can pass 100 %.
- Grouped-query attention: the causal half of the scores and of the
  probabilities times v; q and o moved once a query head, k and v once a
  KEY head.
- Experts: two matmuls over the token-slots routed to the held experts (the
  step's own counter), and the held weights moved once.

The backward counts twice the forward's FLOPs, and moves the forward's bytes
twice (its inputs read again, a gradient written for each); what a
rematerialising program computes again is not needed and not counted.
"""

from __future__ import annotations

from perfbench.reference.backbone_nemotron_twotower import kind


def _sizes(ref):
    dc = ref["decoder"]
    h, w = ref["canvas"]
    tokens = (h // dc["patch"]) * (w // dc["patch"])
    kinds = [kind(dc, l) for l in dc["layers"]]
    return dc, tokens, kinds


def _train(fwd_flops: float, fwd_bytes: float) -> dict:
    return {"flops": 3.0 * fwd_flops, "bytes": 3.0 * fwd_bytes}


def ssm_scan_need(ref, images: int) -> dict:
    dc, tokens, kinds = _sizes(ref)
    layers = kinds.count("ssm")
    h, p, g, n = dc["mamba_num_heads"], dc["mamba_head_dim"], dc["n_groups"], dc["ssm_state_size"]
    per_token = h * 5.0 * p * n
    moved = h * (2 * p * 2 + 4) + g * 2 * n * 2       # x, y bf16 and dt f32 a head; B, C a group
    rows = images * tokens * layers
    return _train(rows * per_token, rows * moved)


def gqa_attn_need(ref, images: int) -> dict:
    dc, tokens, kinds = _sizes(ref)
    layers = kinds.count("gqa")
    h, kv, hd = dc["num_attention_heads"], dc["num_key_value_heads"], dc["head_dim"]
    pairs = tokens * (tokens + 1) / 2.0
    flops = images * layers * h * pairs * 2.0 * (hd + hd)
    moved = images * layers * tokens * (2 * h + 2 * kv) * hd * 2
    return _train(flops, moved)


def moe_mlp_experts_need(ref, slots_per_step: float) -> dict:
    dc, _, kinds = _sizes(ref)
    d, f = dc["hidden_size"], dc["moe_intermediate_size"]
    weights = kinds.count("moe") * dc["n_routed_experts"] * 2 * d * f * 2
    rows = slots_per_step * 2 * d * 2
    return _train(slots_per_step * 2 * 2.0 * d * f, weights + rows)


def uniform_slots(ref, images: int) -> float:
    """Token-slots a step sends to the held experts under a uniform router."""
    dc, tokens, kinds = _sizes(ref)
    return (images * tokens * kinds.count("moe") * dc["num_experts_per_tok"]
            * dc["n_routed_experts"] / dc["n_routed_experts_published"])


def step_flops(ref, images: int, slots_per_step=None) -> float:
    """Matmul + conv FLOPs one optimizer step over ``images`` needs: every
    projection, the three ops above, neck, RPN head and box head, forward and
    backward (every leaf trains), nothing recomputed."""
    dc, tokens, kinds = _sizes(ref)
    d, h, kv, hd = (dc["hidden_size"], dc["num_attention_heads"], dc["num_key_value_heads"],
                    dc["head_dim"])
    inner = dc["mamba_num_heads"] * dc["mamba_head_dim"]
    in_proj = 2 * inner + 2 * dc["n_groups"] * dc["ssm_state_size"] + dc["mamba_num_heads"]
    per_kind = {
        "ssm": d * in_proj + inner * d,
        "gqa": d * (h + 2 * kv) * hd + h * hd * d,
        "moe": d * dc["n_routed_experts_published"] + 2 * d * dc["moe_shared_expert_intermediate_size"],
    }
    per_token = dc["patch"] ** 2 * 3 * d + sum(per_kind[k] for k in kinds)
    c, rc = ref["feature_channels"], ref["rpn"]["channels"]
    k = len(ref["anchor_scales"]) * len(ref["anchor_ratios"])
    per_token += d * c + 9 * c * c + 9 * c * rc + rc * 5 * k
    rc_ = ref["rcnn"]
    hd_ = rc_["hidden_dim"]
    per_roi = rc_["pooled_size"] ** 2 * c * hd_ + hd_ * hd_ + hd_ * 5 * ref["num_classes"]
    forward = 2.0 * images * (tokens * per_token + rc_["roi_batch_size"] * per_roi)
    if slots_per_step is None:
        slots_per_step = uniform_slots(ref, images)
    ops = (ssm_scan_need(ref, images)["flops"] + gqa_attn_need(ref, images)["flops"]
           + moe_mlp_experts_need(ref, slots_per_step)["flops"])
    return 3.0 * forward + ops
