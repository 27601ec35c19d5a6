"""What the ``ling3_flash_vl`` backbone's three new ops NEED in one training
step, and the matmul FLOPs the whole step needs, from the configuration's
sizes: the work of the MATHEMATICS, whatever implements it, so that a share
of a roofline or of the peak reads the same work after a rewrite and cannot
pass 100 %.

- KDA scan: the token-by-token recurrence's FLOPs per head (decay the state,
  k^T S, the rank-one update, q^T S: 7 Dk Dv a token) and q, k, v, g, beta, o
  moved once (bfloat16 but the float32 log-decay and beta).
- MLA attention: the causal half of the scores and of the probabilities
  times v; q, k, v, o moved once.
- Experts: three matmuls over the token-slots routed to the held experts
  (the step's own counter), and the held weights moved once.

The backward counts twice the forward's FLOPs, and moves the forward's bytes
twice (its inputs read again, a gradient written for each); what a
rematerialising program computes again is not needed and not counted.
"""

from __future__ import annotations

from perfbench.reference.backbone_ling3_flash_vl import kinds


def _sizes(ref):
    dc = ref["decoder"]
    h, w = ref["canvas"]
    tokens = (h // dc["patch"]) * (w // dc["patch"])
    mixers = [kinds(dc, l) for l in dc["layers"]]
    return dc, tokens, mixers


def _train(fwd_flops: float, fwd_bytes: float) -> dict:
    return {"flops": 3.0 * fwd_flops, "bytes": 3.0 * fwd_bytes}


def kda_scan_need(ref, images: int) -> dict:
    dc, tokens, mixers = _sizes(ref)
    layers = sum(m == "kda" for m, _ in mixers)
    h, d = dc["num_attention_heads"], dc["head_dim"]
    per_head_token = 7.0 * d * d
    moved = (3 * d + d) * 2 + d * 4 + 4          # q, k, v, o bf16; g, beta float32
    n = images * tokens * h * layers
    return _train(n * per_head_token, n * moved)


def mla_attn_need(ref, images: int) -> dict:
    dc, tokens, mixers = _sizes(ref)
    layers = sum(m == "mla" for m, _ in mixers)
    h = dc["num_attention_heads"]
    dq, dv = dc["qk_nope_head_dim"] + dc["qk_rope_head_dim"], dc["v_head_dim"]
    pairs = tokens * (tokens + 1) / 2.0
    flops = images * layers * h * pairs * 2.0 * (dq + dv)
    moved = images * layers * tokens * h * (2 * dq + 2 * dv) * 2
    return _train(flops, moved)


def moe_experts_need(ref, slots_per_step: float) -> dict:
    dc, _, mixers = _sizes(ref)
    layers = sum(f == "moe" for _, f in mixers)
    d, f = dc["hidden_size"], dc["moe_intermediate_size"]
    weights = layers * dc["num_experts"] * 3 * d * f * 2
    rows = slots_per_step * 2 * d * 2
    return _train(slots_per_step * 3 * 2.0 * d * f, weights + rows)


def uniform_slots(ref, images: int) -> float:
    """Token-slots a step sends to the held experts under a uniform router."""
    dc, tokens, mixers = _sizes(ref)
    layers = sum(f == "moe" for _, f in mixers)
    return (images * tokens * layers * dc["num_experts_per_tok"] * dc["num_experts"]
            / dc["num_experts_published"])


def step_flops(ref, images: int, slots_per_step=None) -> float:
    """Matmul + conv FLOPs one optimizer step over ``images`` needs: every
    projection, the three ops above, neck, RPN head and box head, forward and
    backward (every leaf trains), nothing recomputed."""
    dc, tokens, mixers = _sizes(ref)
    d, h, hd = dc["hidden_size"], dc["num_attention_heads"], dc["head_dim"]
    dn, dr, dv, r = (dc["qk_nope_head_dim"], dc["qk_rope_head_dim"], dc["v_head_dim"],
                     dc["kv_lora_rank"])
    f_e = dc["moe_intermediate_size"]
    per_token = dc["patch"] ** 2 * 3 * d
    for mixer, ff in mixers:
        if mixer == "kda":
            per_token += 5 * d * h * hd + d * h + h * hd * d
        else:
            per_token += d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + d * h + h * dv * d
        if ff == "ffn":
            per_token += 3 * d * dc["intermediate_size"]
        else:
            per_token += d * dc["num_experts_published"] + 3 * d * f_e
    c, rc = ref["feature_channels"], ref["rpn"]["channels"]
    k = len(ref["anchor_scales"]) * len(ref["anchor_ratios"])
    per_token += d * c + 9 * c * c + 9 * c * rc + rc * 5 * k
    rc_ = ref["rcnn"]
    hd_ = rc_["hidden_dim"]
    per_roi = rc_["pooled_size"] ** 2 * c * hd_ + hd_ * hd_ + hd_ * 5 * ref["num_classes"]
    forward = 2.0 * images * (tokens * per_token + rc_["roi_batch_size"] * per_roi)
    if slots_per_step is None:
        slots_per_step = uniform_slots(ref, images)
    ops = (kda_scan_need(ref, images)["flops"] + mla_attn_need(ref, images)["flops"]
           + moe_experts_need(ref, slots_per_step)["flops"])
    return 3.0 * forward + ops
