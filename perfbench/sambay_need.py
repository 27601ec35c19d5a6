"""What the ``phi4_mini_flash`` backbone's three ops NEED in one training
step, and the matmul FLOPs the whole step needs, from the configuration's
sizes: the work of the MATHEMATICS, whatever implements it, so that a share
of a roofline or of the peak reads the same work after a rewrite and cannot
pass 100 % (``ssm_need.py`` and ``ling_need.py`` do the same for the other
decoder families).

- Selective scan: the token-by-token recurrence's FLOPs per channel and state
  (dt A, its exp, decay the state, dt x B, add, times C, sum: 7 a token) and
  x, y (bfloat16), dt (float32) moved once a channel, B and C (float32) once a
  token.  Bytes-bound; a chunked form moves the state too, so nothing can
  pass 100 %.
- Window attention: the band's pairs (window T - window (window - 1) / 2 a
  head) of both maps of every query pair - scores over the key width, the
  probabilities times a value of twice that; q and both maps' results moved
  once a query head, k once a key head, v once a key PAIR.
- Full attention: the same over the causal triangle, in the full-attention
  layer and in every cross layer, whose k and v are that layer's: moved once
  for all of them.

The backward counts twice the forward's FLOPs, and moves the forward's bytes
twice (its inputs read again, a gradient written for each); what a
rematerialising program computes again is not needed and not counted.
"""

from __future__ import annotations

from perfbench.reference.backbone_phi4_mini_flash import kind


def _sizes(ref):
    dc = ref["decoder"]
    h, w = ref["canvas"]
    tokens = (h // dc["patch"]) * (w // dc["patch"])
    kinds = [kind(dc, l) for l in dc["layers"]]
    return dc, tokens, kinds


def _train(fwd_flops: float, fwd_bytes: float) -> dict:
    return {"flops": 3.0 * fwd_flops, "bytes": 3.0 * fwd_bytes}


def mamba_scan_need(ref, images: int) -> dict:
    dc, tokens, kinds = _sizes(ref)
    wide, n = dc["mamba_expand"] * dc["hidden_size"], dc["mamba_d_state"]
    rows = images * tokens * kinds.count("mamba")
    moved = wide * (2 + 2 + 4) + 2 * n * 4            # x, y bf16 and dt f32 a channel; B, C a token
    return _train(rows * 7.0 * wide * n, rows * moved)


def _attention(dc, images, layers, pairs, tokens, kv_layers):
    """``pairs`` (query, key) pairs a head; k and v moved in ``kv_layers`` layers."""
    h, kv, hd = dc["num_attention_heads"], dc["num_key_value_heads"], dc["head_dim"]
    flops = images * layers * h * pairs * 2.0 * (hd + 2 * hd)      # h maps: Dk = hd, Dv = 2 hd
    q_o = tokens * h * (hd + 2 * hd) * 2
    k_v = tokens * (kv * hd + (kv // 2) * 2 * hd) * 2
    return _train(flops, images * (layers * q_o + kv_layers * k_v))


def swa_attn_need(ref, images: int) -> dict:
    dc, tokens, kinds = _sizes(ref)
    w = min(dc["sliding_window"], tokens)
    layers = kinds.count("swa")
    return _attention(dc, images, layers, w * tokens - w * (w - 1) / 2.0, tokens, layers)


def full_attn_need(ref, images: int) -> dict:
    dc, tokens, kinds = _sizes(ref)
    layers = kinds.count("full") + kinds.count("xattn")
    return _attention(dc, images, layers, tokens * (tokens + 1) / 2.0, tokens, kinds.count("full"))


def step_flops(ref, images: int, slots_per_step=None) -> float:
    """Matmul + conv FLOPs one optimizer step over ``images`` needs: every
    projection, the three ops above, neck, RPN head and box head, forward and
    backward (every leaf trains), nothing recomputed.  ``slots_per_step`` is
    the expert families' and is not read: nothing here is routed."""
    dc, tokens, kinds = _sizes(ref)
    d, h, kv, hd = (dc["hidden_size"], dc["num_attention_heads"], dc["num_key_value_heads"],
                    dc["head_dim"])
    wide, n, rank = dc["mamba_expand"] * d, dc["mamba_d_state"], dc["mamba_dt_rank"]
    self_attn = d * (h + 2 * kv) * hd + h * hd * d
    per_kind = {
        "mamba": d * 2 * wide + wide * (rank + 2 * n) + rank * wide + wide * d,
        "swa": self_attn, "full": self_attn, "xattn": 2 * d * h * hd, "gmu": 2 * d * wide,
    }
    per_token = dc["patch"] ** 2 * 3 * d + sum(
        per_kind[k] + 3 * d * dc["intermediate_size"] for k in kinds)
    c, rc = ref["feature_channels"], ref["rpn"]["channels"]
    k = len(ref["anchor_scales"]) * len(ref["anchor_ratios"])
    per_token += d * c + 9 * c * c + 9 * c * rc + rc * 5 * k
    rc_ = ref["rcnn"]
    hd_ = rc_["hidden_dim"]
    per_roi = rc_["pooled_size"] ** 2 * c * hd_ + hd_ * hd_ + hd_ * 5 * ref["num_classes"]
    forward = 2.0 * images * (tokens * per_token + rc_["roi_batch_size"] * per_roi)
    ops = (mamba_scan_need(ref, images)["flops"] + swa_attn_need(ref, images)["flops"]
           + full_attn_need(ref, images)["flops"])
    return 3.0 * forward + ops
