"""What the scan mixers' PROJECTIONS need in one training step, from the
configuration's sizes: the matmuls round the recurrence of a KDA, a Mamba-2 or a
Mamba-1 layer (``ling_need.py``, ``ssm_need.py`` and ``sambay_need.py`` count
the recurrences themselves and the whole step).  A projection of ``i`` inputs
on ``o`` outputs over ``T`` tokens needs 2 T i o FLOPs and moves its weight
(bfloat16) once, its input once and its result once.  The backward counts twice
the forward's FLOPs and moves the forward's bytes twice; what a block's
``jax.checkpoint`` computes again is not needed and not counted, so a program
that recomputes the forward cannot read above 75 % of this need's roofline.
The short convolutions, gates and norms between the matmuls need no matmul:
their time is ``mixer_glue_ms.train``'s.
"""

from __future__ import annotations


def projections(ref) -> tuple[str, int, list]:
    """(the scan mixer's kind, how many layers hold one, [(inputs, outputs)]
    of one layer's projection matmuls); ("", 0, []) for a configuration
    without a decoder."""
    dc = ref.get("decoder")
    if dc is None:
        return "", 0, []
    d = dc["hidden_size"]
    if "mb_per_layer" in dc:                         # SambaY: Mamba-1
        from perfbench.reference.backbone_phi4_mini_flash import kind

        wide, n, rank = dc["mamba_expand"] * d, dc["mamba_d_state"], dc["mamba_dt_rank"]
        layers = sum(kind(dc, l) == "mamba" for l in dc["layers"])
        return "mamba", layers, [(d, 2 * wide), (wide, rank + 2 * n), (rank, wide), (wide, d)]
    if "mamba_num_heads" in dc:                      # the state-space tower: Mamba-2
        from perfbench.reference.backbone_nemotron_twotower import kind

        inner = dc["mamba_num_heads"] * dc["mamba_head_dim"]
        in_proj = 2 * inner + 2 * dc["n_groups"] * dc["ssm_state_size"] + dc["mamba_num_heads"]
        layers = sum(kind(dc, l) == "ssm" for l in dc["layers"])
        return "ssm", layers, [(d, in_proj), (inner, d)]
    from perfbench.reference.backbone_ling3_flash_vl import kinds

    h, hd = dc["num_attention_heads"], dc["head_dim"]
    layers = sum(kinds(dc, l)[0] == "kda" for l in dc["layers"])
    # q, k, v, the decay gate f and the output gate g; beta a head; the output
    return "kda", layers, [(d, h * hd)] * 5 + [(d, h), (h * hd, d)]


def mixer_proj_need(ref, images: int):
    """{"flops", "bytes"} of the scan mixers' projections over ``images``
    images, forward and backward; None without a scan mixer."""
    _, layers, matmuls = projections(ref)
    if not layers:
        return None
    h, w = ref["canvas"]
    tokens = images * (h // ref["decoder"]["patch"]) * (w // ref["decoder"]["patch"])
    flops = layers * sum(2.0 * tokens * i * o for i, o in matmuls)
    moved = layers * sum(2.0 * i * o + 2.0 * tokens * (i + o) for i, o in matmuls)
    return {"flops": 3.0 * flops, "bytes": 3.0 * moved}
