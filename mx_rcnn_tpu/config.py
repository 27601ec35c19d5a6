"""Immutable experiment configuration.

Replaces the reference's mutable global config singleton
(``rcnn/config.py``: one module-level easydict mutated by every CLI via
``generate_config(network, dataset)``) with frozen dataclasses passed
explicitly.  Nothing here is global; a config is constructed once (from a
preset plus CLI overrides) and threaded through the program.

The numeric defaults preserve the reference's semantics where parity
matters (anchor geometry, NMS thresholds, fg/bg sampling quotas, bbox
normalization stds) and upgrade to the FPN-era Detectron recipe where the
BASELINE north star requires it (>=37 COCO mAP needs FPN + ROIAlign + the
modern 1x schedule, not the 2017 C4 recipe).

Presets mirror BASELINE.json's five configs — see :func:`get_config`.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass(frozen=True)
class AnchorConfig:
    """Anchor geometry (reference: config.ANCHOR_SCALES / ANCHOR_RATIOS)."""

    # Scales are in units of the stride at each level.  The reference's C4
    # single-level setup uses base_size 16 with scales (8, 16, 32); FPN uses
    # one scale (8) per level with strides (4..64) covering the same range.
    scales: tuple[float, ...] = (8.0, 16.0, 32.0)
    ratios: tuple[float, ...] = (0.5, 1.0, 2.0)

    def num_anchors(self) -> int:
        return len(self.scales) * len(self.ratios)


@dataclass(frozen=True)
class DecoderConfig:
    """Decoder-only language-model blocks used as a plain stride-16 backbone
    (models/decoder.py; read only when ``BackboneConfig.name`` names one).
    The defaults are Ling-3.0-flash-VL's published sizes
    (huggingface.co/inclusionAI/Ling-3.0-flash-VL config.json) at one chip's
    share: the published layers ``layers`` are held (a layer's kind follows
    from its published index), and of each layer's ``num_experts`` routed
    experts the ``experts_count`` from ``experts_first`` on; the router keeps
    its published width.

    Four families' patterns fit.  ``pattern`` empty: Ling's rule, every layer
    a mixer and a feed-forward (``layer_group_size``, ``first_k_dense``).
    ``pattern`` given (``NEMOTRON_TWOTOWER``): one letter a PUBLISHED layer,
    each layer ONE sub-layer - ``M`` a Mamba-2 state-space mixer (``ssm_*``),
    ``*`` grouped-query attention (``num_kv_heads``), ``E`` the expert layer.
    ``mb_per_layer`` given (``PHI4_MINI_FLASH``): SambaY's rule against the
    published depth ``num_hidden_layers`` = L - every ``mb_per_layer``-th layer
    a Mamba-1 mixer (``mamba_*``) up to L/2 and a Gated Memory Unit after, the
    others differential attention under ``sliding_window`` below L/2, over the
    whole prefix at L/2 + 1, and cross attention to that layer's keys and
    values after; a dense SwiGLU (``intermediate_size``) in every layer,
    LayerNorm with bias (``rms_norm_eps`` is its epsilon).  ``layer_types``
    given (``GRANITE4_H_MICRO``): one word a PUBLISHED layer, ``mamba`` a
    Mamba-2 mixer or ``attention`` grouped-query attention, each followed by a
    dense SwiGLU in the same layer; muP multipliers scale every sub-layer's
    output (``residual_multiplier``), the patch tokens (``embedding_multiplier``)
    and the attention's scores (``attention_multiplier``).  Their defaults
    leave the other families' programs as they were: nothing is multiplied."""

    hidden_size: int = 2560
    num_heads: int = 32
    head_dim: int = 128
    # Published indices of the layers held: the leading dense layer once, and
    # one whole period of ``layer_group_size``.
    layers: tuple[int, ...] = (0, 6, 7, 8, 9, 10, 11)
    first_k_dense: int = 2        # layers below it: dense SwiGLU, no experts
    layer_group_size: int = 6     # the last layer of each group is MLA, the rest KDA
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_experts: int = 512
    experts_first: int = 0
    experts_count: int = 8
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6.0e6
    rms_norm_eps: float = 1.0e-6
    short_conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    patch: int = 16               # stride-16 patchify convolution
    neck_channels: int = 256
    pattern: str = ""             # "" = Ling's rule; else M | * | E a published layer
    num_kv_heads: int = 32        # key/value heads of a ``*`` layer
    ssm_heads: int = 64           # an ``M`` layer: heads x head_dim = the inner width,
    ssm_head_dim: int = 64
    ssm_groups: int = 8           # B and C shared by heads / groups heads, the gated
    ssm_state: int = 128          # norm in as many groups; the state is head_dim x state
    # An expert: "swiglu" (gate, up, down) or "relu2" (down relu(up x)^2).
    expert_act: str = "swiglu"
    shared_intermediate_size: int = 0   # the shared expert's width; 0 = the routed experts'
    mb_per_layer: int = 0         # > 0: a SambaY decoder, a Mamba-1 layer every so many
    num_hidden_layers: int = 0    # its PUBLISHED depth: the rule counts from the middle
    sliding_window: int = 0       # positions a window layer's query sees, itself included
    mamba_expand: int = 2         # a Mamba-1 mixer: inner width = expand x hidden,
    mamba_d_state: int = 16       # states a channel,
    mamba_dt_rank: int = 160      # and the step size's low rank; its conv: short_conv_kernel
    layer_types: tuple[str, ...] = ()   # () = no such rule; else mamba | attention a published layer
    residual_multiplier: float = 1.0    # every sub-layer's output, before it joins the stream
    embedding_multiplier: float = 1.0   # the patch tokens, which stand in for the token embedding
    attention_multiplier: float = 0.0   # a ``gqa`` layer's softmax scale; 0 = head_dim ** -0.5


# Nemotron-Labs-TwoTower-30B-A3B's tower (huggingface.co/nvidia/
# Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 config.json, model_type nemotron_h)
# at one chip's share: published layers 0-12 (the pattern's first two units,
# MEMEM* + EMEMEM*: 6 Mamba-2, 5 expert, 2 attention layers) and 8 of the 128
# routed experts a layer (one of 16 chips that share each layer).  The second
# (denoiser) tower, its conditioning and the block-diffusion decode are not held.
NEMOTRON_TWOTOWER = DecoderConfig(
    hidden_size=2688, num_heads=32, head_dim=128, num_kv_heads=2,
    layers=tuple(range(13)),
    pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    ssm_heads=64, ssm_head_dim=64, ssm_groups=8, ssm_state=128, short_conv_kernel=4,
    moe_intermediate_size=1856, shared_intermediate_size=3712, expert_act="relu2",
    num_experts=128, experts_first=0, experts_count=8, num_experts_per_tok=6,
    n_group=1, topk_group=1, routed_scaling_factor=2.5, rms_norm_eps=1.0e-5,
)

# Phi-4-mini-flash-reasoning's SambaY decoder (huggingface.co/microsoft/
# Phi-4-mini-flash-reasoning config.json, model_type phi4flash; arXiv:2507.06607)
# cut to 8 of its 32 layers by its own rule: published layers 0-3 (Mamba,
# window, Mamba, window) and 16-19 (the Mamba layer that hands on its memory,
# the full-attention layer that hands on its keys and values, a Gated Memory
# Unit, cross attention).  Dense: whole layers here, the others on further
# chips.  The Mamba sizes are its configuration class's defaults.
PHI4_MINI_FLASH = DecoderConfig(
    hidden_size=2560, num_heads=40, num_kv_heads=20, head_dim=64,
    layers=(0, 1, 2, 3, 16, 17, 18, 19), num_hidden_layers=32, mb_per_layer=2,
    sliding_window=512, intermediate_size=10240, rms_norm_eps=1.0e-5, short_conv_kernel=4,
    mamba_expand=2, mamba_d_state=16, mamba_dt_rank=160,
)

# Granite 4.0-H Micro's hybrid decoder (huggingface.co/ibm-granite/
# granite-4.0-h-micro config.json, model_type granitemoehybrid) cut to one
# period of its pattern: published layers 0-9 (nine Mamba-2 mixers with ONE
# group of B and C for all 64 heads, grouped-query attention without
# positional encoding at 5), a SwiGLU of 8192 after every mixer, muP
# multipliers.  Dense: whole layers here, the other 30 on further chips.
GRANITE4_H_MICRO = DecoderConfig(
    hidden_size=2048, num_heads=32, num_kv_heads=8, head_dim=64,
    layers=tuple(range(10)),
    layer_types=tuple("attention" if l % 10 == 5 else "mamba" for l in range(40)),
    ssm_heads=64, ssm_head_dim=64, ssm_groups=1, ssm_state=128, short_conv_kernel=4,
    intermediate_size=8192, rms_norm_eps=1.0e-5,
    residual_multiplier=0.22, embedding_multiplier=12.0, attention_multiplier=0.015625,
)

# Backbone name -> the decoder blocks it holds.
DECODER_BACKBONES = {"ling3_flash_vl": DecoderConfig(), "nemotron_twotower": NEMOTRON_TWOTOWER,
                     "phi4_mini_flash": PHI4_MINI_FLASH, "granite4_h_micro": GRANITE4_H_MICRO}


@dataclass(frozen=True)
class BackboneConfig:
    # resnet50 | resnet101 | vgg16 | ling3_flash_vl | nemotron_twotower | phi4_mini_flash
    # | granite4_h_micro
    # (decoder blocks as a plain backbone, sized by ``decoder``)
    name: str = "resnet50"
    # Stages to freeze, counted like the reference's fixed_param_prefix
    # (conv1 + res2 frozen for ResNet; conv1_/conv2_ for VGG).
    freeze_stages: int = 2
    # Frozen BatchNorm everywhere (reference: use_global_stats=True).
    norm: str = "frozen_bn"  # frozen_bn | bn | gn
    # Compute dtype for conv/matmul (params stay float32).
    dtype: str = "bfloat16"
    # Rematerialize backbone activations on the backward pass
    # (jax.checkpoint per residual block / conv group): trades ~1/3 more
    # backbone FLOPs for O(depth) less HBM — enables bigger canvases or
    # per-chip batches than stored activations would allow.
    remat: bool = False
    # Execute the 7x7/2 RGB stem in space-to-depth form (exact rewrite,
    # 4x denser MXU contraction — models/resnet.py::StemConv).  ResNet only.
    stem_s2d: bool = False
    # Execute the stem's 3x3/2 max-pool as strided slices + elementwise max
    # instead of a reduce_window over the worst-laid-out tensor in the net
    # (models/resnet.py::_maxpool3x3s2_slices; exact, -inf padding both
    # forms; falls back on odd stem-output dims).  ResNet only.
    stem_pool_fold: bool = False
    # Zero-pad C2's 64-wide contractions to the MXU's 128 lanes (exact —
    # padded channels are zero; params keep canonical shapes).  ResNet
    # only; self-limiting to C2, the one sub-128-channel stage.
    c2_pad: bool = False
    # Fold frozen-BN affines into the conv weights: conv(x, W*s) + t, the
    # same math with the multiply riding the existing f32->bf16 weight
    # cast instead of a per-activation multiply-add (measured +1.4 ms
    # across an R101 trunk — FrozenBN does NOT all fuse into the convs).
    # ResNet + frozen_bn only; no-op otherwise.  Param tree unchanged.
    fold_frozen_bn: bool = False
    decoder: DecoderConfig = field(default_factory=DecoderConfig)


@dataclass(frozen=True)
class FPNConfig:
    enabled: bool = True
    channels: int = 256
    min_level: int = 2
    max_level: int = 6  # P6 by max-pool of P5 (RPN only)


@dataclass(frozen=True)
class RPNConfig:
    """RPN head + proposal generation (reference: config.TRAIN/TEST RPN_*)."""

    channels: int = 256  # hidden conv (VGG uses 512 in the reference)
    # Anchor labeling (rcnn/io/rpn.py::assign_anchor semantics).
    batch_size: int = 256
    fg_fraction: float = 0.5
    positive_iou: float = 0.7
    negative_iou: float = 0.3
    allowed_border: float = 0.0
    # Proposal generation (rcnn/symbol/proposal.py semantics).
    train_pre_nms_top_n: int = 2000
    train_post_nms_top_n: int = 1000
    test_pre_nms_top_n: int = 1000
    test_post_nms_top_n: int = 1000
    nms_threshold: float = 0.7
    min_size: float = 0.0
    loss_weight: float = 1.0
    # Pre-NMS top-k selection over the anchor scores.  "hier" — the
    # default — is the blocked two-stage exact reduction
    # (ops/topk.py::hierarchical_top_k): per-tile partial top-k then a
    # merge of survivors, BIT-IDENTICAL to lax.top_k including the
    # snapped-score index-stable tie-breaks (proof in the module
    # docstring, asserted in tests/test_ops.py), but the sort shrinks
    # from the full 268k-anchor operand to ``topk_block``-wide tiles.
    # "exact" = the global lax.top_k (one full sort network — the
    # oracle).  "approx" = lax.approx_max_k (the TPU PartialReduce op)
    # at ``topk_recall`` expected recall of the true top-k: the
    # k'th-ranked RPN scores are deep in the sigmoid tail, so the
    # ~(1-recall) swapped candidates are low-objectness boxes
    # NMS/top-post would drop anyway — a first-class A/B'able training
    # option (measured +1.1 img/s over "exact" in r4b), opt-in because
    # it is the one impl that changes proposals.  Off TPU,
    # approx_max_k lowers to a full sort (exact), so CPU tests and
    # goldens see identical numbers for ALL three impls.
    topk_impl: str = "hier"
    topk_recall: float = 0.95
    # Tile width for the "hier" reduction (also routes the anchor
    # subsampling top_k's in ops/sampling.py::_select_random).  Any
    # value gives the same bits; power-of-two multiples of the 128-lane
    # VPU width keep the batched per-tile sort layout-friendly.  <= 0
    # falls back to the global sort.
    topk_block: int = 32768
    # Anchor-axis tile for assign_anchors' IoU/argmax reductions
    # (ops/sampling.py::_per_anchor_stats_blocked): the (A, G) IoU
    # matrix (34 MB at the recipe canvas) never materializes — each
    # tile's IoU is computed and reduced in one VMEM-resident fusion.
    # Bit-identical to the dense pass (f32 max is exactly associative);
    # <= 0 restores the single-pass dense form.
    assign_block: int = 16384
    # RPN loss reduction domain.  "dense" (default) reduces BCE/smooth-l1
    # over the full (B, A) anchor axis with masks — the historical form,
    # bit-identical to pre-fast-path builds.  "compact" gathers the
    # Q = fg_quota + batch_size sampled rows (AnchorTargets.sel_*) and
    # reduces only those: the same loss up to summation order (the
    # masked-out terms are exact zeros), so metrics match to f32
    # round-off, not bitwise — opt-in for A/B.
    loss_impl: str = "dense"
    # Run the weight-shared head over all FPN levels as ONE packed
    # computation (models/heads.py::RPNHead.packed) instead of five
    # sequential small-spatial convs (the P2 apply alone measured
    # 6.6 ms/step).  Exact — identical per-level outputs; the packing is
    # sliced away before anything downstream.  No-op for single-level
    # (C4) models; disabled automatically under spatial partitioning
    # (parallel/step.py::mesh_safe_model_cfg — the packed canvas would
    # concatenate across height shards).
    packed_head: bool = True


@dataclass(frozen=True)
class RCNNConfig:
    """Second-stage sampling/head (reference: ProposalTarget + heads)."""

    roi_batch_size: int = 512  # reference BATCH_ROIS (128 C4 / 512 FPN)
    fg_fraction: float = 0.25
    fg_iou: float = 0.5
    bg_iou_hi: float = 0.5
    bg_iou_lo: float = 0.0
    # 1/std of the reference's TRAIN.BBOX_STDS (0.1, 0.1, 0.2, 0.2).
    bbox_weights: tuple[float, float, float, float] = (10.0, 10.0, 5.0, 5.0)
    pooled_size: int = 7
    sampling_ratio: int = 2
    hidden_dim: int = 1024  # 2-fc box head width (VGG fc6/fc7 use 4096)
    # Class-agnostic box regression (False = per-class, reference default).
    class_agnostic: bool = False
    loss_weight: float = 1.0
    # ROIAlign backend on a MULTI-level pyramid: "pallas" (default — one
    # batch-folded windowed-DMA kernel launch per step; measured 83.1 ->
    # 77.6 ms/step on the full R50-FPN train step once the whole batch
    # rides one grid) or "xla" (flattened-pyramid gather — the oracle, the
    # backward, and the automatic fallback off-TPU or on unsupported
    # layouts).  Not read on a one-level (C4 / VGG) pyramid: there the one
    # path is the interpolation matmul, ops/roi_align.py::roi_align_matmul.
    roi_align_impl: str = "pallas"
    # Backward for the pallas forward: "pallas" (default — the windowed-DMA
    # scatter-accumulate kernel ops/pallas/roi_align.py::_bwd_kernel, the
    # r3 default previously selected only via env) or "xla" (autodiff
    # through the flattened gather — the A/B and debugging escape hatch).
    # The MX_RCNN_POOL_BWD env var still overrides at trace time.
    roi_align_bwd_impl: str = "pallas"
    # ROI-axis tile for sample_rois' IoU/argmax reductions
    # (ops/sampling.py::_per_row_stats_blocked, the same machinery as
    # rpn.assign_block): the (R+G, G) IoU matrix never materializes —
    # each ROI tile's IoU is computed and reduced in one VMEM-resident
    # fusion.  Bit-identical to the dense pass (elementwise IoU is
    # tiling-independent and the per-row max/argmax never cross tiles);
    # <= 0 (default) restores the single-pass dense form.
    roi_block: int = 0


@dataclass(frozen=True)
class MaskConfig:
    enabled: bool = False
    pooled_size: int = 14
    channels: int = 256
    num_convs: int = 4
    resolution: int = 28
    loss_weight: float = 1.0


@dataclass(frozen=True)
class TestConfig:
    """Inference-time postprocessing (reference: config.TEST + pred_eval)."""

    # Eval images per chip per call (reference: strictly 1).  >1 amortizes
    # per-dispatch overhead and fills the MXU better at eval time — batch 8
    # measured ~3.5x batch-1 throughput (PARITY.md) — so 8 is the default
    # and only deliberately tiny presets (tiny_synthetic's hermetic CPU
    # programs) drop back to 1.
    per_device_batch: int = 8
    score_threshold: float = 0.05
    nms_threshold: float = 0.5  # per-class NMS (reference uses 0.3 for VOC)
    max_detections: int = 100
    # Postprocess NMS structure.  "per_class" replays the reference's
    # per-class loop exactly (one NMS fixed point per foreground class,
    # vmapped — C-1 passes of per_class_k boxes per image).  "fused" —
    # the default — takes the global top-``fused_top_k`` (roi, class)
    # candidates by score and runs ONE class-offset NMS over them
    # (ops/nms.py::batched_nms); per-class results are identical whenever
    # no class overflows the per-class cap and the union of
    # above-threshold candidates fits ``fused_top_k`` (tested), which
    # real images satisfy — only the pre-NMS candidate cap moves from
    # per-class (2*max_detections each) to global.  When the global cap
    # DOES bind, the dropped candidates are the score-ranked-worst
    # pre-NMS; under heavy suppression one of them could have survived
    # its class NMS into the final set, so binding-cap outputs can
    # differ (use "per_class" for exact reference replay there).  TPU
    # rationale: 80 vmapped while-loops run
    # every class lane until the slowest converges; one fused pass
    # converges once.  Measured (BASELINE.md): R50-FPN eval batch-8
    # 82.1 -> 94.9 img/s/chip.
    nms_mode: str = "fused"
    fused_top_k: int = 1000


@dataclass(frozen=True)
class PrecisionConfig:
    """End-to-end mixed-precision policy (utils/precision.py resolves it).

    ``policy`` names the whole-graph dtype contract:

    - ``"mixed"`` (default): heads compute AND emit in the backbone's
      compute dtype — with a bfloat16 backbone nothing f32-sized crosses
      the model/detection boundary (the (B, ~268k) RPN logit and
      (B, ~268k, 4) delta materializations were the last ones).  Losses,
      metrics, the guardian reduction, and the optimizer still accumulate
      in float32 (the explicit upcast allowlist tpulint TPU006 enforces),
      and box *coordinates* stay float32 throughout — only scores/logits
      ride bf16.  With a float32 backbone (tiny_synthetic) this resolves
      to all-f32 and is bit-identical to historical graphs.
    - ``"widen"``: heads compute in the backbone dtype but cast outputs
      to float32 — exactly the pre-r6 graphs, kept as the A/B and
      bisection escape hatch.
    - ``"float32"``: force everything float32 regardless of the backbone
      dtype knob.

    ``accum`` is the accumulation dtype for losses/metrics/reductions;
    anything other than float32 voids the TPU006 contract and the NaN
    guardian's assumptions — it exists for experiments, not recipes.
    """

    policy: str = "mixed"  # mixed | widen | float32
    accum: str = "float32"


@dataclass(frozen=True)
class ModelConfig:
    num_classes: int = 81  # includes background at index 0 (COCO: 80 + 1)
    backbone: BackboneConfig = field(default_factory=BackboneConfig)
    fpn: FPNConfig = field(default_factory=FPNConfig)
    anchors: AnchorConfig = field(default_factory=AnchorConfig)
    rpn: RPNConfig = field(default_factory=RPNConfig)
    rcnn: RCNNConfig = field(default_factory=RCNNConfig)
    mask: MaskConfig = field(default_factory=MaskConfig)
    test: TestConfig = field(default_factory=TestConfig)
    precision: PrecisionConfig = field(default_factory=PrecisionConfig)


@dataclass(frozen=True)
class DataConfig:
    dataset: str = "coco"  # coco | voc | synthetic
    root: str = "data"
    train_split: str = "train2017"
    val_split: str = "val2017"
    # Static LANDSCAPE canvas (H, W), H <= W; portrait images letterbox
    # into its transpose (data/transforms.py::oriented_canvas — batches
    # are single-orientation under aspect_grouping, so each orientation is
    # one compiled program).  The reference resizes short side to
    # SCALES[0] capped at MAX_SIZE and re-binds executors per shape; two
    # static canvases are the TPU-native equivalent that preserves the
    # full short/max rule: 800x1344 fits every 800-short/1333-max resize
    # (1344 = 42*32 for FPN stride divisibility) at ~1.03x the pixels of
    # the old square 1024^2 canvas, which silently clamped most images
    # below the Detectron recipe resolution.
    image_size: tuple[int, int] = (800, 1344)
    short_side: int = 800
    max_side: int = 1333
    max_gt_boxes: int = 100
    flip: bool = True
    # Reference pixel means (BGR 123.68/116.78/103.94 order-swapped); we use
    # RGB ImageNet mean/std.
    pixel_mean: tuple[float, float, float] = (123.675, 116.28, 103.53)
    pixel_std: tuple[float, float, float] = (58.395, 57.12, 57.375)
    aspect_grouping: bool = True
    # Host-side normalization (the reference's rcnn/io/image.py::transform
    # order).  Default OFF: the loader ships uint8 letterboxed pixels (1/4
    # the host->device bytes and device_prefetch HBM of float32) and the
    # (x - mean) / std runs in-graph, fused into the first conv's input
    # (detection/graph.py::prep_images).  True restores float32 host
    # normalization (the fused C++ path); in-memory float synthetic images
    # always normalize on host regardless.
    normalize_on_host: bool = False
    # VOC only: promote "difficult" objects to real gt instead of keeping
    # them as flagged ignore regions (reference:
    # ``rcnn/dataset/pascal_voc.py`` config.USE_DIFFICULT knob).
    use_diff: bool = False
    # Parsed-roidb pickle cache directory (reference: imdb.gt_roidb caches
    # under data/cache/<name>_gt_roidb.pkl).  "" disables; entries are
    # invalidated by the annotation source's mtime.  Also roots the
    # checksummed tensor cache (data/cache.py): decoded+letterboxed pixels
    # memoized under <cache_dir>/tensors/<transform-fingerprint>/ with
    # per-blob CRCs — corrupt blobs are quarantined and rebuilt, never
    # served.
    cache_dir: str = ""
    # Process input service (data/service.py): decode/augment workers as
    # independent failure domains with deterministic reassignment — the
    # yielded schedule is bit-identical for any worker count and after any
    # worker death.  0 (default) keeps the in-process thread pool.
    num_workers: int = 0
    # Per-worker-slot respawn budget after a death/wedge; exhausting every
    # slot degrades to in-process synchronous assembly (run completes).
    worker_respawns: int = 2
    # Zero-copy shm transport for the input service (data/shm_ring.py):
    # each worker ships assembled batches through a CRC-stamped
    # shared-memory ring instead of pickling tensors through the result
    # queue; bounded slots are the backpressure.  Only active when
    # num_workers > 0.  shm_transport=False restores the pickle path.
    shm_transport: bool = True
    # Ring slots per worker.  Each slot holds one batch; more slots buy
    # pipelining headroom at slots*slot_bytes shm per worker.
    shm_slots: int = 4
    # Slot size override in MiB.  0 (default) auto-sizes from the batch
    # shape (canvas, max_gt_boxes, masks/proposals if on) with headroom;
    # a batch that still overflows its slot falls back to pickle for that
    # batch only.
    shm_slot_mb: int = 0


@dataclass(frozen=True)
class ScheduleConfig:
    """MultiFactor-style LR schedule (reference: lr_scheduler in drivers).

    ``decay_steps``/``total_steps`` are denominated at a global batch of
    ``reference_batch`` images; ``build_all`` rescales them by
    ``reference_batch / global_batch`` alongside the linear lr scaling, so
    a preset trains the same number of EPOCHS at any pod size (the
    reference's drivers likewise scale lr by ``len(ctx) * kv.num_workers``
    while keeping epoch-denominated schedules).  ``reference_batch = 0``
    disables both rescalings' step side (steps are absolute; lr still
    scales by global_batch/16) — used by the tiny test preset whose golden
    numbers pin absolute step counts.  ``warmup_steps`` stays absolute
    (warmup guards the first optimizer steps, however large the batch).
    """

    base_lr: float = 0.02  # for global batch `reference_batch`; scaled linearly
    warmup_steps: int = 500
    warmup_factor: float = 1.0 / 3.0
    # Steps at which lr is multiplied by `factor` (in units of train steps
    # at reference_batch).
    decay_steps: tuple[int, ...] = (60000, 80000)
    factor: float = 0.1
    total_steps: int = 90000
    reference_batch: int = 16


@dataclass(frozen=True)
class TrainConfig:
    per_device_batch: int = 1  # reference: 1 image per GPU
    # Chips per image sharing the spatial (height) axis — the mesh's model
    # axis.  1 = pure data parallelism (reference parity).  >1 partitions
    # the backbone convs spatially (XLA halo exchange) for resolutions one
    # chip can't hold; devices must be divisible by it.
    spatial_partition: int = 1
    # Train steps executed per host->device call: >1 moves the step loop
    # onto the device as a lax.scan over a (K, B, ...) stacked batch,
    # amortizing the per-call dispatch cost K-fold.  On the local TPU
    # runtime that cost is 0.2 ms per dispatch (PR 21 chip run), so the
    # default of 1 gives up nothing measurable there.
    # Logging/checkpoint cadence quantizes to K.
    steps_per_call: int = 1
    # Microbatches accumulated per optimizer step (parallel/plan.py): >1
    # scans N microbatches with f32 gradient accumulators and applies ONE
    # update — the global batch multiplies by N without more chips (the
    # large-minibatch lever when the target batch exceeds device memory).
    # Mutually exclusive with steps_per_call>1 and spatial_partition>1.
    # 1 is bit-identical to the plain step.
    accum_steps: int = 1
    # Bucketed gradient all-reduce (parallel/step.py::_bucketed_pmean):
    # > 0 splits the single per-step grads pmean into per-bucket pmeans
    # of ~bucket_mb MiB, grouped in reverse parameter order (the order
    # backward frames complete) so each bucket's DCN/ICI time can hide
    # under the remaining backward compute instead of serializing after
    # it.  Exact: each leaf rides exactly one pmean either way, so the
    # reduction is bitwise identical to the single fused pmean
    # (tests/test_block_parity.py asserts it).  0 (default) keeps the
    # plain GSPMD step — PR 3's bit-exact resume proofs carry over
    # literally.  On jax 0.9.0 only zero vs non-zero matters: the bucket
    # size itself never reaches the compiler (parallel/step.py).
    bucket_mb: int = 0
    momentum: float = 0.9
    weight_decay: float = 1e-4
    grad_clip: float = 35.0  # reference: clip_gradient=5 per-example scale
    schedule: ScheduleConfig = field(default_factory=ScheduleConfig)
    checkpoint_every: int = 5000
    log_every: int = 20
    seed: int = 0
    # NaN guardian (train/guardian.py): rollback-and-skip retries allowed
    # before a non-finite metric becomes a hard TrainingDiverged error.
    # 0 = detect-and-raise immediately (no rollback).
    guardian_rollbacks: int = 2
    # Loss-spike early warning: interval mean this many sigma above the
    # trailing-window mean logs loudly (no rollback — just visibility).
    guardian_spike_z: float = 8.0


@dataclass(frozen=True)
class ObsConfig:
    """Observability plane (mx_rcnn_tpu/obs/): typed journal, metrics
    registry + /metrics endpoint, span tracing, flight recorder.  All
    host-side — tpulint TPU007 keeps obs out of traced modules, so none
    of these knobs can change a compiled program."""

    # Master switch for the DURABLE surfaces (journal/spans/flight files
    # under <workdir>/<name>/obs).  Off, events still derive their log
    # lines and feed the in-memory flight ring — zero filesystem traffic.
    enabled: bool = False
    # Override the artifact directory ("" = <workdir>/<name>/obs).
    dir: str = ""
    # /metrics + /healthz + /statusz HTTP port: -1 = no endpoint,
    # 0 = ephemeral (logged + readable via obs.metrics_port()).
    metrics_port: int = -1
    # Per-step train spans + per-request serving spans -> spans.jsonl
    # (Chrome-trace lines; tools/obs_report.py wraps them loadable).
    spans: bool = True
    # Flight-recorder ring size (most-recent events+spans kept for the
    # postmortem dump).
    flight_size: int = 512
    # Seconds between metrics_flush journal events (0 = only at close).
    flush_s: float = 0.0


@dataclass(frozen=True)
class DeployConfig:
    """Continuous deployment (ctrl/deploy.py): shadow canaries,
    parity-gated promotion, burn-triggered automatic rollback.  All
    knobs read as ``cfg.ctrl.deploy.*`` (docs/deployment.md has the
    full table)."""

    # Master switch: serving entrypoints that honour it (tools/soak.py
    # --deploy, tools/deploy_watch.py) run a Deployer next to the fleet.
    enabled: bool = False
    # Seconds between checkpoint-directory scans.
    poll_s: float = 2.0
    # Fraction of accepted live submissions mirrored to the shadow
    # replica (deterministic every-Nth sampling, N = round(1/rate)).
    mirror_rate: float = 0.25
    # Minimum mirrored live/shadow pairs before the gate may rule.
    min_mirrored: int = 8
    # Maximum seconds a candidate may sit in shadow before the gate
    # rules on whatever evidence it has.
    shadow_window_s: float = 30.0
    # Golden-set mAP gate: allowed absolute mAP regression of the
    # shadow vs the live generation on the golden set.
    map_drop: float = 0.005
    # Shadow-scoped SLO (dedicated SLOEngine over the shadow's private
    # metrics window): targets + burn windows scaled to the shadow
    # phase, not the live 5min/1h pair.
    availability_target: float = 0.95
    latency_target: float = 0.95
    latency_threshold_s: float = 30.0
    burn_fast_s: float = 5.0
    burn_slow_s: float = 15.0
    burn_factor: float = 2.0
    # Post-promote watch: a live burn alert inside this window triggers
    # automatic rollback to the previous generation's retained leaves.
    watch_window_s: float = 60.0


@dataclass(frozen=True)
class CtrlConfig:
    """Closed-loop control plane (mx_rcnn_tpu/ctrl/): SLO burn-rate
    alerting and the SLO-driven autoscaler.  Host-side by construction —
    tpulint TPU007 keeps ctrl (like obs) out of traced modules, so none
    of these knobs can change a compiled program."""

    # Master switch: serving entrypoints that honour it (tools/soak.py)
    # run the autoscaler + SLO engine next to the fleet.
    enabled: bool = False
    # Autoscaler fleet bounds and pressure thresholds
    # (ctrl/autoscale.py).  Load is mean inflight+queue per routable
    # replica; shed_high is sheds/second over the evaluation window.
    min_replicas: int = 1
    max_replicas: int = 8
    load_high: float = 4.0
    load_low: float = 0.5
    shed_high: float = 0.0
    # Windowed p99 (seconds) that counts as pressure; 0 disables the
    # latency signal.
    p99_high_s: float = 0.0
    # Scale-down hysteresis (mirrors serve/degrade.py HysteresisPlanner:
    # scale-UP is immediate, scale-DOWN needs this many consecutive
    # comfortable evaluations) + per-direction cooldowns.
    down_dwell: int = 3
    up_cooldown_s: float = 5.0
    down_cooldown_s: float = 15.0
    # Seconds between autoscaler/SLO evaluations.
    period_s: float = 1.0
    # Default SLOs (ctrl/slo.py): availability over fleet request
    # outcomes, and a latency SLO ("latency_target" of requests under
    # "latency_threshold_s").
    availability_target: float = 0.99
    latency_target: float = 0.99
    latency_threshold_s: float = 30.0
    # Multi-window burn-rate alerting: alert when the burn over BOTH
    # windows exceeds burn_factor x the budget rate.
    burn_fast_s: float = 300.0
    burn_slow_s: float = 3600.0
    burn_factor: float = 2.0
    # Continuous deployment (ctrl/deploy.py): shadow canary + promote +
    # rollback knobs, read as cfg.ctrl.deploy.* (docs/deployment.md).
    deploy: DeployConfig = field(default_factory=DeployConfig)


@dataclass(frozen=True)
class TenancyConfig:
    """Multi-tenant admission (serve/tenancy.py), read as
    cfg.serve.tenancy.* — the knob table lives in docs/serving.md.

    Host-side only: tenancy never reaches a traced module, so no knob
    here can change a compiled program."""

    # Master switch.  Off keeps every admission path and metric series
    # bit-identical to the single-tenant build.
    enabled: bool = False
    # Compact tenant table: "name:weight=4,rate=50,burst=20,priority=0;
    # name2:..." (serve/tenancy.py::parse_table).  A string (not nested
    # config) so `--set serve.tenancy.table=...` works through
    # apply_overrides' scalar coercion.
    table: str = ""
    # Where unknown/absent wire tokens land (never a 500); shares this
    # tenant's bucket and label.
    default_tenant: str = "default"
    # Burn-governor degrade action: a tenant-scoped SLO burn alert
    # multiplies that tenant's admitted rate by this factor until the
    # alert clears (serve/tenancy.py::QuotaGovernor).
    tighten_factor: float = 0.25


@dataclass(frozen=True)
class ServeConfig:
    """Serving-engine defaults consumed by serve/engine.py::build_engine
    and serve/fleet.py::build_fleet (explicit kwargs still win)."""

    # Static micro-batch slots per device call.  1 keeps the
    # one-request-per-call path; >1 enables cross-request packing.
    batch_size: int = 1
    # Continuous batching (serve/batcher.py): pack pending requests from
    # different callers into every bucket slot of each device call,
    # deadline-aware.  De-interleaved responses are bitwise identical to
    # the unpacked path (docs/serving.md).  Only meaningful when
    # batch_size > 1.
    pack: bool = True
    # How long (seconds) the worker lingers for stragglers to top off a
    # partially-filled batch before launching it.  0 launches whatever is
    # packable immediately — lowest latency, occupancy rides on queue
    # depth.
    pack_window_s: float = 0.0
    # Content-addressed result cache (serve/result_cache.py): max cached
    # responses per router (LRU).  0 (default) disables the cache AND
    # in-flight coalescing — duplicate-heavy serving surfaces opt in
    # (tools/loadgen.py defaults its fleets to 256); chaos/fault drills
    # keep it off so every request exercises a real replica.
    result_cache_capacity: int = 0
    # Multi-tenant admission: per-tenant token-bucket quotas +
    # weighted-fair pack shares, read as cfg.serve.tenancy.*
    # (docs/serving.md tenancy section).
    tenancy: TenancyConfig = field(default_factory=TenancyConfig)


@dataclass(frozen=True)
class FabricConfig:
    """Cross-host serving fabric (serve/rpc.py, serve/gossip.py,
    serve/gateway.py): one RPC surface per host, health gossip between
    hosts, and a pod-wide gateway.  All host-side, stdlib-HTTP only."""

    # RPC bind port for this host's fabric endpoint: -1 = fabric off,
    # 0 = ephemeral (tools/serve_host.py logs the bound port).
    rpc_port: int = -1
    # Seconds between gossip rounds (self-refresh + peer exchange).
    gossip_period_s: float = 0.5
    # A peer silent this long is SUSPECT; this much longer total, DEAD.
    suspect_after_s: float = 1.5
    dead_after_s: float = 4.0
    # Gateway: seconds before a pending request gets a duplicate on a
    # second host (None-like <=0 disables cross-host hedging), total
    # attempt budget per request, consecutive request failures that
    # quarantine a host, and the quarantined-host probe period.
    hedge_after_s: float = 0.0
    max_attempts: int = 2
    quarantine_failures: int = 2
    probe_interval_s: float = 0.5


@dataclass(frozen=True)
class Config:
    name: str = "faster_rcnn_r50_fpn_coco"
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    ctrl: CtrlConfig = field(default_factory=CtrlConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    fabric: FabricConfig = field(default_factory=FabricConfig)
    workdir: str = "runs"


def _replace(cfg: Any, **kw: Any) -> Any:
    return dataclasses.replace(cfg, **kw)


def _backbone(name: str) -> BackboneConfig:
    """Preset backbone defaults.  ResNet presets run the TPU layout forms
    by default — space-to-depth stem, slice-max stem pool, C2 lane padding
    — all exact rewrites (parity-tested in tests/test_models.py), so mAP
    and checkpoints are unaffected; only the compiled program changes.
    VGG has no strided RGB stem to rewrite and keeps the dense forms."""
    if name.startswith("resnet"):
        return BackboneConfig(
            name=name, stem_s2d=True, stem_pool_fold=True, c2_pad=True
        )
    if name in DECODER_BACKBONES:
        # Nothing frozen (no pretrained stem to protect); every block is
        # recomputed on the backward pass (12 bytes a parameter leave no
        # room for stored activations).
        return BackboneConfig(name=name, freeze_stages=0, norm="none", remat=True,
                              decoder=DECODER_BACKBONES[name])
    return BackboneConfig(name=name)


def _c4_model(num_classes: int, backbone: str) -> ModelConfig:
    """Classic C4 recipe: single-level stride-16 features, anchor scales
    (8, 16, 32), ROIAlign on C4, conv5-as-head replaced by a 2-fc head."""
    return ModelConfig(
        num_classes=num_classes,
        backbone=_backbone(backbone),
        fpn=FPNConfig(enabled=False),
        anchors=AnchorConfig(scales=(8.0, 16.0, 32.0)),
        rpn=RPNConfig(
            channels=512,
            train_pre_nms_top_n=6000,
            train_post_nms_top_n=2000,
            test_pre_nms_top_n=6000,
            test_post_nms_top_n=300,
        ),
        rcnn=RCNNConfig(roi_batch_size=128),
    )


def _fpn_model(num_classes: int, backbone: str, mask: bool = False) -> ModelConfig:
    return ModelConfig(
        num_classes=num_classes,
        backbone=_backbone(backbone),
        fpn=FPNConfig(enabled=True),
        anchors=AnchorConfig(scales=(8.0,)),
        rpn=RPNConfig(),
        rcnn=RCNNConfig(),
        mask=MaskConfig(enabled=mask),
    )


_PRESETS: dict[str, Any] = {}


def _register(name: str, fn) -> None:
    _PRESETS[name] = fn


# The five BASELINE.json configs.
def _vgg16_voc07_model() -> ModelConfig:
    m = _c4_model(21, "vgg16")
    # Override only the VOC-specific test fields so the C4 recipe's other
    # test defaults (e.g. per_device_batch) carry through.
    return _replace(
        m,
        rcnn=RCNNConfig(roi_batch_size=128, hidden_dim=4096),
        test=_replace(m.test, nms_threshold=0.3),
    )


_register(
    "vgg16_voc07",
    lambda: Config(
        name="vgg16_voc07",
        model=_vgg16_voc07_model(),
        data=DataConfig(
            dataset="voc",
            train_split="2007_trainval",
            val_split="2007_test",
            image_size=(608, 1024),
            short_side=600,
            max_side=1000,
            aspect_grouping=True,
        ),
        train=TrainConfig(
            schedule=ScheduleConfig(
                base_lr=0.001, decay_steps=(50000,), total_steps=70000,
                warmup_steps=100,
            ),
        ),
    ),
)
_register(
    "r50_coco",
    lambda: Config(
        name="r50_coco",
        model=_c4_model(81, "resnet50"),
        data=DataConfig(dataset="coco"),
        train=TrainConfig(per_device_batch=2),
    ),
)
_register(
    "r101_coco",
    lambda: Config(
        name="r101_coco",
        model=_c4_model(81, "resnet101"),
        data=DataConfig(dataset="coco"),
        train=TrainConfig(per_device_batch=2),
    ),
)
_register(
    "r101_fpn_coco",
    lambda: Config(
        name="r101_fpn_coco",
        model=_fpn_model(81, "resnet101"),
        data=DataConfig(dataset="coco"),
        train=TrainConfig(per_device_batch=2),
    ),
)
_register(
    "mask_r50_fpn_coco",
    lambda: Config(
        name="mask_r50_fpn_coco",
        model=_fpn_model(81, "resnet50", mask=True),
        data=DataConfig(dataset="coco"),
        train=TrainConfig(per_device_batch=2),
    ),
)
def _decoder_det_model(backbone: str) -> ModelConfig:
    m = _c4_model(81, backbone)
    # Two images a call at test time too: 4,200 tokens an image through
    # 0.8 G parameters leave no room for the default eight.
    return _replace(
        m, rpn=_replace(m.rpn, channels=256), test=_replace(m.test, per_device_batch=2)
    )


# Ling-3.0-flash-VL's decoder blocks (KDA linear attention, MLA, routed
# experts at one chip's share) as a plain stride-16 backbone (Li et al.,
# arXiv:2203.16527, without the pyramid) under the one-level middle.
_register(
    "ling3_flash_vl_det",
    lambda: Config(
        name="ling3_flash_vl_det",
        model=_decoder_det_model("ling3_flash_vl"),
        data=DataConfig(dataset="coco"),
        train=TrainConfig(per_device_batch=2),
    ),
)
# Nemotron-Labs-TwoTower-30B-A3B's tower (Mamba-2 state-space layers,
# 2-KV-head attention, relu^2 experts at one chip's share) the same way.
_register(
    "nemotron_twotower_det",
    lambda: Config(
        name="nemotron_twotower_det",
        model=_decoder_det_model("nemotron_twotower"),
        data=DataConfig(dataset="coco"),
        train=TrainConfig(per_device_batch=2),
    ),
)
# Phi-4-mini-flash-reasoning's SambaY decoder (Mamba-1 selective scan, window
# and full differential attention, Gated Memory Units and shared-K/V cross
# attention; 8 of 32 layers) the same way.
_register(
    "phi4_mini_flash_det",
    lambda: Config(
        name="phi4_mini_flash_det",
        model=_decoder_det_model("phi4_mini_flash"),
        data=DataConfig(dataset="coco"),
        train=TrainConfig(per_device_batch=2),
    ),
)
# Granite 4.0-H Micro's hybrid decoder (Mamba-2 mixers with one group, NoPE
# grouped-query attention, a SwiGLU after every mixer, muP multipliers; one
# period of 10 of its 40 layers) the same way.
_register(
    "granite4_h_micro_det",
    lambda: Config(
        name="granite4_h_micro_det",
        model=_decoder_det_model("granite4_h_micro"),
        data=DataConfig(dataset="coco"),
        train=TrainConfig(per_device_batch=2),
    ),
)
# Default/flagship and test presets.
_register(
    "r50_fpn_coco",
    lambda: Config(
        name="r50_fpn_coco",
        model=_fpn_model(81, "resnet50"),
        data=DataConfig(dataset="coco"),
        train=TrainConfig(per_device_batch=2),
    ),
)
_register(
    "tiny_synthetic",
    lambda: Config(
        name="tiny_synthetic",
        model=_replace(
            _fpn_model(5, "resnet50"),
            # float32 + nothing frozen for the hermetic CPU programs; the
            # TPU layout forms stay ON so every tiny-preset test exercises
            # the production execution paths (exact rewrites — only
            # intra-conv summation order can differ).
            backbone=_replace(
                _backbone("resnet50"), freeze_stages=0, dtype="float32"
            ),
            rpn=RPNConfig(
                batch_size=64,
                train_pre_nms_top_n=200,
                train_post_nms_top_n=64,
                test_pre_nms_top_n=200,
                test_post_nms_top_n=64,
            ),
            rcnn=RCNNConfig(roi_batch_size=32, hidden_dim=128),
            # Batch 1 keeps the hermetic CPU test programs small.
            test=TestConfig(per_device_batch=1),
        ),
        data=DataConfig(
            dataset="synthetic",
            image_size=(128, 128),
            short_side=128,
            max_side=128,
            max_gt_boxes=8,
        ),
        train=TrainConfig(
            schedule=ScheduleConfig(
                base_lr=0.01, warmup_steps=10, decay_steps=(400,),
                total_steps=500,
                # Absolute steps: the golden overfit numbers pin this
                # preset's exact step count on the 8-device fake mesh.
                reference_batch=0,
            ),
            checkpoint_every=250,
        ),
    ),
)


def available_configs() -> list[str]:
    return sorted(_PRESETS)


def get_config(name: str, **overrides: Any) -> Config:
    """Build a preset config; kwargs replace top-level Config fields.

    Replaces the reference's ``generate_config(network, dataset)`` mutator:
    instead of mutating a global, returns a frozen Config.
    """
    if name not in _PRESETS:
        raise KeyError(f"unknown config {name!r}; available: {available_configs()}")
    cfg = _PRESETS[name]()
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def _coerce(text: str, current: Any) -> Any:
    """Parse ``text`` to the type of ``current`` (the existing field value)."""
    if isinstance(current, bool):
        if text.lower() in ("1", "true", "yes"):
            return True
        if text.lower() in ("0", "false", "no"):
            return False
        raise ValueError(f"expected bool, got {text!r}")
    if isinstance(current, tuple):
        parts = [p for p in text.replace("(", "").replace(")", "").split(",") if p]
        elem = current[0] if current else float("nan")
        return tuple(type(elem)(p) if current else float(p) for p in parts)
    if isinstance(current, int) and not isinstance(current, bool):
        return int(text)
    if isinstance(current, float):
        return float(text)
    return text


def apply_overrides(cfg: Config, assignments: list[str]) -> Config:
    """Apply CLI ``dotted.path=value`` overrides to a frozen config tree.

    The functional replacement for the reference CLIs' ad-hoc mutation of the
    global easydict (e.g. ``config.TRAIN.BATCH_IMAGES = args.batch``): each
    assignment rebuilds the dataclass spine from the leaf up.
    """
    for item in assignments:
        if "=" not in item:
            raise ValueError(f"override {item!r} is not of the form key.path=value")
        path, text = item.split("=", 1)
        keys = path.strip().split(".")
        # Collect the chain of dataclass nodes down to the leaf's parent.
        nodes = [cfg]
        for k in keys[:-1]:
            nodes.append(getattr(nodes[-1], k))
        leaf = getattr(nodes[-1], keys[-1])
        if dataclasses.is_dataclass(leaf):
            raise ValueError(f"{path} is a config section, not a field")
        new_val = _coerce(text.strip(), leaf)
        for node, k in zip(reversed(nodes), reversed(keys)):
            new_val = dataclasses.replace(node, **{k: new_val})
        cfg = new_val
    return cfg
