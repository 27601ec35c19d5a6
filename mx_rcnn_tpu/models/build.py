"""Backbone factory: BackboneConfig -> flax module + metadata."""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from mx_rcnn_tpu.config import DECODER_BACKBONES, BackboneConfig
from mx_rcnn_tpu.models.decoder import DecoderBackbone
from mx_rcnn_tpu.models.resnet import ResNet, STAGE_BLOCKS
from mx_rcnn_tpu.models.vgg import VGG16

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def build_backbone(
    cfg: BackboneConfig,
    out_levels: tuple[int, ...] = (2, 3, 4, 5),
    dtype: jnp.dtype | None = None,
) -> nn.Module:
    """``dtype`` overrides the config knob — the detector passes the
    resolved precision policy's compute dtype so a ``"float32"`` policy
    really forces the whole model to f32, backbone included."""
    dtype = _DTYPES[cfg.dtype] if dtype is None else dtype
    if cfg.name in STAGE_BLOCKS:
        return ResNet(blocks=STAGE_BLOCKS[cfg.name], norm=cfg.norm, dtype=dtype,
                      out_levels=out_levels, remat=cfg.remat,
                      stem_s2d=cfg.stem_s2d, stem_pool_fold=cfg.stem_pool_fold,
                      pad_small_ch=cfg.c2_pad, fold_bn=cfg.fold_frozen_bn,
                      name="backbone")
    if cfg.name == "vgg16":
        if cfg.stem_s2d:
            raise ValueError(
                "backbone.stem_s2d is ResNet-only (VGG's stem is a 3x3/1 "
                "conv stack with no strided RGB conv to rewrite)"
            )
        return VGG16(dtype=dtype, remat=cfg.remat, name="backbone")
    if cfg.name in DECODER_BACKBONES:
        if cfg.stem_s2d:
            raise ValueError("backbone.stem_s2d is ResNet-only (a decoder backbone patchifies)")
        return DecoderBackbone(cfg=cfg.decoder, dtype=dtype, remat=cfg.remat, name="backbone")
    known = sorted(STAGE_BLOCKS) + ["vgg16"] + list(DECODER_BACKBONES)
    raise ValueError(f"unknown backbone {cfg.name!r}; known: {known}")
