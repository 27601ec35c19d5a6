"""Decoder-only language-model blocks as a plain stride-16 detection backbone.

Patchify (a 16x16/16 convolution) -> the decoder blocks over each image's
patch tokens in raster order, each image its own causal sequence -> the last
hidden states back on the (H/16, W/16) grid -> a two-conv neck: the
plain-backbone pattern of Li et al. (arXiv:2203.16527) without its pyramid,
emitted as a one-entry pyramid at level 4 like VGG's.

The blocks are Ling-3.0-flash-VL's (``config.py::DecoderConfig``): pre-norm
residual blocks with RMSNorm; the mixer is KDA linear attention
(``ops/kda.py``) except in the last layer of each group of
``layer_group_size``, where it is latent attention (MLA, ``ops/attention.py``);
the feed-forward is a dense SwiGLU below ``first_k_dense`` and the routed
expert layer (``ops/moe.py``) with its shared expert above.  The expert layer
is told which experts it holds, routes over all of them and computes its own
experts' part; what absent experts would have added is left out and that
partial result goes on.

The flax module only declares the leaves (one nested name per leaf, so the
plan's family rule, the optimizer's decay rule by leaf name and a checkpoint
all see ordinary paths); the arithmetic is the pure :func:`features`, every
block under ``jax.checkpoint``.  Matmul operands are cast to ``dtype``;
norms, the router, decays, softmax and the residual stream stay float32.
Routing counters ride out through the ``counters`` collection.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from mx_rcnn_tpu.config import DecoderConfig
from mx_rcnn_tpu.ops.attention import causal_attention
from mx_rcnn_tpu.ops.kda import kda_chunked, short_conv
from mx_rcnn_tpu.ops.moe import held_experts, route, segment_rows

def layer_kinds(cfg: DecoderConfig, layer: int) -> tuple[str, str]:
    """(mixer, feed-forward) of a published layer index."""
    mixer = "mla" if (layer + 1) % cfg.layer_group_size == 0 else "kda"
    return mixer, ("ffn" if layer < cfg.first_k_dense else "moe")


# -- leaves --------------------------------------------------------------------


def _swiglu_spec(d: int, f: int):
    return (("gate", (("kernel", (d, f)),)), ("up", (("kernel", (d, f)),)),
            ("down", (("kernel", (f, d)),)))


def leaf_spec(cfg: DecoderConfig):
    """The backbone's leaves as nested ((name, subtree or shape), ...).  A leaf
    named ``kernel`` is drawn lecun-normal over all but its last axis; ``scale``
    starts at 1, ``bias`` and the router's selection bias ``e_bias`` (a
    constant, not a parameter) at 0."""
    d, h, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    lin = lambda i, o: (("kernel", (i, o)),)
    scale = lambda n: (("scale", (n,)),)
    kc = cfg.short_conv_kernel
    kda = (
        ("q", lin(d, h * hd)), ("k", lin(d, h * hd)), ("v", lin(d, h * hd)),
        ("conv_q", lin(kc, h * hd)), ("conv_k", lin(kc, h * hd)), ("conv_v", lin(kc, h * hd)),
        ("f", lin(d, h * hd)), ("decay", (("scale", (h,)), ("bias", (h * hd,)))),
        ("b", lin(d, h)), ("g", lin(d, h * hd)), ("o_norm", scale(hd)), ("o", lin(h * hd, d)),
    )
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    mla = (
        ("q", lin(d, h * dq)), ("kva", lin(d, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
        ("kv_norm", scale(cfg.kv_lora_rank)),
        ("kvb", lin(cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
        ("q_norm", scale(dq)), ("k_norm", scale(dq)), ("gate", lin(d, h)),
        ("o", lin(h * cfg.v_head_dim, d)),
    )
    f = cfg.moe_intermediate_size
    ids = range(cfg.experts_first, cfg.experts_first + cfg.experts_count)
    moe = (
        ("router", (("kernel", (d, cfg.num_experts)), ("e_bias", (cfg.num_experts,)))),
        ("shared", _swiglu_spec(d, f)),
        ("experts", tuple((f"e{e}", _swiglu_spec(d, f)) for e in ids)),
    )
    out = [("patchify", (("kernel", (cfg.patch, cfg.patch, 3, d)), ("bias", (d,))))]
    for layer in cfg.layers:
        mixer, ff = layer_kinds(cfg, layer)
        out.append((f"l{layer}", (
            ("norm1", scale(d)), (mixer, kda if mixer == "kda" else mla),
            ("norm2", scale(d)),
            (ff, _swiglu_spec(d, cfg.intermediate_size) if ff == "ffn" else moe),
        )))
    c = cfg.neck_channels
    out += [
        ("final_norm", scale(d)),
        ("neck", (("conv1", (("kernel", (1, 1, d, c)), ("bias", (c,)))),
                  ("conv2", (("kernel", (3, 3, c, c)), ("bias", (c,)))))),
    ]
    return tuple(out)


def _init(name: str):
    if name == "kernel":
        return lambda key, shape: jax.random.normal(key, shape) / math.sqrt(math.prod(shape[:-1]))
    return nn.initializers.ones if name == "scale" else nn.initializers.zeros


class _Leaves(nn.Module):
    """Declares a subtree of :func:`leaf_spec` and returns it as a dict."""

    spec: tuple

    @nn.compact
    def __call__(self):
        out = {}
        for name, sub in self.spec:
            if isinstance(sub[0], tuple):
                out[name] = _Leaves(spec=sub, name=name)()
            elif name == "e_bias":
                out[name] = self.variable("constants", name, nn.initializers.zeros, None, sub).value
            else:
                out[name] = self.param(name, _init(name), sub)
        return out


class DecoderBackbone(nn.Module):
    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = True

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> dict[int, jnp.ndarray]:
        leaves = {name: _Leaves(spec=sub, name=name)() for name, sub in leaf_spec(self.cfg)}
        feats, counters = features(self.cfg, leaves, x, self.dtype, self.remat)
        if not self.is_initializing():  # init keeps no counters in the state
            for name, value in sorted(counters.items()):
                self.sow("counters", name, value)
        return feats


# -- arithmetic ----------------------------------------------------------------


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _dense(x, p, dtype, out=jnp.float32):
    with jax.named_scope("dense"):
        return jnp.dot(x.astype(dtype), p["kernel"].astype(dtype), preferred_element_type=out)


def _swiglu(x, p, dtype):
    return _dense(jax.nn.silu(_dense(x, p["gate"], dtype)) * _dense(x, p["up"], dtype),
                  p["down"], dtype)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_mixer(cfg: DecoderConfig, p, x, dtype):
    """x (B, T, D) normed -> (B, T, D).  The projections hand over ``dtype``
    activations; the glue between the matmuls (short convs, SiLU, norms,
    gates) computes in float32 under ``jax.checkpoint``, so the backward
    keeps the ``dtype`` activations and recomputes the glue."""
    b, t, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    heads = lambda a: a.reshape(b, t, h, hd)
    f32 = lambda a: a.astype(jnp.float32)

    @jax.checkpoint
    def before(y, convs, decay):
        conv = lambda n: heads(jax.nn.silu(short_conv(f32(y[n]), convs[n])))
        q, k, v = _l2(conv("q")) * hd**-0.5, _l2(conv("k")), conv("v")
        rate = jnp.exp(decay["scale"])[:, None]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(rate * heads(f32(y["f"]) + decay["bias"]))
        return q.astype(dtype), k.astype(dtype), v.astype(dtype), g

    @jax.checkpoint
    def after(o, gate, scale):
        o = _rms(o, scale, cfg.rms_norm_eps) * jax.nn.sigmoid(heads(f32(gate)))
        return o.reshape(b, t, h * hd).astype(dtype)

    with jax.named_scope("proj"):
        y = {n: _dense(x, p[n], dtype, out=dtype) for n in ("q", "k", "v", "f", "g")}
        beta = jax.nn.sigmoid(_dense(x, p["b"], dtype))
        convs = {n: p["conv_" + n]["kernel"] for n in ("q", "k", "v")}
        q, k, v, g = before(y, convs, p["decay"])
    with jax.named_scope("scan"):
        o = kda_chunked(q, k, v, g, beta, dtype=dtype, lower_bound=cfg.kda_lower_bound)
    with jax.named_scope("proj"):
        return _dense(after(o, y["g"], p["o_norm"]["scale"]), p["o"], dtype)


def _rope(x, theta: float):
    """Rotary embedding by raster position over the last axis of x
    (B, T, ..., R), halves paired (x1, x2) -> (x1 c - x2 s, x2 c + x1 s)."""
    t, r = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (1, t) + (1,) * (x.ndim - 3) + (r // 2,)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def mla_mixer(cfg: DecoderConfig, p, x, dtype):
    """As :func:`kda_mixer`: ``dtype`` activations between the matmuls, the
    glue (norms, rotary, the gate) in float32 under ``jax.checkpoint``."""
    b, t, _ = x.shape
    h, dn, dr, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps, rank = cfg.rms_norm_eps, cfg.kv_lora_rank
    f32 = lambda a: a.astype(jnp.float32)

    @jax.checkpoint
    def latent(kva, scale):
        return _rms(kva[..., :rank], scale, eps).astype(dtype)

    @jax.checkpoint
    def before(q, kva, kv, q_scale, k_scale):
        q = _rms(q.reshape(b, t, h, dn + dr), q_scale, eps)
        kv = f32(kv).reshape(b, t, h, dn + dv)
        k_rope = jnp.broadcast_to(f32(kva)[..., None, rank:], (b, t, h, dr))
        k = _rms(jnp.concatenate([kv[..., :dn], k_rope], axis=-1), k_scale, eps)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg.rope_theta)], axis=-1)
        k = jnp.concatenate([k[..., :dn], _rope(k[..., dn:], cfg.rope_theta)], axis=-1)
        return q.astype(dtype), k.astype(dtype), kv[..., dn:].astype(dtype)

    @jax.checkpoint
    def after(o, gate):
        return (o * jax.nn.sigmoid(f32(gate))[..., None]).reshape(b, t, h * dv).astype(dtype)

    with jax.named_scope("proj"):
        kva = _dense(x, p["kva"], dtype, out=dtype)
        q, k, v = before(
            _dense(x, p["q"], dtype, out=dtype), kva,
            _dense(latent(kva, p["kv_norm"]["scale"]), p["kvb"], dtype, out=dtype),
            p["q_norm"]["scale"], p["k_norm"]["scale"],
        )
        gate = _dense(x, p["gate"], dtype, out=dtype)
    with jax.named_scope("attn"):
        o = causal_attention(q, k, v, (dn + dr) ** -0.5, dtype=dtype)
    with jax.named_scope("proj"):
        return _dense(after(o, gate), p["o"], dtype)


def moe_layer(cfg: DecoderConfig, p, x, dtype):
    """x (B, T, D) normed -> (the held experts' part + the shared expert, counters)."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    with jax.named_scope("router"):
        experts, weights = route(
            flat, p["router"]["kernel"], p["router"]["e_bias"], cfg.n_group, cfg.topk_group,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor,
        )
    ids = range(cfg.experts_first, cfg.experts_first + cfg.experts_count)
    stack = lambda name: jnp.stack(
        [p["experts"][f"e{e}"][name]["kernel"].astype(dtype) for e in ids]
    )
    segment = segment_rows(b * t, cfg.num_experts_per_tok, cfg.experts_count, cfg.num_experts)
    y, counters = held_experts(
        flat, experts, weights, stack("gate"), stack("up"), stack("down"),
        cfg.experts_first, segment, dtype=dtype,
    )
    with jax.named_scope("shared"):
        y = y + _swiglu(flat, p["shared"], dtype)
    return y.reshape(b, t, d), counters


def _block(cfg: DecoderConfig, layer: int, dtype, p, x):
    """One pre-norm residual block on the float32 stream."""
    mixer, ff = layer_kinds(cfg, layer)
    with jax.named_scope(f"l{layer}"):
        with jax.named_scope(mixer):
            mix = kda_mixer if mixer == "kda" else mla_mixer
            x = x + mix(cfg, p[mixer], _rms(x, p["norm1"]["scale"], cfg.rms_norm_eps), dtype)
        with jax.named_scope(ff):
            normed = _rms(x, p["norm2"]["scale"], cfg.rms_norm_eps)
            if ff == "ffn":
                return x + _swiglu(normed, p["ffn"], dtype), {}
            y, counters = moe_layer(cfg, p["moe"], normed, dtype)
            return x + y, counters


def merge_counters(per_layer: list[dict]) -> dict:
    """One step's routing counters from each expert layer's: slots and drops
    summed, the load's max over mean of the worst layer, the share of tokens
    with no expert here averaged."""
    if not per_layer:
        return {}
    col = lambda name: jnp.stack([c[name] for c in per_layer])
    return {
        "moe_slots_here": jnp.sum(col("moe_slots_here")),
        "moe_load_max_over_mean": jnp.max(col("moe_load_max_over_mean")),
        "moe_dropped_slots": jnp.sum(col("moe_dropped_slots")),
        "moe_tokens_without_held_expert": jnp.mean(col("moe_tokens_without_held_expert")),
    }


def features(cfg: DecoderConfig, leaves: dict, images, dtype=jnp.bfloat16, remat: bool = True):
    """images (B, H, W, 3) normalized -> ({4: (B, H/16, W/16, C)}, counters)."""
    # No preferred_element_type: the convolution's transpose would meet a
    # float32 cotangent with a ``dtype`` kernel, which lax refuses.
    def conv(x, p, stride, pad):
        with jax.named_scope("conv"):
            return lax.conv_general_dilated(
                x.astype(dtype), p["kernel"].astype(dtype), (stride, stride), [(pad, pad)] * 2,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            ).astype(jnp.float32) + p["bias"]

    with jax.named_scope("patchify"):
        x = conv(images, leaves["patchify"], cfg.patch, 0)
    b, gh, gw, d = x.shape
    x = x.reshape(b, gh * gw, d)
    counters = []
    for layer in cfg.layers:
        fn = partial(_block, cfg, layer, dtype)
        x, c = (jax.checkpoint(fn) if remat else fn)(leaves[f"l{layer}"], x)
        if c:
            counters.append(c)
    with jax.named_scope("neck"):
        x = _rms(x, leaves["final_norm"]["scale"], cfg.rms_norm_eps).reshape(b, gh, gw, d)
        x = conv(conv(x, leaves["neck"]["conv1"], 1, 0), leaves["neck"]["conv2"], 1, 1)
    return {4: x.astype(dtype)}, merge_counters(counters)
