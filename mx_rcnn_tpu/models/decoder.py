"""Decoder-only language-model blocks as a plain stride-16 detection backbone.

Patchify (a 16x16/16 convolution) -> the decoder blocks over each image's
patch tokens in raster order, each image its own causal sequence -> the last
hidden states back on the (H/16, W/16) grid -> a two-conv neck: the
plain-backbone pattern of Li et al. (arXiv:2203.16527) without its pyramid,
emitted as a one-entry pyramid at level 4 like VGG's.

A layer is a list of pre-norm residual sub-layers, ``x <- x + f(norm(x))``,
whose kinds follow from the configuration (``config.py::DecoderConfig``,
:func:`sublayers`), four families so far.  Ling-3.0-flash-VL's: a mixer, KDA
linear attention (``ops/kda.py``) except in the last layer of each group of
``layer_group_size``, where it is latent attention (MLA,
``ops/attention.py``), then a feed-forward, a dense SwiGLU below
``first_k_dense`` and the routed expert layer (``ops/moe.py``) with its shared
expert above.  Nemotron-Labs-TwoTower's tower: ONE sub-layer a layer, by the
published pattern's letter a Mamba-2 state-space mixer (``ops/ssd.py``),
grouped-query attention or the expert layer (non-gated relu^2 experts).  The
expert layer is told which experts it holds, routes over all of them and
computes its own experts' part; what absent experts would have added is left
out and that partial result goes on.  Phi-4-mini-flash's SambaY
(``mb_per_layer``; arXiv:2507.06607): LayerNorm with bias, a mixer and a plain
SwiGLU in every layer; the mixer by the published index a Mamba-1 selective
scan (``ops/selective_scan.py``), differential attention (arXiv:2410.05258)
under a window or over the whole prefix, and past the middle a Gated Memory
Unit that reads the middle Mamba layer's scan result or differential cross
attention that reads the full-attention layer's keys and values: those three
tensors (``shared``) pass from block to block beside ``x``.  Granite 4.0-H's
hybrid (``layer_types``): by the published word a Mamba-2 mixer or
grouped-query attention, then a dense SwiGLU, in every layer, each
sub-layer's output scaled by the muP ``residual_multiplier`` before it joins
the stream.

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
from mx_rcnn_tpu.ops.moe import held_experts, route
from mx_rcnn_tpu.ops.selective_scan import selective_scan_chunked
from mx_rcnn_tpu.ops.ssd import ssd_chunked

PATTERN_KINDS = {"M": "ssm", "*": "gqa", "E": "moe"}
LAYER_TYPES = {"mamba": "ssm", "attention": "gqa"}

# The sorted dispatch's segment size went with the dispatch (PR 32); nothing
# reads this.  ``tests/perfbench/_ling_tiny.py``, a benchmark file that only a
# ``benchmark`` PR may edit, still sets the name: that PR drops both.
segment_rows = None


def layer_kinds(cfg: DecoderConfig, layer: int) -> tuple[str, ...]:
    """The kinds of a published layer's sub-layers, in order: a letter of
    ``pattern``; a word of ``layer_types`` and the feed-forward after it;
    SambaY's (mixer, feed-forward) by the layer's place against the published
    depth's middle; else Ling's (mixer, feed-forward)."""
    if cfg.pattern:
        return (PATTERN_KINDS[cfg.pattern[layer]],)
    if cfg.layer_types:
        return LAYER_TYPES[cfg.layer_types[layer]], "ffn"
    if cfg.mb_per_layer:
        middle = cfg.num_hidden_layers // 2     # the self-decoder ends at middle + 1
        if layer % cfg.mb_per_layer == 0:
            return ("mamba" if layer <= middle else "gmu"), "ffn"
        return ("swa" if layer < middle else "full" if layer == middle + 1 else "xattn"), "ffn"
    mixer = "mla" if (layer + 1) % cfg.layer_group_size == 0 else "kda"
    return mixer, ("ffn" if layer < cfg.first_k_dense else "moe")


def sublayers(cfg: DecoderConfig, layer: int) -> tuple[tuple[str, str], ...]:
    """((norm leaf, kind), ...) of a published layer."""
    kinds = layer_kinds(cfg, layer)
    if len(kinds) == 1:
        return (("norm", kinds[0]),)
    return tuple((f"norm{i + 1}", kind) for i, kind in enumerate(kinds))


# -- leaves --------------------------------------------------------------------


def _mlp_spec(d: int, f: int, act: str = "swiglu"):
    gate = (("gate", (("kernel", (d, f)),)),) if act == "swiglu" else ()
    return gate + (("up", (("kernel", (d, f)),)), ("down", (("kernel", (f, d)),)))


def leaf_spec(cfg: DecoderConfig):
    """The backbone's leaves as nested ((name, subtree or shape), ...).  A leaf
    named ``kernel`` is drawn lecun-normal over all but its last axis; ``scale``
    starts at 1, ``bias`` and the router's selection bias ``e_bias`` (a
    constant, not a parameter) at 0; a state-space mixer's ``A_log``,
    ``dt_bias`` and ``D`` and differential attention's ``lambda`` as
    :func:`_init` says."""
    d, h, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    lin = lambda i, o: (("kernel", (i, o)),)
    affine = lambda i, o: (("kernel", (i, o)), ("bias", (o,)))
    scale = lambda n: (("scale", (n,)),)
    # SambaY's norms are LayerNorms with a bias; the other families' RMSNorms.
    norm = (lambda n: (("scale", (n,)), ("bias", (n,)))) if cfg.mb_per_layer else scale
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
    inner, bc = cfg.ssm_heads * cfg.ssm_head_dim, 2 * cfg.ssm_groups * cfg.ssm_state
    ssm = (
        ("in_proj", lin(d, 2 * inner + bc + cfg.ssm_heads)),        # z | x B C | dt
        ("conv", (("kernel", (kc, inner + bc)), ("bias", (inner + bc,)))),
        ("A_log", (cfg.ssm_heads,)), ("dt_bias", (cfg.ssm_heads,)), ("D", (cfg.ssm_heads,)),
        ("norm", scale(inner)), ("out_proj", lin(inner, d)),
    )
    gqa = (("q", lin(d, h * hd)), ("k", lin(d, cfg.num_kv_heads * hd)),
           ("v", lin(d, cfg.num_kv_heads * hd)), ("o", lin(h * hd, d)))
    wide, n1, rank = cfg.mamba_expand * d, cfg.mamba_d_state, cfg.mamba_dt_rank
    mamba = (
        ("in_proj", lin(d, 2 * wide)),                              # x | z
        ("conv", (("kernel", (kc, wide)), ("bias", (wide,)))),
        ("x_proj", lin(wide, rank + 2 * n1)),                       # delta | B | C
        ("dt_proj", lin(rank, wide)), ("dt_bias", (wide,)),
        ("A_log", (wide, n1)), ("D", (wide,)), ("out_proj", lin(wide, d)),
    )
    # lambda's rows: q1, k1, q2, k2; a cross layer projects its queries alone
    diff = lambda qkv: (("Wqkv", affine(d, qkv)), ("lambda", (4, hd)), ("subln", scale(2 * hd)),
                        ("out_proj", affine(h * hd, d)))
    self_attn = diff((h + 2 * cfg.num_kv_heads) * hd)
    gmu = (("in_proj", lin(d, wide)), ("out_proj", lin(wide, d)))
    f, act = cfg.moe_intermediate_size, cfg.expert_act
    ids = range(cfg.experts_first, cfg.experts_first + cfg.experts_count)
    moe = (
        ("router", (("kernel", (d, cfg.num_experts)), ("e_bias", (cfg.num_experts,)))),
        ("shared", _mlp_spec(d, cfg.shared_intermediate_size or f, act)),
        ("experts", tuple((f"e{e}", _mlp_spec(d, f, act)) for e in ids)),
    )
    kinds = {"kda": kda, "mla": mla, "ssm": ssm, "gqa": gqa, "moe": moe,
             "mamba": mamba, "swa": self_attn, "full": self_attn, "xattn": diff(h * hd),
             "gmu": gmu, "ffn": _mlp_spec(d, cfg.intermediate_size)}
    out = [("patchify", (("kernel", (cfg.patch, cfg.patch, 3, d)), ("bias", (d,))))]
    for layer in cfg.layers:
        out.append((f"l{layer}", tuple(
            leaf for name, kind in sublayers(cfg, layer)
            for leaf in ((name, norm(d)), (kind, kinds[kind]))
        )))
    c = cfg.neck_channels
    out += [
        ("final_norm", norm(d)),
        ("neck", (("conv1", (("kernel", (1, 1, d, c)), ("bias", (c,)))),
                  ("conv2", (("kernel", (3, 3, c, c)), ("bias", (c,)))))),
    ]
    return tuple(out)


def _init(name: str):
    if name == "kernel":
        return lambda key, shape: jax.random.normal(key, shape) / math.sqrt(math.prod(shape[:-1]))
    if name == "A_log":     # A = -exp(A_log) in the family's 1-16
        return lambda key, shape: jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))
    if name == "dt_bias":   # softplus(dt_bias) log-uniform in time_step_min-max, 0.001-0.1
        def dt_bias(key, shape):
            dt = jnp.exp(jax.random.uniform(key, shape, minval=math.log(1e-3), maxval=math.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        return dt_bias
    if name == "lambda":    # differential attention's four vectors: normal 0.1
        return lambda key, shape: 0.1 * jax.random.normal(key, shape)
    return nn.initializers.ones if name in ("scale", "D") else nn.initializers.zeros


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


def _norm(x, p, eps):
    """LayerNorm where the leaves hold a bias, else RMSNorm."""
    if "bias" not in p:
        return _rms(x, p["scale"], eps)
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["scale"] + p["bias"]


def _dense(x, p, dtype, out=jnp.float32):
    """x W, plus the bias where the leaves hold one (added in float32)."""
    with jax.named_scope("dense"):
        wide = jnp.float32 if "bias" in p else out
        y = jnp.dot(x.astype(dtype), p["kernel"].astype(dtype), preferred_element_type=wide)
        return (y + p["bias"]).astype(out) if "bias" in p else y


def _mlp(x, p, dtype):
    """SwiGLU where the leaves hold a gate, else ``down relu(up x)^2``."""
    if "gate" in p:
        hidden = jax.nn.silu(_dense(x, p["gate"], dtype)) * _dense(x, p["up"], dtype)
    else:
        hidden = jnp.square(jax.nn.relu(_dense(x, p["up"], dtype)))
    return _dense(hidden, p["down"], dtype)


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


def ssm_mixer(cfg: DecoderConfig, p, x, dtype):
    """A Mamba-2 mixer.  x (B, T, D) normed -> (B, T, D): ``in_proj`` to
    z | x B C | dt, the causal depthwise conv with bias and SiLU over x B C,
    ``dt = softplus(dt + dt_bias)``, the recurrence (``ops/ssd.py``), the
    grouped RMSNorm of ``y * silu(z)``, ``out_proj``.  As :func:`kda_mixer`:
    ``dtype`` activations between the matmuls (``dt`` float32: it is summed
    over a chunk inside an ``exp``), the glue in float32 under ``jax.checkpoint``."""
    b, t, _ = x.shape
    h, hd, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner = h * hd
    f32 = lambda a: a.astype(jnp.float32)

    @jax.checkpoint
    def before(xbc, dt, conv, dt_bias):
        xbc = jax.nn.silu(short_conv(f32(xbc), conv["kernel"]) + conv["bias"])
        xs, bs, cs = jnp.split(xbc.astype(dtype), [inner, inner + g * n], axis=-1)
        return (xs.reshape(b, t, h, hd), bs.reshape(b, t, g, n), cs.reshape(b, t, g, n),
                jax.nn.softplus(dt + dt_bias))

    @jax.checkpoint
    def after(y, z, scale):
        y = (y.reshape(b, t, inner) * jax.nn.silu(f32(z))).reshape(b, t, g, inner // g)
        return _rms(y, scale.reshape(g, inner // g), cfg.rms_norm_eps).reshape(b, t, inner).astype(dtype)

    with jax.named_scope("proj"):
        kernel = p["in_proj"]["kernel"]
        zxbc = _dense(x, {"kernel": kernel[:, :-h]}, dtype, out=dtype)
        dt = _dense(x, {"kernel": kernel[:, -h:]}, dtype)
    with jax.named_scope("conv"):
        xs, bs, cs, dt = before(zxbc[..., inner:], dt, p["conv"], p["dt_bias"])
    with jax.named_scope("scan"):
        y = ssd_chunked(xs, dt, -jnp.exp(p["A_log"]), bs, cs, p["D"], dtype=dtype)
    with jax.named_scope("norm"):
        y = after(y, zxbc[..., :inner], p["norm"]["scale"])
    with jax.named_scope("proj"):
        return _dense(y, p["out_proj"], dtype)


def gqa_mixer(cfg: DecoderConfig, p, x, dtype):
    """Grouped-query causal attention, no bias, no rotary embedding (the
    family's published description: the state-space layers carry position),
    the scores scaled by ``attention_multiplier`` where one is given."""
    b, t, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("proj"):
        q = _dense(x, p["q"], dtype, out=dtype).reshape(b, t, h, hd)
        k = _dense(x, p["k"], dtype, out=dtype).reshape(b, t, kv, hd)
        v = _dense(x, p["v"], dtype, out=dtype).reshape(b, t, kv, hd)
    with jax.named_scope("attn"):
        o = causal_attention(q, k, v, cfg.attention_multiplier or hd ** -0.5, dtype=dtype)
    with jax.named_scope("proj"):
        return _dense(o.reshape(b, t, h * hd), p["o"], dtype)


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
    gated = "gate" in p["experts"][f"e{cfg.experts_first}"]     # as ``_mlp`` tells the two forms
    y, counters = held_experts(
        flat, experts, weights, stack("gate") if gated else None, stack("up"), stack("down"),
        cfg.experts_first, dtype=dtype,
    )
    with jax.named_scope("shared"):
        y = y + _mlp(flat, p["shared"], dtype)
    return y.reshape(b, t, d), counters


def mamba_mixer(cfg: DecoderConfig, p, x, dtype):
    """A Mamba-1 mixer.  x (B, T, D) normed -> ((B, T, D), the scan's result
    before the gate (B, T, expand D) float32): ``in_proj`` to x | z, the causal
    depthwise conv with bias and SiLU over x, ``x_proj`` to delta | B | C,
    ``dt = softplus(dt_proj(delta) + dt_bias)``, the selective scan
    (``ops/selective_scan.py``), ``out_proj(y * silu(z))``.  As
    :func:`kda_mixer`: ``dtype`` activations between the matmuls, the glue in
    float32 under ``jax.checkpoint``; ``dt``, B and C stay float32."""
    wide, n, rank = cfg.mamba_expand * cfg.hidden_size, cfg.mamba_d_state, cfg.mamba_dt_rank
    f32 = lambda a: a.astype(jnp.float32)

    @jax.checkpoint
    def before(xs, conv):
        return jax.nn.silu(short_conv(f32(xs), conv["kernel"]) + conv["bias"]).astype(dtype)

    @jax.checkpoint
    def step_size(delta, dt_proj, dt_bias):
        return jax.nn.softplus(_dense(delta, dt_proj, dtype) + dt_bias)

    @jax.checkpoint
    def gate(y, z):
        return (y * jax.nn.silu(f32(z))).astype(dtype)

    with jax.named_scope("proj"):
        xz = _dense(x, p["in_proj"], dtype, out=dtype)
    with jax.named_scope("conv"):
        xs = before(xz[..., :wide], p["conv"])
    with jax.named_scope("proj"):
        delta, b, c = jnp.split(_dense(xs, p["x_proj"], dtype), [rank, rank + n], axis=-1)
        dt = step_size(delta, p["dt_proj"], p["dt_bias"])
    with jax.named_scope("scan"):
        y = selective_scan_chunked(xs, dt, -jnp.exp(p["A_log"]), b, c, p["D"])
    with jax.named_scope("proj"):
        return _dense(gate(y, xz[..., wide:]), p["out_proj"], dtype), y


def gmu_mixer(cfg: DecoderConfig, p, x, dtype, memory):
    """A Gated Memory Unit: ``out_proj(silu(in_proj(x)) * memory)``, ``memory``
    the middle Mamba layer's scan result (B, T, expand D)."""
    @jax.checkpoint
    def gate(u, memory):
        return (jax.nn.silu(u.astype(jnp.float32)) * memory.astype(jnp.float32)).astype(dtype)

    with jax.named_scope("proj"):
        return _dense(gate(_dense(x, p["in_proj"], dtype, out=dtype), memory), p["out_proj"], dtype)


def lambda_init(layer: int) -> float:
    """Differential attention's constant part of lambda, by PUBLISHED depth."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def diff_mixer(cfg: DecoderConfig, layer: int, p, x, dtype, window=None, kv=None):
    """Differential attention (arXiv:2410.05258), no positional encoding.  x
    (B, T, D) normed -> ((B, T, D), (k, v)).  Query heads pair up (2j, 2j + 1)
    into q1, q2, key heads into k1, k2, and a pair's value heads side by side
    are ONE value of twice the width: a_i = softmax(q_i k_i^T / sqrt(hd)) v
    under the causal mask (and ``window``), a query pair on key pair j // group;
    out = out_proj(RMSNorm(a_1 - lambda a_2) (1 - lambda_init)), lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init.  With ``kv`` (a cross
    layer) the keys and values are another layer's, and ``Wqkv`` projects the
    queries alone."""
    b, t, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    first = lambda_init(layer)

    @jax.checkpoint
    def after(a1, a2, lam, scale):
        lam = jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3])) + first
        o = _rms(a1 - lam * a2, scale, cfg.rms_norm_eps) * (1.0 - first)
        return o.reshape(b, t, h * hd).astype(dtype)

    with jax.named_scope("proj"):
        qkv = _dense(x, p["Wqkv"], dtype, out=dtype)
        q = qkv[..., : h * hd].reshape(b, t, h // 2, 2, hd)
        if kv is None:
            kv = (qkv[..., h * hd: (h + hkv) * hd].reshape(b, t, hkv // 2, 2, hd),
                  qkv[..., (h + hkv) * hd:].reshape(b, t, hkv // 2, 2 * hd))
    k, v = kv
    with jax.named_scope("attn"):
        a1, a2 = (causal_attention(q[..., i, :], k[..., i, :], v, hd ** -0.5, dtype=dtype,
                                   window=window) for i in (0, 1))
    with jax.named_scope("diff"):
        o = after(a1, a2, p["lambda"], p["subln"]["scale"])
    with jax.named_scope("proj"):
        return _dense(o, p["out_proj"], dtype), kv


def _alone(mixer):
    """A mixer that reads nothing of another layer and hands nothing on."""
    return lambda cfg, layer, p, x, dtype, shared: (mixer(cfg, p, x, dtype), shared)


def _mamba(cfg: DecoderConfig, layer: int, p, x, dtype, shared: dict):
    """The middle Mamba layer hands on its scan result ``m`` (in ``dtype``)."""
    y, m = mamba_mixer(cfg, p, x, dtype)
    return y, dict(shared, m=m.astype(dtype)) if layer == cfg.num_hidden_layers // 2 else shared


def _gmu(cfg: DecoderConfig, layer: int, p, x, dtype, shared: dict):
    return gmu_mixer(cfg, p, x, dtype, shared["m"]), shared


def _swa(cfg: DecoderConfig, layer: int, p, x, dtype, shared: dict):
    return diff_mixer(cfg, layer, p, x, dtype, window=cfg.sliding_window)[0], shared


def _full(cfg: DecoderConfig, layer: int, p, x, dtype, shared: dict):
    """The full-attention layer hands on its ``k`` and ``v`` (in ``dtype``)."""
    y, (k, v) = diff_mixer(cfg, layer, p, x, dtype)
    return y, dict(shared, k=k, v=v)


def _xattn(cfg: DecoderConfig, layer: int, p, x, dtype, shared: dict):
    return diff_mixer(cfg, layer, p, x, dtype, kv=(shared["k"], shared["v"]))[0], shared


# kind -> (cfg, layer, p, x, dtype, shared) -> (y, shared): ``shared`` holds the
# tensors an earlier layer handed on to later ones (SambaY's ``m``, ``k``, ``v``).
MIXERS = {"kda": _alone(kda_mixer), "mla": _alone(mla_mixer), "ssm": _alone(ssm_mixer),
          "gqa": _alone(gqa_mixer), "ffn": _alone(lambda cfg, p, x, dtype: _mlp(x, p, dtype)),
          "mamba": _mamba, "gmu": _gmu, "swa": _swa, "full": _full, "xattn": _xattn}


def _block(cfg: DecoderConfig, layer: int, dtype, p, x, shared):
    """One published layer on the float32 stream: its pre-norm residual
    sub-layers in turn.  -> (x, the expert sub-layer's counters or {}, the
    tensors later layers read: ``shared`` as it came, but for two SambaY layers)."""
    counters = {}
    with jax.named_scope(f"l{layer}"):
        for norm, kind in sublayers(cfg, layer):
            with jax.named_scope(kind):
                normed = _norm(x, p[norm], cfg.rms_norm_eps)
                if kind == "moe":
                    y, counters = moe_layer(cfg, p["moe"], normed, dtype)
                else:
                    y, shared = MIXERS[kind](cfg, layer, p[kind], normed, dtype, shared)
                if cfg.residual_multiplier != 1.0:
                    y = y * cfg.residual_multiplier
                x = x + y
    return x, counters, shared


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
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
    b, gh, gw, d = x.shape
    x = x.reshape(b, gh * gw, d)
    counters, shared = [], {}
    for layer in cfg.layers:
        fn = partial(_block, cfg, layer, dtype)
        x, c, shared = (jax.checkpoint(fn) if remat else fn)(leaves[f"l{layer}"], x, shared)
        if c:
            counters.append(c)
    with jax.named_scope("neck"):
        x = _norm(x, leaves["final_norm"], cfg.rms_norm_eps).reshape(b, gh, gw, d)
        x = conv(conv(x, leaves["neck"]["conv1"], 1, 0), leaves["neck"]["conv2"], 1, 1)
    return {4: x.astype(dtype)}, merge_counters(counters)
