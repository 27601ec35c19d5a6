"""Granite 4.0-H Micro's hybrid decoder as a plain stride-16 backbone, plain
float32 (huggingface.co/ibm-granite/granite-4.0-h-micro config.json,
``model_type`` granitemoehybrid; the plain-backbone pattern of Li et al.,
arXiv:2203.16527, without its pyramid).

Patchify (16x16/16 convolution with bias) times ``embedding_multiplier`` (the
patch tokens stand in for the token embedding) -> the held layers over one
image's patch tokens in raster order -> final RMSNorm -> the (H/16, W/16) grid
-> 1x1 conv and 3x3 conv with bias -> level 4.  ``ref["decoder"]`` holds every
size.  Each PUBLISHED layer l is TWO pre-norm residual sub-layers,

    h <- h + m * mixer(RMSNorm(h)),    h <- h + m * SwiGLU(RMSNorm(h)),

m = ``residual_multiplier``, the mixer by the l-th word of ``layer_types``:

- ``mamba`` (Mamba-2): ``backbone_nemotron_twotower.py::ssm``, the same
  equations under the other family's names (:func:`_mamba2`): [z | xBC | dt] =
  W_in x; xBC = SiLU(conv4(xBC) + bias); x (heads x head_dim), B, C (groups x
  state; ONE group here, so every head reads the same B and C); dt =
  softplus(dt + dt_bias), no clamp; A = -exp(A_log) a head; the recurrence
  token by token, y + D x; RMSNorm over groups of inner / groups channels of
  y * SiLU(z) (one group: all 4,096), learned scale; W_out.
- ``attention``: q (heads x head_dim), k, v (num_key_value_heads x head_dim) =
  W x, no bias, NO positional encoding (``position_embedding_type`` nope); K
  and V repeated per query head; dense causal softmax of q.k times
  ``attention_multiplier`` (1/64, not 1/sqrt(head_dim)); W_o.
- SwiGLU: W_down (SiLU(W_gate x) * W_up x), ``intermediate_size`` wide (the
  config's ``shared_intermediate_size``: nothing is routed).

Blocking only, as the guide allows, so that it fits a chip at 4,200
positions: each layer under ``jax.checkpoint``; the recurrence as a scan of
checkpointed scans; the dense scores a block of rows at a time.  Every matmul
at ``highest`` and through the ``matmul`` hook (the recurrence's operands x,
B, C once, before the scan).  The Mamba-2 mixer is the other state-space
family's, by import; nothing of the program is read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import backbone_nemotron_twotower as mamba2
from perfbench.reference.backbone_nemotron_twotower import HI, _mm, _rms
from perfbench.reference.layers import conv

ATTN_ROWS = 128   # 32 x 128 x 4,200 float32 scores a block, 69 MB
KINDS = {"mamba": "ssm", "attention": "gqa"}


def kind(dc, layer):
    """The mixer of a PUBLISHED layer, from ``layer_types``."""
    return KINDS[dc["layer_types"][layer]]


def branch_scale(dc):
    """What each sub-layer's output is multiplied by before it joins the stream."""
    return dc["residual_multiplier"]


def _mamba2(dc):
    """The Mamba-2 sizes under the names ``backbone_nemotron_twotower.py`` reads."""
    return {"mamba_num_heads": dc["mamba_n_heads"], "mamba_head_dim": dc["mamba_d_head"],
            "n_groups": dc["mamba_n_groups"], "ssm_state_size": dc["mamba_d_state"],
            "norm_eps": dc["rms_norm_eps"]}


# -- leaves --------------------------------------------------------------------


def specs(ref):
    """``A_log`` and ``dt_bias`` are drawn uniform in 0.7..1 like a norm's
    scale and the patchify kernel lecun-normal: the entry maps them onto the
    family's ranges before either side sees the weights
    (entries/train_lean_granite.py::granite_ranges)."""
    dc = ref["decoder"]
    d, h, kv, hd = (dc["hidden_size"], dc["num_attention_heads"], dc["num_key_value_heads"],
                    dc["head_dim"])
    f = dc["intermediate_size"]
    bb = "params/backbone"
    out = [(f"{bb}/patchify/kernel", (dc["patch"], dc["patch"], 3, d), "lecun"),
           (f"{bb}/patchify/bias", (d,), "bias")]
    for l in dc["layers"]:
        p = f"{bb}/l{l}"
        out.append((f"{p}/norm1/scale", (d,), "bn_scale"))
        if kind(dc, l) == "ssm":
            m, heads = f"{p}/ssm", dc["mamba_n_heads"]
            inner, conv_dim = mamba2._ssm_sizes(_mamba2(dc))
            out += [(f"{m}/in_proj/kernel", (d, inner + conv_dim + heads), "lecun"),
                    (f"{m}/conv/kernel", (dc["mamba_d_conv"], conv_dim), "lecun"),
                    (f"{m}/conv/bias", (conv_dim,), "bias"),
                    (f"{m}/A_log", (heads,), "bn_scale"), (f"{m}/dt_bias", (heads,), "bn_scale"),
                    (f"{m}/D", (heads,), "bn_scale"), (f"{m}/norm/scale", (inner,), "bn_scale"),
                    (f"{m}/out_proj/kernel", (inner, d), "lecun")]
        else:
            m = f"{p}/gqa"
            out += [(f"{m}/q/kernel", (d, h * hd), "lecun"), (f"{m}/k/kernel", (d, kv * hd), "lecun"),
                    (f"{m}/v/kernel", (d, kv * hd), "lecun"), (f"{m}/o/kernel", (h * hd, d), "lecun")]
        out += [(f"{p}/norm2/scale", (d,), "bn_scale"),
                (f"{p}/ffn/gate/kernel", (d, f), "lecun"), (f"{p}/ffn/up/kernel", (d, f), "lecun"),
                (f"{p}/ffn/down/kernel", (f, d), "lecun")]
    c = ref["feature_channels"]
    out += [(f"{bb}/final_norm/scale", (d,), "bn_scale"),
            (f"{bb}/neck/conv1/kernel", (1, 1, d, c), "lecun"), (f"{bb}/neck/conv1/bias", (c,), "bias"),
            (f"{bb}/neck/conv2/kernel", (3, 3, c, c), "lecun"), (f"{bb}/neck/conv2/bias", (c,), "bias")]
    return out


# -- layers --------------------------------------------------------------------


def ssm(dc, w, p, x, matmul):
    return mamba2.ssm(_mamba2(dc), w, p, x, matmul)


def gqa(dc, w, p, x, matmul):
    t = x.shape[0]
    h, kv, hd = dc["num_attention_heads"], dc["num_key_value_heads"], dc["head_dim"]
    q = _mm(x, w[f"{p}/q/kernel"], matmul).reshape(t, h, hd)
    k = jnp.repeat(_mm(x, w[f"{p}/k/kernel"], matmul).reshape(t, kv, hd), h // kv, axis=1)
    v = jnp.repeat(_mm(x, w[f"{p}/v/kernel"], matmul).reshape(t, kv, hd), h // kv, axis=1)
    if matmul is not None:
        q, k, v = matmul(q), matmul(k), matmul(v)

    @jax.checkpoint
    def rows(q_rows, first):
        s = jnp.einsum("qhd,khd->hqk", q_rows, k, precision=HI) * dc["attention_multiplier"]
        row = first + jnp.arange(q_rows.shape[0])
        s = jnp.where(row[:, None] >= jnp.arange(t)[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    o = jnp.concatenate([rows(q[lo:lo + ATTN_ROWS], lo) for lo in range(0, t, ATTN_ROWS)])
    return _mm(o.reshape(t, h * hd), w[f"{p}/o/kernel"], matmul)


def swiglu(w, p, x, matmul):
    gate = jax.nn.silu(_mm(x, w[f"{p}/gate/kernel"], matmul))
    return _mm(gate * _mm(x, w[f"{p}/up/kernel"], matmul), w[f"{p}/down/kernel"], matmul)


def features(ref, w, x, matmul=None):
    dc = ref["decoder"]
    bb = "params/backbone"
    eps = dc["rms_norm_eps"]
    x = conv(x, w[f"{bb}/patchify/kernel"], dc["patch"], 0, matmul) + w[f"{bb}/patchify/bias"]
    x = x * dc["embedding_multiplier"]
    _, gh, gw, d = x.shape
    x = x.reshape(gh * gw, d)
    for l in dc["layers"]:

        @jax.checkpoint
        def layer(w, x, p=f"{bb}/l{l}", kind=kind(dc, l)):
            mix = ssm if kind == "ssm" else gqa
            x = x + branch_scale(dc) * mix(dc, w, f"{p}/{kind}", _rms(x, w[f"{p}/norm1/scale"], eps),
                                           matmul)
            return x + branch_scale(dc) * swiglu(w, f"{p}/ffn", _rms(x, w[f"{p}/norm2/scale"], eps),
                                                 matmul)

        x = layer(w, x)
    x = _rms(x, w[f"{bb}/final_norm/scale"], eps).reshape(1, gh, gw, d)
    x = conv(x, w[f"{bb}/neck/conv1/kernel"], 1, 0, matmul) + w[f"{bb}/neck/conv1/bias"]
    x = conv(x, w[f"{bb}/neck/conv2/kernel"], 1, 1, matmul) + w[f"{bb}/neck/conv2/bias"]
    return {4: x}
