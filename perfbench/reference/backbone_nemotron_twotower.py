"""Nemotron-Labs-TwoTower-30B-A3B's tower as a plain stride-16 backbone, plain
float32 (huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16
config.json, ``model_type`` nemotron_h; the plain-backbone pattern of Li et
al., arXiv:2203.16527, without its pyramid).  The second (denoiser) tower, its
adaLN and cross-tower conditioning and the block-diffusion decode are not
held: the config has no key for any of them, and a backbone never generates.

Patchify (16x16/16 convolution with bias) -> the held layers over one image's
patch tokens in raster order -> final RMSNorm -> the (H/16, W/16) grid -> 1x1
conv and 3x3 conv with bias -> level 4.  ``ref["decoder"]`` holds every size.
Each PUBLISHED layer l is ONE pre-norm residual sub-layer, x <- x +
f(RMSNorm(x)), its kind the l-th letter of ``pattern``:

- ``M`` (Mamba-2): [z | xBC | dt] = W_in x; xBC = SiLU(conv4(xBC) + bias)
  (causal depthwise conv over positions); x (heads x head_dim), B, C (groups x
  state; a group serves heads / groups consecutive heads); dt = softplus(dt +
  dt_bias), no clamp; A = -exp(A_log) a head; S_t = exp(dt_t A) S_{t-1} + dt_t
  x_t B_t^T, y_t = S_t C_t + D x_t, S = 0 before the image's first token, TOKEN
  BY TOKEN; RMSNorm over groups of inner / groups channels of y * SiLU(z),
  learned scale; W_out.
- ``*``: q (heads x head_dim), k, v (num_key_value_heads x head_dim) = W x,
  no bias, no rotary embedding; K and V repeated per query head; dense causal
  softmax of q.k / sqrt(head_dim); W_o.
- ``E``: s = sigmoid(W_r x) over all ``n_routed_experts_published``; top
  ``num_experts_per_tok`` of s + bias (one group); w = scale * s_e / sum of
  the selected s; y = sum over the selected experts HELD HERE
  (``experts_first``, ``n_routed_experts``) of w_e E_e(x), plus the shared
  expert; an expert is W_down relu(W_up x)^2.  What the absent experts would
  have added is left out (the chip's share of a 16-chip deployment), and that
  partial result goes on.

Blocking only, as the guide allows, so that it fits a chip at 4,200
positions: each layer under ``jax.checkpoint``; the recurrence as a scan of
checkpointed scans (about sqrt(T) x sqrt(T)); the dense scores a block of rows
at a time.  Every matmul at ``highest`` and through the ``matmul`` hook (the
recurrence's operands x, B, C once, before the scan).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.layers import conv

HI = lax.Precision.HIGHEST
ATTN_ROWS = 128   # 32 x 128 x 4,200 float32 scores a block, 69 MB: XLA keeps sixteen such alive in the backward
KINDS = {"M": "ssm", "*": "gqa", "E": "moe"}


def kind(dc, layer):
    return KINDS[dc["pattern"][layer]]


def held(dc):
    return range(dc["experts_first"], dc["experts_first"] + dc["n_routed_experts"])


def _ssm_sizes(dc):
    inner = dc["mamba_num_heads"] * dc["mamba_head_dim"]
    return inner, inner + 2 * dc["n_groups"] * dc["ssm_state_size"]


# -- leaves --------------------------------------------------------------------


def _mlp_specs(p, d, f):
    return [(f"{p}/up/kernel", (d, f), "lecun"), (f"{p}/down/kernel", (f, d), "lecun")]


def specs(ref):
    """``A_log`` and ``dt_bias`` are drawn uniform in 0.7..1 like a norm's
    scale: the entry maps that onto the family's ranges before either side
    sees the weights (entries/train_lean_ssm.py::ssm_ranges)."""
    dc = ref["decoder"]
    d, h, kv, hd = (dc["hidden_size"], dc["num_attention_heads"], dc["num_key_value_heads"],
                    dc["head_dim"])
    bb = "params/backbone"
    out = [(f"{bb}/patchify/kernel", (dc["patch"], dc["patch"], 3, d), "lecun"),
           (f"{bb}/patchify/bias", (d,), "bias")]
    for l in dc["layers"]:
        p = f"{bb}/l{l}"
        out.append((f"{p}/norm/scale", (d,), "bn_scale"))
        if kind(dc, l) == "ssm":
            m, heads = f"{p}/ssm", dc["mamba_num_heads"]
            inner, conv_dim = _ssm_sizes(dc)
            out += [(f"{m}/in_proj/kernel", (d, inner + conv_dim + heads), "lecun"),
                    (f"{m}/conv/kernel", (dc["conv_kernel"], conv_dim), "lecun"),
                    (f"{m}/conv/bias", (conv_dim,), "bias"),
                    (f"{m}/A_log", (heads,), "bn_scale"), (f"{m}/dt_bias", (heads,), "bn_scale"),
                    (f"{m}/D", (heads,), "bn_scale"), (f"{m}/norm/scale", (inner,), "bn_scale"),
                    (f"{m}/out_proj/kernel", (inner, d), "lecun")]
        elif kind(dc, l) == "gqa":
            m = f"{p}/gqa"
            out += [(f"{m}/q/kernel", (d, h * hd), "lecun"), (f"{m}/k/kernel", (d, kv * hd), "lecun"),
                    (f"{m}/v/kernel", (d, kv * hd), "lecun"), (f"{m}/o/kernel", (h * hd, d), "lecun")]
        else:
            e_all = dc["n_routed_experts_published"]
            out += [(f"{p}/moe/router/kernel", (d, e_all), "lecun"),
                    (f"constants/backbone/l{l}/moe/router/e_bias", (e_all,), "bias")]
            out += _mlp_specs(f"{p}/moe/shared", d, dc["moe_shared_expert_intermediate_size"])
            for e in held(dc):
                out += _mlp_specs(f"{p}/moe/experts/e{e}", d, dc["moe_intermediate_size"])
    c = ref["feature_channels"]
    out += [(f"{bb}/final_norm/scale", (d,), "bn_scale"),
            (f"{bb}/neck/conv1/kernel", (1, 1, d, c), "lecun"), (f"{bb}/neck/conv1/bias", (c,), "bias"),
            (f"{bb}/neck/conv2/kernel", (3, 3, c, c), "lecun"), (f"{bb}/neck/conv2/bias", (c,), "bias")]
    return out


# -- layers --------------------------------------------------------------------


def _mm(a, b, matmul):
    if matmul is not None:
        a, b = matmul(a), matmul(b)
    return jnp.dot(a, b, precision=HI)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _relu2_mlp(w, p, x, matmul):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w[f"{p}/up/kernel"], matmul))),
               w[f"{p}/down/kernel"], matmul)


def _conv_positions(x, k):
    """y_t = sum_j k[j] x_{t - (K - 1) + j}; positions before the first are 0."""
    n = k.shape[0]
    xp = jnp.concatenate([jnp.zeros((n - 1, x.shape[1]), x.dtype), x])
    return sum(xp[j:j + x.shape[0]] * k[j] for j in range(n))


def recurrence(x, dt, a, b, c):
    """The state-space recurrence token by token: x (T, H, P), dt (T, H), a
    (H,), b, c (T, H, N) -> y (T, H, P) without the skip.  A scan of
    checkpointed scans, so that the backward keeps about 2 sqrt(T) states and
    not T."""
    t, h, p = x.shape
    n = b.shape[-1]
    outer = math.ceil(math.sqrt(t))
    inner = -(-t // outer)
    pad = outer * inner - t   # padded positions: dt 0 leaves the state as it is

    def fold(m):
        m = jnp.concatenate([m, jnp.zeros((pad,) + m.shape[1:], m.dtype)])
        return m.reshape((outer, inner) + m.shape[1:])

    def token(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=HI)

    @jax.checkpoint
    def run(s, xs):
        return lax.scan(token, s, xs)

    _, y = lax.scan(run, jnp.zeros((h, p, n), jnp.float32), tuple(map(fold, (x, dt, b, c))))
    return y.reshape(outer * inner, h, p)[:t]


def ssm(dc, w, p, x, matmul):
    t = x.shape[0]
    h, hd, g, n = dc["mamba_num_heads"], dc["mamba_head_dim"], dc["n_groups"], dc["ssm_state_size"]
    inner, conv_dim = _ssm_sizes(dc)
    z, xbc, dt = jnp.split(_mm(x, w[f"{p}/in_proj/kernel"], matmul), [inner, inner + conv_dim], axis=1)
    xbc = jax.nn.silu(_conv_positions(xbc, w[f"{p}/conv/kernel"]) + w[f"{p}/conv/bias"])
    xs, b, c = jnp.split(xbc, [inner, inner + g * n], axis=1)
    per_head = lambda m: jnp.repeat(m.reshape(t, g, n), h // g, axis=1)
    xs, b, c = xs.reshape(t, h, hd), per_head(b), per_head(c)
    dt = jax.nn.softplus(dt + w[f"{p}/dt_bias"])
    if matmul is not None:
        xs, b, c = matmul(xs), matmul(b), matmul(c)
    y = recurrence(xs, dt, -jnp.exp(w[f"{p}/A_log"]), b, c) + w[f"{p}/D"][:, None] * xs
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = _rms(y, w[f"{p}/norm/scale"].reshape(g, inner // g), dc["norm_eps"])
    return _mm(y.reshape(t, inner), w[f"{p}/out_proj/kernel"], matmul)


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
        s = jnp.einsum("qhd,khd->hqk", q_rows, k, precision=HI) / math.sqrt(hd)
        row = first + jnp.arange(q_rows.shape[0])
        s = jnp.where(row[:, None] >= jnp.arange(t)[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    o = jnp.concatenate([rows(q[lo:lo + ATTN_ROWS], lo) for lo in range(0, t, ATTN_ROWS)])
    return _mm(o.reshape(t, h * hd), w[f"{p}/o/kernel"], matmul)


def router(dc, w, p, x, matmul):
    """-> (experts (T, k), weights (T, k)) over all the published experts."""
    s = jax.nn.sigmoid(_mm(x, w[f"{p}/router/kernel"], matmul))
    sel = s + w[p.replace("params/", "constants/", 1) + "/router/e_bias"]
    _, experts = lax.top_k(sel, dc["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, experts, axis=1)
    weights = dc["routed_scaling_factor"] * picked / jnp.sum(picked, axis=1, keepdims=True)
    return experts, weights


def experts_here(dc, w, p, x, matmul):
    """-> (the layer's result, token-slots routed to the experts held here)."""
    experts, weights = router(dc, w, p, x, matmul)
    y = _relu2_mlp(w, f"{p}/shared", x, matmul)
    for e in held(dc):
        w_e = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        y = y + w_e[:, None] * _relu2_mlp(w, f"{p}/experts/e{e}", x, matmul)
    here = (experts >= held(dc).start) & (experts < held(dc).stop)
    return y, jnp.sum(here.astype(jnp.float32))


def _forward(ref, w, x, matmul):
    dc = ref["decoder"]
    bb = "params/backbone"
    eps = dc["norm_eps"]
    x = conv(x, w[f"{bb}/patchify/kernel"], dc["patch"], 0, matmul) + w[f"{bb}/patchify/bias"]
    _, gh, gw, d = x.shape
    x = x.reshape(gh * gw, d)
    slots = 0.0
    for l in dc["layers"]:

        @jax.checkpoint
        def layer(w, x, p=f"{bb}/l{l}", kind=kind(dc, l)):
            normed = _rms(x, w[f"{p}/norm/scale"], eps)
            if kind == "moe":
                y, here = experts_here(dc, w, f"{p}/moe", normed, matmul)
                return x + y, here
            mix = ssm if kind == "ssm" else gqa
            return x + mix(dc, w, f"{p}/{kind}", normed, matmul), 0.0

        x, here = layer(w, x)
        slots = slots + here
    x = _rms(x, w[f"{bb}/final_norm/scale"], eps).reshape(1, gh, gw, d)
    x = conv(x, w[f"{bb}/neck/conv1/kernel"], 1, 0, matmul) + w[f"{bb}/neck/conv1/bias"]
    x = conv(x, w[f"{bb}/neck/conv2/kernel"], 1, 1, matmul) + w[f"{bb}/neck/conv2/bias"]
    return {4: x}, slots


def features(ref, w, x, matmul=None):
    return _forward(ref, w, x, matmul)[0]


def slots_here(ref, w, x):
    """Token-slots one image's forward routes to the experts held here, summed
    over the expert layers: the reference's side of the program's
    ``moe_slots_here`` (their gap counts the picks that rounding flipped
    across the share's edge)."""
    return _forward(ref, w, x, None)[1]
