"""Phi-4-mini-flash-reasoning's SambaY decoder as a plain stride-16 backbone,
plain float32 (huggingface.co/microsoft/Phi-4-mini-flash-reasoning
config.json, ``model_type`` phi4flash; "SambaY with Differential Attention",
arXiv:2507.06607; the plain-backbone pattern of Li et al., arXiv:2203.16527,
without its pyramid).  The token embedding and the tied head are not held: a
backbone never reads them.

Patchify (16x16/16 convolution with bias) -> the held layers over one image's
patch tokens in raster order -> final LayerNorm -> the (H/16, W/16) grid -> 1x1
conv and 3x3 conv with bias -> level 4.  ``ref["decoder"]`` holds every size.
Each PUBLISHED layer l is x <- x + mixer(LN1(x)); x <- x + MLP(LN2(x)), LN a
LayerNorm with scale and bias, MLP = W_down(W_up x * SiLU(W_gate x)), no bias.
No rotary or other positional encoding.  With L = ``num_hidden_layers_published``
the mixer is, by the model's own rule:

- l % ``mb_per_layer`` == 0 and l <= L/2 - Mamba-1: x | z = W_in u; x =
  SiLU(conv4(x) + bias) (causal depthwise conv over positions); delta | B | C
  = W_x x; dt = softplus(W_dt delta + dt_bias); A = -exp(A_log) (channels x
  states); h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t^T, y_t = h_t C_t + D x_t,
  h = 0 before the image's first token, TOKEN BY TOKEN; out = W_out (y *
  SiLU(z)).  Layer L/2 also hands y (before the gate) on as the memory.
- otherwise below L/2 - differential attention under a window: a query sees
  itself and the ``sliding_window`` - 1 positions before it.  l = L/2 + 1 -
  the same over the whole causal prefix; it also hands its k and v on.
  Heads pair up (2j, 2j + 1) into q1, q2 (and k1, k2); a pair's two value
  heads side by side are one value of twice the width; a query pair reads key
  pair j // (query pairs / key pairs); a_i = softmax(q_i k_i^T / sqrt(head_dim)
  + mask) v; lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
  lambda_init = 0.8 - 0.6 exp(-0.3 l), l the PUBLISHED index; out = W_o
  (RMSNorm(a_1 - lambda a_2) (1 - lambda_init)) + bias; W_qkv with bias.
- l % ``mb_per_layer`` == 0 past L/2 - Gated Memory Unit: out = W_out
  (SiLU(W_in u) * memory).
- otherwise past L/2 + 1 - differential cross attention: queries from this
  layer (W_qkv projects them alone), k and v layer L/2 + 1's, causal.

Departures from the published description: fc1 is held as its two halves
(``gate``, ``up``) and fc2 as ``down``; the four lambda vectors are the rows
(q1, k1, q2, k2) of one leaf.  Blocking only, as the guide allows, so that it
fits a chip at 4,200 positions: each layer under ``jax.checkpoint``; the
recurrence as a scan of checkpointed scans (about sqrt(T) x sqrt(T)); the
dense scores a block of rows at a time.  Every matmul at ``highest`` and
through the ``matmul`` hook (the scan's x once, before the scan, as the
program holds it in its matmuls' type).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.layers import conv

HI = lax.Precision.HIGHEST
ATTN_ROWS = 128   # 20 x 128 x 4,200 float32 scores a block and map, 43 MB


def kind(dc, layer):
    middle = dc["num_hidden_layers_published"] // 2
    if layer % dc["mb_per_layer"] == 0:
        return "mamba" if layer <= middle else "gmu"
    return "swa" if layer < middle else "full" if layer == middle + 1 else "xattn"


def window(dc, kind):
    """Positions a query of a ``kind`` layer sees, itself included; None = all before it."""
    return dc["sliding_window"] if kind == "swa" else None


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def difference(a1, a2, lam):
    return a1 - lam * a2


def handed_on(tensor):
    """What a later layer reads of an earlier layer's tensor: the tensor."""
    return tensor


def _wide(dc):
    return dc["mamba_expand"] * dc["hidden_size"]


# -- leaves --------------------------------------------------------------------


def _norm_specs(p, d):
    return [(f"{p}/scale", (d,), "bn_scale"), (f"{p}/bias", (d,), "bias")]


def _affine_specs(p, i, o):
    return [(f"{p}/kernel", (i, o), "lecun"), (f"{p}/bias", (o,), "bias")]


def specs(ref):
    """``A_log`` and ``dt_bias`` are drawn uniform in 0.7..1 like a norm's
    scale and ``lambda`` like a bias: the entry maps them onto the family's
    ranges before either side sees the weights
    (entries/train_lean_sambay.py::sambay_ranges)."""
    dc = ref["decoder"]
    d, h, kv, hd = (dc["hidden_size"], dc["num_attention_heads"], dc["num_key_value_heads"],
                    dc["head_dim"])
    wide, n, rank, f = _wide(dc), dc["mamba_d_state"], dc["mamba_dt_rank"], dc["intermediate_size"]
    bb = "params/backbone"
    out = [(f"{bb}/patchify/kernel", (dc["patch"], dc["patch"], 3, d), "lecun"),
           (f"{bb}/patchify/bias", (d,), "bias")]
    for l in dc["layers"]:
        p, k = f"{bb}/l{l}", kind(dc, l)
        m = f"{p}/{k}"
        out += _norm_specs(f"{p}/norm1", d)
        if k == "mamba":
            out += [(f"{m}/in_proj/kernel", (d, 2 * wide), "lecun"),
                    (f"{m}/conv/kernel", (dc["mamba_d_conv"], wide), "lecun"),
                    (f"{m}/conv/bias", (wide,), "bias"),
                    (f"{m}/x_proj/kernel", (wide, rank + 2 * n), "lecun"),
                    (f"{m}/dt_proj/kernel", (rank, wide), "lecun"),
                    (f"{m}/dt_bias", (wide,), "bn_scale"), (f"{m}/A_log", (wide, n), "bn_scale"),
                    (f"{m}/D", (wide,), "bn_scale"), (f"{m}/out_proj/kernel", (wide, d), "lecun")]
        elif k == "gmu":
            out += [(f"{m}/in_proj/kernel", (d, wide), "lecun"),
                    (f"{m}/out_proj/kernel", (wide, d), "lecun")]
        else:
            qkv = h * hd if k == "xattn" else (h + 2 * kv) * hd
            out += _affine_specs(f"{m}/Wqkv", d, qkv)
            out += [(f"{m}/lambda", (4, hd), "bias"), (f"{m}/subln/scale", (2 * hd,), "bn_scale")]
            out += _affine_specs(f"{m}/out_proj", h * hd, d)
        out += _norm_specs(f"{p}/norm2", d)
        out += [(f"{p}/ffn/gate/kernel", (d, f), "lecun"), (f"{p}/ffn/up/kernel", (d, f), "lecun"),
                (f"{p}/ffn/down/kernel", (f, d), "lecun")]
    c = ref["feature_channels"]
    out += _norm_specs(f"{bb}/final_norm", d)
    out += [(f"{bb}/neck/conv1/kernel", (1, 1, d, c), "lecun"), (f"{bb}/neck/conv1/bias", (c,), "bias"),
            (f"{bb}/neck/conv2/kernel", (3, 3, c, c), "lecun"), (f"{bb}/neck/conv2/bias", (c,), "bias")]
    return out


# -- layers --------------------------------------------------------------------


def _mm(a, b, matmul):
    if matmul is not None:
        a, b = matmul(a), matmul(b)
    return jnp.dot(a, b, precision=HI)


def _layer_norm(w, p, x, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w[f"{p}/scale"] + w[f"{p}/bias"]


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _conv_positions(x, k):
    """y_t = sum_j k[j] x_{t - (K - 1) + j}; positions before the first are 0."""
    n = k.shape[0]
    xp = jnp.concatenate([jnp.zeros((n - 1, x.shape[1]), x.dtype), x])
    return sum(xp[j:j + x.shape[0]] * k[j] for j in range(n))


def recurrence(x, dt, a, b, c):
    """The selective scan token by token: x, dt (T, C), a (C, N), b, c (T, N)
    -> y (T, C) without the skip.  A scan of checkpointed scans, so that the
    backward keeps about 2 sqrt(T) states and not T."""
    t, ch = x.shape
    outer = math.ceil(math.sqrt(t))
    inner = -(-t // outer)
    pad = outer * inner - t   # padded positions: dt 0 leaves the state as it is

    def fold(m):
        m = jnp.concatenate([m, jnp.zeros((pad,) + m.shape[1:], m.dtype)])
        return m.reshape((outer, inner) + m.shape[1:])

    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, jnp.dot(h, c_t, precision=HI)

    @jax.checkpoint
    def run(h, xs):
        return lax.scan(token, h, xs)

    _, y = lax.scan(run, jnp.zeros((ch, a.shape[1]), jnp.float32), tuple(map(fold, (x, dt, b, c))))
    return y.reshape(outer * inner, ch)[:t]


def mamba(dc, w, p, x, matmul):
    """-> (the mixer's result, the scan's result before the gate)."""
    wide, n, rank = _wide(dc), dc["mamba_d_state"], dc["mamba_dt_rank"]
    xs, z = jnp.split(_mm(x, w[f"{p}/in_proj/kernel"], matmul), [wide], axis=1)
    xs = jax.nn.silu(_conv_positions(xs, w[f"{p}/conv/kernel"]) + w[f"{p}/conv/bias"])
    delta, b, c = jnp.split(_mm(xs, w[f"{p}/x_proj/kernel"], matmul), [rank, rank + n], axis=1)
    dt = jax.nn.softplus(_mm(delta, w[f"{p}/dt_proj/kernel"], matmul) + w[f"{p}/dt_bias"])
    if matmul is not None:
        xs = matmul(xs)
    y = recurrence(xs, dt, -jnp.exp(w[f"{p}/A_log"]), b, c) + w[f"{p}/D"] * xs
    return _mm(y * jax.nn.silu(z), w[f"{p}/out_proj/kernel"], matmul), y


def gmu(dc, w, p, x, matmul, memory):
    u = jax.nn.silu(_mm(x, w[f"{p}/in_proj/kernel"], matmul))
    return _mm(u * memory, w[f"{p}/out_proj/kernel"], matmul)


def attention(q, k, v, seen):
    """q (T, H, Dk), k (T, Hkv, Dk), v (T, Hkv, Dv) -> (T, H, Dv): dense causal
    softmax, a query seeing ``seen`` positions (None: all) up to itself."""
    t, rep = q.shape[0], q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scale = 1.0 / math.sqrt(q.shape[-1])

    @jax.checkpoint
    def rows(q_rows, first):
        s = jnp.einsum("qhd,khd->hqk", q_rows, k, precision=HI) * scale
        ahead = (first + jnp.arange(q_rows.shape[0]))[:, None] - jnp.arange(t)[None, :]
        ok = ahead >= 0 if seen is None else (ahead >= 0) & (ahead < seen)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1), v,
                          precision=HI)

    return jnp.concatenate([rows(q[lo:lo + ATTN_ROWS], lo) for lo in range(0, t, ATTN_ROWS)])


def diff_attention(dc, w, p, layer, x, matmul, seen, kv=None):
    """-> (the mixer's result, (k, v)); with ``kv`` the keys and values are
    another layer's and W_qkv holds the queries alone."""
    t = x.shape[0]
    h, hkv, hd = dc["num_attention_heads"], dc["num_key_value_heads"], dc["head_dim"]
    qkv = _mm(x, w[f"{p}/Wqkv/kernel"], matmul) + w[f"{p}/Wqkv/bias"]
    q = qkv[:, : h * hd].reshape(t, h // 2, 2, hd)
    if kv is None:
        kv = (qkv[:, h * hd: (h + hkv) * hd].reshape(t, hkv // 2, 2, hd),
              qkv[:, (h + hkv) * hd:].reshape(t, hkv // 2, 2 * hd))
    k, v = kv
    if matmul is not None:
        q, k, v = matmul(q), matmul(k), matmul(v)
    a1, a2 = (attention(q[:, :, i], k[:, :, i], v, seen) for i in (0, 1))
    lam, first = w[f"{p}/lambda"], lambda_init(layer)
    lam = jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3])) + first
    o = _rms(difference(a1, a2, lam), w[f"{p}/subln/scale"], dc["layer_norm_eps"]) * (1.0 - first)
    return _mm(o.reshape(t, h * hd), w[f"{p}/out_proj/kernel"], matmul) + w[f"{p}/out_proj/bias"], kv


def mlp(w, p, x, matmul):
    hidden = jax.nn.silu(_mm(x, w[f"{p}/gate/kernel"], matmul)) * _mm(x, w[f"{p}/up/kernel"], matmul)
    return _mm(hidden, w[f"{p}/down/kernel"], matmul)


def features(ref, w, x, matmul=None):
    dc = ref["decoder"]
    bb, eps = "params/backbone", dc["layer_norm_eps"]
    middle = dc["num_hidden_layers_published"] // 2
    x = conv(x, w[f"{bb}/patchify/kernel"], dc["patch"], 0, matmul) + w[f"{bb}/patchify/bias"]
    _, gh, gw, d = x.shape
    x = x.reshape(gh * gw, d)
    shared = {}
    for l in dc["layers"]:

        @jax.checkpoint
        def layer(w, x, shared, l=l, p=f"{bb}/l{l}", k=kind(dc, l)):
            normed = _layer_norm(w, f"{p}/norm1", x, eps)
            if k == "mamba":
                y, memory = mamba(dc, w, f"{p}/{k}", normed, matmul)
                if l == middle:
                    shared = dict(shared, m=memory)
            elif k == "gmu":
                y = gmu(dc, w, f"{p}/{k}", normed, matmul, handed_on(shared["m"]))
            elif k == "xattn":
                kv = (handed_on(shared["k"]), handed_on(shared["v"]))
                y, _ = diff_attention(dc, w, f"{p}/{k}", l, normed, matmul, None, kv)
            else:
                y, (keys, values) = diff_attention(dc, w, f"{p}/{k}", l, normed, matmul, window(dc, k))
                if k == "full":
                    shared = dict(shared, k=keys, v=values)
            x = x + y
            return x + mlp(w, f"{p}/ffn", _layer_norm(w, f"{p}/norm2", x, eps), matmul), shared

        x, shared = layer(w, x, shared)
    x = _layer_norm(w, f"{bb}/final_norm", x, eps).reshape(1, gh, gw, d)
    x = conv(x, w[f"{bb}/neck/conv1/kernel"], 1, 0, matmul) + w[f"{bb}/neck/conv1/bias"]
    x = conv(x, w[f"{bb}/neck/conv2/kernel"], 1, 1, matmul) + w[f"{bb}/neck/conv2/bias"]
    return {4: x}
