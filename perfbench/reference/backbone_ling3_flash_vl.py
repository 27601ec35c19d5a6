"""Ling-3.0-flash-VL's decoder blocks as a plain stride-16 backbone, plain
float32 (huggingface.co/inclusionAI/Ling-3.0-flash-VL config.json; the
plain-backbone pattern of Li et al., arXiv:2203.16527, without its pyramid).

Patchify (16x16/16 convolution with bias) -> pre-norm residual blocks over
one image's patch tokens in raster order (RMSNorm, eps from the file) ->
final RMSNorm -> the (H/16, W/16) grid -> 1x1 conv and 3x3 conv with bias ->
level 4.  ``ref["decoder"]`` holds every size; a layer's kind follows from its
PUBLISHED index l: dense SwiGLU below ``first_k_dense_replace``, routed
experts above; latent attention (MLA) where (l + 1) % ``layer_group_size`` is
0, KDA linear attention elsewhere.

- KDA: q, k, v = SiLU(conv4(W x)) (causal depthwise conv over positions);
  q, k L2-normalised per head, q scaled by head_dim^-1/2; log-decay per
  channel g = lower_bound * sigmoid(exp(A_h) (W_f x + b)); beta = sigmoid(W_b
  x) per head; S_t = (I - beta k k^T) Diag(e^g) S_{t-1} + beta k v^T, o = S^T
  q, S = 0 before the image's first token, TOKEN BY TOKEN; y = W_o
  (RMSNorm_head(o) * sigmoid(W_g x)).
- MLA: q = W_q x -> per head [nope | rope]; [c | k_rope] = W_kva x; [k_nope |
  v] = W_kvb RMSNorm(c); RMSNorm on q and k per head over nope + rope;
  rotary (halves paired) over the rope dims by raster position; dense causal
  softmax of q.k / sqrt(nope + rope); one sigmoid gate per head; W_o.
- Experts: s = sigmoid(W_r x) over all ``num_experts_published``; selection on
  s + bias by groups (a group's score the sum of its two best, the best
  ``topk_group`` groups kept), top ``num_experts_per_tok``; w = scale * s_e /
  sum of the selected s; y = sum over the selected experts HELD HERE
  (``experts_first``, ``num_experts``) of w_e E_e(x), plus the shared expert.
  What the absent experts would have added is left out (the chip's share of
  a 64-chip deployment), and that partial result goes on.

Blocking only, as the guide allows, so that it fits a chip at 4,200
positions: each block under ``jax.checkpoint``; the recurrence as a scan of
checkpointed scans (about sqrt(T) x sqrt(T)); the dense scores a block of rows
at a time.  Every matmul at ``highest`` and through the ``matmul`` hook (the
recurrence's operands q, k, v once, before the scan).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.layers import conv

HI = lax.Precision.HIGHEST
ATTN_ROWS = 512


def kinds(dc, layer):
    mixer = "mla" if (layer + 1) % dc["layer_group_size"] == 0 else "kda"
    return mixer, ("ffn" if layer < dc["first_k_dense_replace"] else "moe")


def held(dc):
    return range(dc["experts_first"], dc["experts_first"] + dc["num_experts"])


# -- leaves --------------------------------------------------------------------


def _swiglu_specs(p, d, f):
    return [(f"{p}/gate/kernel", (d, f), "lecun"), (f"{p}/up/kernel", (d, f), "lecun"),
            (f"{p}/down/kernel", (f, d), "lecun")]


def specs(ref):
    dc = ref["decoder"]
    d, h, hd = dc["hidden_size"], dc["num_attention_heads"], dc["head_dim"]
    dn, dr, dv, r = (dc["qk_nope_head_dim"], dc["qk_rope_head_dim"], dc["v_head_dim"],
                     dc["kv_lora_rank"])
    bb = "params/backbone"
    out = [(f"{bb}/patchify/kernel", (dc["patch"], dc["patch"], 3, d), "lecun"),
           (f"{bb}/patchify/bias", (d,), "bias")]
    for l in dc["layers"]:
        p = f"{bb}/l{l}"
        mixer, ff = kinds(dc, l)
        out += [(f"{p}/norm1/scale", (d,), "bn_scale"), (f"{p}/norm2/scale", (d,), "bn_scale")]
        if mixer == "kda":
            m = f"{p}/kda"
            for name in ("q", "k", "v", "f", "g"):
                out.append((f"{m}/{name}/kernel", (d, h * hd), "lecun"))
            for name in ("conv_q", "conv_k", "conv_v"):
                out.append((f"{m}/{name}/kernel", (dc["short_conv_kernel_size"], h * hd), "lecun"))
            out += [(f"{m}/decay/scale", (h,), "bn_scale"), (f"{m}/decay/bias", (h * hd,), "bias"),
                    (f"{m}/b/kernel", (d, h), "lecun"), (f"{m}/o_norm/scale", (hd,), "bn_scale"),
                    (f"{m}/o/kernel", (h * hd, d), "lecun")]
        else:
            m = f"{p}/mla"
            out += [(f"{m}/q/kernel", (d, h * (dn + dr)), "lecun"),
                    (f"{m}/kva/kernel", (d, r + dr), "lecun"),
                    (f"{m}/kv_norm/scale", (r,), "bn_scale"),
                    (f"{m}/kvb/kernel", (r, h * (dn + dv)), "lecun"),
                    (f"{m}/q_norm/scale", (dn + dr,), "bn_scale"),
                    (f"{m}/k_norm/scale", (dn + dr,), "bn_scale"),
                    (f"{m}/gate/kernel", (d, h), "lecun"),
                    (f"{m}/o/kernel", (h * dv, d), "lecun")]
        if ff == "ffn":
            out += _swiglu_specs(f"{p}/ffn", d, dc["intermediate_size"])
        else:
            e_all, f = dc["num_experts_published"], dc["moe_intermediate_size"]
            out += [(f"{p}/moe/router/kernel", (d, e_all), "lecun"),
                    (f"constants/backbone/l{l}/moe/router/e_bias", (e_all,), "bias")]
            out += _swiglu_specs(f"{p}/moe/shared", d, f)
            for e in held(dc):
                out += _swiglu_specs(f"{p}/moe/experts/e{e}", d, f)
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


def _swiglu(w, p, x, matmul):
    up = jax.nn.silu(_mm(x, w[f"{p}/gate/kernel"], matmul)) * _mm(x, w[f"{p}/up/kernel"], matmul)
    return _mm(up, w[f"{p}/down/kernel"], matmul)


def _conv_positions(x, k):
    """y_t = sum_j k[j] x_{t - (K - 1) + j}; positions before the first are 0."""
    n = k.shape[0]
    xp = jnp.concatenate([jnp.zeros((n - 1, x.shape[1]), x.dtype), x])
    return sum(xp[j:j + x.shape[0]] * k[j] for j in range(n))


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token: q, k, g (T, H, Dk), v (T, H, Dv), beta
    (T, H) -> o (T, H, Dv).  A scan of checkpointed scans, so that the backward
    keeps about 2 sqrt(T) states and not T."""
    t, h, dk = k.shape
    dv = v.shape[-1]
    outer = math.ceil(math.sqrt(t))
    inner = -(-t // outer)
    pad = outer * inner - t   # padded positions: beta 0, g 0 leave the state as it is

    def fold(x):
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        return x.reshape((outer, inner) + x.shape[1:])

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, s, precision=HI))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=HI)

    @jax.checkpoint
    def run(s, xs):
        return lax.scan(token, s, xs)

    _, o = lax.scan(run, jnp.zeros((h, dk, dv), jnp.float32), tuple(map(fold, (q, k, v, g, beta))))
    return o.reshape(outer * inner, h, dv)[:t]


def kda(dc, w, p, x, matmul):
    t = x.shape[0]
    h, hd = dc["num_attention_heads"], dc["head_dim"]
    heads = lambda a: a.reshape(t, h, hd)
    l2 = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    short = lambda n: heads(jax.nn.silu(
        _conv_positions(_mm(x, w[f"{p}/{n}/kernel"], matmul), w[f"{p}/conv_{n}/kernel"])
    ))
    q, k, v = l2(short("q")) * hd**-0.5, l2(short("k")), short("v")
    rate = jnp.exp(w[f"{p}/decay/scale"])[:, None]
    g = dc["kda_lower_bound"] * jax.nn.sigmoid(
        rate * heads(_mm(x, w[f"{p}/f/kernel"], matmul) + w[f"{p}/decay/bias"])
    )
    beta = jax.nn.sigmoid(_mm(x, w[f"{p}/b/kernel"], matmul))
    if matmul is not None:
        q, k, v = matmul(q), matmul(k), matmul(v)
    o = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid(heads(_mm(x, w[f"{p}/g/kernel"], matmul)))
    o = _rms(o, w[f"{p}/o_norm/scale"], dc["rms_norm_eps"]) * gate
    return _mm(o.reshape(t, h * hd), w[f"{p}/o/kernel"], matmul)


def _rotary(x, theta):
    """x (T, ..., R) by raster position 0..T-1, halves paired."""
    t, r = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (r // 2,))
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def mla(dc, w, p, x, matmul):
    t = x.shape[0]
    h, dn, dr, dv, r = (dc["num_attention_heads"], dc["qk_nope_head_dim"],
                        dc["qk_rope_head_dim"], dc["v_head_dim"], dc["kv_lora_rank"])
    eps = dc["rms_norm_eps"]
    q = _mm(x, w[f"{p}/q/kernel"], matmul).reshape(t, h, dn + dr)
    kva = _mm(x, w[f"{p}/kva/kernel"], matmul)
    kv = _mm(_rms(kva[:, :r], w[f"{p}/kv_norm/scale"], eps), w[f"{p}/kvb/kernel"], matmul)
    kv = kv.reshape(t, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(kva[:, None, r:], (t, h, dr))], axis=-1)
    q = _rms(q, w[f"{p}/q_norm/scale"], eps)
    k = _rms(k, w[f"{p}/k_norm/scale"], eps)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], dc["rope_theta"])], axis=-1)
    k = jnp.concatenate([k[..., :dn], _rotary(k[..., dn:], dc["rope_theta"])], axis=-1)
    v = kv[..., dn:]
    if matmul is not None:
        q, k, v = matmul(q), matmul(k), matmul(v)

    @jax.checkpoint
    def rows(q_rows, first):
        s = jnp.einsum("qhd,khd->hqk", q_rows, k, precision=HI) / math.sqrt(dn + dr)
        row = first + jnp.arange(q_rows.shape[0])
        s = jnp.where(row[:, None] >= jnp.arange(t)[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    o = jnp.concatenate([rows(q[lo:lo + ATTN_ROWS], lo) for lo in range(0, t, ATTN_ROWS)])
    gate = jax.nn.sigmoid(_mm(x, w[f"{p}/gate/kernel"], matmul))
    return _mm((o * gate[:, :, None]).reshape(t, h * dv), w[f"{p}/o/kernel"], matmul)


def router(dc, w, p, x, matmul):
    """-> (experts (T, k), weights (T, k)) over all the published experts."""
    s = jax.nn.sigmoid(_mm(x, w[f"{p}/router/kernel"], matmul))
    t, e = s.shape
    groups = dc["n_group"]
    sel = s + w[p.replace("params/", "constants/", 1) + "/router/e_bias"]
    best_two = lax.top_k(sel.reshape(t, groups, e // groups), 2)[0].sum(axis=-1)
    _, kept = lax.top_k(best_two, dc["topk_group"])
    keep = jnp.any(kept[:, :, None] == jnp.arange(groups)[None, None, :], axis=1)
    sel = jnp.where(jnp.repeat(keep, e // groups, axis=1), sel, -jnp.inf)
    _, experts = lax.top_k(sel, dc["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, experts, axis=1)
    weights = dc["routed_scaling_factor"] * picked / jnp.sum(picked, axis=1, keepdims=True)
    return experts, weights


def experts_here(dc, w, p, x, matmul):
    """-> (the layer's result, token-slots routed to the experts held here)."""
    experts, weights = router(dc, w, p, x, matmul)
    y = _swiglu(w, f"{p}/shared", x, matmul)
    for e in held(dc):
        w_e = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        y = y + w_e[:, None] * _swiglu(w, f"{p}/experts/e{e}", x, matmul)
    here = (experts >= held(dc).start) & (experts < held(dc).stop)
    return y, jnp.sum(here.astype(jnp.float32))


def _forward(ref, w, x, matmul):
    dc = ref["decoder"]
    bb = "params/backbone"
    eps = dc["rms_norm_eps"]
    x = conv(x, w[f"{bb}/patchify/kernel"], dc["patch"], 0, matmul) + w[f"{bb}/patchify/bias"]
    _, gh, gw, d = x.shape
    x = x.reshape(gh * gw, d)
    slots = 0.0
    for l in dc["layers"]:
        mixer, ff = kinds(dc, l)

        @jax.checkpoint
        def block(w, x, p=f"{bb}/l{l}", mixer=mixer, ff=ff):
            mix = kda if mixer == "kda" else mla
            x = x + mix(dc, w, f"{p}/{mixer}", _rms(x, w[f"{p}/norm1/scale"], eps), matmul)
            normed = _rms(x, w[f"{p}/norm2/scale"], eps)
            if ff == "ffn":
                return x + _swiglu(w, f"{p}/ffn", normed, matmul), 0.0
            y, here = experts_here(dc, w, f"{p}/moe", normed, matmul)
            return x + y, here

        x, here = block(w, x)
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
