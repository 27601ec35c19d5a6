"""Kimi Delta Attention (KDA): the gated delta rule with a per-channel decay,
as a chunked scan whose work is matmuls: the scan over chunks in plain XLA,
what happens inside a chunk as a Pallas kernel pair on the TPU
(``ops/pallas/kda.py``) and in plain XLA everywhere else.

Per head, with state ``S`` (Dk, Dv), ``alpha_t = exp(g_t)`` per key channel:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,    S_0 = 0 at each sequence's first position.

:func:`kda_recurrent` is that recurrence token by token (the oracle).
:func:`kda_chunked` cuts the positions into chunks of ``chunk`` (64): with
``G_t`` the log-decay summed from the chunk's start,

    A[t,s] = beta_t (k_t e^{G_t}) . (k_s e^{-G_s})   (s < t)
    P[t,s] =        (q_t e^{G_t}) . (k_s e^{-G_s})   (s <= t)
    T = (I + A)^{-1},  W = T (beta k e^{G}),  U0 = T (beta v)

are computed for every chunk at once (on the TPU with a chunk resident in VMEM:
nothing of it but its inputs and these results crosses HBM), and a ``lax.scan``
over the chunks carries the state through three matmuls a chunk:

    U = U0 - W S,   O = (q e^{G}) S + P U,   S' = e^{G_C} S + (k e^{G_C - G})^T U.

``e^{-G_s}`` overflows float32 over a whole chunk, which is what the
published safe gate (``g >= lower_bound`` = -5 a position) is for: rows are
taken ``sub`` (16) at a time against the cumulative decay at the MIDDLE of
their own sub-chunk, so every exponent that counts lies within
5 * sub / 2 = 40 of zero on both sides (columns further on are above the
diagonal, masked, and clamped just past that).  Measured from the sub-chunk's
start the same products span e^-80 .. e^80: they fit, but their cotangents
(e^-80 times a small gradient) fall under float32's 1e-38 and the decay's
gradient loses its largest terms wherever a channel decays hard.  What happens inside a chunk (A, P,
the triangular inverse by blocked forward substitution, W, U0) is float32 at
``highest``: it is a hundredth of the layer's FLOPs, and I + A is badly
conditioned where keys look alike, so a bfloat16 A would be amplified.  The
scan over chunks, where the work is, takes ``dtype`` operands; sums, decays
and the state stay float32.  The backward is autodiff through the scan over
chunks; the chunk-local part's is a hand-written kernel on the TPU and
autodiff of the XLA form elsewhere, each recomputing the chunk from its inputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from mx_rcnn_tpu.ops.pallas import kda as kda_kernel

HI = lax.Precision.HIGHEST
# Positions per chunk of the scan: a program choice that follows from the
# arithmetic (a chunk's work is 64-row matmuls), not an option; tests pass others.
CHUNK = 64


def short_conv(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Causal depthwise convolution over positions: x (B, T, C), w (K, C);
    ``w[K - 1]`` multiplies the current position, the sequence starts from
    zeros."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = xp[:, 0:t] * w[0]
    for j in range(1, k):
        out = out + xp[:, j:j + t] * w[j]
    return out


def kda_recurrent(q, k, v, g, beta):
    """The recurrence, one position at a time.  q, k, g (B, T, H, Dk),
    v (B, T, H, Dv), beta (B, T, H); float32.  -> o (B, T, H, Dv)."""
    b, _, h, dk = k.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        with jax.named_scope("token"):
            s = s * jnp.exp(g_t)[..., None]
            u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HI))
            s = s + k_t[..., None] * u[..., None, :]
            return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=HI)

    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


_BASE = 16  # rows of the diagonal blocks inverted by substitution


@jax.custom_vjp
def _unit_lower_inverse(a):
    """(I + A)^{-1} for strictly lower-triangular A (..., C, C), C a power of
    two: forward substitution, row by row, on the 16 x 16 diagonal blocks (all
    of them at once), then pairs of inverted blocks merged,
    [[T1, 0], [-T2 A21 T1, T2]], up to the whole.  Every intermediate is part
    of an inverse, so nothing grows: the product (I + X)(I + X^2)(I + X^4)...
    of X = -A is the same matrix on paper, but tokens that look alike have keys
    that look alike, A is then near a constant times the all-ones triangle,
    whose powers reach 1e17 at C = 64, and the product cancels to noise in
    float32.  The backward is -T^T dT T^T from the result alone."""
    c = a.shape[-1]
    m = min(_BASE, c)

    def diagonal_blocks(size):          # (..., C, C) -> (..., C / size, size, size)
        n = c // size
        blocks = a.reshape(a.shape[:-2] + (n, size, n, size))
        return jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)

    with jax.named_scope("inverse"):
        d = diagonal_blocks(m)
        eye = jnp.eye(m, dtype=a.dtype)
        rows = [jnp.broadcast_to(eye[0], d.shape[:-2] + (m,))]
        for i in range(1, m):               # row i of T = e_i - A[i, :i] T[:i]
            done = jnp.stack(rows, axis=-2)
            rows.append(
                eye[i] - jnp.einsum("...j,...jk->...k", d[..., i, :i], done, precision=HI)
            )
        t = jnp.stack(rows, axis=-2)
        while m < c:
            a21 = diagonal_blocks(2 * m)[..., m:, :m]
            t1, t2 = t[..., 0::2, :, :], t[..., 1::2, :, :]
            t21 = -jnp.matmul(jnp.matmul(t2, a21, precision=HI), t1, precision=HI)
            t = jnp.concatenate([
                jnp.concatenate([t1, jnp.zeros_like(t1)], axis=-1),
                jnp.concatenate([t21, t2], axis=-1),
            ], axis=-2)
            m *= 2
        return t[..., 0, :, :]


def _inverse_fwd(a):
    t = _unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    with jax.named_scope("inverse"):
        return (-jnp.matmul(jnp.matmul(tt, dt, precision=HI), tt, precision=HI),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _intra(q, k, v, g, beta, sub, far, dtype):
    """What happens inside a chunk, for every chunk at once, in plain XLA: q, k,
    v, g (B, H, N, C, D), beta (B, H, N, C, 1) -> the scan's six operands, each
    (B, H, N, ...).  The path of every shape and platform the kernel of
    ``ops/pallas/kda.py`` is not written for, and that kernel's oracle."""
    b, h, n, chunk, dk = k.shape
    r = chunk // sub
    with jax.named_scope("chunk"):
        f32 = lambda x: x.astype(jnp.float32)
        gc = jnp.cumsum(g, axis=3)                            # G_t, inclusive
        # The cumulative decay at the MIDDLE of each sub-chunk: rows and
        # columns of a sub-chunk then lie within half its length of it.
        g0 = gc[:, :, :, sub // 2 - 1::sub]                   # (B, H, N, r, Dk)
        rows = jnp.exp(gc.reshape(b, h, n, r, sub, dk) - g0[:, :, :, :, None, :])
        cols = jnp.exp(jnp.minimum(
            g0[:, :, :, :, None, :] - gc[:, :, :, None, :, :], far
        ))                                                    # (B, H, N, r, C, Dk)
        k_cols = f32(k)[:, :, :, None] * cols
        split = lambda x: f32(x).reshape(b, h, n, r, sub, dk)
        inner = lambda x: jnp.einsum(
            "bhnrik,bhnrjk->bhnrij", split(x) * rows, k_cols, precision=HI
        ).reshape(b, h, n, chunk, chunk)
        a, p = inner(k), inner(q)
        pos = jnp.arange(chunk)
        a = jnp.where(pos[:, None] > pos[None, :], a * beta, 0.0)
        p = jnp.where(pos[:, None] >= pos[None, :], p, 0.0)
        tri = _unit_lower_inverse(a)
        decay = jnp.exp(gc)                                   # e^{G_t} <= 1
        w = jnp.matmul(tri, beta * f32(k) * decay, precision=HI)
        u0 = jnp.matmul(tri, beta * f32(v), precision=HI)
        g_end = gc[:, :, :, -1:, :]                           # G_C
        xs = (w, u0, f32(q) * decay, p, f32(k) * jnp.exp(g_end - gc))
        return tuple(x.astype(dtype) for x in xs) + (jnp.exp(g_end[:, :, :, 0, :]),)


def _takes_kernel(chunk: int, sub: int, dk: int, dv: int) -> bool:
    """The chunk-local part runs as the Pallas kernel pair where there is a TPU
    to run it and the shapes are the ones it is written for."""
    return jax.default_backend() == "tpu" and kda_kernel.supported(chunk, sub, dk, dv)


def kda_chunked(q, k, v, g, beta, chunk: int = CHUNK, sub: int = 16, dtype=jnp.bfloat16,
                lower_bound: float = -5.0):
    """Chunked form of :func:`kda_recurrent` (same arguments and result).
    ``g`` must lie in [``lower_bound``, 0] a position (the safe gate):
    ``-lower_bound * sub / 2`` is the largest exponent that counts (columns
    past it are above the diagonal, masked, and clamped one above it, so a
    value in range never sits ON the clamp, where ``minimum`` halves
    gradients).  q, k, v and what the scan is handed are kept in ``dtype``.
    The backward keeps the chunk-local part's inputs, the scan's operands and
    one state a chunk: the kernel pair recomputes a chunk in VMEM, the XLA
    form and the scan's body are each under ``jax.checkpoint``."""
    if chunk % sub or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} must be a power of two and a multiple of sub {sub}")
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    n = -(-t // chunk)
    pad = n * chunk - t
    far = -lower_bound * sub / 2 + 1.0

    def chunks(x, kind):  # (B, T, H, ...) -> (B, H, N, C, ...)
        x = jnp.pad(x.astype(kind), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    def mm(eq, x, y):
        with jax.named_scope("state"):
            return jnp.einsum(
                eq, x.astype(dtype), y.astype(dtype), preferred_element_type=jnp.float32
            )

    @jax.checkpoint
    def step(s, xs):
        w_c, u0_c, q_c, p_c, k_c, d_c = xs
        u = u0_c - mm("bhck,bhkv->bhcv", w_c, s)
        o = mm("bhck,bhkv->bhcv", q_c, s) + mm("bhcj,bhjv->bhcv", p_c, u)
        s = s * d_c[..., None] + mm("bhck,bhcv->bhkv", k_c, u)
        return s, o

    kernel = _takes_kernel(chunk, sub, dk, dv)
    with jax.named_scope("intra"):
        if kernel:      # chunk-major already
            xs = kda_kernel.kda_intra(q, k, v, g, beta, dtype, far)
        else:
            xs = jax.checkpoint(_intra, static_argnums=(5, 6, 7))(
                chunks(q, dtype), chunks(k, dtype), chunks(v, dtype), chunks(g, jnp.float32),
                chunks(beta, jnp.float32)[..., None], sub, far, dtype)
    with jax.named_scope("inter"):
        if not kernel:
            xs = tuple(jnp.moveaxis(x, 2, 0) for x in xs)
        _, o = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)[:, :, :t]
    return jnp.moveaxis(o, 1, 2)
