"""The Mamba-2 state-space recurrence (SSD: a scalar decay a head, B and C
shared by a group of heads) as a chunked scan whose work is matmuls, in plain
XLA.

Per head, with state ``S`` (P, N), ``a_t = dt_t A`` (``A < 0`` one scalar a
head, ``dt_t > 0``), and the head's group's ``B_t``, ``C_t`` (N,):

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t,    S_0 = 0 at each sequence's first position.

:func:`ssd_recurrent` is that recurrence token by token (the oracle).
:func:`ssd_chunked` cuts the positions into chunks of ``chunk`` (128, the
published chunk): with ``G_t`` the sum of ``a`` from the chunk's start up to
and including ``t``,

    y_t = sum_{s <= t} exp(G_t - G_s) (C_t . B_s) dt_s x_s      inside the chunk
        + exp(G_t) S_in C_t                                     from the chunks before
    S_out = exp(G_L) S_in + sum_s exp(G_L - G_s) dt_s x_s B_s^T

so a chunk is three matmuls over all chunks at once (``intra``), and what
runs one chunk after the other (``inter``) is the state's decay-and-add alone,
then one matmul hands every chunk its incoming state's part.  The decay matrix
is ``exp`` of a masked DIFFERENCE of cumulative sums, every exponent <= 0: a
quotient of cumulative products would put ``exp(-G_s)`` up to e^200 beside
cotangents under float32's range.  Rows past ``T`` in the last chunk are
neutral (``dt`` = 0: no decay, no input).  ``dt``, the decays, the cumulative
sums and the state are float32; the matmuls take ``dtype`` operands and
accumulate in float32.

Two paths compute :func:`ssd_chunked`, with the same arithmetic:

- the XLA form below (off the TPU and at every shape the kernel is not
  written for; the kernel's second oracle): all chunks at once, the decay
  matrices and the state through HBM.  Its backward is autodiff.
- the Pallas kernel pair (``ops/pallas/ssd.py``, ``ssd_fwd`` / ``ssd_bwd``;
  :func:`ssd_chunked` on a TPU where ``supported`` says the shapes are the
  kernel's): a chunk a grid step with the decay matrices, ``C B^T`` and the
  state in VMEM.  Its backward is written by hand under a ``jax.custom_vjp``
  and walks the chunks in reverse from the states kept at their boundaries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from mx_rcnn_tpu.ops.pallas import ssd as ssd_kernel

HI = lax.Precision.HIGHEST
# Positions per chunk: the published ``chunk_size``; a program choice, not an
# option (it moves speed and memory, never the result); tests pass others.
CHUNK = 128


def ssd_recurrent(x, dt, a, b, c, d):
    """The recurrence, one position at a time.  x (B, T, H, P), dt (B, T, H),
    a (H,) negative, b, c (B, T, G, N), d (H,); float32.  -> y (B, T, H, P)."""
    bt, _, h, p = x.shape
    g, n = b.shape[2:]
    heads = lambda m: jnp.repeat(m, h // g, axis=1)      # (B, G, N) -> (B, H, N)

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        with jax.named_scope("token"):
            s = jnp.exp(dt_t * a)[..., None, None] * s \
                + (dt_t[..., None] * x_t)[..., None] * heads(b_t)[:, :, None, :]
            return s, jnp.einsum("bhpn,bhn->bhp", s, heads(c_t), precision=HI)

    xs = tuple(jnp.moveaxis(m.astype(jnp.float32), 1, 0) for m in (x, dt, b, c))
    _, y = lax.scan(step, jnp.zeros((bt, h, p, n), jnp.float32), xs)
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def _takes_kernel(t: int, heads: int, head_dim: int, groups: int, state: int, chunk: int) -> bool:
    """The Pallas kernel pair runs where there is a TPU to run it and the
    shapes are the ones it is written for."""
    return jax.default_backend() == "tpu" and ssd_kernel.supported(
        t, heads, head_dim, groups, state, chunk)


def ssd_chunked(x, dt, a, b, c, d, chunk: int = CHUNK, dtype=jnp.bfloat16):
    """Chunked form of :func:`ssd_recurrent`; y is float32.  The kernel pair
    where :func:`_takes_kernel` says so, else the XLA form (``chunk`` is that
    form's; the kernel's is its own, the same)."""
    bt, t, h, p = x.shape
    g, n = b.shape[2:]
    if _takes_kernel(t, h, p, g, n, chunk):
        return ssd_kernel.ssd(x, dt, a, b, c, d, dtype=dtype)
    r = h // g
    nc = -(-t // chunk)
    f32 = jnp.float32

    def fold(m):                                # (B, T, ...) -> (B, nc, L, ...)
        m = jnp.pad(m, ((0, 0), (0, nc * chunk - t)) + ((0, 0),) * (m.ndim - 2))
        return m.reshape((bt, nc, chunk) + m.shape[2:])

    xc = fold(x.astype(dtype)).reshape(bt, nc, chunk, g, r, p)
    bc, cc = fold(b.astype(dtype)), fold(c.astype(dtype))            # (B, nc, L, G, N)
    dtc = jnp.moveaxis(fold(dt.astype(f32)), 2, 3)                   # (B, nc, H, L)
    with jax.named_scope("intra"):
        cum = jnp.cumsum(dtc * a.astype(f32)[:, None], axis=-1)      # G_t, <= 0
        causal = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
        decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc, preferred_element_type=f32)
        m = cb[:, :, :, None] * (decay * dtc[..., None, :]).reshape(bt, nc, g, r, chunk, chunk)
        y = jnp.einsum("bcgrls,bcsgrp->bclgrp", m.astype(dtype), xc, preferred_element_type=f32)
        # What the chunk adds to the state, decayed to the chunk's end.
        to_end = (jnp.exp(cum[..., -1:] - cum) * dtc).reshape(bt, nc, g, r, chunk)
        xw = (xc.astype(f32) * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype)
        added = jnp.einsum("bclgrp,bclgn->bcgrpn", xw, bc, preferred_element_type=f32)
    with jax.named_scope("inter"):
        whole = jnp.exp(cum[..., -1]).reshape(bt, nc, g, r)          # a chunk's whole decay

        def carry(s, xs):
            decay_c, added_c = xs
            return decay_c[..., None, None] * s + added_c, s         # emits the state coming IN

        _, s_in = lax.scan(
            carry, jnp.zeros((bt, g, r, p, n), f32),
            (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)),
        )
        s_in = jnp.moveaxis(s_in, 0, 1).astype(dtype)                # (B, nc, G, R, P, N)
        y_in = jnp.einsum("bclgn,bcgrpn->bclgrp", cc, s_in, preferred_element_type=f32)
        since = jnp.exp(cum).reshape(bt, nc, g, r, chunk)            # decay since the chunk began
        y = y + y_in * jnp.moveaxis(since, -1, 2)[..., None]
    y = y.reshape(bt, nc * chunk, h, p)[:, :t]
    return y + d.astype(f32)[:, None] * x.astype(f32)
