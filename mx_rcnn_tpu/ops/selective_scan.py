"""The Mamba-1 selective scan (a decay per channel AND state, input-dependent
``dt``, ``B`` and ``C``).  Three forms of one function:

Per channel ``c`` of the inner width and state ``n``, with ``A[c, n] < 0``,
``dt_t[c] > 0`` and the position's ``B_t``, ``C_t`` (N,):

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[c, n] C_t[n] + D[c] x_t[c],   h = 0 before the first position.

The decay differs per channel and state, so a chunk has no matmul form
(``ops/ssd.py``'s needs one scalar a head): the work is elementwise over
(T, channels, N).

- :func:`selective_scan_recurrent` - the recurrence token by token in plain
  XLA: the oracle of both others.
- the chunked XLA form (:func:`selective_scan_chunked` off the TPU and at every
  shape the kernel is not written for; the kernel's second oracle, the same
  arithmetic in another order of sums).  It cuts the positions into chunks of
  ``chunk``:

  - ``intra``: the recurrence from a ZERO state inside every chunk at once, one
    position of all chunks a step (``chunk`` steps over a (B, chunks, N,
    channels) state instead of T steps over a (B, N, channels) one: the same
    bytes, a thirty-third of the steps).  Its backward keeps the state every
    few steps and recomputes between (:func:`_scan_in_blocks`), never a
    chunk's history.
  - ``inter``: the chunks one after the other, carrying the (B, N, channels)
    state: a chunk's incoming state decayed to each of its positions gives the
    rest of ``y``, and decayed to the chunk's end, plus what the chunk added,
    is the next chunk's.  The step is under ``jax.checkpoint``: the backward
    keeps the states at the chunk boundaries alone and recomputes a chunk.

  Every state crosses HBM once a step of either scan; the backward is autodiff.
- the Pallas kernel pair (``ops/pallas/selective_scan.py``;
  :func:`selective_scan_chunked` on a TPU where ``supported`` says the shapes
  are the kernel's): the positions in order with the (N, channels) state in
  VMEM, no ``intra`` / ``inter`` split; a hand-written backward that walks the
  chunks in reverse from the states kept at their boundaries.

In all three every exponent is ``A`` times a sum of ``dt`` over positions of
one chunk at most, <= 0: no quotient of cumulative products (``dt A`` reaches
-8 a token).  Rows past ``T`` in the last chunk are neutral (``dt`` = 0: no
decay, no input).  Everything is float32 whatever the inputs' types; channels
ride the lanes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from mx_rcnn_tpu.ops.pallas import selective_scan as scan_kernel

HI = lax.Precision.HIGHEST
# Positions per chunk: a program choice beside ``ssd.py::CHUNK``, not an option
# (it moves speed and memory, never the result); tests pass others.
CHUNK = 128


def selective_scan_recurrent(x, dt, a, b, c, d):
    """The recurrence, one position at a time.  x, dt (B, T, C), a (C, N)
    negative, b, c (B, T, N), d (C,); float32.  -> y (B, T, C)."""
    bt, _, ch = x.shape

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        with jax.named_scope("token"):
            h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
            return h, jnp.einsum("bcn,bn->bc", h, c_t, precision=HI)

    xs = tuple(jnp.moveaxis(m.astype(jnp.float32), 1, 0) for m in (x, dt, b, c))
    _, y = lax.scan(step, jnp.zeros((bt, ch, a.shape[1]), jnp.float32), xs)
    return jnp.moveaxis(y, 0, 1) + d * x


def _scan_in_blocks(step, init, xs):
    """``lax.scan(step, init, xs)`` whose backward keeps the carry once a block
    of about sqrt(length) steps and recomputes a block from it."""
    length = xs[0].shape[0]
    block = next(i for i in range(math.isqrt(length), 0, -1) if length % i == 0)
    fold = lambda m: m.reshape((length // block, block) + m.shape[1:])

    @jax.checkpoint
    def steps(h, xs):
        return lax.scan(step, h, xs)

    h, y = lax.scan(steps, init, tuple(map(fold, xs)))
    return h, y.reshape((length,) + y.shape[2:])


def _takes_kernel(t: int, channels: int, n: int, chunk: int) -> bool:
    """The Pallas kernel pair runs where there is a TPU to run it and the
    shapes are the ones it is written for."""
    return jax.default_backend() == "tpu" and scan_kernel.supported(t, channels, n, chunk)


def selective_scan_chunked(x, dt, a, b, c, d, chunk: int = CHUNK):
    """Chunked form of :func:`selective_scan_recurrent`; y is float32.  The
    kernel pair where :func:`_takes_kernel` says so, else the XLA form
    (``chunk`` is that form's; the kernel's is its own, the same)."""
    bt, t, ch = x.shape
    n = a.shape[1]
    if _takes_kernel(t, ch, n, chunk):
        return scan_kernel.selective_scan(x, dt, a, b, c, d)
    nc = -(-t // chunk)
    f32 = jnp.float32
    a_t = a.astype(f32).T                                   # (N, C): channels on the lanes

    def fold(m):                                            # (B, T, W) -> (L, B, nc, W)
        m = jnp.pad(m.astype(f32), ((0, 0), (0, nc * chunk - t), (0, 0)))
        return jnp.moveaxis(m.reshape(bt, nc, chunk, m.shape[-1]), 2, 0)

    xc, dtc, bc, cc = fold(x), fold(dt), fold(b), fold(c)

    def token(h, xs):                                       # h (B, nc, N, C), one position of every chunk
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[..., None, :] * a_t) * h + b_t[..., :, None] * (dt_t * x_t)[..., None, :]
        return h, jnp.sum(c_t[..., :, None] * h, axis=-2)

    with jax.named_scope("intra"):
        added, y = _scan_in_blocks(token, jnp.zeros((bt, nc, n, ch), f32), (xc, dtc, bc, cc))
    with jax.named_scope("inter"):
        cum = jnp.cumsum(dtc, axis=0)                       # sum of dt since the chunk began, (L, B, nc, C)

        @jax.checkpoint
        def carry(s, xs):                                   # s (B, N, C): the state coming IN
            cum_c, c_c, added_c = xs                        # (L, B, C), (L, B, N), (B, N, C)
            since = jnp.exp(cum_c[..., None, :] * a_t)      # (L, B, N, C), every exponent <= 0
            y_in = jnp.sum(c_c[..., :, None] * since * s, axis=-2)
            return since[-1] * s + added_c, y_in

        per_chunk = (jnp.moveaxis(cum, 2, 0), jnp.moveaxis(cc, 2, 0), jnp.moveaxis(added, 1, 0))
        _, y_in = lax.scan(carry, jnp.zeros((bt, n, ch), f32), per_chunk)   # (nc, L, B, C)
        y = y + jnp.moveaxis(y_in, 0, 2)
    y = jnp.moveaxis(y, 0, 2).reshape(bt, nc * chunk, ch)[:, :t]
    return y + d.astype(f32) * x.astype(f32)
