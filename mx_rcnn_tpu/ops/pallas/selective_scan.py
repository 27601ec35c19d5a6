"""The Mamba-1 selective scan (``ops/selective_scan.py``) with its state in VMEM.

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]

The decay differs per channel and state, so the work is elementwise; what the
XLA form pays for is the state's trips through HBM, a step at a time.  Here a
grid step is one chunk of ``CHUNK`` positions of ``BLOCK`` channels of one
sequence: the (N, BLOCK) float32 state is N vregs that a loop over the chunk's
positions carries, and between chunks it waits in VMEM scratch (the grid's
chunk axis is sequential).  A position's ``BLOCK`` channels are one whole
(8, 128) vreg, N is the LEADING axis of the state and ``sum_n`` is plain
adds; ``B_t[n]`` and ``C_t[n]`` are scalars read from SMEM.  x, ``dt``, ``y``
and their cotangents stay (B, T, C) as XLA lays them out: a block is (CHUNK,
BLOCK), whose rows lie eight to a tile, and one load with a sublane stride
takes a position's row out of its eight tiles as that vreg (:func:`_row`; a
(B, T, C / 128, 128) view would be a copy of each array in XLA, as long as
the forward kernel itself).  Everything is float32 (x may arrive bfloat16 and is
widened here), every exponent is <= 0, rows past T in the last chunk are
neutral (``dt`` = 0, x = 0: no decay, no input).

- ``selective_scan_fwd``: positions in order.  x and ``dt`` are read once,
  ``y`` written once, and the state coming INTO each chunk is written out,
  (B, chunks, N, C) float32: the backward's only residual besides the inputs.
- ``selective_scan_bwd``: chunks in reverse.  A chunk's states are recomputed
  from its incoming state into VMEM (``CHUNK + 1`` states of a block: 8 MB at
  N = 16), then the positions are walked backwards with
  ``g_t = a_{t+1} g_{t+1} + C_t dy_t`` carried as the state is forward:

      dC_t[n] = sum_c dy_t h_t          dB_t[n] = sum_c g_t dt_t x_t
      dx_t    = dt_t sum_n g_t B_t + D dy_t
      ddt_t   = sum_n g_t a_t h_{t-1} A + x_t sum_n g_t B_t
      dA      = sum_{b,t} g_t a_t h_{t-1} dt_t,       dD = sum_{b,t} dy_t x_t.

  ``dB`` and ``dC`` sum over channels: the kernel sums the 8 sublanes of each
  product (eight (8, 128) products folded into one by three butterfly stages
  of selects and sublane rolls), adds the channel blocks up in a resident
  output block and leaves the 128 lanes to XLA: (B, T, N, 128) float32 each,
  69 MB a layer at the decoder cell's shape.  Inside the loop over positions
  no array is both read and written: a load does not pass a store to the
  same array, so a sum kept in VMEM and added to a position at a time chains
  every position's loads behind the last one's stores (314 bundles a pair of
  positions against 212).  The loop writes its terms of ``dA`` and the folded
  products to scratch, and they are added up once a grid step.

The exponential is the chip's ``2^x``: the kernels are handed ``A log2 e``.

The grid is (sequence, chunk, block of channels), the last two sequential: a
chunk's ``B`` and ``C`` stay in SMEM and its block of the ``dB``, ``dC`` sums
in VMEM while the blocks of channels go by.

:func:`selective_scan` is the pair under one ``jax.custom_vjp``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128     # positions a grid step: the distance between two kept states
LANES = 128
SUBLANES = 8
BLOCK = SUBLANES * LANES    # channels a grid step: a state of N vregs
UNROLL = 4      # positions a trip of the loops over a chunk
# What the backward holds of one chunk in VMEM: its states and its terms of dA
# (``_HISTORY_BYTES`` each at most: N <= 16), the blocks of x, dt, dy, dx, ddt
# and of the dB, dC sums twice each and float32 copies of the first four.
_HISTORY_BYTES = 12 << 20
_VMEM_BYTES = 48 << 20

_F32 = jnp.float32
_LN2 = math.log(2.0)


def supported(t: int, channels: int, n: int, chunk: int) -> bool:
    """The shapes the kernels are written for: whole blocks of channels, states
    in whole groups of eight (the backward folds eight products into a vreg),
    a chunk's recomputed states within VMEM.  Any length."""
    return (chunk == CHUNK and t >= 1 and channels % BLOCK == 0 and n % SUBLANES == 0
            and (CHUNK + 1) * n * BLOCK * 4 <= _HISTORY_BYTES)


def _inside(length, chunk):
    """Which rows of chunk ``chunk`` lie inside the sequence, or None where
    every chunk is whole.  The rows of the last chunk past T are in no array:
    whatever a block holds there must not be read as a number."""
    if length % CHUNK == 0:
        return None
    left = length - chunk * CHUNK
    return lax.broadcasted_iota(jnp.int32, (CHUNK, BLOCK), 0) < left


def _widen(ref, inside):
    x = ref[0].astype(_F32)
    return x if inside is None else jnp.where(inside, x, 0.0)


def _row(ref, t):
    """Position ``t`` of a (CHUNK, BLOCK) array as one (8, 128) vreg: a load
    that takes a sublane from each of the row's eight tiles."""
    return ref[pl.ds(t, 1), :].reshape(SUBLANES, LANES)


def _set_row(ref, t, value):
    ref[pl.ds(t, 1), :] = value.reshape(1, BLOCK)


def _over_chunk(step, carry):
    """``step(s, carry)`` for s = 0 .. CHUNK - 1, ``UNROLL`` of them a trip."""
    def trip(s, carry):
        for r in range(UNROLL):
            carry = step(s * UNROLL + r, carry)
        return carry

    return lax.fori_loop(0, CHUNK // UNROLL, trip, carry)


def _next_state(h_i, i, t, dt_t, u, a_ref, b_ref, n):
    """``h_t[i]`` from ``h_{t-1}[i]``; u = dt_t x_t."""
    return jnp.exp2(dt_t * a_ref[0, i]) * h_i + u * b_ref[0, 0, 0, t * n + i]


def _fwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, kept_ref, h_ref, xf_ref,
                dtf_ref, *, length, n):
    k, j = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        h_ref[j] = jnp.zeros((n, SUBLANES, LANES), _F32)

    kept_ref[0, 0, 0] = h_ref[j]
    inside = _inside(length, k)
    xf_ref[...] = _widen(x_ref, inside)
    dtf_ref[...] = _widen(dt_ref, inside)
    d = d_ref[0]

    def token(t, h):
        x_t, dt_t = _row(xf_ref, t), _row(dtf_ref, t)
        u = dt_t * x_t
        y = d * x_t
        out = []
        for i in range(n):
            h_i = _next_state(h[i], i, t, dt_t, u, a_ref, b_ref, n)
            y = y + h_i * c_ref[0, 0, 0, t * n + i]
            out.append(h_i)
        _set_row(y_ref.at[0], t, y)
        return tuple(out)

    h = _over_chunk(token, tuple(h_ref[j, i] for i in range(n)))
    for i in range(n):
        h_ref[j, i] = h[i]


def _sublane_sums(q):
    """Eight (8, 128) arrays -> one whose row i is the sum over the sublanes of
    ``q[i]``: three stages, each folding pairs of arrays into one that keeps
    half of each one's sublanes."""
    sub = lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    for shift in (4, 2, 1):
        low, half = (sub & shift) == 0, len(q) // 2
        q = [jnp.where(low, q[i] + pltpu.roll(q[i], SUBLANES - shift, 0),
                       q[i + half] + pltpu.roll(q[i + half], shift, 0)) for i in range(half)]
    return q[0]


def _bwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, dy_ref, kept_ref,
                dx_ref, ddt_ref, da_ref, dd_ref, dbp_ref, dcp_ref,
                g_ref, hist_ref, to_a_ref, to_b_ref, to_c_ref, xf_ref, dtf_ref, dyf_ref, dxf_ref,
                *, length, n):
    k, j = pl.program_id(1), pl.program_id(2)       # k counts the chunks from the LAST

    @pl.when(k == 0)
    def _():
        g_ref[j] = jnp.zeros((n, SUBLANES, LANES), _F32)
        da_ref[0, j] = jnp.zeros((n, SUBLANES, LANES), _F32)
        dd_ref[0, j] = jnp.zeros((SUBLANES, LANES), _F32)

    @pl.when(j == 0)
    def _():
        dbp_ref[...] = jnp.zeros(dbp_ref.shape, _F32)
        dcp_ref[...] = jnp.zeros(dcp_ref.shape, _F32)

    inside = _inside(length, pl.num_programs(1) - 1 - k)
    xf_ref[...] = _widen(x_ref, inside)
    dtf_ref[...] = _widen(dt_ref, inside)
    dyf_ref[...] = _widen(dy_ref, inside)
    d = d_ref[0]
    dd_ref[0, j] += jnp.sum((dyf_ref[...] * xf_ref[...]).reshape(CHUNK, SUBLANES, LANES), axis=0)

    # The chunk's states again: hist[t + 1] = h_t, hist[0] the state coming in.
    hist_ref[0] = kept_ref[0, 0, 0]

    def token(t, h):
        dt_t = _row(dtf_ref, t)
        u = dt_t * _row(xf_ref, t)
        out = []
        for i in range(n):
            h_i = _next_state(h[i], i, t, dt_t, u, a_ref, b_ref, n)
            hist_ref[t + 1, i] = h_i
            out.append(h_i)
        return tuple(out)

    _over_chunk(token, tuple(hist_ref[0, i] for i in range(n)))

    def back(s, g):         # g[i] = a_{t+1} g_{t+1}: what the later positions hand back
        t = CHUNK - 1 - s
        x_t, dt_t, dy_t = _row(xf_ref, t), _row(dtf_ref, t), _row(dyf_ref, t)
        u = dt_t * x_t
        through_b = through_a = jnp.zeros((SUBLANES, LANES), _F32)
        out = []
        for lo in range(0, n, SUBLANES):        # eight states, then their sums over channels
            to_b, to_c = [], []
            for i in range(lo, lo + SUBLANES):
                a_i = a_ref[0, i]
                g_i = g[i] + dy_t * c_ref[0, 0, 0, t * n + i]
                to_c.append(dy_t * hist_ref[t + 1, i])
                to_b.append(g_i * u)
                through_b = through_b + g_i * b_ref[0, 0, 0, t * n + i]
                g_i = jnp.exp2(dt_t * a_i) * g_i
                w = g_i * hist_ref[t, i]
                through_a = through_a + w * a_i
                to_a_ref[t, i] = w * dt_t
                out.append(g_i)
            to_b_ref[t, lo:lo + SUBLANES] = _sublane_sums(to_b)
            to_c_ref[t, lo:lo + SUBLANES] = _sublane_sums(to_c)
        _set_row(dxf_ref, t, dt_t * through_b + d * dy_t)
        _set_row(ddt_ref.at[0], t, through_a * _LN2 + x_t * through_b)
        return tuple(out)

    g = _over_chunk(back, tuple(g_ref[j, i] for i in range(n)))
    for i in range(n):
        g_ref[j, i] = g[i]
    dx_ref[0] = dxf_ref[...].astype(dx_ref.dtype)
    # What the loop only wrote (a load does not pass a store to the same
    # array, and an array read and written a position would chain them all).
    da_ref[0, j] += jnp.sum(to_a_ref[...], axis=0)
    dbp_ref[0] += to_b_ref[...]
    dcp_ref[0] += to_c_ref[...]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _call(backward, interpret, x, dt, a, d, b, c, *residuals):
    """One of the two kernels.  x, dt (B, T, C), a (C / BLOCK, N, 8,
    128), d (C / BLOCK, 8, 128), b, c (B, chunks, 1, CHUNK * N) with zeros past
    T.  Under ``jax.jit`` so that a model's layers share one trace and one
    lowering of each kernel."""
    bt, t = x.shape[:2]
    blocks, n = a.shape[:2]
    chunks = b.shape[1]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    # the backward takes the chunks from the last to the first
    at = (lambda k: chunks - 1 - k) if backward else (lambda k: k)
    rows = vmem((1, CHUNK, BLOCK), lambda i, k, j: (i, at(k), j))
    a_spec = vmem((1, n, SUBLANES, LANES), lambda i, k, j: (j, 0, 0, 0))
    d_spec = vmem((1, SUBLANES, LANES), lambda i, k, j: (j, 0, 0))
    scalars = pl.BlockSpec((1, 1, 1, CHUNK * n), lambda i, k, j: (i, at(k), 0, 0),
                           memory_space=pltpu.SMEM)
    kept = vmem((1, 1, 1, n, SUBLANES, LANES), lambda i, k, j: (i, at(k), j, 0, 0, 0))
    state = pltpu.VMEM((blocks, n, SUBLANES, LANES), _F32)
    chunk = pltpu.VMEM((CHUNK, BLOCK), _F32)
    if backward:
        kernel, name = _bwd_kernel, "selective_scan_bwd"
        whole = lambda *shape: vmem((1,) + shape, lambda i, k, j: (i,) + (0,) * len(shape))
        partial_sums = vmem((1, CHUNK, n, LANES), lambda i, k, j: (i, at(k), 0, 0))
        in_specs = [rows, rows, a_spec, d_spec, scalars, scalars, rows, kept]
        out_specs = [rows, rows, whole(blocks, n, SUBLANES, LANES), whole(blocks, SUBLANES, LANES),
                     partial_sums, partial_sums]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype), f32(*dt.shape),
                     f32(bt, blocks, n, SUBLANES, LANES), f32(bt, blocks, SUBLANES, LANES),
                     f32(bt, chunks * CHUNK, n, LANES), f32(bt, chunks * CHUNK, n, LANES)]
        sums = pltpu.VMEM((CHUNK, n, LANES), _F32)
        scratch = [state, pltpu.VMEM((CHUNK + 1, n, SUBLANES, LANES), _F32),
                   pltpu.VMEM((CHUNK, n, SUBLANES, LANES), _F32), sums, sums] + [chunk] * 4
    else:
        kernel, name = _fwd_kernel, "selective_scan_fwd"
        in_specs = [rows, rows, a_spec, d_spec, scalars, scalars]
        out_specs = [rows, kept]
        out_shape = [f32(*dt.shape), f32(bt, chunks, blocks, n, SUBLANES, LANES)]
        scratch = [state] + [chunk] * 2
    return pl.pallas_call(
        functools.partial(kernel, length=t, n=n),
        grid=(bt, chunks, blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        # The names the kernels run under in a device trace.
        name=name,
    )(x, dt, a, d, b, c, *residuals)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, d, b, c, interpret):
    return _call(False, interpret, x, dt, a, d, b, c)[0]


def _scan_fwd(x, dt, a, d, b, c, interpret):
    y, kept = _call(False, interpret, x, dt, a, d, b, c)
    return y, (x, dt, a, d, b, c, kept)


def _scan_bwd(interpret, residuals, dy):
    x, dt, a, d, b, c, kept = residuals
    dx, ddt, da, dd, dbp, dcp = _call(True, interpret, x, dt, a, d, b, c, dy, kept)
    lanes = lambda p: jnp.sum(p, axis=-1).reshape(b.shape)      # the sum over channels' last step
    # (the kernel sums dA; its argument, and so this cotangent, is A log2 e)
    return dx, ddt, jnp.sum(da, axis=0) * _LN2, jnp.sum(dd, axis=0), lanes(dbp), lanes(dcp)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _operands(x, dt, a, b, c, d):
    """The arguments of :func:`selective_scan` in the kernels' layouts."""
    bt, t, ch = x.shape
    n = a.shape[1]
    chunks, blocks = -(-t // CHUNK), ch // BLOCK
    # (B, T, N) -> (B, chunks, 1, CHUNK * N), zeros past T: scalars in SMEM
    scalars = lambda m: jnp.pad(m.astype(_F32), ((0, 0), (0, chunks * CHUNK - t), (0, 0))).reshape(
        bt, chunks, 1, CHUNK * n)
    # exp(dt A) = 2^(dt A log2 e): the kernels take A log2 e
    a = jnp.moveaxis((a.astype(_F32) / _LN2).T.reshape(n, blocks, SUBLANES, LANES), 1, 0)
    return (x, dt.astype(_F32), a, d.astype(_F32).reshape(blocks, SUBLANES, LANES),
            scalars(b), scalars(c))


def selective_scan(x, dt, a, b, c, d):
    """``ops/selective_scan.py::selective_scan_recurrent``'s arguments and
    result (x, dt (B, T, C), a (C, N) negative, b, c (B, T, N), d (C,) ->
    float32 y (B, T, C)) through the kernel pair; x keeps its type into the
    kernel, the rest is float32.  Only the layouts change here, so autodiff
    carries the kernels' cotangents back to the arguments'.  Off the TPU the
    same kernels run interpreted."""
    return _scan(*_operands(x, dt, a, b, c, d), jax.default_backend() != "tpu")
