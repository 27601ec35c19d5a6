"""The Mamba-2 chunked scan (``ops/ssd.py``) with its decay matrices and state in VMEM.

Per head, with ``a_t = dt_t A`` and ``G_t`` the sum of ``a`` from the chunk's
start up to and including ``t`` (every exponent below is <= 0):

    y_t   = sum_{s <= t} exp(G_t - G_s) dt_s (C_t . B_s) x_s + exp(G_t) S_in C_t + D x_t
    S_out = exp(G_L) S_in + sum_s exp(G_L - G_s) dt_s x_s B_s^T

The XLA form builds the decay matrix and the masked product for every chunk
and head at once, float32 through HBM (277 MB each a layer at the decoder
cell's shape), and carries the state through HBM a chunk at a time.  Here a
grid step is one chunk of ``CHUNK`` positions of one sequence, all heads: the
chunk axis is sequential and the (P, N) float32 state of every head waits in
VMEM scratch between chunks.  Inside a step a loop walks the groups (B and C
are one group's); ``C B^T`` is formed once a group, and the heads go two at a
time, a pair of 64-wide heads being one vreg's 128 lanes of x and y.  Per head
the (CHUNK, CHUNK) decay matrix is ``exp`` of the masked difference of the
cumulative sums, times ``dt_s``, times ``C B^T``; cast to ``dtype`` it is the
operand of ``y_intra``'s matmul.  The state's part is ``C S_in^T`` scaled by
``exp(G_t)``, and the state is decayed and added to with one matmul a pair.

- ``ssd_fwd`` writes ``y`` (float32) and the state coming INTO each chunk,
  (B, chunks, H P, N) float32: the backward's only residual besides the inputs.
- ``ssd_bwd`` walks the chunks in reverse carrying ``dS`` in VMEM, recomputes
  the cumulative sums, decays and ``C B^T`` from the inputs, and returns dx,
  dB, dC, ddt and a chunk's terms of dA and dD (summed in XLA).  A group's dB
  and dC are whole inside the step.  ``d G`` is the row sums less the column
  sums of ``dM * M`` (one float32 array: where tokens look alike the two
  cancel as they do under autodiff of the XLA form, PERF.md 7 w), plus the
  state's and ``y_in``'s terms, reverse-summed inside the chunk.

The arithmetic is the XLA form's: matmul operands in ``dtype`` (float32 ones
at ``highest``), float32 accumulation; ``dt``, the cumulative sums, the decays
and the state float32; ``D x`` added in float32 from x as it came.  The
cumulative sums, their transposes and the reverse sums are float32 matmuls
against 0/1 matrices at ``highest``.  x, B, C, ``dt``, ``y`` and the
cotangents stay in the layouts XLA hands over: (B, T, H P), (B, T, G N),
(B, T, H).  Rows past T in the last chunk are read as neutral (``dt`` = 0,
x = B = C = dy = 0), so no ``pad`` or ``moveaxis`` copy is left in XLA.

:func:`ssd` is the pair under one ``jax.custom_vjp``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128     # positions a grid step: the published chunk
LANES = 128
HEAD_DIM = LANES // 2   # two heads a vreg's lanes
SUBLANES = 8
_VMEM_BYTES = 64 << 20

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _vmem_bytes(heads: int, groups: int) -> int:
    """The backward's blocks, double-buffered (x and dx at most float32, dy,
    the kept state, B, C, dB, dC), and the carried cotangent of the state."""
    hp, gn = heads * HEAD_DIM, groups * LANES
    return 2 * (CHUNK * hp * 4 * 3 + hp * LANES * 4 + CHUNK * gn * 4 * 4) + hp * LANES * 4


def supported(t: int, heads: int, head_dim: int, groups: int, state: int, chunk: int) -> bool:
    """The shapes the kernels are written for: heads of 64 in whole groups of
    eight rows (a group's cumulative sums are one (8, CHUNK) block), states of
    128, the module's own chunk, the blocks within VMEM.  Any length."""
    return (chunk == CHUNK and t >= 1 and head_dim == HEAD_DIM and state == LANES
            and groups >= 1 and heads % groups == 0 and (heads // groups) % SUBLANES == 0
            and _vmem_bytes(heads, groups) <= _VMEM_BYTES)


def _dot(x, y, dims=_NN):
    """Float32 out; float32 operands (the tests', the sums) at ``highest``."""
    precision = _HI if x.dtype == _F32 else None
    with jax.named_scope("chunk"):
        return lax.dot_general(x, y, dims, precision=precision, preferred_element_type=_F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _inside(length, chunk):
    """Which rows of chunk ``chunk`` lie inside the sequence (CHUNK, 1), or None
    where every chunk is whole.  The rows of the last chunk past T are in no
    array: whatever a block holds there must not be read as a number."""
    if length % CHUNK == 0:
        return None
    return _iota((CHUNK, 1), 0) < length - chunk * CHUNK


def _masked(x, inside):
    return x if inside is None else jnp.where(inside, x, jnp.zeros((), x.dtype))


def _block(j):
    """The ``j``-th 128 lanes: a group's B or C, a pair of heads' x or y."""
    return pl.ds(pl.multiple_of(j * LANES, LANES), LANES)


def _sums(dt, a):
    """The chunk's cumulative sums ``G`` (CHUNK, H) of ``dt A``, inclusive."""
    tri = jnp.where(_iota((CHUNK, CHUNK), 0) >= _iota((CHUNK, CHUNK), 1), 1.0, 0.0)
    return _dot(tri, dt * a)


class _Head:
    """What one head needs of the chunk's sums: ``G_t`` down the sublanes
    (CHUNK, 1), ``G_s`` and ``dt_s`` along the lanes (1, CHUNK), ``dt_t``, and
    ``G_L`` as a column."""

    def __init__(self, g_cols, dt_cols, rows_ref, h, r, heads):
        self.g_col = g_cols[:, r:r + 1]
        self.dt_col = dt_cols[:, r:r + 1]
        self.g_row = rows_ref[pl.ds(h, 1), :]
        self.dt_row = rows_ref[pl.ds(heads + h, 1), :]
        self.g_end = jnp.broadcast_to(g_cols[CHUNK - 1:CHUNK], g_cols.shape)[:, r:r + 1]

    def decay(self):
        """exp(G_t - G_s), masked causal: (CHUNK, CHUNK), every exponent <= 0."""
        causal = _iota((CHUNK, CHUNK), 0) >= _iota((CHUNK, CHUNK), 1)
        return jnp.exp(jnp.where(causal, self.g_col - self.g_row, -jnp.inf))


def _halves(first, second, shape, axis=1):
    """Two heads' columns (or rows) side by side: the pair's layout."""
    return jnp.where(_iota(shape, axis) < HEAD_DIM, first, second)


def _group_sums(rows_ref, g, per_group, heads):
    """A group's ``G`` and ``dt`` as columns (CHUNK, heads of the group)."""
    at = pl.multiple_of(g * per_group, SUBLANES)
    return rows_ref[pl.ds(at, per_group), :].T, rows_ref[pl.ds(heads + at, per_group), :].T


def _prologue(dt_ref, a_ref, rows_ref, inside):
    """``dt`` (masked) of the chunk; ``G`` and ``dt`` kept as rows (2H, CHUNK)
    for the heads to read: G on the first H, dt on the next."""
    dt = _masked(dt_ref[0], inside)
    heads = dt.shape[1]
    rows_ref[0:heads, :] = _sums(dt, a_ref[...]).T
    rows_ref[heads:2 * heads, :] = dt.T
    return dt


def _fwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, kept_ref, s_ref, rows_ref,
                *, length, groups, dtype):
    k = pl.program_id(1)
    heads = dt_ref.shape[2]
    per_group = heads // groups

    @pl.when(k == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, _F32)

    kept_ref[0, 0] = s_ref[...]
    inside = _inside(length, k)
    _prologue(dt_ref, a_ref, rows_ref, inside)

    def group(g, carry):
        lanes = _block(g)
        bg = _masked(b_ref[0, :, lanes], inside).astype(dtype)
        cg = _masked(c_ref[0, :, lanes], inside).astype(dtype)
        cb = _dot(cg, bg, _NT)                                        # C_t . B_s
        g_cols, dt_cols = _group_sums(rows_ref, g, per_group, heads)
        for j in range(per_group // 2):
            h = g * per_group + 2 * j
            pair = _block(g * (per_group // 2) + j)
            hd = [_Head(g_cols, dt_cols, rows_ref, h + i, 2 * j + i, heads) for i in (0, 1)]
            x = _masked(x_ref[0, :, pair], inside)
            xb = x.astype(dtype)
            y = [_dot((cb * (hd[i].decay() * hd[i].dt_row)).astype(dtype), xb) for i in (0, 1)]
            y = _halves(y[0], y[1], y[0].shape)
            state = s_ref[pair, :]
            y_in = _dot(cg, state.astype(dtype), _NT)
            since = _halves(jnp.exp(hd[0].g_col), jnp.exp(hd[1].g_col), y_in.shape)
            d = _halves(d_ref[h], d_ref[h + 1], (1, LANES))
            y_ref[0, :, pair] = y + y_in * since + d * x.astype(_F32)
            # what the chunk adds to the state, decayed to the chunk's end
            to_end = [jnp.exp(hd[i].g_end - hd[i].g_col) * hd[i].dt_col for i in (0, 1)]
            xw = (xb.astype(_F32) * _halves(to_end[0], to_end[1], (CHUNK, LANES))).astype(dtype)
            whole = _halves(jnp.exp(hd[0].g_end), jnp.exp(hd[1].g_end), (LANES, 1), axis=0)
            s_ref[pair, :] = whole * state + _dot(xw, bg, _TN)
        return carry

    lax.fori_loop(0, groups, group, 0)


def _half_sums(x, i):
    """Sums over the lanes of head ``i`` of a pair: (CHUNK, 1)."""
    lane = _iota(x.shape, 1)
    return jnp.sum(jnp.where((lane < HEAD_DIM) == (i == 0), x, 0.0), axis=1, keepdims=True)


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, dy_ref, kept_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                ds_ref, rows_ref, back_ref, cols_ref, *, length, groups, dtype):
    k = pl.program_id(1)                        # counts the chunks from the LAST
    heads = dt_ref.shape[2]
    per_group = heads // groups

    @pl.when(k == 0)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, _F32)

    inside = _inside(length, pl.num_programs(1) - 1 - k)
    dt = _prologue(dt_ref, a_ref, rows_ref, inside)
    cols_ref[...] = jnp.zeros(cols_ref.shape, _F32)
    sub = _iota((LANES, 1), 0)
    head_lane = _iota((CHUNK, heads), 1)
    last = _iota((CHUNK, 1), 0) == CHUNK - 1

    def group(g, carry):
        lanes = _block(g)
        bg = _masked(b_ref[0, :, lanes], inside).astype(dtype)
        cg = _masked(c_ref[0, :, lanes], inside).astype(dtype)
        cb = _dot(cg, bg, _NT)
        g_cols, dt_cols = _group_sums(rows_ref, g, per_group, heads)
        dcb = jnp.zeros((CHUNK, CHUNK), _F32)
        dcg = jnp.zeros((CHUNK, LANES), _F32)
        dbg = jnp.zeros((CHUNK, LANES), _F32)
        d_g = jnp.zeros((CHUNK, heads), _F32)        # d G_t, the heads' columns' part
        d_dt = jnp.zeros((CHUNK, heads), _F32)
        for j in range(per_group // 2):
            h = g * per_group + 2 * j
            pair = _block(g * (per_group // 2) + j)
            hd = [_Head(g_cols, dt_cols, rows_ref, h + i, 2 * j + i, heads) for i in (0, 1)]
            x = _masked(x_ref[0, :, pair], inside)
            xb = x.astype(dtype)
            xr = xb.astype(_F32)
            dy = _masked(dy_ref[0, :, pair], inside)
            dyb = dy.astype(dtype)
            state = kept_ref[0, 0, pair, :]
            sb = state.astype(dtype)
            ds_out = ds_ref[pair, :]
            dsb = ds_out.astype(dtype)
            d = _halves(d_ref[h], d_ref[h + 1], (1, LANES))
            dd_ref[0, 0, :, pair] = jnp.sum(dy * x.astype(_F32), axis=0, keepdims=True)
            # the state's way out: S_out = exp(G_L) S_in + (x w)^T B
            to_end = [jnp.exp(hd[i].g_end - hd[i].g_col) for i in (0, 1)]
            w = [to_end[i] * hd[i].dt_col for i in (0, 1)]
            w2 = _halves(w[0], w[1], (CHUNK, LANES))
            v = _dot(bg, dsb, _NT)                                    # d(x w)
            dx = w2 * v + d * dy
            dbg = dbg + _dot((xr * w2).astype(dtype), dsb)
            dw = [_half_sums(xr * v, i) for i in (0, 1)]
            # y_in = exp(G_t) C S_in^T
            since = _halves(jnp.exp(hd[0].g_col), jnp.exp(hd[1].g_col), (CHUNK, LANES))
            dyi = dy * since
            dyib = dyi.astype(dtype)
            dcg = dcg + _dot(dyib, sb)
            through_y_in = dyi * _dot(cg, sb, _NT)
            whole = [jnp.exp(hd[i].g_end) for i in (0, 1)]
            carried = [jnp.sum(jnp.sum(jnp.where((sub < HEAD_DIM) == (i == 0), ds_out * state, 0.0),
                                    axis=0, keepdims=True), axis=1, keepdims=True) for i in (0, 1)]
            ds_ref[pair, :] = _halves(whole[0], whole[1], (LANES, 1), axis=0) * ds_out \
                + _dot(dyib, cg, _TN)
            dxs = []
            for i in (0, 1):
                e = hd[i].decay()
                m = (cb * (e * hd[i].dt_row)).astype(dtype)
                mine = (_iota((CHUNK, LANES), 1) < HEAD_DIM) == (i == 0)
                dm = _dot(jnp.where(mine, dyb, jnp.zeros((), dtype)), xb, _NT)
                dxs.append(_dot(m, dyb, _TN))
                ke = dm * e
                dcb = dcb + ke * hd[i].dt_row
                r = ke * cb
                q = r * hd[i].dt_row                                  # dM * M
                wdw = w[i] * dw[i]
                d_end = whole[i] * carried[i] + jnp.sum(wdw, axis=0, keepdims=True)
                col = jnp.sum(q, axis=1, keepdims=True) + _half_sums(through_y_in, i) - wdw \
                    + jnp.where(last, d_end, 0.0)
                at = head_lane == h + i
                d_g = jnp.where(at, col, d_g)
                d_dt = jnp.where(at, to_end[i] * dw[i], d_dt)
                back_ref[pl.ds(h + i, 1), :] = -jnp.sum(q, axis=0, keepdims=True)
                back_ref[pl.ds(heads + h + i, 1), :] = jnp.sum(r, axis=0, keepdims=True)
            dx = dx + _halves(dxs[0], dxs[1], (CHUNK, LANES))
            dx_ref[0, :, pair] = dx.astype(dx_ref.dtype)
        dcbb = dcb.astype(dtype)
        dc_ref[0, :, lanes] = (dcg + _dot(dcbb, bg)).astype(dc_ref.dtype)
        db_ref[0, :, lanes] = (dbg + _dot(dcbb, cg, _TN)).astype(db_ref.dtype)
        cols_ref[0] += d_g
        cols_ref[1] += d_dt
        return carry

    lax.fori_loop(0, groups, group, 0)
    d_g = cols_ref[0] + back_ref[0:heads, :].T
    # G is a cumulative sum: d a_s = sum_{t >= s} d G_t
    after = jnp.where(_iota((CHUNK, CHUNK), 0) <= _iota((CHUNK, CHUNK), 1), 1.0, 0.0)
    d_a = _dot(after, d_g)
    ddt_ref[0] = cols_ref[1] + back_ref[heads:2 * heads, :].T + a_ref[...] * d_a
    da_ref[0, 0] = jnp.sum(dt * d_a, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _call(backward, dtype, interpret, x, dt, b, c, a, d, *residuals):
    """One of the two kernels.  x (B, T, H P), dt (B, T, H) float32, b, c
    (B, T, G N), a (1, H) float32, d (H,) float32.  Under ``jax.jit`` so that
    a model's layers share one trace and one lowering of each kernel."""
    bt, t, hp = x.shape
    heads, groups = dt.shape[2], b.shape[2] // LANES
    chunks = -(-t // CHUNK)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    # the backward takes the chunks from the last to the first
    at = (lambda k: chunks - 1 - k) if backward else (lambda k: k)
    rows = lambda width: vmem((1, CHUNK, width), lambda i, k: (i, at(k), 0))
    whole = lambda *shape: vmem(shape, lambda i, k: (0,) * len(shape))
    kept = vmem((1, 1, hp, LANES), lambda i, k: (i, at(k), 0, 0))
    terms = lambda width: vmem((1, 1, 1, width), lambda i, k: (i, at(k), 0, 0))
    scalars = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [rows(hp), rows(heads), rows(b.shape[2]), rows(c.shape[2]), whole(1, heads), scalars]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)
    like = lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype)
    sums = pltpu.VMEM((2 * heads, CHUNK), _F32)
    state = pltpu.VMEM((hp, LANES), _F32)
    if backward:
        kernel, name = _bwd_kernel, "ssd_bwd"
        in_specs += [rows(hp), kept]
        out_specs = [rows(hp), rows(heads), rows(b.shape[2]), rows(c.shape[2]),
                     terms(heads), terms(hp)]
        out_shape = [like(x), f32(*dt.shape), like(b), like(c),
                     f32(bt, chunks, 1, heads), f32(bt, chunks, 1, hp)]
        scratch = [state, sums, sums, pltpu.VMEM((2, CHUNK, heads), _F32)]
    else:
        kernel, name = _fwd_kernel, "ssd_fwd"
        out_specs = [rows(hp), kept]
        out_shape = [f32(bt, t, hp), f32(bt, chunks, hp, LANES)]
        scratch = [state, sums]
    return pl.pallas_call(
        functools.partial(kernel, length=t, groups=groups, dtype=dtype),
        grid=(bt, chunks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        # The names the kernels run under in a device trace.
        name=name,
    )(x, dt, b, c, a, d, *residuals)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, b, c, a, d, dtype, interpret):
    return _call(False, dtype, interpret, x, dt, b, c, a, d)[0]


def _ssd_fwd(x, dt, b, c, a, d, dtype, interpret):
    y, kept = _call(False, dtype, interpret, x, dt, b, c, a, d)
    return y, (x, dt, b, c, a, d, kept)


def _ssd_bwd(dtype, interpret, residuals, dy):
    x, dt, b, c, a, d, kept = residuals
    dx, ddt, db, dc, da, dd = _call(True, dtype, interpret, x, dt, b, c, a, d, dy, kept)
    heads = dt.shape[2]
    return (dx, ddt, db, dc, jnp.sum(da, axis=(0, 1)),
            jnp.sum(dd, axis=(0, 1, 2)).reshape(heads, -1).sum(axis=1))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, a, b, c, d, dtype=jnp.bfloat16):
    """``ops/ssd.py::ssd_recurrent``'s arguments and result (x (B, T, H, P), dt
    (B, T, H), a (H,) negative, b, c (B, T, G, N), d (H,) -> float32 y (B, T,
    H, P)) through the kernel pair, matmul operands in ``dtype``.  x, B and C
    keep their types into the kernels, ``dt``, ``a`` and ``d`` are float32;
    only free reshapes happen here, so autodiff carries the kernels'
    cotangents back to the arguments'.  Off the TPU the same kernels run
    interpreted."""
    bt, t, h, p = x.shape
    g, n = b.shape[2:]
    y = _ssd(x.reshape(bt, t, h * p), dt.astype(_F32), b.reshape(bt, t, g * n),
             c.reshape(bt, t, g * n), a.astype(_F32).reshape(1, h), d.astype(_F32),
             jnp.dtype(dtype), jax.default_backend() != "tpu")
    return y.reshape(bt, t, h, p)
