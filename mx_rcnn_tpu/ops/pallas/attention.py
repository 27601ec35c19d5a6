"""Causal softmax attention with its scores in VMEM.

One head's whole sequence — a few thousand positions of q, k and v, a
megabyte each — is resident in VMEM for a grid step; the scores exist a tile
(``TILE`` query rows by ``TILE`` key columns) at a time and never cross HBM.
The forward (``flash_attention_fwd``) walks the key tiles at or below a query
tile's diagonal with the running max and sum and writes the output and one
log-sum-exp a row.  The backward (``flash_attention_bwd``) is written by hand
under a ``jax.custom_vjp`` whose residuals are q, k, v, o and the row
statistics: it recomputes a tile's probabilities from them and accumulates
dq, dk and dv in float32, all three in ONE walk over the triangle (five
matmuls a tile where two kernels, one for dq and one for dk and dv, take
seven), with keys on the rows of a tile so that only dq's matmul wants a
transposed operand.  Tiles wholly above the diagonal are never visited, the
diagonal's are masked, and the last tile of a sequence that is no multiple
of a tile is a narrower one whose rows past the end read as zeros.  Under a
``window`` (a query sees itself and the ``window - 1`` positions before it)
the key tiles wholly before a query tile's band are never visited either and
those that meet its leading edge are masked as the diagonal's are
(``_band``, ``_seen``): two key tiles a query tile at a window of one tile,
in the forward and in the backward; without a window the walk is what it was.

The arithmetic is ``ops/attention.py::causal_attention``'s: operands in
``dtype``, scores, max, sum, log-sum-exp and every accumulator float32, the
probabilities (and the scores' cotangents) cast to ``dtype`` only as a
matmul's operand.  Float32 operands (the tests') multiply at ``highest``.
One thing the XLA form has by construction has to be kept by hand: the
softmax's backward, p * (dp - sum(p * dp)), sums to nothing over a row's keys,
and where tokens look alike dq is the small remainder of that cancellation.
The kernels stand sum(o * do) in for sum(p * dp), which holds only if o is
the mean of the values under the probabilities AS THE MATMUL TOOK THEM (the
forward divides by the sum of the rounded probabilities) and if that sum sees
do as dp's matmul does (rounded to ``dtype``).

Keys and values may have fewer heads than the queries: the grid runs
(batch, key head, query head of its group) and K and V's blocks do not move
over the last axis, so they cross HBM once a key head; dk and dv sum over the
group in VMEM.  The query/key width may differ from the value width and need
be no multiple of 128: it is padded with zeros to the next one (192 -> 256
costs a 128-wide MXU the same two passes).

Layout: q, k, v stay (B, T, heads * width) — a block is every row of one
head's lanes; the statistics are (B, H, tiles, 8, TILE), a row a tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 512      # query rows and key columns of a tile of scores: a program choice
LANES = 128
_ROWS = 8       # sublanes a row statistic is stored on
# What a grid step may hold in VMEM (of a v5e's 128 MiB); ``supported`` refuses
# a sequence whose blocks, double-buffered, and accumulators pass it.
_VMEM_BYTES = 96 * 2**20

_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_MASKED = -1e30     # a masked score: exp(_MASKED - max) is 0, and no inf - inf


def _up(x: int, m: int) -> int:
    return -(-x // m) * m


def _vmem_bytes(t: int, dk: int, dv: int, itemsize: int) -> int:
    """The backward's blocks (q, k, v, dq, dk, dv in ``dtype``, o and its
    cotangent float32, all double-buffered), its three float32 accumulators
    and a few tiles of scores."""
    tb = _up(t, LANES)
    blocks = 2 * tb * ((2 * dk + dv) * 2 * itemsize + 2 * dv * 4)
    return blocks + tb * (2 * dk + dv) * 4 + 8 * TILE * TILE * 4


def supported(t: int, h: int, hkv: int, dk: int, dv: int, dtype) -> bool:
    """The shapes the kernels are written for: value heads of whole vregs,
    query heads a multiple of the key heads, bfloat16 or float32, and a
    sequence whose head fits VMEM."""
    dtype = jnp.dtype(dtype)
    return (dv % LANES == 0 and h % hkv == 0
            and dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and _vmem_bytes(t, _up(dk, LANES), dv, dtype.itemsize) <= _VMEM_BYTES)


def _tiles(length: int, tile: int):
    """(first row, rows) of each tile: whole ones, then one as wide as what is
    left, in whole vregs."""
    return [(lo, min(tile, _up(length - lo, LANES))) for lo in range(0, length, tile)]


def _dot(x, y, dims):
    """Float32 out; float32 operands (the tests', the sums of o * do) at ``highest``."""
    precision = lax.Precision.HIGHEST if x.dtype == _F32 else None
    with jax.named_scope("tile"):
        return lax.dot_general(x, y, dims, precision=precision, preferred_element_type=_F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _loader(lo, rows, length):
    """Reads rows ``lo`` .. ``lo + rows`` of a head's block.  The rows of a
    sequence's last tile that lie past its end are in no array: whatever the
    block holds there reads as zeros (a query and a key that meet nobody)."""
    at = pl.ds(lo, rows)
    if not isinstance(lo, int) or lo + rows <= length:
        return lambda ref: ref[0, at, :]
    return lambda ref: jnp.where(
        _iota((rows, ref.shape[2]), 0) < length - lo, ref[0, at, :], jnp.zeros((), ref.dtype))


def _band(window, tile: int, n: int) -> tuple[int, int]:
    """Of the ``n`` tiles before (after) a tile's diagonal, counted from it:
    (how many lie wholly inside the band of ``window``, how many meet it at
    all).  Tiles a whole tile apart hold pairs k tile - (tile - 1) ..
    k tile + (tile - 1) positions apart, and a pair is seen under window - 1."""
    if window is None:
        return n, n
    return min(n, max(window // tile - 1, 0)), min(n, (window - 2) // tile + 1)


def _seen(shape, queries: int, apart: int, window, diagonal: bool):
    """The pairs of a tile of scores that see each other, or None where all do.
    ``queries`` is the axis the queries lie on, ``apart`` how far the tile's
    first query is past its first key."""
    seen = (_iota(shape, queries) >= _iota(shape, 1 - queries)) if diagonal else None
    if window is not None and apart + shape[queries] > window:
        edge = _iota(shape, queries) - _iota(shape, 1 - queries) < window - apart
        seen = edge if seen is None else seen & edge
    return seen


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, length, tile, scale, window):
    kind = q_ref.dtype

    for n, (lo, rows) in enumerate(_tiles(length, tile)):
        q = _loader(lo, rows, length)(q_ref)
        inside, met = _band(window, tile, n)

        def keys(at, cols, carry, diagonal, q=q, lo=lo):
            m, l, acc = carry
            load = _loader(at, cols, length)
            s = _dot(q, load(k_ref), _NT) * scale
            seen = None if not isinstance(at, int) else _seen(s.shape, 0, lo - at, window, diagonal)
            if seen is not None:
                s = jnp.where(seen, s, _MASKED)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            # The sum is of the probabilities AS THE MATMUL TAKES THEM: o is then
            # a convex combination of the values to the last bit, and the backward's
            # sum(o * do) is the mean of its own p's cotangents (see _bwd_kernel).
            p = jnp.exp(s - m_new).astype(kind)
            l = alpha * l + jnp.sum(p.astype(_F32), axis=1, keepdims=True)
            acc = alpha * acc + _dot(p, load(v_ref), _NN)
            return m_new, l, acc

        carry = (jnp.full((rows, 1), _MASKED, _F32), jnp.zeros((rows, 1), _F32),
                 jnp.zeros((rows, v_ref.shape[2]), _F32))
        # The tiles that meet the band's leading edge (under a window: what a row
        # without a key there gathers is wiped by its first key's ``alpha`` = 0),
        # the tiles wholly inside the band (every one below the diagonal without
        # a window), then the diagonal's.
        for j in range(n - met, n - inside):
            carry = keys(j * tile, tile, carry, False)
        if inside:
            carry = lax.fori_loop(
                n - inside, n,
                lambda j, c: keys(pl.multiple_of(j * tile, tile), tile, c, False), carry)
        m, l, acc = keys(lo, rows, carry, True)
        o_ref[0, pl.ds(lo, rows), :] = acc / l
        lse = jnp.broadcast_to(m + jnp.log(l), (rows, LANES))
        lse_ref[0, 0, n, :, pl.ds(0, rows)] = lse.T[:_ROWS]


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref,
                dq_acc, dk_acc, dv_acc, di_ref, *, length, tile, scale, window, group):
    kind = q_ref.dtype
    member = pl.program_id(2)
    tiles = _tiles(length, tile)
    last = len(tiles) - 1
    # The loops over whole tiles take ``tile`` rows unmasked: they stop short of a
    # last tile that is narrower or overhangs the sequence (it may be either alone).
    whole = last + (length % tile == 0)

    # sum(o * do) a row, as rows, of do AS THE MATMULS TAKE IT: the softmax's
    # backward is p * (dp - sum(p * dp)), whose sum over the keys is nothing, and
    # sum(o * do) stands for sum(p * dp) only where both see the same do.  Where
    # tokens look alike dq is what is left after that sum cancels: a do rounded
    # in dp and not here leaves 2^-9 of dp on every key of a row, ten times the
    # dq that is there to find (PERF.md section 6, PR 33).  dq starts from
    # nothing in every grid step.
    for n, (lo, rows) in enumerate(tiles):
        load = _loader(lo, rows, length)
        prod = load(o_ref) * load(do_ref).astype(kind).astype(_F32)
        di_ref[n, :, pl.ds(0, rows)] = _dot(jnp.ones((_ROWS, prod.shape[1]), _F32), prod, _NT)
    dq_acc[...] = jnp.zeros_like(dq_acc)

    for n, (lo, cols) in enumerate(tiles):
        load = _loader(lo, cols, length)
        k, v = load(k_ref), load(v_ref)

        def queries(i, at, rows, carry, diagonal, k=k, v=v, lo=lo):
            dk, dv = carry
            load = _loader(at, rows, length)
            q, do = load(q_ref), load(do_ref).astype(kind)
            # keys on the rows, queries on the lanes
            p = jnp.exp(_dot(k, q, _NT) * scale - lse_ref[0, 0, i, 0:1, pl.ds(0, rows)])
            seen = None if not isinstance(at, int) else _seen(p.shape, 1, at - lo, window, diagonal)
            if seen is not None:
                p = jnp.where(seen, p, 0.0)
            dv = dv + _dot(p.astype(kind), do, _NN)
            ds = (p * (_dot(v, do, _NT) - di_ref[i, 0:1, pl.ds(0, rows)]) * scale).astype(kind)
            dk = dk + _dot(ds, q, _NN)
            dq_acc[pl.ds(at, rows), :] += _dot(ds, k, _TN)
            return dk, dv

        carry = (jnp.zeros((cols, k.shape[1]), _F32), jnp.zeros((cols, v.shape[1]), _F32))
        carry = queries(n, lo, cols, carry, True)
        # the query tiles below it wholly inside the band (every one without a
        # window): whole ones, then the sequence's last; then those that meet the
        # band's leading edge
        inside, met = _band(window, tile, last - n)
        stop = min(whole, n + inside + 1)
        if n + 1 < stop:
            carry = lax.fori_loop(
                n + 1, stop,
                lambda i, c: queries(i, pl.multiple_of(i * tile, tile), tile, c, False), carry)
        if n < last and whole == last and last <= n + inside:
            carry = queries(last, *tiles[last], carry, False)
        for i in range(n + inside + 1, n + met + 1):
            carry = queries(i, *tiles[i], carry, False)
        dk, dv = carry
        at = pl.ds(lo, cols)

        # dk and dv sum over the query heads of the key head
        @pl.when(member == 0)
        def _():
            dk_acc[at, :] = dk
            dv_acc[at, :] = dv

        @pl.when(member > 0)
        def _():
            dk_acc[at, :] += dk
            dv_acc[at, :] += dv

    dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(member == group - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _call(backward, heads, scale, tile, interpret, window, q, k, v, *residuals):
    """One of the two kernels over q (B, T, H * Dk), k (B, T, Hkv * Dk) and
    v (B, T, Hkv * Dv).  Under ``jax.jit`` so that a model's layers share one
    trace and one lowering of each kernel."""
    h, hkv = heads
    b, t, _ = q.shape
    dk, dv = k.shape[2] // hkv, v.shape[2] // hkv
    group, tb, n = h // hkv, _up(t, LANES), len(_tiles(t, tile))
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    query = lambda d: vmem((1, tb, d), lambda i, j, m: (i, 0, j * group + m))
    key = lambda d: vmem((1, tb, d), lambda i, j, m: (i, 0, j))
    stats = vmem((1, 1, n, _ROWS, tile), lambda i, j, m: (i, j * group + m, 0, 0, 0))
    stats_shape = jax.ShapeDtypeStruct((b, h, n, _ROWS, tile), _F32)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    if backward:
        kernel, name = functools.partial(_bwd_kernel, group=group), "flash_attention_bwd"
        in_specs = [query(dk), key(dk), key(dv), query(dv), query(dv), stats]
        out_specs, out_shape = [query(dk), key(dk), key(dv)], [like(q), like(k), like(v)]
        scratch = [pltpu.VMEM((tb, d), _F32) for d in (dk, dk, dv)] + [
            pltpu.VMEM((n, _ROWS, tile), _F32)]
        # dk and dv are carried from one query head of a key head to the next
        semantics = ("parallel", "parallel", "arbitrary")
    else:
        kernel, name = _fwd_kernel, "flash_attention_fwd"
        in_specs = [query(dk), key(dk), key(dv)]
        out_specs = [query(dv), stats]
        out_shape = [jax.ShapeDtypeStruct((b, t, h * dv), _F32), stats_shape]
        scratch, semantics = [], ("parallel", "parallel", "parallel")
    return pl.pallas_call(
        functools.partial(kernel, length=t, tile=tile, scale=scale, window=window),
        grid=(b, hkv, group),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        # The names the kernels run under in a device trace.
        name=name,
    )(q, k, v, *residuals)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attention(q, k, v, heads, scale, tile, interpret, window):
    return _call(False, heads, scale, tile, interpret, window, q, k, v)[0]


def _attention_fwd(q, k, v, heads, scale, tile, interpret, window):
    o, lse = _call(False, heads, scale, tile, interpret, window, q, k, v)
    return o, (q, k, v, o, lse)


def _attention_bwd(heads, scale, tile, interpret, window, residuals, do):
    q, k, v, o, lse = residuals
    return tuple(_call(True, heads, scale, tile, interpret, window, q, k, v, o, do, lse))


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q, k, v, scale: float, dtype=jnp.bfloat16, window: int | None = None):
    """q (B, T, H, Dk), k (B, T, Hkv, Dk), v (B, T, Hkv, Dv), Hkv dividing H
    -> (B, T, H, Dv) float32, as ``ops/attention.py::causal_attention``
    (``window``: a query sees itself and the ``window - 1`` positions before
    it).  Off the TPU the same kernels run interpreted."""
    b, t, h, dk = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    pad = _up(dk, LANES) - dk

    def flat(x, extra=0):
        x = jnp.pad(x.astype(dtype), ((0, 0), (0, 0), (0, 0), (0, extra)))
        return x.reshape(b, t, -1)

    o = _attention(flat(q, pad), flat(k, pad), flat(v), (h, hkv), scale, TILE,
                   jax.default_backend() != "tpu", window)
    return o.reshape(b, t, h, dv)
