"""The chunk-local part of the KDA scan (``ops/kda.py``) with one chunk in VMEM.

What happens inside a chunk of 64 positions — the cumulative decay, A and P
against the sub-chunk-middle reference, T = (I + A)^{-1}, W, U0 and the three
scaled operands of the scan over chunks — is a few dozen small float32
matmuls and exponentials over (64, 128) and (64, 64) arrays.  XLA runs them
for all chunks of a layer at once with every intermediate through HBM; here a
grid step takes a few whole chunks of one head and nothing but the inputs and
the six results crosses HBM.  The arithmetic is ``ops/kda.py::_intra``'s:
float32, every dot at ``highest`` (I + A is badly conditioned where keys look
alike), rows and columns measured from the middle of their sub-chunk with the
clamp at ``far``, the inverse by forward substitution on the 16 x 16 diagonal
blocks and two pairwise merges.  What differs is what costs time on the chip
and changes no number: columns past a sub-chunk's own (above the diagonal,
masked) are not computed, the substitution runs on the four diagonal blocks
side by side, a merge computes only the rows it changes, and the chunks of a
grid step are worked on together (:func:`_interleaved`).

:func:`kda_intra` is the pair under a ``jax.custom_vjp`` whose residuals are
the inputs alone: the backward kernel (``kda_intra_bwd``) recomputes the
chunk's forward in VMEM and returns dq, dk, dv, dg, dbeta from the six
cotangents by hand — the transposes of the dots, the derivative of the decays
and -T^T dT T^T for the inverse.

Layout: inputs stay (B, T, H * D) — a block is 64-row chunks of one head's
128 lanes — and the results come out (N, B, H, C, .), chunk-major, as the
scan over chunks takes them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64      # positions a chunk: the kernel's one shape
SUB = 16        # rows of a sub-chunk and of a diagonal block of the inverse
LANES = 128     # Dk and Dv come in whole vregs
_MAX_CHUNKS_PER_STEP = 8    # 12 MB of VMEM for the backward's blocks and spills
_LAG = 2                    # pieces of work between the start of one chunk and the next

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def supported(chunk: int, sub: int, dk: int, dv: int) -> bool:
    """The shapes the kernels are written for."""
    return chunk == CHUNK and sub == SUB and dk == LANES and dv == LANES


def _dot(x, y, dims=_NN):
    with jax.named_scope("chunk"):
        return lax.dot_general(x, y, dims, precision=_HI, preferred_element_type=_F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _alone(pieces):
    """A generator of pieces of work run to its end by itself; its value."""
    while True:
        try:
            next(pieces)
        except StopIteration as done:
            return done.value


def _interleaved(chunks):
    """Runs the generators of a grid step's chunks together, a piece of each in
    turn, each begun ``_LAG`` pieces after the one before it.  One chunk alone
    is a chain of small dependent operations — the MXU waits for the
    substitution and the other way round — and the compiler schedules what it
    is handed nearly in the order it is handed it: chunks written one after
    the other run one after the other (1.46 against 0.87 us a chunk forward,
    2.53 against 1.54 backward, PERF.md section 6, PR 31)."""
    waiting, live = list(chunks), []
    turn = 0
    while waiting or live:
        if waiting and turn % _LAG == 0:
            live.append(waiting.pop(0))
        for pieces in list(live):
            try:
                next(pieces)
            except StopIteration:
                live.remove(pieces)
        turn += 1


def _unit_lower_inverse(a, row, col):
    """(I + a)^{-1} for a strictly lower-triangular a (64, 64), as
    ``ops/kda.py::_unit_lower_inverse``: the four 16 x 16 diagonal blocks by
    forward substitution, then pairs merged, [[T1, 0], [-T2 A21 T1, T2]].
    The substitution runs on the four blocks side by side, (16, 64): row j of
    every block is final after step j and leaves the rows below it, each by
    its own coefficient a[i, j], which one gather along the lanes spreads
    over its block.  A generator: it yields between pieces of work."""
    lane, at = _iota((SUB, CHUNK), 1), _iota((SUB, CHUNK), 0)
    block = lane // SUB
    side = sum(jnp.where(block == b, a[b * SUB:(b + 1) * SUB], 0.0) for b in range(CHUNK // SUB))
    t = jnp.where(lane - block * SUB == at, 1.0, 0.0)
    for j in range(SUB - 1):
        t = t - jnp.take_along_axis(side, block * SUB + j, axis=1) * jnp.broadcast_to(
            t[j:j + 1], t.shape)
        if j % 4 == 3:
            yield
    t = jnp.concatenate([jnp.where(block == b, t, 0.0) for b in range(CHUNK // SUB)])
    for m in (SUB, 2 * SUB):
        # only the rows of each pair's second block change
        blocks = [t[i:i + m] for i in range(0, CHUNK, m)]
        low = jnp.concatenate(blocks[1::2])
        below = ((row // (2 * m)) == (col // (2 * m))) & ((row // m) > (col // m))
        x = _dot(low, jnp.where(below, a, 0.0))
        yield
        low = low - _dot(x, t)
        yield
        blocks[1::2] = [low[i:i + m] for i in range(0, low.shape[0], m)]
        t = jnp.concatenate(blocks)
    return t


def _forward(q, k, v, g, beta_row, far):
    """One chunk: q, k, v, g (64, 128) float32, beta_row (1, 64).  Everything
    the backward needs again, by name.  A generator, as the inverse."""
    row, col = _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)
    beta = jnp.sum(jnp.where(row == col, beta_row, 0.0), axis=1, keepdims=True)   # (64, 1)
    gc = _dot(jnp.where(row >= col, 1.0, 0.0), g)                  # G_t, inclusive
    yield
    # The cumulative decay at the MIDDLE of each sub-chunk (ops/kda.py).
    g0 = [gc[i + SUB // 2 - 1:i + SUB // 2] for i in range(0, CHUNK, SUB)]
    rows = jnp.exp(gc - jnp.concatenate([jnp.broadcast_to(x, (SUB, LANES)) for x in g0]))
    xk, xq = k * rows, q * rows
    cols, kc, s = [], [], []
    for r, i in enumerate(range(0, CHUNK, SUB)):
        # columns past the sub-chunk are above the diagonal: not computed
        n = i + SUB
        cols.append(jnp.exp(jnp.minimum(g0[r] - gc[:n], far)))
        kc.append(k[:n] * cols[r])
        s.append(jnp.pad(_dot(jnp.concatenate([xk[i:i + SUB], xq[i:i + SUB]]), kc[r], _NT),
                         ((0, 0), (0, CHUNK - n))))
        yield
    araw = jnp.concatenate([x[:SUB] for x in s])
    p = jnp.where(row >= col, jnp.concatenate([x[SUB:] for x in s]), 0.0)
    tri = yield from _unit_lower_inverse(jnp.where(row > col, araw * beta, 0.0), row, col)
    decay = jnp.exp(gc)                                             # e^{G_t} <= 1
    kb, vb = beta * k * decay, beta * v
    g_end = gc[CHUNK - 1:]
    tail = jnp.exp(g_end - gc)
    return dict(
        qe=q * decay, p=p, ke=k * tail, eg=jnp.exp(g_end),
        beta=beta, gc=gc, g0=g0, rows=rows, xk=xk, xq=xq, cols=cols, kc=kc, araw=araw, tri=tri,
        decay=decay, kb=kb, vb=vb, tail=tail, row=row, col=col,
    )


def _backward(q, k, v, g, beta_row, dw, du, dqe, dp, dke, deg, far):
    """The cotangents of one chunk's inputs from those of its six results.
    A generator, as the forward."""
    f = yield from _forward(q, k, v, g, beta_row, far)
    row, col, beta, tri, gc = f["row"], f["col"], f["beta"], f["tri"], f["gc"]
    rows, decay, tail = f["rows"], f["decay"], f["tail"]
    # W = T kb, U0 = T vb; T = (I + A)^{-1}: dA = -T^T dT T^T
    d_tri = _dot(dw, f["kb"], _NT)
    yield
    d_tri = d_tri + _dot(du, f["vb"], _NT)
    yield
    dkb = _dot(tri, dw, _TN)
    yield
    dvb = _dot(tri, du, _TN)
    yield
    x = _dot(d_tri, tri, _NT)
    yield
    da = jnp.where(row > col, -_dot(tri, x, _TN), 0.0)
    yield
    dbeta = jnp.sum(da * f["araw"], axis=1, keepdims=True)
    daraw, dpraw = da * beta, jnp.where(row >= col, dp, 0.0)

    lane_row = _iota((CHUNK, LANES), 0)
    dgc = (dkb * beta * k + dqe * q) * decay        # through e^{G_t}
    through_tail = dke * k * tail                   # through e^{G_C - G_t}
    dgc = dgc - through_tail
    dg_end = jnp.sum(through_tail, axis=0, keepdims=True) + deg * f["eg"]
    dgc = dgc + jnp.where(lane_row == CHUNK - 1, dg_end, 0.0)
    dk = dkb * beta * decay + dke * tail
    dxk, dxq = [], []
    for r, i in enumerate(range(0, CHUNK, SUB)):
        n = i + SUB                                  # the columns at or below the diagonal
        below = lambda x: jnp.pad(x, ((0, CHUNK - n), (0, 0)))
        ds = jnp.concatenate([daraw[i:n, :n], dpraw[i:n, :n]])                # (32, n)
        dx = _dot(ds, f["kc"][r])                                             # (32, 128)
        yield
        dkc = _dot(ds, jnp.concatenate([f["xk"][i:n], f["xq"][i:n]]), _TN)    # (n, 128)
        yield
        dxk.append(dx[:SUB])
        dxq.append(dx[SUB:])
        dk = dk + below(dkc * f["cols"][r])
        # cols = exp(min(g0 - G, far)): the clamp passes nothing on
        e = jnp.where(f["g0"][r] - gc[:n] < far, dkc * f["kc"][r], 0.0)
        de_rows = (dx[:SUB] * k[i:n] + dx[SUB:] * q[i:n]) * rows[i:n]
        dg0 = jnp.sum(e, axis=0, keepdims=True) - jnp.sum(de_rows, axis=0, keepdims=True)
        dgc = dgc - below(e) + jnp.where(lane_row == i + SUB // 2 - 1, dg0, 0.0)
    dxk, dxq = jnp.concatenate(dxk), jnp.concatenate(dxq)
    dgc = dgc + (dxk * k + dxq * q) * rows          # through rows = e^{G_t - g0}
    dk = dk + dxk * rows
    dq = dxq * rows + dqe * decay
    dv = dvb * beta
    dbeta = dbeta + jnp.sum(dkb * k * decay + dvb * v, axis=1, keepdims=True)
    dg = _dot(jnp.where(row <= col, 1.0, 0.0), dgc)                 # cumsum, transposed
    dbeta_row = jnp.sum(jnp.where(row == col, dbeta, 0.0), axis=0, keepdims=True)
    return dq, dk, dv, dg, dbeta_row


def _loader(c, steps, length):
    """Reads chunk ``c`` of a grid step's block of q, k, v or g as float32.
    The rows of a sequence's last chunk that lie past its end are in no array:
    whatever the block holds there reads as zeros (no key, no decay)."""
    at = pl.ds(c * CHUNK, CHUNK)
    if length % CHUNK == 0 or c < steps - 1:
        return lambda ref: ref[0, at, :].astype(_F32)
    left = length - (pl.program_id(2) * steps + c) * CHUNK
    inside = _iota((CHUNK, LANES), 0) < left
    return lambda ref: jnp.where(inside, ref[0, at, :].astype(_F32), 0.0)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, w_ref, u_ref, qe_ref, p_ref, ke_ref, eg_ref,
                *, steps, length, far):
    def chunk(c):
        load = _loader(c, steps, length)
        f = yield from _forward(load(q_ref), load(k_ref), load(v_ref), load(g_ref),
                                b_ref[0, 0, 0, pl.ds(c, 1), :], far)
        w_ref[c, 0, 0] = _dot(f["tri"], f["kb"]).astype(w_ref.dtype)
        yield
        u_ref[c, 0, 0] = _dot(f["tri"], f["vb"]).astype(u_ref.dtype)
        for ref, name in ((qe_ref, "qe"), (p_ref, "p"), (ke_ref, "ke"), (eg_ref, "eg")):
            ref[c, 0, 0] = f[name].astype(ref.dtype)

    _interleaved(chunk(c) for c in range(steps))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, dw_ref, du_ref, dqe_ref, dp_ref, dke_ref,
                deg_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, *, steps, length, far):
    def chunk(c):
        at, load = pl.ds(c * CHUNK, CHUNK), _loader(c, steps, length)
        cot = lambda ref: ref[c, 0, 0].astype(_F32)
        out = yield from _backward(
            load(q_ref), load(k_ref), load(v_ref), load(g_ref), b_ref[0, 0, 0, pl.ds(c, 1), :],
            cot(dw_ref), cot(du_ref), cot(dqe_ref), cot(dp_ref), cot(dke_ref), cot(deg_ref), far,
        )
        for ref, x in zip((dq_ref, dk_ref, dv_ref, dg_ref), out):
            ref[0, at, :] = x.astype(ref.dtype)
        db_ref[0, 0, 0, pl.ds(c, 1), :] = out[4]

    _interleaved(chunk(c) for c in range(steps))


def _chunks_per_step(n: int) -> int:
    return max(d for d in range(1, _MAX_CHUNKS_PER_STEP + 1) if n % d == 0)


def _specs(b, h, n, steps, dtype):
    """Block specs and shapes shared by the two kernels; the grid is
    (batch, head, groups of ``steps`` chunks)."""
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    token = vmem((1, steps * CHUNK, LANES), lambda i, j, m: (i, m, j))
    beta = vmem((1, 1, 1, steps, CHUNK), lambda i, j, m: (i, j, m, 0, 0))
    result = lambda last: vmem((steps, 1, 1) + last, lambda i, j, m: (m, i, j, 0, 0))
    results = [result((CHUNK, LANES))] * 3 + [result((CHUNK, CHUNK)), result((CHUNK, LANES)),
                                              result((1, LANES))]
    shape = lambda last, kind: jax.ShapeDtypeStruct((n, b, h) + last, kind)
    result_shapes = [shape((CHUNK, LANES), dtype)] * 3 + [
        shape((CHUNK, CHUNK), dtype), shape((CHUNK, LANES), dtype), shape((1, LANES), _F32)]
    return token, beta, results, result_shapes


@functools.partial(jax.jit, static_argnums=(0, 7, 8, 9))
def _call(backward, q, k, v, g, beta, cotangents, dtype, far, interpret):
    """One of the two kernels over (B, T, H * 128) inputs.  Under ``jax.jit``
    so that a model's layers share one trace and one lowering of each kernel
    (0.4 s a call site otherwise, 18 of them in the decoder cell's step)."""
    kernel, name = (_bwd_kernel, "kda_intra_bwd") if backward else (_fwd_kernel, "kda_intra_fwd")
    b, t, hd = q.shape
    h, steps = hd // LANES, beta.shape[3]
    n = beta.shape[2] * steps               # chunks: the last may overhang the sequence
    token, beta_spec, results, result_shapes = _specs(b, h, n, steps, dtype)
    if backward:
        in_specs = [token] * 4 + [beta_spec] + results
        out_specs = [token] * 4 + [beta_spec]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, g, beta)]
    else:
        in_specs, out_specs, out_shape = [token] * 4 + [beta_spec], results, result_shapes
    return pl.pallas_call(
        functools.partial(kernel, steps=steps, length=t, far=far),
        grid=(b, h, n // steps),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        # The names the kernels run under in a device trace.
        name=name,
    )(q, k, v, g, beta, *cotangents)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _intra(q, k, v, g, beta, dtype, far, interpret):
    return tuple(_call(False, q, k, v, g, beta, (), dtype, far, interpret))


def _intra_fwd(q, k, v, g, beta, dtype, far, interpret):
    return _intra(q, k, v, g, beta, dtype, far, interpret), (q, k, v, g, beta)


def _intra_bwd(dtype, far, interpret, inputs, cotangents):
    return tuple(_call(True, *inputs, cotangents, dtype, far, interpret))


_intra.defvjp(_intra_fwd, _intra_bwd)


def kda_intra(q, k, v, g, beta, dtype, far: float):
    """q, k, v (B, T, H, 128) in ``dtype``, g float32 likewise, beta (B, T, H)
    float32 -> (W, U0, q e^G, P, k e^{G_C - G}) in ``dtype`` and e^{G_C} in
    float32, each (N, B, H, ...) over the N = ceil(T / 64) chunks.  Positions
    past T are zeros (no decay, no key): they leave the state as it is; only
    beta is padded, the kernels zero their own reads past T.  Off the TPU the
    same kernels run interpreted."""
    b, t, h, d = k.shape
    n = -(-t // CHUNK)
    flat = lambda x: x.reshape(b, t, h * d)
    steps = _chunks_per_step(n)
    beta = jnp.pad(beta.astype(_F32), ((0, 0), (0, n * CHUNK - t), (0, 0)))
    beta = jnp.moveaxis(beta, 2, 1).reshape(b, h, n // steps, steps, CHUNK)
    w, u0, qe, p, ke, eg = _intra(
        flat(q.astype(dtype)), flat(k.astype(dtype)), flat(v.astype(dtype)),
        flat(g.astype(_F32)), beta, dtype, far, jax.default_backend() != "tpu",
    )
    return w, u0, qe, p, ke, eg[:, :, :, 0]
