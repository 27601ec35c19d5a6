"""Causal softmax attention over a few thousand positions that never writes
the (heads, T, T) scores to HBM.  Three forms of one function:

- :func:`causal_attention_dense` — the whole score matrix in float32 at
  ``highest``, K and V repeated for grouped queries: the oracle of both others.
- the blocked XLA form (:func:`causal_attention` off the TPU and at every
  shape the kernel is not written for): query rows are taken ``block`` at a
  time against the keys up to the block's last row, each block under
  ``jax.checkpoint`` so the backward recomputes its scores instead of keeping
  the probabilities.  The loop over blocks is unrolled at trace time with
  static key prefixes, so only the triangle is computed at block granularity:
  (1 + 1/n) / 2 of the square for n blocks.  A block's scores still cross HBM
  (the first matmul writes them, the softmax reads and rewrites them).  It is
  the kernel's second oracle: the same arithmetic in another order of sums.
- the Pallas kernel pair (``ops/pallas/attention.py``; :func:`causal_attention`
  on a TPU where ``supported`` says the shapes are the kernel's): a head's
  sequence resident in VMEM, the scores a tile at a time with the running max
  and sum, a hand-written backward from q, k, v, o and one log-sum-exp a row.

In all three, scores, softmax and sums are float32; in the last two the
matmuls take ``dtype`` operands and the probabilities are cast to ``dtype``
only as the second matmul's operand.  Keys and values may have fewer heads
than the queries (grouped-query attention): a key head then serves
``H / Hkv`` consecutive query heads and K and V cross HBM once a key head,
never repeated (the blocked form gives the group an axis of its own through
both matmuls, the kernel runs the group's heads one after the other over K
and V blocks that do not move).  Under a ``window`` a query sees itself and
the ``window - 1`` positions before it: the oracle masks the rest, the blocked
form takes a block's keys from the band's first position on (static ranges)
and the kernel pair visits only the key tiles that meet the band; a window
that holds the whole sequence is no window, in all three.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.ops.pallas import attention as attention_kernel

# Query rows per block: a program choice (at 4,200 positions a block's scores
# are 32 x 256 x 4,200 float32 = 138 MB an image), not an option; tests pass others.
BLOCK = 256


def causal_attention_dense(q, k, v, scale: float, window: int | None = None):
    """q (B, T, H, Dq), k (B, T, Hkv, Dq), v (B, T, Hkv, Dv), float32, Hkv
    dividing H -> (B, T, H, Dv).  ``window``: a query sees itself and the
    ``window - 1`` positions before it."""
    t, rep = q.shape[1], q.shape[2] // k.shape[2]
    if rep > 1:
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    with jax.named_scope("dense_scores"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
        ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
        seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision="highest")


def _takes_kernel(t: int, h: int, hkv: int, dk: int, dv: int, dtype) -> bool:
    """The Pallas kernel pair runs where there is a TPU to run it and the
    shapes are the ones it is written for."""
    return jax.default_backend() == "tpu" and attention_kernel.supported(t, h, hkv, dk, dv, dtype)


def causal_attention(q, k, v, scale: float, block: int = BLOCK, dtype=jnp.bfloat16,
                     window: int | None = None):
    """:func:`causal_attention_dense` with ``dtype`` operands, float32 out: the
    kernel pair where :func:`_takes_kernel` says so, else the blocked XLA form
    (``block`` is that form's rows).  Under a ``window`` neither form visits
    the keys wholly before a block's (a tile's) band."""
    b, t, h, _ = q.shape
    kv = k.shape[2]
    if window is not None and window >= t:
        window = None       # the band holds the whole triangle
    if _takes_kernel(t, h, kv, q.shape[3], v.shape[3], dtype):
        return attention_kernel.flash_attention(q, k, v, scale, dtype, window)
    q, k, v = q.astype(dtype), k.astype(dtype), v.astype(dtype)
    # With as many key heads as query heads the plain contraction; else the
    # query heads of one key head on an axis g of their own.
    scores, values = ("bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd") if kv == h else \
        ("bqhgd,bkhd->bhgqk", "bhgqk,bkhd->bqhgd")
    if kv != h:
        q = q.reshape(b, t, kv, h // kv, q.shape[-1])

    def rows(lo, hi, q, k, v):
        # The slices are taken INSIDE the checkpoint: what the backward keeps
        # is the whole q, k and v once, not a prefix of k and v a block.
        first = 0 if window is None else max(0, lo - window + 1)
        q_b, k_b, v_b = q[:, lo:hi], k[:, first:hi], v[:, first:hi]
        with jax.named_scope("rows"):
            s = jnp.einsum(scores, q_b, k_b, preferred_element_type=jnp.float32) * scale
            row = lo + jnp.arange(hi - lo)
            # (the columns' iota is written twice so that, without a window, the
            # lowered program is to the letter what it was before windows)
            seen = row[:, None] >= jnp.arange(first, hi)[None, :]
            if window is not None:
                seen &= row[:, None] - jnp.arange(first, hi)[None, :] < window
            s = jnp.where(seen, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(dtype)
            return jnp.einsum(values, p, v_b, preferred_element_type=jnp.float32)

    out = [
        jax.checkpoint(partial(rows, lo, min(lo + block, t)))(q, k, v)
        for lo in range(0, t, block)
    ]
    return jnp.concatenate(out, axis=1).reshape(b, t, h, v.shape[-1])
