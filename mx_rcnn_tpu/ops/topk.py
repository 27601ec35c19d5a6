"""Blocked (hierarchical) exact top-k.

``lax.top_k`` over the full anchor set is the single hottest non-matmul
op in the train step (7.40 ms for the two 268,569-anchor images of the
recipe batch — ``tools/perf_breakdown.py`` micro-bench): XLA lowers it to
a full sort of the operand.  :func:`hierarchical_top_k` replaces the one
global sort with a two-stage reduction —

  1. reshape the operand into ``nb`` contiguous blocks and take a
     per-block ``top_k`` (one batched sort over ``block``-sized rows,
     VPU-friendly and parallel across blocks);
  2. merge: one final ``top_k`` over the ``nb * min(k, block)``
     survivors, then gather the surviving global indices.

EXACTNESS (bit-identical to ``lax.top_k``, including ties):

``lax.top_k`` orders by (value desc, index asc) — the lower index wins a
tie.  The blocked reduction preserves that total order end to end:

- Any element of the true global top-k has fewer than ``k`` elements
  ahead of it in that order *globally*, hence fewer than ``k`` ahead of
  it *within its own block*, so it survives stage 1 (which keeps
  ``min(k, block)`` per block).  The survivor set therefore contains the
  true top-k.
- Stage 1 emits survivors in (block asc, within-block rank asc) layout.
  Restricted to any fixed value, within-block rank asc == index asc
  (per-block ``top_k`` is index-stable) and blocks are index-contiguous,
  so survivor *position* order restricted to equal values equals global
  *index* order.  Stage 2's ``top_k`` breaks its ties by survivor
  position — i.e. by global index — exactly like the global sort.
- Padding (added to fill the last block) carries the dtype's minimum and
  sits at the highest indices of the last block, so it loses every tie
  against real entries; and since ``k <= a`` there are always at least
  ``k`` real survivors (any full block alone yields ``min(k, block)``
  of them), padding can never be selected.

Used by proposal generation (``ops/proposals.py``, ``topk_impl="hier"``,
the default) and anchor subsampling (``ops/sampling.py::_select_random``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _floor_value(dtype):
    """Value that sorts (weakly) below every element of ``dtype``.

    Static dtype dispatch on the host — a branch on the dtype, never on a
    traced value.  Through jax's dtype lattice, not numpy's: bfloat16 (the
    RPN head's output dtype under the mixed precision policy, so the dtype
    of the scores the recipe-width R50-FPN step ranks) is a float to jax
    and an opaque 'V' kind to numpy.
    """
    if jax.dtypes.issubdtype(dtype, np.inexact):
        return -np.inf
    return jnp.iinfo(dtype).min


def hierarchical_top_k(scores: jnp.ndarray, k: int, block: int = 32768):
    """Exact ``lax.top_k(scores, k)`` via a blocked two-stage reduction.

    Bit-identical values AND indices (see the module docstring for the
    tie-break proof).  Falls back to the plain ``lax.top_k`` whenever
    blocking cannot help: ``a <= block`` (single block) or ``k >= block``
    (every block would survive whole).

    Args:
      scores: (A,) operand — 1-D; callers batch via ``vmap``.
      k: number of entries to keep (``k <= A``, as for ``lax.top_k``).
      block: stage-1 tile width.  Power-of-two multiples of the 128-lane
        VPU width keep the batched per-block sort layout-friendly.

    Returns:
      ``(values (k,), indices (k,))`` exactly as ``lax.top_k``.
    """
    if scores.ndim != 1:
        raise ValueError(f"hierarchical_top_k expects 1-D scores, got {scores.shape}")
    a = scores.shape[0]
    if k > a:
        raise ValueError(f"k={k} exceeds operand size {a}")
    if block <= 0 or a <= block or k >= block:
        return lax.top_k(scores, k)

    with jax.named_scope("topk_hier"):
        nb = -(-a // block)
        pad = nb * block - a
        if pad:
            scores = jnp.concatenate(
                [scores, jnp.full((pad,), _floor_value(scores.dtype), scores.dtype)]
            )
        tiles = scores.reshape(nb, block)
        kb = min(k, block)
        part_vals, part_idx = lax.top_k(tiles, kb)          # (nb, kb)
        gidx = part_idx + jnp.arange(nb, dtype=part_idx.dtype)[:, None] * block
        top_vals, pos = lax.top_k(part_vals.reshape(-1), k)
        return top_vals, jnp.take(gidx.reshape(-1), pos)
