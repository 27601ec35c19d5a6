"""A mixture-of-experts layer that is told which experts it holds: it routes
over ALL the published experts, and computes the part of the result that its
own experts give (what expert parallelism asks of a layer; on one chip there
is no exchange, and nothing stands in for the absent chips).

:func:`route` is the published router: sigmoid scores in float32, selection on
score + bias by groups (a group's score the sum of its two best, the best
``topk_group`` groups kept), the top ``k`` experts among them, weights
``scale * s_e / sum of the selected s`` over all ``k`` whether held or not.

:func:`held_experts` dispatches without dropping: the token-slots whose
expert lies in ``[first, first + count)`` are sorted by expert and go through
``jax.lax.ragged_dot`` (one grouped matmul per projection), then are summed
back onto their tokens.  Shapes are static and a router may send every token
to one expert (tokens that look alike do), so the sorted rows are as many as
every token's every slot that could land here; they are taken ``segment``
rows at a time under a ``lax.scan`` whose body skips (``lax.cond``) the
segments past the last routed slot.  Work and memory follow the slots that
were routed, a few microseconds a skipped segment aside, and nothing can
overflow: ``moe_dropped_slots`` is computed from the same sizes and reads 0.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def route(x, w_router, e_bias, n_group: int, topk_group: int, k: int, scale: float):
    """x (T, D) -> (experts (T, k) int32, weights (T, k) float32)."""
    with jax.named_scope("scores"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w_router.astype(jnp.float32), precision=lax.Precision.HIGHEST
        ))
    t, e = s.shape
    sel = s + e_bias.astype(jnp.float32)
    grouped = sel.reshape(t, n_group, e // n_group)
    # A group's two best, without sorting the group: the best, and the best of the rest.
    best = jnp.max(grouped, axis=-1, keepdims=True)
    at = jnp.argmax(grouped, axis=-1, keepdims=True)
    rest = jnp.where(jnp.arange(e // n_group) == at, -jnp.inf, grouped)
    group_score = best[..., 0] + jnp.max(rest, axis=-1)
    _, best = lax.top_k(group_score, topk_group)
    keep = jnp.zeros((t, n_group), bool).at[jnp.arange(t)[:, None], best].set(True)
    masked = jnp.where(jnp.repeat(keep, e // n_group, axis=1), sel, -jnp.inf)
    _, experts = lax.top_k(masked, k)
    picked = jnp.take_along_axis(s, experts, axis=1)
    return experts.astype(jnp.int32), scale * picked / jnp.sum(picked, axis=1, keepdims=True)


# Rows of one dispatch segment, as a multiple of the slots a uniform router
# sends to the held experts: a program choice, not an option.  It moves speed
# and memory, never the result (every slot has a row whatever the segment).
SEGMENT_FACTOR = 4


def segment_rows(tokens: int, k: int, count: int, num_experts: int) -> int:
    """Rows of one dispatch segment: ``SEGMENT_FACTOR`` times the slots a
    uniform router sends to ``count`` of ``num_experts``, rounded up to 128
    rows (one segment then holds a balanced step's slots with room), and never
    more than every slot a token could send here."""
    uniform = tokens * k * count / num_experts
    return min(tokens * min(k, count), math.ceil(SEGMENT_FACTOR * uniform / 128) * 128)


def held_experts(x, experts, weights, w_gate, w_up, w_down, first: int, segment: int,
                 dtype=jnp.bfloat16):
    """x (T, D); experts/weights (T, k); w_gate, w_up (count, D, F), w_down
    (count, F, D): the held experts' SwiGLU on the slots routed to them,
    weighted and summed per token.  -> (y (T, D) float32, counters)."""
    t, k = experts.shape
    count = w_gate.shape[0]
    worst = t * min(k, count)                 # slots that could land here
    n_seg = -(-worst // segment)
    rows = n_seg * segment
    with jax.named_scope("dispatch"):
        local = experts.reshape(-1) - first
        here = (local >= 0) & (local < count)
        local = jnp.where(here, local, count)
        order = jnp.argsort(local, stable=True)               # held slots first, by expert
        order = jnp.pad(order, (0, max(0, rows - t * k)))[:rows]
        load = jnp.sum(local[:, None] == jnp.arange(count)[None, :], axis=0)
        ends = jnp.minimum(jnp.cumsum(load), rows)            # == cumsum(load): rows >= worst
        routed = ends[-1]
        row_w = jnp.where(jnp.arange(rows) < routed, weights.reshape(-1)[order], 0.0)
        xq = x.astype(dtype)
    gate, up, down = (w.astype(dtype) for w in (w_gate, w_up, w_down))

    @jax.checkpoint
    def segment_out(token, w_row, start):
        # This segment's share of each expert's group.
        sizes = jnp.diff(jnp.clip(ends - start, 0, segment), prepend=0).astype(jnp.int32)
        dot = lambda a, w: lax.ragged_dot(
            a.astype(dtype), w, sizes, preferred_element_type=jnp.float32
        )
        # Rows past the last routed slot belong to no group: what the grouped
        # matmul leaves there, forward or backward, is not defined, so they are
        # selected away on the way in, in between and on the way out (a select
        # also stops their cotangents; a product with 0 would pass a NaN on).
        live = (w_row != 0.0)[:, None]
        with jax.named_scope("dispatch"):
            rows_in = jnp.where(live, xq[token], 0)
        with jax.named_scope("experts"):
            keep = lambda a: jnp.where(live, a, 0.0)
            hidden = jax.nn.silu(keep(dot(rows_in, gate))) * keep(dot(rows_in, up))
            out = dot(hidden, down)
        with jax.named_scope("combine"):
            return jnp.where(live, out * w_row[:, None], 0.0)

    def one_segment(y, xs):
        # The sum onto the tokens stays outside the checkpoint, which would
        # otherwise keep a copy of ``y`` for every segment.
        token, w_row, start = xs

        def run(y):
            out = segment_out(token, w_row, start)
            with jax.named_scope("combine"):
                return y.at[token].add(out)

        return lax.cond(start < routed, run, lambda y: y, y), None

    starts = jnp.arange(n_seg, dtype=jnp.int32) * segment
    y, _ = lax.scan(
        one_segment, jnp.zeros((t, x.shape[1]), jnp.float32),
        ((order // k).reshape(n_seg, segment), row_w.reshape(n_seg, segment), starts),
    )
    slots = jnp.sum(load)
    counters = {
        "moe_slots_here": slots.astype(jnp.float32),
        "moe_load_max_over_mean": jnp.max(load) * count / jnp.maximum(slots, 1).astype(jnp.float32),
        "moe_dropped_slots": (slots - routed).astype(jnp.float32),
        "moe_tokens_without_held_expert": 1.0 - jnp.mean(
            jnp.any(here.reshape(t, k), axis=1).astype(jnp.float32)
        ),
    }
    return y, counters
