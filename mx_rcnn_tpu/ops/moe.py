"""A mixture-of-experts layer that is told which experts it holds: it routes
over ALL the published experts, and computes the part of the result that its
own experts give (what expert parallelism asks of a layer; on one chip there
is no exchange, and nothing stands in for the absent chips).

:func:`route` is the published router: sigmoid scores in float32, selection on
score + bias by groups (a group's score the sum of its two best, the best
``topk_group`` groups kept; one group: no group stage), the top ``k`` experts
among them, weights ``scale * s_e / sum of the selected s`` over all ``k``
whether held or not.

:func:`held_experts` computes the held experts' part WITHOUT a dispatch: every
held expert over EVERY token, as one MLP of width ``count * F`` whose hidden
units are weighted by the token's routing weight for their expert (0 where the
token did not pick it).  Its work is ``count * T`` rows whatever the router
does, so a step's time does not move with the seed's routing and no slot can
be dropped.  It is ``count * num_experts / k`` times the FLOPs a balanced
load needs (21 x at 8 of 128 held, top 6) and was still faster on the chip
than a sort by expert, a gather, ``lax.ragged_dot`` and a scatter at every
routing met (PERF.md section 6, PR 32): this is the form for a share of a few
experts; a share of many wants a dispatch that costs what its slots need.
"""

from __future__ import annotations

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
    if n_group > 1:
        grouped = sel.reshape(t, n_group, e // n_group)
        # A group's two best, without sorting the group: the best, and the best of the rest.
        best = jnp.max(grouped, axis=-1, keepdims=True)
        at = jnp.argmax(grouped, axis=-1, keepdims=True)
        rest = jnp.where(jnp.arange(e // n_group) == at, -jnp.inf, grouped)
        group_score = best[..., 0] + jnp.max(rest, axis=-1)
        _, best = lax.top_k(group_score, topk_group)
        keep = jnp.zeros((t, n_group), bool).at[jnp.arange(t)[:, None], best].set(True)
        sel = jnp.where(jnp.repeat(keep, e // n_group, axis=1), sel, -jnp.inf)
    _, experts = lax.top_k(sel, k)
    picked = jnp.take_along_axis(s, experts, axis=1)
    return experts.astype(jnp.int32), scale * picked / jnp.sum(picked, axis=1, keepdims=True)


def held_experts(x, experts, weights, w_gate, w_up, w_down, first: int, dtype=jnp.bfloat16):
    """x (T, D); experts/weights (T, k); w_up (count, D, F), w_down (count,
    F, D) and, for the gated SwiGLU expert, w_gate (count, D, F); ``w_gate``
    None is the two-matrix ``W_down relu(W_up x)^2``.  ``sum_e w[t, e] E_e(x_t)``
    over the experts ``first .. first + count`` with ``w[t, e]`` the token's
    routing weight for expert e, 0 where it did not pick it.
    -> (y (T, D) float32, counters)."""
    count = w_up.shape[0]
    with jax.named_scope("dispatch"):
        picked = (experts - first)[:, :, None] == jnp.arange(count)          # (T, k, count)
        w_te = jnp.sum(jnp.where(picked, weights[:, :, None], 0.0), axis=1)   # (T, count)
        load = jnp.sum(picked, axis=(0, 1))
    with jax.named_scope("experts"):
        xq = x.astype(dtype)
        wide = lambda w: jnp.einsum("td,edf->tef", xq, w.astype(dtype),
                                    preferred_element_type=jnp.float32)
        hidden = jnp.square(jax.nn.relu(wide(w_up))) if w_gate is None \
            else jax.nn.silu(wide(w_gate)) * wide(w_up)
        y = jnp.einsum("tef,efd->td", (hidden * w_te[:, :, None]).astype(dtype),
                       w_down.astype(dtype), preferred_element_type=jnp.float32)
    slots = jnp.sum(load)
    counters = {
        "moe_slots_here": slots.astype(jnp.float32),
        "moe_load_max_over_mean": jnp.max(load) * count / jnp.maximum(slots, 1).astype(jnp.float32),
        "moe_dropped_slots": jnp.zeros((), jnp.float32),    # no dispatch: nothing to drop
        "moe_tokens_without_held_expert": 1.0 - jnp.mean(
            jnp.any(picked, axis=(1, 2)).astype(jnp.float32)
        ),
    }
    return y, counters
