"""Weights from ``--seed``: every leaf of a configuration in one jitted call,
on the device, in float32 (the type the detector keeps its masters in).

The benchmark makes the weights, not the program: the same numbers go to the
program (as its parameter tree, by leaf name) and to the plain reference, and
neither side sees anything the other made.  ``kind`` picks the distribution.
Output layers are drawn wide enough that logits are of order one, so that a
loss or a gradient computed in too low a precision reads differently; a
"0.01-normal" head gives log(classes) whatever the arithmetic.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0):
    """A key for any whole-number seed (the driver's pass 2**31)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def _leaf(key, shape, kind):
    fan_in = math.prod(shape[:-1]) if len(shape) > 1 else 1
    normal = lambda std: std * jax.random.normal(key, shape, jnp.float32)
    uniform = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if kind == "he":
        return normal(math.sqrt(2.0 / fan_in))
    if kind == "lecun":
        return normal(math.sqrt(1.0 / fan_in))
    if kind == "out_cls":
        return normal(math.sqrt(1.0 / fan_in))
    if kind == "out_rpn":
        return normal(0.5 * math.sqrt(1.0 / fan_in))
    if kind == "out_box":
        return normal(0.1 * math.sqrt(1.0 / fan_in))
    if kind == "bias":
        return normal(0.02)
    if kind == "cls_bias":
        # Background prior (nine in ten; at least three in four sampled rois are
        # background and a trained head is surer still): a
        # head that starts there (as a trained one is) has no coherent pull
        # on every weight at once, so the gradient's norm stays under the
        # optimizer's clip and leaf norms mean the same on both sides.
        prior = math.log(9.0 * (shape[0] - 1))
        return normal(0.02).at[0].add(prior)
    if kind == "bn_scale":
        return uniform(0.7, 1.0)
    if kind == "bn_scale_res":
        return uniform(0.2, 0.4)
    if kind in ("bn_bias", "bn_mean"):
        return normal(0.05)
    if kind == "bn_var":
        return uniform(0.8, 1.2)
    raise ValueError(f"unknown weight kind {kind!r}")


def make_weights(seed: int, specs) -> dict:
    """{path: array} for ``specs`` = [(path, shape, kind)], one device call."""
    specs = tuple((p, tuple(s), k) for p, s, k in specs)

    @jax.jit
    def build(key):
        return {
            p: _leaf(jax.random.fold_in(key, i), s, k)
            for i, (p, s, k) in enumerate(specs)
        }

    return build(seed_key(seed, 1))


def nest(flat: dict, prefix: str) -> dict:
    """{"a/b/c": x} under ``prefix`` -> {"a": {"b": {"c": x}}} (the detector's
    tree of that collection)."""
    out: dict = {}
    for path, leaf in flat.items():
        if not path.startswith(prefix + "/"):
            continue
        node = out
        parts = path[len(prefix) + 1:].split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return out


def flatten(tree, prefix: str) -> dict:
    out = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k in node:
                walk(node[k], f"{path}/{k}")
        else:
            out[path] = node

    walk(tree, prefix)
    return out
