"""int8 weight-only PTQ for serving: box-head program + full network.

Two quantization surfaces, same numerics (symmetric per-output-channel
int8 with f32 scales, ``utils/precision.py``):

* **Box head** (``quantize_box_head`` / ``apply_box_head_q8``) — the
  original ``full_q8`` degrade level.  Weight-only int8 over the four
  BoxHead Dense kernels (fc6 / fc7 / cls_score / bbox_pred); biases
  stay f32.  At serving time the int8 weights dequantize to bf16
  in-graph (one f32 multiply per weight, fused by XLA into the
  parameter load — the same shape of trick as the frozen-BN fold) and
  the dots run bf16 x bf16 with f32 accumulation via
  ``preferred_element_type`` — the MXU's native mode.  Logits/deltas
  are emitted f32, the BoxHead output contract, so postprocess
  (softmax, decode, NMS) is byte-for-byte the production graph.

* **Full network** (``quantize_network`` / ``dequantize_network``) —
  the ``full_q8n`` degrade level.  Every ``params`` kernel with an
  output-channel axis (backbone convs, FPN laterals/top-down, the RPN
  head, the box head) is replaced by an int8/scale pair; biases and the
  frozen-BN ``constants`` collection pass through f32.
  ``dequantize_network`` runs INSIDE the jitted serving program: the
  scale multiply happens in f32 (exact: ``q`` is integral, ``scale`` a
  power-free f32, so ``q*scale`` round-trips the rounded weight
  bit-for-bit) and the reconstructed master rides the model's existing
  flax param→compute cast — dequant→bf16 compute with
  ``preferred_element_type=f32`` accumulation, no second cast path.
  Under the all-f32 tiny_synthetic policy the only error is the int8
  rounding itself, so CPU tests can pin per-layer budgets exactly
  (|w - deq| ≤ scale/2 per channel).

Why weight-only: it needs NO calibration data.  The head's Dense
kernels dominate its bytes (fc6 alone is ``S*S*C x 1024``; the VGG
recipe's fc6/fc7 are ~0.5 GB of f32 — 4x smaller as int8); the
full-network tree cuts weight HBM traffic ~4x across the backbone/FPN/
RPN too, which is where the serving FLOPs live (ROADMAP item 1).
Activations stay in the policy dtype — activation quantization would
cost a calibration sweep for little serving win.

Numerics: symmetric int8 with per-output-channel scales keeps the
worst-case relative weight error ~= 1/254 per channel; the acceptance
tolerance (tests/test_precision.py) is per-layer error budgets plus an
mAP-parity gate on final detections, because the softmax/NMS pipeline
absorbs sub-percent logit noise for all but threshold-straddling
detections.

Wiring: the quantizers run once at runner construction (the quantized
trees are device_put and PASSED AS ARGUMENTS to the jitted steps —
closed-over arrays would embed as HLO constants and blow the
remote-compile request limit, see serve/engine.py's eval note);
:func:`apply_box_head_q8` is injected into
``detection/graph.py::forward_inference`` through ``box_head_apply``,
while :func:`dequantize_network` reconstructs the whole variables tree
in-graph so ``forward_inference`` itself is untouched.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.utils.precision import dequantize, quantize_per_channel

# The BoxHead Dense layers, in application order (models/heads.py).
QUANT_LAYERS = ("fc6", "fc7", "cls_score", "bbox_pred")


def quantize_box_head(variables) -> dict:
    """Quantize the box head's Dense kernels out of a full variables tree.

    Returns ``{layer: {"q": int8 (in, out), "scale": f32 (1, out),
    "bias": f32 (out,)}}`` — a plain pytree, safe to ``device_put`` and
    pass through jit boundaries."""
    params = variables["params"]["box_head"]
    out = {}
    for name in QUANT_LAYERS:
        q, scale = quantize_per_channel(
            jnp.asarray(params[name]["kernel"]), axis=-1
        )
        out[name] = {
            "q": q,
            "scale": scale,
            "bias": jnp.asarray(params[name]["bias"], jnp.float32),
        }
    return out


def apply_box_head_q8(
    qtree: dict, pooled: jnp.ndarray, compute_dtype: Any = jnp.bfloat16
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The int8/bf16 box-head program (BoxHead.__call__'s contract).

    pooled: (R, S, S, C) pooled features -> f32 (R, num_classes) logits,
    f32 (R, n_reg, 4) deltas.  Each Dense: dequant int8 -> bf16 weights,
    bf16 activations, f32-accumulated dot, f32 bias add; ReLU runs on
    the f32 accumulator and the result downcasts once into the next
    layer's bf16 operand.
    """

    def dense(x: jnp.ndarray, name: str) -> jnp.ndarray:
        layer = qtree[name]
        w = dequantize(layer["q"], layer["scale"], compute_dtype)
        y = jax.lax.dot_general(
            x.astype(compute_dtype), w,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return y + layer["bias"]

    r = pooled.shape[0]
    x = pooled.reshape(r, -1)
    x = jax.nn.relu(dense(x, "fc6"))
    x = jax.nn.relu(dense(x, "fc7"))
    logits = dense(x, "cls_score")
    deltas = dense(x, "bbox_pred")
    return (
        logits.astype(jnp.float32),
        deltas.reshape(r, -1, 4).astype(jnp.float32),
    )


# ---------------------------------------------------------------------------
# full-network PTQ (the ``full_q8n`` degrade level)
# ---------------------------------------------------------------------------


def _path_keys(path) -> list:
    """Dict/attr key names along a tree_util key path (version-robust)."""
    out = []
    for k in path:
        key = getattr(k, "key", None)
        if key is None:
            key = getattr(k, "name", None)
        out.append(key)
    return out


def is_quantized_leaf(x: Any) -> bool:
    """True for the ``{"q": int8, "scale": f32}`` marker dicts that
    :func:`quantize_network` substitutes for quantizable kernels."""
    return isinstance(x, dict) and set(x.keys()) == {"q", "scale"}


def quantize_network(variables) -> dict:
    """Whole-tree weight-only PTQ: every ``params`` leaf named
    ``kernel`` with ndim >= 2 (conv and dense kernels all share that
    name and layout — output channel last) becomes ``{"q": int8,
    "scale": f32}``; every other leaf (biases, frozen-BN ``constants``)
    passes through unchanged.  The result is a plain pytree with the
    same dict skeleton as ``variables``, safe to ``device_put`` and
    pass through jit boundaries."""
    from jax.tree_util import tree_map_with_path

    def one(path, leaf):
        keys = _path_keys(path)
        if any(k in keys for k in ("router", "kda", "ssm", "mamba", "lambda")):
            # A decoder backbone (models/decoder.py): int8 weights move the
            # router's top-k picks, the KDA decay gate, a state-space scan's
            # decays (A_log, dt_bias, D, the conv and the projections dt, B
            # and C come from: a Mamba-2 ``ssm`` mixer's in_proj, a Mamba-1
            # ``mamba`` mixer's x_proj and dt_proj) and differential
            # attention's lambda, and the repo has no calibration or parity
            # check for any of them.
            raise NotImplementedError(
                "full-network int8 (the full_q8n level) is not defined for a "
                f"decoder backbone: leaf {'/'.join(map(str, keys))!r} belongs to an "
                "expert router, a KDA mixer, a state-space (ssm, mamba) mixer or "
                "differential attention's lambda, whose selection, decay gate, "
                "scan (A_log, dt_bias, D, x_proj, dt_proj, its conv) and "
                "difference need a calibrated quantization this repo does not "
                "have; serve it at the float levels"
            )
        leaf = jnp.asarray(leaf)
        if keys and keys[0] == "params" and keys[-1] == "kernel" \
                and leaf.ndim >= 2:
            q, scale = quantize_per_channel(leaf, axis=-1)
            return {"q": q, "scale": scale}
        return leaf

    return tree_map_with_path(one, variables)


def dequantize_network(qnet, dtype: Any = jnp.float32):
    """In-graph inverse of :func:`quantize_network`: rebuild a full
    variables tree the model can apply.  Dequantization to f32 is exact
    modulo the original int8 rounding (integral ``q`` times its channel
    scale), and the reconstructed masters then ride the model's normal
    flax param→compute-dtype cast — so the q8n program IS the production
    graph with rounded weights, nothing else moves."""
    return jax.tree_util.tree_map(
        lambda x: dequantize(x["q"], x["scale"], dtype)
        if is_quantized_leaf(x) else x,
        qnet,
        is_leaf=is_quantized_leaf,
    )
