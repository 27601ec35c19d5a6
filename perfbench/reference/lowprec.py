"""The control's arithmetic: the plain reference with every matmul and
convolution operand rounded to a lower precision than the configuration
states - what a later PR would be tempted to do.  The configurations here
state bfloat16 compute, so the control rounds to eight bits.

``int8`` is the step this chip tempts to (the v5e multiplies int8 at twice its
bfloat16 peak and has no fp8 unit): symmetric, one scale per tensor (amax to
127).  ``fp8`` is float8_e4m3fn with one scale per tensor (amax to the
format's largest finite value), the usual forward format of fp8 training.
Both roundings are straight-through, so the backward's matmuls see the rounded
operands and an unrounded cotangent.  ``bf16`` is there to show the comparison
does NOT fire on the stated precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _straight_through(fn):
    @jax.custom_vjp
    def f(x):
        return fn(x)

    f.defvjp(lambda x: (fn(x), None), lambda _, g: (g,))
    return f


def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _int8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 127.0 / amax
    return jnp.round(x * scale) / scale


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


ROUNDINGS = {
    "int8": _straight_through(_int8), "fp8": _straight_through(_fp8),
    "bf16": _straight_through(_bf16),
}
