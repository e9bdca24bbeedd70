"""Plain matrix products at a stated precision, for the reference models.

``mm(eq, a, b, mode)`` is ``jnp.einsum`` with both operands rounded to the
precision ``mode`` names and the product taken at full f32 precision, so
the result is the same on a TPU and on a CPU:

* ``f32``  -- no rounding: the reference itself.
* ``high`` -- operands kept to 16 significant bits (a bf16 head plus a bf16
  tail), the accuracy of XLA's three-pass ``Precision.HIGH``: the control
  for a configuration that states f32 at ``highest``.
* ``bf16`` -- operands rounded to bfloat16.
* ``fp8``  -- operands rounded to float8 e4m3 after a per-tensor scale to
  its range; cotangents to e5m2 with their own scale, as fp8 training does:
  the control for a configuration that states bf16.

Rounding works on the f32 bit pattern (round to nearest, ties to even),
not through a cast to the narrow type and back: XLA may fold such a cast
pair away (excess precision), which would leave the control in f32. The
rounding is its own VJP rule: the backward pass rounds each cotangent the
same way before it meets the next product, as a lower-precision backward
pass would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "high", "bf16", "fp8")

#: (explicit mantissa bits, smallest normal exponent, largest finite value)
_E4M3 = (3, -6, 448.0)
_E5M2 = (2, -14, 57344.0)


def round_mantissa(x, bits: int):
    """``x`` (f32) rounded to ``bits`` explicit mantissa bits, exponent
    range unchanged."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    half = jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & jnp.uint32(1))
    u = (u + half) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def round_float8(x, fmt):
    """``x`` scaled to the format's range, rounded to it (normals to its
    mantissa, subnormals to its smallest step), and scaled back."""
    bits, emin, top = fmt
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    y = x / scale
    step = 2.0 ** (emin - bits)
    sub = jnp.round(y / step) * step
    y = jnp.where(jnp.abs(y) < 2.0 ** emin, sub, round_mantissa(y, bits))
    return jnp.clip(y, -top, top) * scale


def _forward_round(x, mode):
    if mode == "high":
        return round_mantissa(x, 15)
    if mode == "bf16":
        return round_mantissa(x, 7)
    return round_float8(x, _E4M3)


def _backward_round(x, mode):
    if mode == "fp8":
        return round_float8(x, _E5M2)
    return _forward_round(x, mode)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def rounded(x, mode):
    return _forward_round(x, mode)


def _rounded_fwd(x, mode):
    return _forward_round(x, mode), None


def _rounded_bwd(mode, _, ct):
    return (_backward_round(ct, mode),)


rounded.defvjp(_rounded_fwd, _rounded_bwd)


def mm(eq: str, a, b, mode: str = "f32"):
    """``einsum(eq, a, b)`` in f32 with operands rounded to ``mode``."""
    if mode not in MODES:
        raise ValueError(f"unknown precision mode {mode!r}")
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode != "f32":
        a, b = rounded(a, mode), rounded(b, mode)
    return jnp.einsum(eq, a, b, precision=HIGHEST)
