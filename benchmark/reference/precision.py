"""How the plain references compute at a stated precision.

  float32   every array float32, matrix products at `highest`
  bfloat16  weights of rank >= 2 and activations in bfloat16, statistics,
            the loss and the master weights in float32 (mixed precision)
  fp8       as bfloat16, and both operands of every matrix product or
            convolution rounded to float8_e4m3fn's values under a per-tensor scale
            in the forward pass (straight through in the backward pass,
            which multiplies the rounded operands in bfloat16)

float32 is the reference; the lower ones are the controls of "How
`correct` is decided": the step that would tempt a later PR.
"""
import jax.numpy as jnp
from jax import lax

MODES = ("float32", "bfloat16", "fp8")
_E4M3_MAX = 448.0


def act_dtype(mode):
    return jnp.float32 if mode == "float32" else jnp.bfloat16


def matmul_precision(mode):
    return lax.Precision.HIGHEST if mode == "float32" else None


def weight(w, mode):
    """A weight as the forward pass reads it."""
    if mode != "float32" and w.ndim >= 2:
        return w.astype(jnp.bfloat16)
    return w


def _round_e4m3(v):
    """float32 `v`, |v| <= 448, rounded to the nearest float8_e4m3fn value
    (three bits of mantissa, exponents from -6, steps of 2**-9 below
    that), ties to even, by arithmetic. Not by a cast to the type and
    back: the TPU compiler may drop such a pair as excess precision, and
    did (PERF.md, PR 27), which left a control that rounded nothing."""
    _, e = jnp.frexp(v)                       # v = m * 2**e, 0.5 <= |m| < 1
    step = jnp.exp2((jnp.maximum(e - 1, -6) - 3).astype(jnp.float32))
    return jnp.clip(jnp.round(v / step) * step, -_E4M3_MAX, _E4M3_MAX)


def operand(x, mode):
    """An operand of a matrix product as the unit multiplies it."""
    if mode != "fp8":
        return x
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / _E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (_round_e4m3(xf / scale) * scale).astype(x.dtype)
    # straight through: the backward pass sees the rounding as the identity
    # (a cotangent cast to e4m3 without a scale of its own underflows to 0)
    return x + lax.stop_gradient(q - x)
