"""The gated short convolution of LFM2: a mixer with no state beyond a few
tokens and no attention.

    [B; C; x~] = W_in u            (3H outputs, in that order)
    z = B * x~
    y_t = sum_j w[:, j] z_{t - (K - 1) + j}     (depthwise, causal)
    out = W_out (C * y)

Zeros stand before each sequence's start, so nothing crosses from one
sequence of a batch to the next. The two products take u's dtype in and
give float32 sums, as `moe.shared_expert_ffn` does; the gates multiply in
float32 and hand the next product u's dtype. The convolution is
`linear_attention._depthwise_causal`, with its own backward. The whole
mixer is one op kind, so its device time has one owner,
`mx._contrib_short_conv.*`; each trace counts one layer in
`short_conv.layers`.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..observability import registry as _obs
from .linear_attention import _depthwise_causal, _precision
from .moe import _mm
from .registry import register

__all__ = ["short_conv"]

LAYERS = _obs.counter(
    "short_conv.layers",
    "Times short_conv (ops/short_conv.py, _contrib_short_conv) was traced "
    "into a program: once a gated short-convolution layer each time its "
    "program is traced")


def short_conv(x, w_in, w_conv, w_out):
    """x: (B, T, H); w_in: (3H, H); w_conv: (H, K); w_out: (H, H), the
    matrices (out, in). Returns (B, T, H) in x's dtype."""
    LAYERS.inc()
    H = w_out.shape[0]
    prec = _precision(x.dtype)
    bcx = _mm(x, w_in.astype(x.dtype), (x.ndim - 1, 1), prec)
    b, c, xt = bcx[..., :H], bcx[..., H:2 * H], bcx[..., 2 * H:]
    y = _depthwise_causal((b * xt).astype(x.dtype), w_conv)
    gated = (c * y.astype(jnp.float32)).astype(x.dtype)
    return _mm(gated, w_out.astype(x.dtype), (x.ndim - 1, 1),
               prec).astype(x.dtype)


@register("_contrib_short_conv")
def _short_conv_op(x, in_weight, conv_weight, out_weight):
    return short_conv(x, in_weight, conv_weight, out_weight)
