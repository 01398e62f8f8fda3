"""Causal grouped-query attention in row blocks, and the rotary embedding.

`blocked_causal_attention` never holds a T x T array: the queries are
cut into blocks of `block_q` rows and each block attends to its causal
prefix of keys alone, so the work is (n + 1) / 2n of the square for n
blocks. Every block is a `jax.checkpoint`: the forward pass keeps the
block's inputs and output only, and the backward pass recomputes one
block's scores, takes their gradient and adds the block's share to the
prefix of dK and dV. The scores, the softmax and the sums are float32;
the two products take their operands in the inputs' dtype.

The value heads may be of another size than the query and key heads
(latent attention: a key is a head's own part with a part that all heads
share joined on, 192 wide against values of 128); the output then has
the values' size. Such a call is counted in `attention.latent.layers`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..observability import registry as _obs
from .linear_attention import _precision
from .registry import register

__all__ = ["blocked_causal_attention", "rotary_embedding"]

_NEG_INF = -1e30

LATENT_LAYERS = _obs.counter(
    "attention.latent.layers",
    "Times blocked_causal_attention was traced into a program with value "
    "heads of another size than the query and key heads: once a latent "
    "attention layer each time its program is traced")


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _row_block(q, k, v, first_row, scale):
    """q: (B, Q, Hkv, G, D), rows first_row..first_row+Q-1; k: (B, S,
    Hkv, D) and v: (B, S, Hkv, Dv), columns 0..S-1. Returns (B, Q, Hkv,
    G, Dv). `first_row` None: no mask."""
    prec = _precision(q.dtype)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=prec,
                   preferred_element_type=jnp.float32) * scale
    if first_row is not None:
        rows = first_row + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        cols = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=prec,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def blocked_causal_attention(q, k, v, block_q=512, scale=None, causal=True):
    """q: (B, T, Hq, D); k: (B, T, Hkv, D); v: (B, T, Hkv, Dv), Hq a
    multiple of Hkv (query head h reads key/value head h // (Hq // Hkv)).
    Returns (B, T, Hq, Dv). `scale` is D^-1/2 unless given.
    `causal=False` gives every block all the keys and no mask."""
    B, T, Hq, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    if Dv != D:
        LATENT_LAYERS.inc()
    if Hq % Hkv:
        raise ValueError("blocked_causal_attention: %d query heads over %d "
                         "key/value heads" % (Hq, Hkv))
    bq = min(int(block_q), T)
    if T % bq:
        raise ValueError("blocked_causal_attention: %d rows do not divide "
                         "into blocks of %d" % (T, bq))
    scale = float(D) ** -0.5 if scale is None else float(scale)
    qg = q.reshape(B, T, Hkv, Hq // Hkv, D)
    if causal:
        out = [_row_block(qg[:, i:i + bq], k[:, :i + bq], v[:, :i + bq], i,
                          scale) for i in range(0, T, bq)]
    else:
        out = [_row_block(qg[:, i:i + bq], k, v, None, scale)
               for i in range(0, T, bq)]
    return jnp.concatenate(out, axis=1).reshape(B, T, Hq, Dv)


def rotary_embedding(x, rotary_dim, theta=10000.0):
    """Rotate the first `rotary_dim` of the last axis by position, in
    halves (dimension i pairs with i + rotary_dim / 2). x: (B, T, H, D)."""
    T, half = x.shape[1], int(rotary_dim) // 2
    inv = float(theta) ** (-jnp.arange(half, dtype=jnp.float32)
                           * 2.0 / rotary_dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = (xf[..., :half], xf[..., half:2 * half],
                    xf[..., 2 * half:])
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1).astype(x.dtype)


@register("_contrib_causal_gqa_attention")
def _causal_gqa_attention_op(q, k, v, *, block_q=512, scale=None):
    # kept by a rematerialised group (graph.REMAT_KEEP): the layer's
    # second forward pass then skips the attention, whose backward
    # recomputes its scores block by block anyway
    return checkpoint_name(blocked_causal_attention(q, k, v, block_q, scale),
                           "mx.keep")


@register("_contrib_rotary_embedding")
def _rotary_embedding_op(x, *, rotary_dim, theta=10000.0):
    return rotary_embedding(x, rotary_dim, theta)
