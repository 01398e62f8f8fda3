"""Plain reference: GPT-2's decoder (Radford et al. 2019; the 117M row:
12 layers, 12 heads, width 768, 1024 positions, 50257 tokens) with the
next-token cross-entropy averaged over all positions, in `jax.numpy`.

Imports nothing of the program. Pre-norm blocks, learned positions, one
fused QKV projection, GELU in its tanh form (GPT-2's own), LayerNorm eps
1e-5, the output head tied to the token embedding. Linear weights are
(out, in), as the program names and stores them.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import precision as P

EPS = 1e-5


def _linear(x, w, b, mode):
    y = lax.dot_general(P.operand(x, mode),
                        P.operand(P.weight(w, mode), mode),
                        (((x.ndim - 1,), (1,)), ((), ())),
                        precision=P.matmul_precision(mode))
    return y if b is None else y + b.astype(y.dtype)


def _layer_norm(x, gamma, beta):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) * lax.rsqrt(var + EPS) * gamma + beta).astype(x.dtype)


def _block(p, x, i, heads, mode):
    b, t, e = x.shape
    d = e // heads
    h = _layer_norm(x, p["h%d_ln1_gamma" % i], p["h%d_ln1_beta" % i])
    qkv = _linear(h, p["h%d_attn_qkv_weight" % i],
                  p["h%d_attn_qkv_bias" % i], mode)
    q, k, v = (qkv[..., j * e:(j + 1) * e].reshape(b, t, heads, d)
               for j in range(3))
    prec = P.matmul_precision(mode)
    scores = jnp.einsum("bihd,bjhd->bhij", P.operand(q, mode),
                        P.operand(k, mode), precision=prec)
    scores = scores.astype(jnp.float32) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhij,bjhd->bihd", P.operand(probs, mode),
                     P.operand(v, mode), precision=prec).reshape(b, t, e)
    x = x + _linear(ctx, p["h%d_attn_out_weight" % i],
                    p["h%d_attn_out_bias" % i], mode)
    h = _layer_norm(x, p["h%d_ln2_gamma" % i], p["h%d_ln2_beta" % i])
    h = jax.nn.gelu(_linear(h, p["h%d_mlp_up_weight" % i],
                            p["h%d_mlp_up_bias" % i], mode),
                    approximate=True)
    return x + _linear(h, p["h%d_mlp_down_weight" % i],
                       p["h%d_mlp_down_bias" % i], mode)


def loss(p, tokens, labels, mode="float32", heads=12, remat=True):
    layers = 1 + max(int(n[1:n.index("_")]) for n in p
                     if n.startswith("h") and n[1].isdigit())
    t = tokens.shape[1]
    emb = P.weight(p["tok_embed_weight"], mode)
    x = jnp.take(emb, tokens.astype(jnp.int32), axis=0)
    x = x + P.weight(p["pos_embed_weight"], mode)[:t][None]
    for i in range(layers):
        # recompute a block's inside in the backward pass: the same
        # arithmetic, and float32 scores at the timed batch fit
        block = (lambda q, inp, i=i: _block(q, inp, i, heads, mode))
        x = (jax.checkpoint(block) if remat else block)(p, x)
    x = _layer_norm(x, p["lnf_gamma"], p["lnf_beta"])
    logits = _linear(x, p["tok_embed_weight"], None, mode)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked)


def trainable(name):
    return True
