"""Plain reference: LFM2-MoE's decoder (LiquidAI/LFM2-8B-A1B,
`config.json`) with the next-token cross-entropy averaged over all
positions, in `jax.numpy`. Imports nothing of the program.

RMS norm: y = w * x / sqrt(mean(x^2) + eps), w from one. Layer i (counted
from 0 over the layers kept, whose kinds `layer_types` gives): h = x +
mixer_i(norm(x)), then x' = h + ffn_i(norm(h)); the mixer is the gated
short convolution where the kind is "conv" and attention where it is
"full_attention"; the feed-forward is a SwiGLU in the first
`num_dense_layers` layers and the experts elsewhere. Linear weights are
(out, in). No biases. The head is the embedding (tied).

Gated short convolution (width H, L taps): [B, C, x~] = u W_in (3H
outputs in that order); z = B * x~; y_t = sum_{j < L} w[:, j] * z_{t - L +
1 + j}, zeros before each sequence's start (PyTorch's depthwise Conv1d
with L - 1 zeros of padding, its first T outputs); out = (C * y) W_out.

Attention (Hq query heads over Hkv key/value heads of D = H / Hq): q, k, v
= u W_q, u W_k, u W_v; q and k each through an RMS norm over the head
(one weight of D a kind); then rotary over all D dimensions in halves
(dimension i with i + D/2) at `theta`, position t the token's place in
its sequence; query head h reads key/value head h // (Hq / Hkv); causal
softmax at D^-1/2, the whole row of scores materialised (in blocks of
rows, each a `jax.checkpoint`); out = attn W_o.

Experts: s = sigmoid(u W_r) over ALL experts in float32; the k experts
with the largest s + b (b the expert bias); the weights are s of the
chosen, without b, over (their sum + 1e-6), times `routed_scale`; the
output is a loop over the experts HELD here, each a SwiGLU applied to
every token and weighed by the token's weight for it (0 where it was not
chosen); what the absent experts would add is left out. No shared
expert.

Departures of the program from this, each inside the limits of the
cell: bfloat16 operands of the matrix products with float32 sums,
bfloat16 activations between layers and between the short convolution's
gate and its taps; attention in row blocks over the causal prefix only;
the held experts computed on the rows routed to them alone, sorted and
multiplied in tiles, so the sums run in another order; every decoder
layer recomputed in the backward pass. Against the released model, here
and in the program alike (the configuration's `assumed`): the head tied
to the embedding; b zero and untrained (the release moves it by a
balancing rule that `config.json` does not give).

Why every held expert sees every token here: without dropping a token a
held expert may be chosen by every token of the batch, so a plain
program of static shapes that gathered each expert's rows would hold N
rows for it all the same; the weight of 0 does what the gather would,
with no sort and no scatter that the program's algorithm shares.
"""
import jax
import jax.numpy as jnp
from jax import lax

from . import precision as P

ROW_BLOCK = 256         # rows of attention scores held at once
LOSS_BLOCK = 1024       # rows of logits held at once

# Memory, not arithmetic: with `remat` each mixer, each feed-forward, each
# held expert, each block of attention rows and each block of logits is a
# `jax.checkpoint` (its inside computed again in the backward pass), so
# that float32 at 2 x 4096 tokens fits beside the weights and Adam's state.


def _each(fn, xs, looped):
    """fn over the leading axis of `xs`, stacked: `lax.map` (one copy of
    the body in the program), or written out where a test wants every
    pass counted."""
    if looped:
        return lax.map(fn, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    return jnp.stack([fn(jax.tree.map(lambda a: a[i], xs)) for i in range(n)])


def _linear(x, w, mode):
    return lax.dot_general(P.operand(x, mode),
                           P.operand(P.weight(w, mode), mode),
                           (((x.ndim - 1,), (1,)), ((), ())),
                           precision=P.matmul_precision(mode))


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _short_conv(p, x, n, mode, kw):
    B, T, H = x.shape
    bcx = _linear(x, p[n + "conv_in_weight"], mode).astype(jnp.float32)
    b, c, xt = bcx[..., :H], bcx[..., H:2 * H], bcx[..., 2 * H:]
    z = (b * xt).astype(x.dtype).astype(jnp.float32)
    w = P.weight(p[n + "conv_weight"], mode).astype(jnp.float32)   # (H, L)
    L = w.shape[1]
    padded = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + T] * w[:, j] for j in range(L))
    y = y.astype(x.dtype).astype(jnp.float32)
    return _linear((c * y).astype(x.dtype), p[n + "conv_out_weight"], mode)


def _rotary(x, theta):
    """x (B, T, heads, D) rotated by position, dimension i with i + D/2."""
    T, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    turned = jnp.concatenate([-xf[..., D // 2:], xf[..., :D // 2]], axis=-1)
    return (xf * jnp.cos(ang) + turned * jnp.sin(ang)).astype(x.dtype)


def _attention(p, x, n, mode, kw):
    Hq, Hkv, eps = kw["heads"], kw["kv_heads"], kw["eps"]
    B, T, H = x.shape
    D = H // Hq
    q = _linear(x, p[n + "attn_q_weight"], mode).reshape(B, T, Hq, D)
    k = _linear(x, p[n + "attn_k_weight"], mode).reshape(B, T, Hkv, D)
    v = _linear(x, p[n + "attn_v_weight"], mode).reshape(B, T, Hkv, D)
    q = _rotary(_rms(q, p[n + "attn_q_norm_weight"], eps), kw["theta"])
    k = _rotary(_rms(k, p[n + "attn_k_norm_weight"], eps), kw["theta"])
    k, v = (jnp.repeat(t, Hq // Hkv, axis=2) for t in (k, v))
    prec = P.matmul_precision(mode)

    def rows(first):
        q_rows = lax.dynamic_slice_in_dim(q, first, step, axis=1)
        s = jnp.einsum("bihd,bjhd->bhij", P.operand(q_rows, mode),
                       P.operand(k, mode), precision=prec)
        s = s.astype(jnp.float32) * D ** -0.5
        i = first + jnp.arange(step)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= i, s, -jnp.inf)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        return jnp.einsum("bhij,bjhd->bihd", P.operand(probs, mode),
                          P.operand(v, mode), precision=prec)

    step = min(ROW_BLOCK, T)
    if kw.get("remat", True):
        rows = jax.checkpoint(rows)
    o = _each(rows, jnp.arange(0, T, step), kw.get("remat", True))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, -1)
    return _linear(o.astype(x.dtype), p[n + "attn_out_weight"], mode)


def _swiglu(x, wg, wu, wd, mode):
    h = jax.nn.silu(_linear(x, wg, mode).astype(jnp.float32)) \
        * _linear(x, wu, mode).astype(jnp.float32)
    return _linear(h.astype(x.dtype), wd, mode)


def _dense(p, x, n, mode, kw):
    return _swiglu(x, p[n + "mlp_gate_weight"], p[n + "mlp_up_weight"],
                   p[n + "mlp_down_weight"], mode)


def _experts(p, x, n, mode, kw):
    logits = lax.dot_general(
        x.astype(jnp.float32), p[n + "moe_router_weight"],
        (((x.ndim - 1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, top_i = lax.top_k(s + p[n + "moe_expert_bias"], kw["top_k"])
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    top_w = kw["routed_scale"] * top_w / (jnp.sum(top_w, -1, keepdims=True)
                                          + 1e-6)

    def expert(out, held):                      # one held expert, all tokens
        j, wg, wu, wd = held
        w_e = jnp.sum(jnp.where(top_i == kw["held_start"] + j, top_w, 0.0), -1)
        y = _swiglu(x, wg, wu, wd, mode).astype(jnp.float32)
        return out + w_e[..., None] * y, None

    if kw.get("remat", True):
        expert = jax.checkpoint(expert)
    n_held = p[n + "moe_gate_weight"].shape[0]
    held = (jnp.arange(n_held), p[n + "moe_gate_weight"],
            p[n + "moe_up_weight"], p[n + "moe_down_weight"])
    out = jnp.zeros(x.shape, jnp.float32)
    if kw.get("remat", True):
        out, _ = lax.scan(expert, out, held)
    else:
        for j in range(n_held):
            out, _ = expert(out, tuple(a[j] for a in held))
    return out.astype(x.dtype)


def _layer(p, x, i, mode, kw):
    n = "l%d_" % i
    mixer = _short_conv if kw["layer_types"][i] == "conv" else _attention
    ffn = _dense if i < kw["num_dense_layers"] else _experts

    def mix(p, x):
        return x + mixer(p, _rms(x, p[n + "in_norm_weight"], kw["eps"]),
                         n, mode, kw)

    def feed(p, x):
        return x + ffn(p, _rms(x, p[n + "post_norm_weight"], kw["eps"]),
                       n, mode, kw)

    if kw.get("remat", True):
        mix, feed = jax.checkpoint(mix), jax.checkpoint(feed)
    return feed(p, mix(p, x))


def _hidden(p, tokens, mode, kw):
    """The final norm's output, float32 (B, T, H)."""
    kw.setdefault("eps", 1e-5)
    embed = P.weight(p["embed_weight"], mode)
    x = jnp.take(embed, tokens.astype(jnp.int32), axis=0)
    for i in range(len(kw["layer_types"])):
        x = _layer(p, x, i, mode, kw)
    return _rms(x, p["final_norm_weight"], kw["eps"]).astype(jnp.float32)


def logits(p, tokens, mode="float32", **kw):
    """(B, T, V) float32, every row at once: for the tests' sizes."""
    kw["remat"] = False
    return _linear(_hidden(p, tokens, mode, kw), p["embed_weight"], mode)


def loss(p, tokens, labels, mode="float32", remat=True, **kw):
    kw["remat"] = remat
    x = _hidden(p, tokens, mode, kw)

    def picked(block):                       # sum of log p(target), float32
        rows, targets = block
        logp = jax.nn.log_softmax(_linear(rows, p["embed_weight"], mode),
                                  axis=-1)
        return jnp.sum(jnp.take_along_axis(
            logp, targets.astype(jnp.int32)[..., None], axis=-1))

    if remat:
        picked = jax.checkpoint(picked)
    B, T, H = x.shape
    step = min(LOSS_BLOCK, T)
    blocks = (jnp.moveaxis(x.reshape(B, T // step, step, H), 1, 0),
              jnp.moveaxis(labels.reshape(B, T // step, step), 1, 0))
    total = jnp.sum(_each(picked, blocks, remat))
    return -total / labels.size


def trainable(name):
    """The `_stats` leaves are the program's device counters; the expert
    bias is not trained."""
    return not name.endswith(("_stats", "_expert_bias"))
