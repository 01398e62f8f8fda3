"""Plain reference: Kimi Linear's decoder (moonshotai/Kimi-Linear-48B-A3B-
Instruct, `config.json`) with the next-token cross-entropy averaged over
all positions, in `jax.numpy`. Imports nothing of the program.

RMS norm: y = x / sqrt(mean(x^2) + eps) * w. Decoder layer i, counted
from 1 as `linear_attn_config` counts: x += mixer_i(norm(x)), then
x += ffn_i(norm(x)); the mixer is latent attention where i is in
`full_attn_layers` and Kimi Delta Attention elsewhere; the feed-forward
is a SwiGLU in the first `first_k_dense_replace` layers and the experts
elsewhere. Linear weights are (out, in). No biases. The head is not tied.

Kimi Delta Attention (H heads of D): [q, k, v] = x W_qkv through a causal
depthwise convolution of width K and SiLU; q, k L2-normalised over the
head (x / sqrt(sum x^2 + 1e-6)), q scaled by D^-1/2; per token, head h
and key channel d, g = -exp(A_log[h]) softplus(a + dt_bias) in float32
with a = (x W_f_down) W_f_up; beta = sigmoid(x W_b). Per head, S (D x D)
from zero, TOKEN BY TOKEN: S <- Diag(exp(g_t)) S; d = beta_t (v_t - S^T
k_t); S <- S + k_t d^T; o_t = S^T q_t. Then o <- w_n o / sqrt(mean(o^2)
+ eps) sigmoid((x W_g_down) W_g_up) per head, and out = o W_o.

Latent attention (H heads; `q_lora_rank` null): q = x W_q, 192 a head;
[c, k_r] = x W_kva (512 + 64); [k_n, v] = RMSNorm(c) W_kvb, 128 + 128 a
head; head h's key is [k_n[h], k_r], the 64-wide part shared by all
heads; NO rotary embedding (`mla_use_nope`); causal softmax at
192^-1/2, the whole row of scores materialised (in blocks of rows, each
a `jax.checkpoint`, so that float32 scores at 4096 tokens fit); out =
attn W_o.

Experts: s = sigmoid(x W_r) over ALL experts in float32; the k experts
with the largest s + b (b the score-correction bias); the weights are s
of the chosen, without b, divided by their sum and multiplied by
`routed_scale`; the routed output is a loop over the experts HELD here,
each applied to every token and weighted by the token's weight for it
(0 where it was not chosen); what the absent experts would add is left
out. Plus the shared expert, the same SwiGLU, ungated.

Departures of the program from this, each inside the limits of the
cell: the recurrence in chunks of 64 tokens (a triangular solve a
chunk, the decays taken against a reference row a block of 16, the state
carried between chunks) in place of token by token; bfloat16 operands of
the matrix products with float32 sums, bfloat16 activations between
layers; attention in row blocks over the causal prefix only; the held
experts' rows sorted and multiplied in tiles, so the sums run in another
order; every decoder layer recomputed in the backward pass. Against the
released model, here and in the program alike (the configuration's
`assumed`): q, k and v of a delta-attention layer as one projection and
one convolution over [q, k, v]; the low-rank widths of the decay's and
the gate's projections (`head_dim`, 128) and no bias on them; b zero and
untrained; no rotary in the latent layers.
"""
import jax
import jax.numpy as jnp
from jax import lax

from . import precision as P

TOKEN_BLOCK = 64        # tokens of the recurrence between two checkpoints
ROW_BLOCK = 256         # rows of attention scores held at once
LOSS_BLOCK = 1024       # rows of logits held at once

# Memory, not arithmetic: with `remat` each mixer, each feed-forward, each
# held expert, each block of attention rows and each block of logits is a
# `jax.checkpoint` (its inside computed again in the backward pass), so
# that float32 at 4096 tokens fits beside the weights and Adam's state.


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


def _recurrence(q, k, v, g, beta, mode, per_channel=True):
    """q, k: (B, T, H, Dk), v: (B, T, H, Dv), g: (B, T, H, Dk) and beta:
    (B, T, H) float32. The state is float32; token by token.
    `per_channel=False` is the fault a test plants: every channel of a
    head decays by the head's mean log decay."""
    B, T, H, Dk = q.shape
    prec = P.matmul_precision(mode)
    if not per_channel:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S
        Sk = jnp.einsum("bhkv,bhk->bhv", P.operand(S, mode),
                        P.operand(k_t, mode).astype(jnp.float32),
                        precision=prec)
        d = b_t[..., None] * (v_t.astype(jnp.float32) - Sk)
        S = S + k_t.astype(jnp.float32)[..., :, None] * d[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", P.operand(S, mode),
                       P.operand(q_t, mode).astype(jnp.float32),
                       precision=prec)
        return S, o

    @jax.checkpoint
    def block(S, xs):
        return lax.scan(token, S, xs)

    n = max(T // TOKEN_BLOCK, 1)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((n, T // n) + x.shape[:1]
                                             + x.shape[2:])
               for x in (q, k, v, g, beta))
    _, o = lax.scan(block, jnp.zeros((B, H, Dk, v.shape[-1]), jnp.float32),
                    xs)
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1)


def _delta_attention(p, x, n, mode, kw):
    H, eps = kw["linear_heads"], kw["eps"]
    B, T, _ = x.shape
    D = p[n + "kda_norm_weight"].shape[0]
    hd = H * D
    qkv = _linear(x, p[n + "kda_qkv_weight"], mode)
    w = p[n + "kda_conv_weight"].astype(jnp.float32)          # (3 hd, K)
    K = w.shape[1]
    padded = jnp.pad(qkv.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + T] * w[:, j] for j in range(K))
    qkv = jax.nn.silu(conv).astype(qkv.dtype)
    q, k, v = (qkv[..., j * hd:(j + 1) * hd].reshape(B, T, H, D)
               for j in range(3))

    def l2(t):
        tf = t.astype(jnp.float32)
        return tf * lax.rsqrt(jnp.sum(tf * tf, -1, keepdims=True) + 1e-6)

    def low_rank(which):
        down = _linear(x, p[n + "kda_%s_down_weight" % which], mode)
        return _linear(down, p[n + "kda_%s_up_weight" % which],
                       mode).astype(jnp.float32).reshape(B, T, H, D)

    g = -jnp.exp(p[n + "kda_A_log"])[:, None] * jax.nn.softplus(
        low_rank("f") + p[n + "kda_dt_bias"].reshape(H, D))
    beta = jax.nn.sigmoid(_linear(x, p[n + "kda_b_weight"],
                                  mode).astype(jnp.float32))
    o = _recurrence((l2(q) * D ** -0.5).astype(x.dtype), l2(k).astype(x.dtype),
                    v, g, beta, mode, kw.get("per_channel", True))
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    o = o * p[n + "kda_norm_weight"] * jax.nn.sigmoid(low_rank("g"))
    return _linear(o.reshape(B, T, hd).astype(x.dtype),
                   p[n + "kda_out_weight"], mode)


def _latent_attention(p, x, n, mode, kw):
    H, eps = kw["heads"], kw["eps"]
    B, T, _ = x.shape
    kv = p[n + "mla_kv_norm_weight"].shape[0]
    D = p[n + "mla_q_weight"].shape[0] // H                   # 192
    Dr = p[n + "mla_kva_weight"].shape[0] - kv                # 64
    Dn = D - Dr
    q = _linear(x, p[n + "mla_q_weight"], mode).reshape(B, T, H, D)
    kva = _linear(x, p[n + "mla_kva_weight"], mode)
    kvb = _linear(_rms(kva[..., :kv], p[n + "mla_kv_norm_weight"], eps),
                  p[n + "mla_kvb_weight"], mode).reshape(B, T, H, -1)
    shared = jnp.broadcast_to(kva[..., None, kv:], (B, T, H, Dr))
    k = jnp.concatenate([kvb[..., :Dn], shared], axis=-1)
    v = kvb[..., Dn:]
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
    return _linear(o.astype(x.dtype), p[n + "mla_out_weight"], mode)


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
    _, top_i = lax.top_k(s + p[n + "moe_router_bias"], kw["top_k"])
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    top_w = kw["routed_scale"] * top_w / jnp.sum(top_w, -1, keepdims=True)

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
    shared = _swiglu(x, p[n + "moe_shared_gate_weight"],
                     p[n + "moe_shared_up_weight"],
                     p[n + "moe_shared_down_weight"], mode)
    return (out + shared.astype(jnp.float32)).astype(x.dtype)


def _layer(p, x, i, mode, kw):
    n = "l%d_" % i
    mixer = _latent_attention if i in kw["full_attn_layers"] \
        else _delta_attention
    ffn = _dense if i <= kw["first_k_dense_replace"] else _experts

    def mix(p, x):
        return x + mixer(p, _rms(x, p[n + "in_norm_weight"], kw["eps"]),
                         n, mode, kw)

    def feed(p, x):
        return x + ffn(p, _rms(x, p[n + "post_norm_weight"], kw["eps"]),
                       n, mode, kw)

    if kw.get("remat", True):
        mix, feed = jax.checkpoint(mix), jax.checkpoint(feed)
    return feed(p, mix(p, x))


def loss(p, tokens, labels, mode="float32", remat=True, **kw):
    kw.setdefault("eps", 1e-5)
    kw["remat"] = remat
    layers = max(int(n[1:n.index("_")]) for n in p
                 if n[0] == "l" and n[1].isdigit())
    x = jnp.take(P.weight(p["embed_weight"], mode),
                 tokens.astype(jnp.int32), axis=0)
    for i in range(1, layers + 1):
        x = _layer(p, x, i, mode, kw)
    x = _rms(x, p["final_norm_weight"], kw["eps"]).astype(jnp.float32)

    def picked(block):                       # sum of log p(target), float32
        rows, targets = block
        logp = jax.nn.log_softmax(_linear(rows, p["head_weight"], mode),
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
    """The `_stats` leaves are the program's device counters; the router's
    score-correction bias is not trained."""
    return not name.endswith(("_stats", "_router_bias"))
