"""Plain reference: Qwen3-Next's decoder (Qwen/Qwen3-Next-80B-A3B-Instruct,
`config.json`) with the next-token cross-entropy averaged over all
positions, in `jax.numpy`. Imports nothing of the program.

RMS norm: y = x / sqrt(mean(x^2) + eps) * (1 + w). Decoder layer i:
x += mixer_i(norm(x)), then x += experts(norm(x)); the mixer is gated
attention where (i + 1) % interval == 0 and Gated DeltaNet elsewhere.
Linear weights are (out, in). No biases. The head is not tied.

Gated DeltaNet (Hk key heads, Hv value heads): [q, k, v, z] = x W_qkvz,
[b, a] = x W_ba; [q, k, v] through a causal depthwise convolution of
width K and SiLU; beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias)
in float32; q, k L2-normalised over the head (x / sqrt(sum x^2 + 1e-6),
the released code's form), q scaled by Dk^-1/2; key head h // (Hv / Hk)
serves value head h. Per value head, S (Dk x Dv) from zero, TOKEN BY
TOKEN: S <- exp(g_t) S; d = beta_t (v_t - S^T k_t); S <- S + k_t d^T;
o_t = S^T q_t. Then o <- w_n o / sqrt(mean(o^2) + eps) silu(z) per head,
and out = o W_o.

Gated attention (Hq query heads over Hkv key/value heads): [q, gate] = x
W_q split per head, k = x W_k, v = x W_v; RMS norm of q and k over the
head; rotary embedding on the first `rotary_dim` dimensions, in halves;
causal softmax attention at D^-1/2, the whole row of scores materialised
(in blocks of rows, each block a `jax.checkpoint`, so that float32
scores at 8192 tokens fit); out = (attn * sigmoid(gate)) W_o.

Experts: p = softmax(x W_r) over ALL experts in float32; top-k; the k
weights renormalised to one; the routed output is a loop over the
experts HELD here, each applied to every token and weighted by the
token's weight for it (0 where it was not chosen), a `lax.scan`; what the absent
experts would add is left out. Plus sigmoid(x w_s) shared(x).

Departures of the program from this, each inside the limits of the
cell: the recurrence in chunks of 64 tokens (a triangular solve a chunk,
the state carried between chunks) in place of token by token; bfloat16
operands of the matrix products with float32 sums, bfloat16 activations
between layers; attention in row blocks over the causal prefix only; the
held experts' rows sorted and multiplied in tiles, so the sums run in
another order; every decoder layer recomputed in the backward pass.
Against the released model, here and in the program alike: the fused
projections are laid out in plain blocks ([q, k, v, z], [b, a]), not
interleaved by key head; no multi-token-prediction block (it is not in
`config.json`).
"""
import jax
import jax.numpy as jnp
from jax import lax

from . import precision as P

TOKEN_BLOCK = 64        # tokens of the recurrence between two checkpoints
ROW_BLOCK = 256         # rows of attention scores held at once
LOSS_BLOCK = 1024       # rows of logits held at once

# Memory, not arithmetic: with `remat` each mixer, each expert layer, each
# held expert, each block of attention rows and each block of logits is a
# `jax.checkpoint` (its inside computed again in the backward pass), so
# that float32 at 8192 tokens fits beside the weights and Adam's state.


def _each(fn, xs, looped):
    """fn over the leading axis of `xs`, stacked: `lax.map` (one copy of
    the body in the program, which is what keeps the compile short at
    8192 tokens), or written out where a test wants every pass counted."""
    if looped:
        return lax.map(fn, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    return jnp.stack([fn(jax.tree.map(lambda a: a[i], xs)) for i in range(n)])


def _linear(x, w, mode):
    return lax.dot_general(P.operand(x, mode),
                           P.operand(P.weight(w, mode), mode),
                           (((x.ndim - 1,), (1,)), ((), ())),
                           precision=P.matmul_precision(mode))


def _rms(x, w, eps, offset=1.0):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * (offset + w)).astype(x.dtype)


def _recurrence(q, k, v, g, beta, mode):
    """q, k, v: (B, T, Hv, D) (keys already repeated), g, beta: (B, T, Hv)
    float32. The state is float32; token by token."""
    B, T, Hv, Dk = q.shape
    prec = P.matmul_precision(mode)

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None, None] * S
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
    _, o = lax.scan(block, jnp.zeros((B, Hv, Dk, v.shape[-1]), jnp.float32),
                    xs)
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1)


def _delta_net(p, x, n, mode, kw):
    Hk, Hv, eps = kw["linear_key_heads"], kw["linear_value_heads"], kw["eps"]
    B, T, _ = x.shape
    Dv = p[n + "gdn_norm_weight"].shape[0]
    vd = Hv * Dv
    kd = (p[n + "gdn_conv_weight"].shape[0] - vd) // 2
    Dk = kd // Hk
    qkvz = _linear(x, p[n + "gdn_qkvz_weight"], mode)
    ba = _linear(x, p[n + "gdn_ba_weight"], mode).astype(jnp.float32)
    qkv, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    w = p[n + "gdn_conv_weight"].astype(jnp.float32)          # (C, K)
    K = w.shape[1]
    padded = jnp.pad(qkv.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + T] * w[:, j] for j in range(K))
    qkv = jax.nn.silu(conv).astype(qkv.dtype)
    q = qkv[..., :kd].reshape(B, T, Hk, Dk)
    k = qkv[..., kd:2 * kd].reshape(B, T, Hk, Dk)
    v = qkv[..., 2 * kd:].reshape(B, T, Hv, Dv)

    def l2(t):
        tf = t.astype(jnp.float32)
        return tf * lax.rsqrt(jnp.sum(tf * tf, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q) * Dk ** -0.5, Hv // Hk, axis=2)
    k = jnp.repeat(l2(k), Hv // Hk, axis=2)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p[n + "gdn_A_log"]) * jax.nn.softplus(
        ba[..., Hv:] + p[n + "gdn_dt_bias"])
    o = _recurrence(q.astype(x.dtype), k.astype(x.dtype), v, g, beta, mode)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    o = o * p[n + "gdn_norm_weight"] * jax.nn.silu(
        z.reshape(B, T, Hv, Dv).astype(jnp.float32))
    return _linear(o.reshape(B, T, vd).astype(x.dtype),
                   p[n + "gdn_out_weight"], mode)


def _rotary(x, rotary_dim, theta):
    T, half = x.shape[1], rotary_dim // 2
    freq = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]   # (1,T,1,rot)
    xr = x[..., :rotary_dim].astype(jnp.float32)
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    xr = xr * jnp.cos(ang) + turned * jnp.sin(ang)
    return jnp.concatenate([xr.astype(x.dtype), x[..., rotary_dim:]], -1)


def _attention(p, x, n, mode, kw):
    Hq, Hkv, eps = kw["heads"], kw["kv_heads"], kw["eps"]
    B, T, _ = x.shape
    D = p[n + "attn_q_norm_weight"].shape[0]
    qg = _linear(x, p[n + "attn_q_weight"], mode).reshape(B, T, Hq, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = _linear(x, p[n + "attn_k_weight"], mode).reshape(B, T, Hkv, D)
    v = _linear(x, p[n + "attn_v_weight"], mode).reshape(B, T, Hkv, D)
    q = _rotary(_rms(q, p[n + "attn_q_norm_weight"], eps),
                kw["rotary_dim"], kw["rope_theta"])
    k = _rotary(_rms(k, p[n + "attn_k_norm_weight"], eps),
                kw["rotary_dim"], kw["rope_theta"])
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
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
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, Hq, D)
    o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
    return _linear(o.reshape(B, T, Hq * D).astype(x.dtype),
                   p[n + "attn_out_weight"], mode)


def _swiglu(x, wg, wu, wd, mode):
    h = jax.nn.silu(_linear(x, wg, mode).astype(jnp.float32)) \
        * _linear(x, wu, mode).astype(jnp.float32)
    return _linear(h.astype(x.dtype), wd, mode)


def _experts(p, x, n, mode, kw):
    logits = lax.dot_general(
        x.astype(jnp.float32), p[n + "moe_router_weight"],
        (((x.ndim - 1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = lax.top_k(probs, kw["top_k"])
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
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
    sg = jax.nn.sigmoid(_linear(x, p[n + "moe_shared_expert_gate_weight"],
                                mode).astype(jnp.float32))
    return (out + sg * shared.astype(jnp.float32)).astype(x.dtype)


def _layer(p, x, i, mode, kw):
    n = "l%d_" % i
    full = (i + 1) % kw["full_attention_interval"] == 0
    mixer = _attention if full else _delta_net

    def mix(p, x):
        return x + mixer(p, _rms(x, p[n + "in_norm_weight"], kw["eps"]),
                         n, mode, kw)

    def sparse(p, x):
        return x + _experts(p, _rms(x, p[n + "post_norm_weight"], kw["eps"]),
                            n, mode, kw)

    if kw.get("remat", True):
        mix, sparse = jax.checkpoint(mix), jax.checkpoint(sparse)
    return sparse(p, mix(p, x))


def loss(p, tokens, labels, mode="float32", remat=True, **kw):
    kw.setdefault("eps", 1e-6)
    kw["remat"] = remat
    layers = 1 + max(int(n[1:n.index("_")]) for n in p
                     if n[0] == "l" and n[1].isdigit())
    x = jnp.take(P.weight(p["embed_weight"], mode),
                 tokens.astype(jnp.int32), axis=0)
    for i in range(layers):
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
    """The `_stats` leaves are the program's device counters."""
    return not name.endswith("_stats")
