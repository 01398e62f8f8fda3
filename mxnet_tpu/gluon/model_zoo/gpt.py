"""Minimal GPT-style autoregressive decoder (the ROADMAP item-3 seed).

A pre-norm causal transformer small enough to train and serve in CI,
built to be frozen by `serving.DecodeEngine` into the two compiled
decode programs (padded-bucket prefill + donated one-token step):

- `hybrid_forward` is the standard Gluon path: full-context causal
  forward over the registered F ops, so the block hybridizes, trains
  through Trainer/autograd, and exports like any model_zoo member.
- The pure-JAX mirror (`forward_fn`/`prefill_fn`/`step_fn`) implements
  the SAME math as jit-ready functions of an explicit param dict — the
  incremental KV-cached step reproduces the full-context forward
  exactly (causal attention at position p over cached K/V for 0..p is
  the full-forward row p), which is what makes greedy decode through
  the cache token-identical to a full re-forward.
- `step(token, kv_cache, position)` is the eager single-token
  convenience over `step_fn` for direct use without an engine.

Cache layout (shared with serving/decode.py):

    k, v : (num_layers, slots, max_seq_len, num_heads, head_dim)

one statically-shaped buffer per tensor so the decode step never
changes shape and never recompiles; a sequence occupies one slot, its
row count tracked by a per-slot position vector.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ...base import MXNetError
from ...ndarray import NDArray
from ..block import HybridBlock

__all__ = ["GPTDecoder", "get_gpt"]

# additive attention mask value: large enough that exp(x - max)
# underflows to exactly 0.0 in fp32, small enough to stay finite in
# bf16 — the SAME constant in the traced forward and the decode step,
# so masked positions contribute exact zeros on both paths
_MASK = 1e30
_LN_EPS = 1e-5


# ---------------------------------------------------------------------------
# pure-JAX core: one implementation of the per-layer math, shared by the
# full-context forward (training reference / prefill) and the one-token
# step. Mirrors the registered ops bit-for-bit (FullyConnected's
# dot_general, LayerNorm's rsqrt form, softmax's fp32 inner).
# ---------------------------------------------------------------------------

def _linear(x, w, b=None):
    """y = x @ w.T (+ b), exactly ops/nn.py _fully_connected."""
    y = lax.dot_general(x, w, (((x.ndim - 1,), (1,)), ((), ())))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def _layer_norm(x, gamma, beta):
    """Exactly ops/nn.py _layer_norm (axis=-1)."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + _LN_EPS)
    return y * gamma + beta


def _softmax(x, axis=-1):
    """Exactly ops/nn.py _softmax: fp32 inner for low-precision x."""
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return jax.nn.softmax(x.astype(jnp.float32),
                              axis=axis).astype(x.dtype)
    return jax.nn.softmax(x, axis=axis)


def _forward_jax(cfg, P, tokens, collect_kv=False):
    """Full-context causal forward. tokens: (B, T) int32. Returns
    logits (B, T, V) in fp32, plus per-layer pre-attention K/V stacks
    (num_layers, B, T, H, D) when `collect_kv` (the prefill path)."""
    E, H, D = cfg["embed_dim"], cfg["num_heads"], cfg["head_dim"]
    T = tokens.shape[1]
    x = jnp.take(P["tok_embed_weight"], tokens.astype(jnp.int32), axis=0)
    x = x + P["pos_embed_weight"][:T][None, :, :]
    pos = jnp.arange(T)
    # (1, 1, T, T) additive causal mask: 0 where key j <= query i
    add = (pos[None, :] <= pos[:, None]).astype(jnp.float32) - 1.0
    add = (add * _MASK)[None, None, :, :]
    scale = 1.0 / float(np.sqrt(D))
    ks, vs = [], []
    for i in range(cfg["num_layers"]):
        h = _layer_norm(x, P["h%d_ln1_gamma" % i], P["h%d_ln1_beta" % i])
        qkv = _linear(h, P["h%d_attn_qkv_weight" % i],
                      P["h%d_attn_qkv_bias" % i])
        q = qkv[..., :E].reshape(qkv.shape[0], T, H, D)
        k = qkv[..., E:2 * E].reshape(qkv.shape[0], T, H, D)
        v = qkv[..., 2 * E:].reshape(qkv.shape[0], T, H, D)
        if collect_kv:
            ks.append(k)
            vs.append(v)
        # scores[b,h,i,j] = q[b,i,h,:] . k[b,j,h,:]
        scores = jnp.einsum("bihd,bjhd->bhij", q, k) * scale
        # mask joins in the scores' dtype (a bf16 engine must not be
        # silently promoted back to fp32 by the additive mask; -1e30
        # rounds in bf16 but exp still underflows to exact 0)
        p = _softmax(scores + add.astype(scores.dtype), axis=-1)
        ctx = jnp.einsum("bhij,bjhd->bihd", p, v)
        ctx = ctx.reshape(ctx.shape[0], T, E)
        x = x + _linear(ctx, P["h%d_attn_out_weight" % i],
                        P["h%d_attn_out_bias" % i])
        h2 = _layer_norm(x, P["h%d_ln2_gamma" % i], P["h%d_ln2_beta" % i])
        up = jax.nn.gelu(_linear(h2, P["h%d_mlp_up_weight" % i],
                                 P["h%d_mlp_up_bias" % i]))
        x = x + _linear(up, P["h%d_mlp_down_weight" % i],
                        P["h%d_mlp_down_bias" % i])
    xf = _layer_norm(x, P["lnf_gamma"], P["lnf_beta"])
    logits = _linear(xf, P["tok_embed_weight"])          # tied head: x @ E^T
    return logits.astype(jnp.float32), ks, vs


def _prefill_jax(cfg, P, tokens, length):
    """Prefill one sequence: tokens (1, Lb) padded to a bucket length,
    `length` the true prompt length (traced int32 scalar). Returns
    (next_token () int32, k, v (num_layers, max_seq_len, H, D)) with
    rows >= length zeroed and padded out to max_seq_len — fixed output
    shapes so the admit program compiles once, whatever the bucket."""
    L, Lb = cfg["max_seq_len"], tokens.shape[1]
    logits, ks, vs = _forward_jax(cfg, P, tokens, collect_kv=True)
    next_token = jnp.argmax(
        jnp.take(logits[0], length - 1, axis=0)).astype(jnp.int32)
    live = (jnp.arange(Lb) < length)[:, None, None]

    def pack(seq):                      # (1, Lb, H, D) -> (L, H, D)
        seq = jnp.where(live, seq[0], jnp.zeros_like(seq[0]))
        return jnp.pad(seq, ((0, L - Lb), (0, 0), (0, 0)))

    k = jnp.stack([pack(s) for s in ks])
    v = jnp.stack([pack(s) for s in vs])
    return next_token, k, v


def _next_token_jax(cfg, P, tokens, length):
    """Greedy next token of one sequence by a full forward pass, no
    cache: tokens (1, L) padded to the context window, `length` the
    real length (traced). Attention is causal, so what lies beyond
    `length` never reaches the row that is read."""
    logits = _forward_jax(cfg, P, tokens)[0]
    return jnp.argmax(jnp.take(logits[0], length - 1, axis=0))


def _step_jax(cfg, P, cache_k, cache_v, positions, active, tokens):
    """One decode step for every slot at once. cache_k/cache_v:
    (num_layers, S, L, H, D) donated; positions (S,) int32 donated —
    the number of cached tokens per slot (== the position this step's
    token is written at); active (S,) bool; tokens (S,) int32 the last
    generated (or prefill-produced) token per slot. Returns
    (cache_k, cache_v, positions', next_tokens); inactive slots keep
    their position and their outputs are discarded by the scheduler."""
    E, H, D = cfg["embed_dim"], cfg["num_heads"], cfg["head_dim"]
    L = cfg["max_seq_len"]
    S = positions.shape[0]
    slot = jnp.arange(S)
    x = jnp.take(P["tok_embed_weight"], tokens.astype(jnp.int32), axis=0)
    x = x + jnp.take(P["pos_embed_weight"], positions, axis=0)
    # (S, 1, L) additive mask: key l visible while l <= position
    add = ((jnp.arange(L)[None, :] <= positions[:, None])
           .astype(jnp.float32) - 1.0) * _MASK
    add = add[:, None, :]
    scale = 1.0 / float(np.sqrt(D))
    for i in range(cfg["num_layers"]):
        h = _layer_norm(x, P["h%d_ln1_gamma" % i], P["h%d_ln1_beta" % i])
        qkv = _linear(h, P["h%d_attn_qkv_weight" % i],
                      P["h%d_attn_qkv_bias" % i])
        q = qkv[..., :E].reshape(S, H, D)
        k = qkv[..., E:2 * E].reshape(S, H, D)
        v = qkv[..., 2 * E:].reshape(S, H, D)
        cache_k = cache_k.at[i, slot, positions].set(k)
        cache_v = cache_v.at[i, slot, positions].set(v)
        scores = jnp.einsum("shd,slhd->shl", q, cache_k[i]) * scale
        p = _softmax(scores + add.astype(scores.dtype), axis=-1)
        ctx = jnp.einsum("shl,slhd->shd", p, cache_v[i]).reshape(S, E)
        x = x + _linear(ctx, P["h%d_attn_out_weight" % i],
                        P["h%d_attn_out_bias" % i])
        h2 = _layer_norm(x, P["h%d_ln2_gamma" % i], P["h%d_ln2_beta" % i])
        up = jax.nn.gelu(_linear(h2, P["h%d_mlp_up_weight" % i],
                                 P["h%d_mlp_up_bias" % i]))
        x = x + _linear(up, P["h%d_mlp_down_weight" % i],
                        P["h%d_mlp_down_bias" % i])
    xf = _layer_norm(x, P["lnf_gamma"], P["lnf_beta"])
    logits = _linear(xf, P["tok_embed_weight"]).astype(jnp.float32)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    positions = jnp.where(active, positions + 1, positions)
    return cache_k, cache_v, positions, next_tokens


class GPTDecoder(HybridBlock):
    """Minimal GPT: learned token+position embeddings, pre-norm blocks
    (fused-QKV multi-head causal attention + GELU MLP), final LayerNorm,
    weight-tied LM head. `forward(tokens)` -> logits (B, T, vocab)."""

    def __init__(self, vocab_size, max_seq_len=128, num_layers=2,
                 num_heads=2, embed_dim=32, mlp_ratio=4, eos_token=None,
                 **kwargs):
        super().__init__(**kwargs)
        if embed_dim % num_heads:
            raise MXNetError(
                "embed_dim=%d must divide by num_heads=%d"
                % (embed_dim, num_heads))
        self._cfg = {
            "vocab_size": int(vocab_size),
            "max_seq_len": int(max_seq_len),
            "num_layers": int(num_layers),
            "num_heads": int(num_heads),
            "embed_dim": int(embed_dim),
            "head_dim": int(embed_dim) // int(num_heads),
            "mlp_hidden": int(embed_dim) * int(mlp_ratio),
            "eos_token": None if eos_token is None else int(eos_token),
        }
        E, M = self._cfg["embed_dim"], self._cfg["mlp_hidden"]
        with self.name_scope():
            def p(name, shape, init=None):
                setattr(self, name, self.params.get(name, shape=shape,
                                                    init=init))
            p("tok_embed_weight", (vocab_size, E))
            p("pos_embed_weight", (max_seq_len, E))
            for i in range(num_layers):
                p("h%d_ln1_gamma" % i, (E,), "ones")
                p("h%d_ln1_beta" % i, (E,), "zeros")
                p("h%d_attn_qkv_weight" % i, (3 * E, E))
                p("h%d_attn_qkv_bias" % i, (3 * E,), "zeros")
                p("h%d_attn_out_weight" % i, (E, E))
                p("h%d_attn_out_bias" % i, (E,), "zeros")
                p("h%d_ln2_gamma" % i, (E,), "ones")
                p("h%d_ln2_beta" % i, (E,), "zeros")
                p("h%d_mlp_up_weight" % i, (M, E))
                p("h%d_mlp_up_bias" % i, (M,), "zeros")
                p("h%d_mlp_down_weight" % i, (E, M))
                p("h%d_mlp_down_bias" % i, (E,), "zeros")
            p("lnf_gamma", (E,), "ones")
            p("lnf_beta", (E,), "zeros")
        # the engine-less paths (`step`, `generate_reference`) each
        # compile once: run op by op they compile every `jnp` call
        # again for every sequence length
        self._step_jit = jax.jit(partial(_step_jax, self._cfg))
        self._next_token_jit = jax.jit(partial(_next_token_jax,
                                               self._cfg))

    # -- Gluon path ----------------------------------------------------
    def hybrid_forward(self, F, tokens, **P):
        cfg = self._cfg
        E, H, D = cfg["embed_dim"], cfg["num_heads"], cfg["head_dim"]
        V, M = cfg["vocab_size"], cfg["mlp_hidden"]
        x = F.Embedding(tokens, P["tok_embed_weight"], input_dim=V,
                        output_dim=E)
        # (T, E) slice of the position table, shape-agnostically: the
        # leading axis of tokens^T is T, which slice_like can see
        pos = F.slice_like(P["pos_embed_weight"], F.transpose(tokens),
                           axes=(0,))
        x = F.broadcast_add(x, F.expand_dims(pos, axis=0))
        for i in range(cfg["num_layers"]):
            h = F.LayerNorm(x, gamma=P["h%d_ln1_gamma" % i],
                            beta=P["h%d_ln1_beta" % i], axis=-1,
                            eps=_LN_EPS)
            qkv = F.FullyConnected(h, P["h%d_attn_qkv_weight" % i],
                                   P["h%d_attn_qkv_bias" % i],
                                   num_hidden=3 * E, flatten=False)

            def heads(t):               # (B,T,E) -> (B,H,T,D)
                t = F.reshape(t, shape=(0, 0, H, D))
                return F.transpose(t, axes=(0, 2, 1, 3))

            q = heads(F.slice_axis(qkv, axis=-1, begin=0, end=E))
            k = heads(F.slice_axis(qkv, axis=-1, begin=E, end=2 * E))
            v = heads(F.slice_axis(qkv, axis=-1, begin=2 * E,
                                   end=3 * E))
            # one op, which chooses from the shape it is traced with: the
            # Pallas kernels where T and D tile, else batch_dot, softmax
            # over the masked scores and batch_dot as they stood here
            ctx = F.contrib.flash_attention(q, k, v, causal=True)
            ctx = F.reshape(F.transpose(ctx, axes=(0, 2, 1, 3)),
                            shape=(0, 0, E))
            x = x + F.FullyConnected(ctx,
                                     P["h%d_attn_out_weight" % i],
                                     P["h%d_attn_out_bias" % i],
                                     num_hidden=E, flatten=False)
            h2 = F.LayerNorm(x, gamma=P["h%d_ln2_gamma" % i],
                             beta=P["h%d_ln2_beta" % i], axis=-1,
                             eps=_LN_EPS)
            up = F.Activation(
                F.FullyConnected(h2, P["h%d_mlp_up_weight" % i],
                                 P["h%d_mlp_up_bias" % i],
                                 num_hidden=M, flatten=False),
                act_type="gelu")
            x = x + F.FullyConnected(up, P["h%d_mlp_down_weight" % i],
                                     P["h%d_mlp_down_bias" % i],
                                     num_hidden=E, flatten=False)
        xf = F.LayerNorm(x, gamma=P["lnf_gamma"], beta=P["lnf_beta"],
                         axis=-1, eps=_LN_EPS)
        return F.FullyConnected(xf, P["tok_embed_weight"], no_bias=True,
                                num_hidden=V, flatten=False)

    # -- decode protocol (consumed by serving.DecodeEngine) ------------
    def decode_spec(self):
        """Static decode configuration (a copy; mutate freely)."""
        return dict(self._cfg)

    def decode_params(self, dtype=None):
        """{short_name: jnp array} of the current parameter values,
        optionally cast to a serving dtype ('bf16')."""
        out = {}
        for name, param in self._attr_params.items():
            v = param.data()._data
            if dtype in ("bf16", "bfloat16") and \
                    v.dtype in (jnp.float32, jnp.float64):
                v = v.astype(jnp.bfloat16)
            out[name] = v
        return out

    def init_cache(self, slots, dtype=None):
        """Statically-shaped per-slot KV cache:
        (num_layers, slots, max_seq_len, num_heads, head_dim) x2."""
        cfg = self._cfg
        dt = jnp.bfloat16 if dtype in ("bf16", "bfloat16") \
            else jnp.float32
        shape = (cfg["num_layers"], int(slots), cfg["max_seq_len"],
                 cfg["num_heads"], cfg["head_dim"])
        return jnp.zeros(shape, dt), jnp.zeros(shape, dt)

    def forward_fn(self):
        """Pure fn(params, tokens) -> fp32 logits (B, T, V)."""
        cfg = self._cfg
        return lambda P, tokens: _forward_jax(cfg, P, tokens)[0]

    def prefill_fn(self):
        """Pure fn(params, tokens (1, Lb), length) ->
        (next_token, k, v) with k/v padded to max_seq_len."""
        cfg = self._cfg
        return lambda P, tokens, length: _prefill_jax(cfg, P, tokens,
                                                      length)

    def step_fn(self):
        """Pure fn(params, cache_k, cache_v, positions, active, tokens)
        -> (cache_k, cache_v, positions', next_tokens)."""
        cfg = self._cfg
        return (lambda P, ck, cv, pos, act, tok:
                _step_jax(cfg, P, ck, cv, pos, act, tok))

    def step(self, token, kv_cache, position):
        """Single-token decode over all slots, without an engine:
        `token` (S,) int array (the last generated token per slot),
        `kv_cache` the (k, v) pair from `init_cache`, `position` (S,)
        int32 cached-row counts. Returns (next_token NDArray (S,), (k, v), position')."""
        ck, cv = kv_cache
        tok = token._data if isinstance(token, NDArray) \
            else jnp.asarray(np.asarray(token))
        pos = position._data if isinstance(position, NDArray) \
            else jnp.asarray(np.asarray(position, dtype=np.int32))
        active = jnp.ones(pos.shape, bool)
        ck, cv, pos, nxt = self._step_jit(
            self.decode_params(), ck, cv,
            pos.astype(jnp.int32), active, tok.astype(jnp.int32))
        return NDArray(nxt), (ck, cv), NDArray(pos)

    def generate_reference(self, tokens, max_new_tokens):
        """Greedy decode by FULL re-forward each step — the cache-free
        reference the KV-cached path must match token for token. Stops
        early on eos_token (included in the output) or when the context
        window fills. Returns np int32 array of generated tokens."""
        cfg = self._cfg
        P = self.decode_params()
        seq = [int(t) for t in np.asarray(tokens).reshape(-1)]
        padded = np.zeros((1, cfg["max_seq_len"]), np.int32)
        out = []
        for _ in range(int(max_new_tokens)):
            if len(seq) > cfg["max_seq_len"]:
                break          # context window full: nothing to forward
            padded[0, :len(seq)] = seq
            nxt = int(self._next_token_jit(P, padded, len(seq)))
            out.append(nxt)
            seq.append(nxt)
            if cfg["eos_token"] is not None and nxt == cfg["eos_token"]:
                break
        return np.asarray(out, dtype=np.int32)


def get_gpt(vocab_size, **kwargs):
    """Model-zoo style constructor for :class:`GPTDecoder`."""
    return GPTDecoder(vocab_size, **kwargs)
