"""LFM2-MoE's decoder: gated short convolutions and grouped-query
attention mixed as `layer_types` says, a dense SwiGLU in the first
layers and a sigmoid-scored expert layer in the others.

The layer equations are those of the model's public `config.json`
(LiquidAI/LFM2-8B-A1B) and are written out in
`benchmark/reference/lfm2_8b_a1b.py`, the plain reference this block is
tested against. Layer i: `h = x + mixer_i(norm(x))`, then
`x' = h + ffn_i(norm(h))`. The mixer is the gated short convolution
(`_contrib_short_conv`: `W_out (C * conv(B * x~))` with `[B; C; x~] =
W_in u`) where `layer_types[i]` is "conv", and attention where it is
"full_attention": query and key heads through a per-head RMS norm, then
rotary over the whole head at `rope_theta`, then a causal softmax at
D^-1/2 over grouped key/value heads. The feed-forward is a SwiGLU of
`intermediate_size` in the first `num_dense_layers` layers and, in the
others, the experts: sigmoid scores, the k chosen by score plus the
expert bias, weighed by the score over the sum of the k plus 1e-6, no
shared expert. No biases; RMS norms scale by `w`, from one; the head is
the embedding (`tie_word_embeddings`).

Built as `KimiLinearDecoder` is: registered ops only, so every node
keeps its `mx.<op>.<node>` scope and `ShardedTrainer` trains it like the
other decoders; `experts_held` of the experts from `held_start`, the
layers and the rows of the vocabulary are arguments because a chip holds
a share of the model; each decoder layer is one group of
rematerialisation (`HybridBlock.remat_scope`).

Against the released code: the expert matrices are three arrays of
(experts, out, in), and the expert bias is an input that no gradient
reaches (the release moves it by a balancing rule outside
`config.json`).
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock

__all__ = ["Lfm2MoeDecoder", "get_lfm2_moe"]

_KINDS = ("conv", "full_attention")


class Lfm2MoeDecoder(HybridBlock):
    """`forward(tokens (B, T) int32)` -> logits (B, T, vocab_size)."""

    def __init__(self, vocab_size,
                 layer_types=("conv", "full_attention", "conv", "conv",
                              "conv"),
                 hidden_size=2048, num_attention_heads=32,
                 num_key_value_heads=8, conv_L_cache=3,
                 intermediate_size=7168, num_dense_layers=1, num_experts=32,
                 num_experts_per_tok=4, moe_intermediate_size=1792,
                 routed_scaling_factor=1.0, experts_held=None, held_start=0,
                 rope_theta=1e6, norm_eps=1e-5, tie_word_embeddings=True,
                 block_q=256, expert_tile=256, remat=True, **kwargs):
        super().__init__(**kwargs)
        held = num_experts if experts_held is None else int(experts_held)
        if held_start < 0 or held_start + held > num_experts:
            raise MXNetError("experts %d..%d are not among the layer's %d"
                             % (held_start, held_start + held - 1,
                                num_experts))
        if set(layer_types) - set(_KINDS):
            raise MXNetError("layer_types: %s are not among %s"
                             % (sorted(set(layer_types) - set(_KINDS)),
                                _KINDS))
        if hidden_size % num_attention_heads \
                or num_attention_heads % num_key_value_heads:
            raise MXNetError("the query heads must divide the width and be "
                             "a multiple of the key/value heads")
        self._cfg = c = dict(
            V=int(vocab_size), types=tuple(layer_types), H=int(hidden_size),
            Hq=int(num_attention_heads), Hkv=int(num_key_value_heads),
            D=int(hidden_size) // int(num_attention_heads),
            K=int(conv_L_cache), Id=int(intermediate_size),
            dense=int(num_dense_layers), E_all=int(num_experts),
            k=int(num_experts_per_tok), I=int(moe_intermediate_size),
            scale=float(routed_scaling_factor), E=held,
            start=int(held_start), theta=float(rope_theta),
            eps=float(norm_eps), tied=bool(tie_word_embeddings),
            block_q=int(block_q), tile=int(expert_tile), remat=bool(remat))
        H, D = c["H"], c["D"]
        with self.name_scope():
            def p(name, shape, init=None, **kw):
                setattr(self, name, self.params.get(name, shape=shape,
                                                    init=init, **kw))

            def untrained(name, size):
                p(name, (size,), "zeros", grad_req="null",
                  differentiable=False)

            p("embed_weight", (c["V"], H))
            for i, kind in enumerate(c["types"]):
                n = "l%d_" % i
                p(n + "in_norm_weight", (H,), "ones")
                if kind == "conv":
                    p(n + "conv_in_weight", (3 * H, H))
                    p(n + "conv_weight", (H, c["K"]))
                    p(n + "conv_out_weight", (H, H))
                else:
                    p(n + "attn_q_weight", (c["Hq"] * D, H))
                    p(n + "attn_k_weight", (c["Hkv"] * D, H))
                    p(n + "attn_v_weight", (c["Hkv"] * D, H))
                    p(n + "attn_q_norm_weight", (D,), "ones")
                    p(n + "attn_k_norm_weight", (D,), "ones")
                    p(n + "attn_out_weight", (H, c["Hq"] * D))
                p(n + "post_norm_weight", (H,), "ones")
                if i < c["dense"]:
                    p(n + "mlp_gate_weight", (c["Id"], H))
                    p(n + "mlp_up_weight", (c["Id"], H))
                    p(n + "mlp_down_weight", (H, c["Id"]))
                else:
                    p(n + "moe_router_weight", (c["E_all"], H))
                    untrained(n + "moe_expert_bias", c["E_all"])
                    p(n + "moe_gate_weight", (held, c["I"], H))
                    p(n + "moe_up_weight", (held, c["I"], H))
                    p(n + "moe_down_weight", (held, H, c["I"]))
                    untrained(n + "moe_stats", 2)
            p("final_norm_weight", (H,), "ones")
            if not c["tied"]:
                p("head_weight", (c["V"], H))

    # -- the two mixers and the two feed-forwards, over F's registered ops --
    def _linear(self, F, x, w, n_out):
        return F.FullyConnected(x, w, no_bias=True, num_hidden=n_out,
                                flatten=False)

    def _norm(self, F, x, w):
        return F._contrib_rms_norm(x, w, eps=self._cfg["eps"])

    def _short_conv(self, F, x, P, n):
        return F._contrib_short_conv(x, P[n + "conv_in_weight"],
                                     P[n + "conv_weight"],
                                     P[n + "conv_out_weight"])

    def _attention(self, F, x, P, n):
        c = self._cfg
        Hq, Hkv, D = c["Hq"], c["Hkv"], c["D"]

        def heads(which, count):
            t = self._linear(F, x, P[n + "attn_%s_weight" % which], count * D)
            return F.reshape(t, shape=(0, 0, count, D))

        def rotated(t, which):
            return F._contrib_rotary_embedding(
                self._norm(F, t, P[n + "attn_%s_norm_weight" % which]),
                rotary_dim=D, theta=c["theta"])

        o = F._contrib_causal_gqa_attention(
            rotated(heads("q", Hq), "q"), rotated(heads("k", Hkv), "k"),
            heads("v", Hkv), block_q=c["block_q"])
        return self._linear(F, F.reshape(o, shape=(0, 0, Hq * D)),
                            P[n + "attn_out_weight"], c["H"])

    def _dense(self, F, x, P, n):
        c = self._cfg
        gate = self._linear(F, x, P[n + "mlp_gate_weight"], c["Id"])
        up = self._linear(F, x, P[n + "mlp_up_weight"], c["Id"])
        return self._linear(F, F.Activation(gate, act_type="silu") * up,
                            P[n + "mlp_down_weight"], c["H"])

    def _experts(self, F, x, P, n):
        c = self._cfg
        return F._contrib_moe_held_ffn(
            x, P[n + "moe_router_weight"], P[n + "moe_gate_weight"],
            P[n + "moe_up_weight"], P[n + "moe_down_weight"],
            P[n + "moe_stats"], P[n + "moe_expert_bias"], top_k=c["k"],
            held_start=c["start"], tile=c["tile"], score="sigmoid",
            scale=c["scale"], with_bias=True, eps=1e-6)

    def _layer(self, F, x, P, i):
        c, n = self._cfg, "l%d_" % i
        mixer = self._short_conv if c["types"][i] == "conv" \
            else self._attention
        x = x + mixer(F, self._norm(F, x, P[n + "in_norm_weight"]), P, n)
        ffn = self._dense if i < c["dense"] else self._experts
        return x + ffn(F, self._norm(F, x, P[n + "post_norm_weight"]), P, n)

    def hybrid_forward(self, F, tokens, **P):
        c = self._cfg
        x = F.Embedding(tokens, P["embed_weight"], input_dim=c["V"],
                        output_dim=c["H"])
        for i in range(len(c["types"])):
            if c["remat"]:
                with self.remat_scope("l%d" % i):
                    x = self._layer(F, x, P, i)
            else:
                x = self._layer(F, x, P, i)
        # float32 from here: the logits, their softmax and the loss
        x = F.cast(self._norm(F, x, P["final_norm_weight"]), dtype="float32")
        head = P["embed_weight"] if c["tied"] else P["head_weight"]
        return self._linear(F, x, head, c["V"])


def get_lfm2_moe(vocab_size, **kwargs):
    """Model-zoo style constructor for :class:`Lfm2MoeDecoder`."""
    return Lfm2MoeDecoder(vocab_size, **kwargs)
