"""Kimi Linear's decoder: Kimi Delta Attention (a gated delta rule with
one decay a key channel) and latent attention without positions, mixed
as two lists of the configuration say; a dense first layer, then a
sparse expert layer with a sigmoid-scored router in every block.

The layer equations are those of the model's public `config.json`
(moonshotai/Kimi-Linear-48B-A3B-Instruct) and are written out in
`benchmark/reference/kimi_linear_48b_a3b.py`, the plain reference this
block is tested against. Decoder layer i (counted from 1, as
`linear_attn_config` counts): `x += mixer_i(norm(x))`, then
`x += ffn_i(norm(x))`; the mixer is latent attention where i is in
`full_attn_layers` and Kimi Delta Attention where it is in `kda_layers`;
the feed-forward is a SwiGLU of `intermediate_size` in the first
`first_k_dense_replace` layers and the experts elsewhere. No biases; RMS
norms scale by `w`, from one; no rotary embedding (`mla_use_nope`); the
head is not tied.

Built as `Qwen3NextDecoder` is: registered ops only, so every node keeps
its `mx.<op>.<node>` scope and `ShardedTrainer` trains it like the other
decoders; `experts_held` of the experts from `held_start`, the number of
layers and the rows of the vocabulary are arguments because a chip holds
a share of the model; each decoder layer is one group of
rematerialisation (`HybridBlock.remat_scope`).

Against the released code: q, k and v of a delta-attention layer are one
fused projection and one convolution over `[q, k, v]` (the release keeps
three of each; with random weights the same model), the expert matrices
are three arrays of (experts, out, in), and the router's bias is an
input that no gradient reaches (the release moves it by a rule outside
`config.json`).
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock

__all__ = ["KimiLinearDecoder", "get_kimi_linear"]


class KimiLinearDecoder(HybridBlock):
    """`forward(tokens (B, T) int32)` -> logits (B, T, vocab_size)."""

    def __init__(self, vocab_size, num_layers=5, hidden_size=2304,
                 kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
                 kda_num_heads=32, kda_head_dim=128, short_conv_kernel_size=4,
                 kda_low_rank_dim=128, num_attention_heads=32,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 kv_lora_rank=512, first_k_dense_replace=1,
                 intermediate_size=9216, num_experts=256,
                 num_experts_per_token=8, moe_intermediate_size=1024,
                 routed_scaling_factor=2.446, experts_held=None, held_start=0,
                 rms_norm_eps=1e-5, chunk=64, block_q=256, expert_tile=256,
                 remat=True, **kwargs):
        super().__init__(**kwargs)
        held = num_experts if experts_held is None else int(experts_held)
        if held_start < 0 or held_start + held > num_experts:
            raise MXNetError("experts %d..%d are not among the layer's %d"
                             % (held_start, held_start + held - 1,
                                num_experts))
        kda, full = set(kda_layers), set(full_attn_layers)
        layers = set(range(1, int(num_layers) + 1))
        if kda & full or not layers <= kda | full:
            raise MXNetError("kda_layers and full_attn_layers have to name "
                             "each of the layers 1..%d once" % num_layers)
        self._cfg = c = dict(
            V=int(vocab_size), L=int(num_layers), H=int(hidden_size),
            full=frozenset(full & layers), Hl=int(kda_num_heads),
            Dl=int(kda_head_dim), K=int(short_conv_kernel_size),
            r=int(kda_low_rank_dim), Hq=int(num_attention_heads),
            Dn=int(qk_nope_head_dim), Dr=int(qk_rope_head_dim),
            Dv=int(v_head_dim), kv=int(kv_lora_rank),
            dense=int(first_k_dense_replace), Id=int(intermediate_size),
            E_all=int(num_experts), k=int(num_experts_per_token),
            I=int(moe_intermediate_size), scale=float(routed_scaling_factor),
            E=held, start=int(held_start), eps=float(rms_norm_eps),
            chunk=int(chunk), block_q=int(block_q), tile=int(expert_tile),
            remat=bool(remat))
        H, hd = c["H"], c["Hl"] * c["Dl"]
        with self.name_scope():
            def p(name, shape, init=None, **kw):
                setattr(self, name, self.params.get(name, shape=shape,
                                                    init=init, **kw))

            def untrained(name, size):
                p(name, (size,), "zeros", grad_req="null",
                  differentiable=False)

            p("embed_weight", (c["V"], H))
            for i in range(1, c["L"] + 1):
                n = "l%d_" % i
                p(n + "in_norm_weight", (H,), "ones")
                if i in c["full"]:
                    p(n + "mla_q_weight", (c["Hq"] * (c["Dn"] + c["Dr"]), H))
                    p(n + "mla_kva_weight", (c["kv"] + c["Dr"], H))
                    p(n + "mla_kv_norm_weight", (c["kv"],), "ones")
                    p(n + "mla_kvb_weight",
                      (c["Hq"] * (c["Dn"] + c["Dv"]), c["kv"]))
                    p(n + "mla_out_weight", (H, c["Hq"] * c["Dv"]))
                else:
                    p(n + "kda_qkv_weight", (3 * hd, H))
                    p(n + "kda_conv_weight", (3 * hd, c["K"]))
                    p(n + "kda_f_down_weight", (c["r"], H))
                    p(n + "kda_f_up_weight", (hd, c["r"]))
                    p(n + "kda_dt_bias", (hd,), "ones")
                    p(n + "kda_A_log", (c["Hl"],), "zeros")
                    p(n + "kda_b_weight", (c["Hl"], H))
                    p(n + "kda_g_down_weight", (c["r"], H))
                    p(n + "kda_g_up_weight", (hd, c["r"]))
                    p(n + "kda_norm_weight", (c["Dl"],), "ones")
                    p(n + "kda_out_weight", (H, hd))
                    untrained(n + "kda_stats", 1)
                p(n + "post_norm_weight", (H,), "ones")
                if i <= c["dense"]:
                    p(n + "mlp_gate_weight", (c["Id"], H))
                    p(n + "mlp_up_weight", (c["Id"], H))
                    p(n + "mlp_down_weight", (H, c["Id"]))
                else:
                    p(n + "moe_router_weight", (c["E_all"], H))
                    untrained(n + "moe_router_bias", c["E_all"])
                    p(n + "moe_gate_weight", (held, c["I"], H))
                    p(n + "moe_up_weight", (held, c["I"], H))
                    p(n + "moe_down_weight", (held, H, c["I"]))
                    untrained(n + "moe_stats", 2)
                    p(n + "moe_shared_gate_weight", (c["I"], H))
                    p(n + "moe_shared_up_weight", (c["I"], H))
                    p(n + "moe_shared_down_weight", (H, c["I"]))
            p("final_norm_weight", (H,), "ones")
            p("head_weight", (c["V"], H))

    # -- the two mixers and the two feed-forwards, over F's registered ops --
    def _linear(self, F, x, w, n_out):
        return F.FullyConnected(x, w, no_bias=True, num_hidden=n_out,
                                flatten=False)

    def _norm(self, F, x, w):
        return F._contrib_rms_norm(x, w, eps=self._cfg["eps"])

    def _delta_attention(self, F, x, P, n):
        c = self._cfg
        Hl, Dl = c["Hl"], c["Dl"]
        hd = Hl * Dl
        qkv = F._contrib_causal_conv1d(
            self._linear(F, x, P[n + "kda_qkv_weight"], 3 * hd),
            P[n + "kda_conv_weight"], activation="silu")

        def low_rank(which):
            down = self._linear(F, x, P[n + "kda_%s_down_weight" % which],
                                c["r"])
            up = self._linear(F, down, P[n + "kda_%s_up_weight" % which], hd)
            return F.reshape(up, shape=(0, 0, Hl, Dl))

        def heads(j):
            t = F.slice_axis(qkv, axis=-1, begin=j * hd, end=(j + 1) * hd)
            return F.reshape(t, shape=(0, 0, Hl, Dl))

        o = F._contrib_gated_delta_rule(
            heads(0), heads(1), heads(2), low_rank("f"),
            self._linear(F, x, P[n + "kda_b_weight"], Hl),
            P[n + "kda_A_log"], P[n + "kda_dt_bias"], P[n + "kda_stats"],
            chunk=c["chunk"])
        o = F._contrib_gated_rms_norm(o, low_rank("g"),
                                      P[n + "kda_norm_weight"], eps=c["eps"],
                                      activation="sigmoid")
        return self._linear(F, F.reshape(o, shape=(0, 0, hd)),
                            P[n + "kda_out_weight"], c["H"])

    def _latent_attention(self, F, x, P, n):
        c = self._cfg
        Hq, Dn, Dr, Dv, kv = c["Hq"], c["Dn"], c["Dr"], c["Dv"], c["kv"]
        q = F.reshape(self._linear(F, x, P[n + "mla_q_weight"],
                                   Hq * (Dn + Dr)), shape=(0, 0, Hq, Dn + Dr))
        kva = self._linear(F, x, P[n + "mla_kva_weight"], kv + Dr)
        latent = self._norm(F, F.slice_axis(kva, axis=-1, begin=0, end=kv),
                            P[n + "mla_kv_norm_weight"])
        kvb = F.reshape(self._linear(F, latent, P[n + "mla_kvb_weight"],
                                     Hq * (Dn + Dv)),
                        shape=(0, 0, Hq, Dn + Dv))
        # the part of a key that all heads share; no rotary (mla_use_nope)
        shared = F.broadcast_axis(
            F.reshape(F.slice_axis(kva, axis=-1, begin=kv, end=kv + Dr),
                      shape=(0, 0, 1, Dr)), axis=2, size=Hq)
        k = F.concat(F.slice_axis(kvb, axis=-1, begin=0, end=Dn), shared,
                     dim=3)
        o = F._contrib_causal_gqa_attention(
            q, k, F.slice_axis(kvb, axis=-1, begin=Dn, end=Dn + Dv),
            block_q=c["block_q"], scale=float(Dn + Dr) ** -0.5)
        return self._linear(F, F.reshape(o, shape=(0, 0, Hq * Dv)),
                            P[n + "mla_out_weight"], c["H"])

    def _dense(self, F, x, P, n):
        c = self._cfg
        gate = self._linear(F, x, P[n + "mlp_gate_weight"], c["Id"])
        up = self._linear(F, x, P[n + "mlp_up_weight"], c["Id"])
        return self._linear(F, F.Activation(gate, act_type="silu") * up,
                            P[n + "mlp_down_weight"], c["H"])

    def _experts(self, F, x, P, n):
        c = self._cfg
        routed = F._contrib_moe_held_ffn(
            x, P[n + "moe_router_weight"], P[n + "moe_gate_weight"],
            P[n + "moe_up_weight"], P[n + "moe_down_weight"],
            P[n + "moe_stats"], P[n + "moe_router_bias"], top_k=c["k"],
            held_start=c["start"], tile=c["tile"], score="sigmoid",
            scale=c["scale"], with_bias=True)
        shared = F._contrib_shared_expert_ffn(
            x, P[n + "moe_shared_gate_weight"], P[n + "moe_shared_up_weight"],
            P[n + "moe_shared_down_weight"], gated=False)
        return routed + shared

    def _layer(self, F, x, P, i):
        c, n = self._cfg, "l%d_" % i
        mixer = self._latent_attention if i in c["full"] \
            else self._delta_attention
        x = x + mixer(F, self._norm(F, x, P[n + "in_norm_weight"]), P, n)
        ffn = self._dense if i <= c["dense"] else self._experts
        return x + ffn(F, self._norm(F, x, P[n + "post_norm_weight"]), P, n)

    def hybrid_forward(self, F, tokens, **P):
        c = self._cfg
        x = F.Embedding(tokens, P["embed_weight"], input_dim=c["V"],
                        output_dim=c["H"])
        for i in range(1, c["L"] + 1):
            if c["remat"]:
                with self.remat_scope("l%d" % i):
                    x = self._layer(F, x, P, i)
            else:
                x = self._layer(F, x, P, i)
        # float32 from here: the logits, their softmax and the loss
        x = F.cast(self._norm(F, x, P["final_norm_weight"]), dtype="float32")
        return self._linear(F, x, P["head_weight"], c["V"])


def get_kimi_linear(vocab_size, **kwargs):
    """Model-zoo style constructor for :class:`KimiLinearDecoder`."""
    return KimiLinearDecoder(vocab_size, **kwargs)
