"""Qwen3-Next's decoder: Gated DeltaNet linear attention and gated
softmax attention in periods, a sparse expert layer in every block.

The layer equations are those of the model's public `config.json`
(Qwen/Qwen3-Next-80B-A3B-Instruct) and are written out in
`benchmark/reference/qwen3_next_80b_a3b.py`, the plain reference this
block is tested against. Decoder layer i: `x += mixer_i(norm(x))`, then
`x += experts(norm(x))`; the mixer is gated attention where
`(i + 1) % full_attention_interval == 0` and Gated DeltaNet elsewhere.
No biases; RMS norms scale by `1 + w`; the head is not tied.

The block is built from registered ops, so every node of its traced
graph carries its `mx.<op>.<node>` scope and `ShardedTrainer` trains it
as it trains `GPTDecoder`. Three things are arguments because a chip
holds a share of the model: how many layers, which experts
(`experts_held` of them from `held_start`; the router still scores all
`num_experts`, and what the absent experts would add is left out), and
how many rows of the vocabulary. Each decoder layer is a group of
rematerialisation (`HybridBlock.remat_scope`): a training step keeps
the layer's input and computes its inside again in the backward pass.

Against the released code: the fused projections are laid out as
`[q, k, v, z]` and `[b, a]` in plain blocks (the release interleaves
them by key head), the expert matrices are three arrays of (experts,
out, in), and the multi-token-prediction block is not built.
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock

__all__ = ["Qwen3NextDecoder", "get_qwen3_next"]


class Qwen3NextDecoder(HybridBlock):
    """`forward(tokens (B, T) int32)` -> logits (B, T, vocab_size)."""

    def __init__(self, vocab_size, num_layers=4, hidden_size=2048,
                 full_attention_interval=4, num_attention_heads=16,
                 num_key_value_heads=2, head_dim=256,
                 partial_rotary_factor=0.25, rope_theta=1e7,
                 linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_key_head_dim=128, linear_value_head_dim=128,
                 linear_conv_kernel_dim=4, num_experts=512,
                 num_experts_per_tok=10, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, experts_held=None,
                 held_start=0, rms_norm_eps=1e-6, chunk=64, block_q=256,
                 expert_tile=256, remat=True, **kwargs):
        super().__init__(**kwargs)
        held = num_experts if experts_held is None else int(experts_held)
        if held_start < 0 or held_start + held > num_experts:
            raise MXNetError("experts %d..%d are not among the layer's %d"
                             % (held_start, held_start + held - 1,
                                num_experts))
        if linear_num_value_heads % linear_num_key_heads \
                or num_attention_heads % num_key_value_heads:
            raise MXNetError("value (query) heads must be a multiple of "
                             "the key heads")
        self._cfg = c = dict(
            V=int(vocab_size), L=int(num_layers), H=int(hidden_size),
            every=int(full_attention_interval), Hq=int(num_attention_heads),
            Hkv=int(num_key_value_heads), D=int(head_dim),
            rot=int(head_dim * partial_rotary_factor),
            theta=float(rope_theta), Hk=int(linear_num_key_heads),
            Hv=int(linear_num_value_heads), Dk=int(linear_key_head_dim),
            Dv=int(linear_value_head_dim), K=int(linear_conv_kernel_dim),
            E_all=int(num_experts), k=int(num_experts_per_tok),
            I=int(moe_intermediate_size),
            Is=int(shared_expert_intermediate_size), E=held,
            start=int(held_start), eps=float(rms_norm_eps),
            chunk=int(chunk), block_q=int(block_q), tile=int(expert_tile),
            remat=bool(remat))
        H = c["H"]
        kd, vd = c["Hk"] * c["Dk"], c["Hv"] * c["Dv"]
        with self.name_scope():
            def p(name, shape, init=None, **kw):
                setattr(self, name, self.params.get(name, shape=shape,
                                                    init=init, **kw))

            def counter(name, size):
                p(name, (size,), "zeros", grad_req="null",
                  differentiable=False)

            p("embed_weight", (c["V"], H))
            for i in range(c["L"]):
                n = "l%d_" % i
                p(n + "in_norm_weight", (H,), "zeros")
                if self.is_full_attention(i):
                    p(n + "attn_q_weight", (2 * c["Hq"] * c["D"], H))
                    p(n + "attn_k_weight", (c["Hkv"] * c["D"], H))
                    p(n + "attn_v_weight", (c["Hkv"] * c["D"], H))
                    p(n + "attn_q_norm_weight", (c["D"],), "zeros")
                    p(n + "attn_k_norm_weight", (c["D"],), "zeros")
                    p(n + "attn_out_weight", (H, c["Hq"] * c["D"]))
                else:
                    p(n + "gdn_qkvz_weight", (2 * kd + 2 * vd, H))
                    p(n + "gdn_ba_weight", (2 * c["Hv"], H))
                    p(n + "gdn_conv_weight", (2 * kd + vd, c["K"]))
                    p(n + "gdn_dt_bias", (c["Hv"],), "ones")
                    p(n + "gdn_A_log", (c["Hv"],), "zeros")
                    p(n + "gdn_norm_weight", (c["Dv"],), "ones")
                    p(n + "gdn_out_weight", (H, vd))
                    counter(n + "gdn_stats", 1)
                p(n + "post_norm_weight", (H,), "zeros")
                p(n + "moe_router_weight", (c["E_all"], H))
                p(n + "moe_gate_weight", (held, c["I"], H))
                p(n + "moe_up_weight", (held, c["I"], H))
                p(n + "moe_down_weight", (held, H, c["I"]))
                counter(n + "moe_stats", 2)
                p(n + "moe_shared_gate_weight", (c["Is"], H))
                p(n + "moe_shared_up_weight", (c["Is"], H))
                p(n + "moe_shared_down_weight", (H, c["Is"]))
                p(n + "moe_shared_expert_gate_weight", (1, H))
            p("final_norm_weight", (H,), "zeros")
            p("head_weight", (c["V"], H))

    def is_full_attention(self, i):
        return (i + 1) % self._cfg["every"] == 0

    # -- the two mixers and the expert layer, over F's registered ops -----
    def _linear(self, F, x, w, n_out):
        return F.FullyConnected(x, w, no_bias=True, num_hidden=n_out,
                                flatten=False)

    def _norm(self, F, x, w):
        return F._contrib_rms_norm(x, w, eps=self._cfg["eps"], offset=1.0)

    def _delta_net(self, F, x, P, n):
        c = self._cfg
        kd, vd = c["Hk"] * c["Dk"], c["Hv"] * c["Dv"]
        qkvz = self._linear(F, x, P[n + "gdn_qkvz_weight"], 2 * kd + 2 * vd)
        ba = self._linear(F, x, P[n + "gdn_ba_weight"], 2 * c["Hv"])
        qkv = F._contrib_causal_conv1d(
            F.slice_axis(qkvz, axis=-1, begin=0, end=2 * kd + vd),
            P[n + "gdn_conv_weight"], activation="silu")
        z = F.slice_axis(qkvz, axis=-1, begin=2 * kd + vd, end=2 * kd + 2 * vd)

        def heads(t, begin, count, size):
            t = F.slice_axis(t, axis=-1, begin=begin, end=begin + count * size)
            return F.reshape(t, shape=(0, 0, count, size))

        o = F._contrib_gated_delta_rule(
            heads(qkv, 0, c["Hk"], c["Dk"]), heads(qkv, kd, c["Hk"], c["Dk"]),
            heads(qkv, 2 * kd, c["Hv"], c["Dv"]),
            F.slice_axis(ba, axis=-1, begin=c["Hv"], end=2 * c["Hv"]),
            F.slice_axis(ba, axis=-1, begin=0, end=c["Hv"]),
            P[n + "gdn_A_log"], P[n + "gdn_dt_bias"], P[n + "gdn_stats"],
            chunk=c["chunk"])
        o = F._contrib_gated_rms_norm(
            o, F.reshape(z, shape=(0, 0, c["Hv"], c["Dv"])),
            P[n + "gdn_norm_weight"], eps=c["eps"])
        return self._linear(F, F.reshape(o, shape=(0, 0, vd)),
                            P[n + "gdn_out_weight"], c["H"])

    def _attention(self, F, x, P, n):
        c = self._cfg
        Hq, Hkv, D = c["Hq"], c["Hkv"], c["D"]
        qg = F.reshape(self._linear(F, x, P[n + "attn_q_weight"], 2 * Hq * D),
                       shape=(0, 0, Hq, 2 * D))
        q = F.slice_axis(qg, axis=-1, begin=0, end=D)
        gate = F.slice_axis(qg, axis=-1, begin=D, end=2 * D)
        k = F.reshape(self._linear(F, x, P[n + "attn_k_weight"], Hkv * D),
                      shape=(0, 0, Hkv, D))
        v = F.reshape(self._linear(F, x, P[n + "attn_v_weight"], Hkv * D),
                      shape=(0, 0, Hkv, D))

        def rotated(t, w):
            return F._contrib_rotary_embedding(
                self._norm(F, t, w), rotary_dim=c["rot"], theta=c["theta"])

        o = F._contrib_causal_gqa_attention(
            rotated(q, P[n + "attn_q_norm_weight"]),
            rotated(k, P[n + "attn_k_norm_weight"]), v,
            block_q=c["block_q"])
        o = F.reshape(o * F.sigmoid(gate), shape=(0, 0, Hq * D))
        return self._linear(F, o, P[n + "attn_out_weight"], c["H"])

    def _experts(self, F, x, P, n):
        c = self._cfg
        routed = F._contrib_moe_held_ffn(
            x, P[n + "moe_router_weight"], P[n + "moe_gate_weight"],
            P[n + "moe_up_weight"], P[n + "moe_down_weight"],
            P[n + "moe_stats"], top_k=c["k"], held_start=c["start"],
            tile=c["tile"])
        shared = F._contrib_shared_expert_ffn(
            x, P[n + "moe_shared_gate_weight"], P[n + "moe_shared_up_weight"],
            P[n + "moe_shared_down_weight"],
            P[n + "moe_shared_expert_gate_weight"])
        return routed + shared

    def _layer(self, F, x, P, i):
        n = "l%d_" % i
        h = self._norm(F, x, P[n + "in_norm_weight"])
        mixer = self._attention if self.is_full_attention(i) \
            else self._delta_net
        x = x + mixer(F, h, P, n)
        return x + self._experts(
            F, self._norm(F, x, P[n + "post_norm_weight"]), P, n)

    def hybrid_forward(self, F, tokens, **P):
        c = self._cfg
        x = F.Embedding(tokens, P["embed_weight"], input_dim=c["V"],
                        output_dim=c["H"])
        for i in range(c["L"]):
            if c["remat"]:
                with self.remat_scope("l%d" % i):
                    x = self._layer(F, x, P, i)
            else:
                x = self._layer(F, x, P, i)
        # float32 from here: the logits, their softmax and the loss
        x = F.cast(self._norm(F, x, P["final_norm_weight"]), dtype="float32")
        return self._linear(F, x, P["head_weight"], c["V"])


def get_qwen3_next(vocab_size, **kwargs):
    """Model-zoo style constructor for :class:`Qwen3NextDecoder`."""
    return Qwen3NextDecoder(vocab_size, **kwargs)
