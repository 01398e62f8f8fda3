"""Model FLOPs of one Kimi Linear training step, from the layers' shapes,
and each kernel's FLOPs and least bytes.

Counted, per sequence of T tokens, 2 FLOPs a multiply-add, forward once
and backward twice, nothing recomputed:

  Kimi Delta Attention layer  the projections (H x 3 Hd for q, k and v,
                         H x r and r x Hd for the decay and again for the
                         gate, H x heads for beta, Hd x H), the
                         convolution's K taps on 3 Hd channels, and the
                         PLAIN recurrence's three Dk x Dv products a token
                         and head (S^T k, k d^T, S^T q): what the
                         mathematics needs, however the program chunks it
  latent attention layer  the projections (H x heads (Dn + Dr), H x (kv +
                         Dr), kv x heads (Dn + Dv), heads Dv x H) and the
                         causal HALF of the square: T (T + 1) / 2 pairs,
                         a product of Dn + Dr and one of Dv a pair and head
  dense feed-forward     3 H x I_dense, the first layers
  experts, other layers  the router (H x E_all), the shared expert (3 H x
                         I) and the EXPECTED held experts a token,
                         k * E_held / E_all, of 3 H x I each
  head                   H x V

Not counted: the embedding's look-up, norms, activations, softmax, the
decays, the triangular solve of the chunked form, the loss, Adam.

`causal_share` and `held_per_token` let a test count what the plain
reference computes instead (the whole square, every held expert on every
token) and hold that count to XLA's.
"""


def _shape(config):
    m = config["model"]["kwargs"] if "model" in config else config
    get = lambda k, d: m.get(k, d)                      # noqa: E731
    held = get("experts_held", None)
    L = get("num_layers", 5)
    full = [i for i in get("full_attn_layers", (4,)) if 1 <= i <= L]
    dense = min(get("first_k_dense_replace", 1), L)
    return dict(
        V=m["vocab_size"], H=get("hidden_size", 2304), full=len(full),
        kda=L - len(full), dense=dense, sparse=L - dense,
        Hl=get("kda_num_heads", 32), Dl=get("kda_head_dim", 128),
        K=get("short_conv_kernel_size", 4), r=get("kda_low_rank_dim", 128),
        Hq=get("num_attention_heads", 32), Dn=get("qk_nope_head_dim", 128),
        Dr=get("qk_rope_head_dim", 64), Dv=get("v_head_dim", 128),
        kv=get("kv_lora_rank", 512), Id=get("intermediate_size", 9216),
        E_all=get("num_experts", 256), k=get("num_experts_per_token", 8),
        I=get("moe_intermediate_size", 1024),
        E=get("num_experts", 256) if held is None else held)


def forward_macs(config, length, causal_share=None, held_per_token=None):
    """{part: multiply-adds of one sequence's forward pass}."""
    s, t = _shape(config), int(length)
    H, hd = s["H"], s["Hl"] * s["Dl"]
    pairs = t * (t + 1) / 2 if causal_share is None else causal_share * t * t
    held = s["k"] * s["E"] / s["E_all"] if held_per_token is None \
        else held_per_token
    return {
        "linear_projections": s["kda"] * t * (
            H * 3 * hd + 2 * (H * s["r"] + s["r"] * hd) + H * s["Hl"]
            + hd * H),
        "linear_attention": s["kda"] * t * (
            s["K"] * 3 * hd + 3 * s["Hl"] * s["Dl"] * s["Dl"]),
        "attention_projections": s["full"] * t * (
            H * s["Hq"] * (s["Dn"] + s["Dr"]) + H * (s["kv"] + s["Dr"])
            + s["kv"] * s["Hq"] * (s["Dn"] + s["Dv"]) + s["Hq"] * s["Dv"] * H),
        "attention": s["full"] * pairs * s["Hq"] * (s["Dn"] + s["Dr"]
                                                    + s["Dv"]),
        "dense": s["dense"] * t * 3 * H * s["Id"],
        "moe": s["sparse"] * t * H * (s["E_all"] + 3 * s["I"]
                                      + held * 3 * s["I"]),
        "head": t * H * s["V"],
    }


def train_flops_per_sample(config, length=None, **kw):
    length = config["input"]["length"] if length is None else length
    return 3 * 2 * sum(forward_macs(config, length, **kw).values())


def kernel_counts(config, batch, length=None, act_bytes=2):
    """{kernel: (FLOPs, least bytes)} of one training step of `batch`
    sequences, forward and backward, for the owners the per-layer metrics
    read. Least bytes: each array the kernel must read or write once, in
    the compute dtype (the decays in float32, once a key channel),
    forward; three times that for forward and backward (the backward
    reads the inputs and the output's gradient and writes the inputs'
    gradients). Held experts' matrices count once each way."""
    s = _shape(config)
    t = (config["input"]["length"] if length is None else length) * batch
    macs = forward_macs(config, t // batch)
    hd, b = s["Hl"] * s["Dl"], act_bytes
    linear_bytes = s["kda"] * t * (
        2 * 3 * hd * b                       # the convolution: in, out
        + 3 * hd * b + hd * 4 + s["Hl"] * 4 + hd * b   # the rule: q k v, g, beta, o
        + 3 * hd * b)                        # the gated norm: o, gate, out
    attention_bytes = s["full"] * t * s["Hq"] * (
        2 * (s["Dn"] + s["Dr"]) + 2 * s["Dv"]) * b     # q, k, v, o
    expert_weights = (s["E_all"] * s["H"] + 3 * s["I"] * s["H"]
                      + s["E"] * 3 * s["I"] * s["H"]) * b
    moe_bytes = s["sparse"] * (expert_weights + 2 * t * s["H"] * b)
    return {
        "linear_attention": (6 * batch * macs["linear_attention"],
                             3 * linear_bytes),
        "attention": (6 * batch * macs["attention"], 3 * attention_bytes),
        "moe": (6 * batch * macs["moe"], 3 * moe_bytes),
    }
