"""Model FLOPs of one LFM2-MoE training step, from the layers' shapes,
and each kernel's FLOPs and least bytes.

Counted, per sequence of T tokens, 2 FLOPs a multiply-add, forward once
and backward twice, nothing recomputed:

  gated short convolution  the projections (H x 3H in, H x H out) and
                           the L taps on H channels
  attention layer          the projections (H x Hq D, H x Hkv D twice,
                           Hq D x H) and the causal HALF of the square:
                           T (T + 1) / 2 pairs, a product of D for the
                           scores and one of D for the values a pair and
                           query head
  dense feed-forward       3 H x I_dense, the first layers
  experts, other layers    the router (H x E_all) and the EXPECTED held
                           experts a token, k * E_held / E_all, of 3 H x I
                           each (one a token in the cell: 4 * 8 / 32)
  head                     H x V (the embedding, tied)

Not counted: the embedding's look-up, norms, rotary, the gates of the
short convolution, activations, softmax, the loss, Adam.

`causal_share` and `held_per_token` let a test count what the plain
reference computes instead (the whole square, every held expert on every
token) and hold that count to XLA's.
"""


def _shape(config):
    m = config["model"]["kwargs"] if "model" in config else config
    get = lambda k, d: m.get(k, d)                      # noqa: E731
    types = list(get("layer_types", ("conv", "full_attention", "conv",
                                     "conv", "conv")))
    held = get("experts_held", None)
    H, Hq = get("hidden_size", 2048), get("num_attention_heads", 32)
    dense = min(get("num_dense_layers", 1), len(types))
    return dict(
        V=m["vocab_size"], H=H, conv=types.count("conv"),
        full=types.count("full_attention"), dense=dense,
        sparse=len(types) - dense, Hq=Hq, D=H // Hq,
        Hkv=get("num_key_value_heads", 8), L=get("conv_L_cache", 3),
        Id=get("intermediate_size", 7168), E_all=get("num_experts", 32),
        k=get("num_experts_per_tok", 4), I=get("moe_intermediate_size", 1792),
        E=get("num_experts", 32) if held is None else held)


def forward_macs(config, length, causal_share=None, held_per_token=None):
    """{part: multiply-adds of one sequence's forward pass}."""
    s, t = _shape(config), int(length)
    H, D = s["H"], s["D"]
    pairs = t * (t + 1) / 2 if causal_share is None else causal_share * t * t
    held = s["k"] * s["E"] / s["E_all"] if held_per_token is None \
        else held_per_token
    return {
        "short_conv": s["conv"] * t * (3 * H * H + s["L"] * H + H * H),
        "attention_projections": s["full"] * t * (
            2 * H * s["Hq"] * D + 2 * H * s["Hkv"] * D),
        "attention": s["full"] * pairs * s["Hq"] * 2 * D,
        "dense": s["dense"] * t * 3 * H * s["Id"],
        "moe": s["sparse"] * t * H * (s["E_all"] + held * 3 * s["I"]),
        "head": t * H * s["V"],
    }


def train_flops_per_sample(config, length=None, **kw):
    length = config["input"]["length"] if length is None else length
    return 3 * 2 * sum(forward_macs(config, length, **kw).values())


def kernel_counts(config, batch, length=None, act_bytes=2):
    """{kernel: (FLOPs, least bytes)} of one training step of `batch`
    sequences, forward and backward, for the owners the per-layer metrics
    read. Least bytes: each array the kernel must read or write once, in
    the compute dtype, forward; three times that for forward and backward
    (the backward reads the inputs and the output's gradient and writes
    the inputs' gradients). Weights count once each way."""
    s = _shape(config)
    t = (config["input"]["length"] if length is None else length) * batch
    macs = forward_macs(config, t // batch)
    H, D, b = s["H"], s["D"], act_bytes
    conv_bytes = s["conv"] * (2 * t * H * b                 # u in, out
                              + (4 * H * H + s["L"] * H) * b)
    attention_bytes = s["full"] * t * (2 * s["Hq"] + 2 * s["Hkv"]) * D * b
    expert_weights = (s["E_all"] * H + s["E"] * 3 * s["I"] * H) * b
    moe_bytes = s["sparse"] * (expert_weights + 2 * t * H * b)
    return {
        "short_conv": (6 * batch * macs["short_conv"], 3 * conv_bytes),
        "attention": (6 * batch * macs["attention"], 3 * attention_bytes),
        "moe": (6 * batch * macs["moe"], 3 * moe_bytes),
    }
