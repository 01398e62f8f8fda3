"""Model FLOPs of one Qwen3-Next training step, from the layers' shapes,
and each new kernel's FLOPs and least bytes.

Counted, per sequence of T tokens, 2 FLOPs a multiply-add, forward once
and backward twice, nothing recomputed:

  Gated DeltaNet layer   the projections (H x (2 Kd + 2 Vd), H x 2 Hv,
                         Vd x H), the convolution's K taps on 2 Kd + Vd
                         channels, and the PLAIN recurrence's three
                         Dk x Dv products a token and value head (S^T k,
                         k d^T, S^T q): what the mathematics needs, however
                         the program chunks it
  gated attention layer  the projections (H x 2 Hq D, 2 of H x Hkv D,
                         Hq D x H) and the causal HALF of the square: T (T
                         + 1) / 2 pairs, two products of D a pair and head
  experts, every layer   the router (H x E_all), the shared expert (3 H x
                         Is and its gate), and the EXPECTED held experts a
                         token, k * E_held / E_all, of 3 H x I each
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
    return dict(
        V=m["vocab_size"], L=get("num_layers", 4), H=get("hidden_size", 2048),
        every=get("full_attention_interval", 4),
        Hq=get("num_attention_heads", 16), Hkv=get("num_key_value_heads", 2),
        D=get("head_dim", 256), Hk=get("linear_num_key_heads", 16),
        Hv=get("linear_num_value_heads", 32),
        Dk=get("linear_key_head_dim", 128),
        Dv=get("linear_value_head_dim", 128),
        K=get("linear_conv_kernel_dim", 4), E_all=get("num_experts", 512),
        k=get("num_experts_per_tok", 10),
        I=get("moe_intermediate_size", 512),
        Is=get("shared_expert_intermediate_size", 512),
        E=get("num_experts", 512) if held is None else held)


def forward_macs(config, length, causal_share=None, held_per_token=None):
    """{part: multiply-adds of one sequence's forward pass}."""
    s, t = _shape(config), int(length)
    kd, vd = s["Hk"] * s["Dk"], s["Hv"] * s["Dv"]
    full = sum(1 for i in range(s["L"]) if (i + 1) % s["every"] == 0)
    linear = s["L"] - full
    pairs = t * (t + 1) / 2 if causal_share is None else causal_share * t * t
    held = s["k"] * s["E"] / s["E_all"] if held_per_token is None \
        else held_per_token
    return {
        "linear_projections": linear * t * s["H"] * (2 * kd + 2 * vd
                                                     + 2 * s["Hv"] + vd),
        "linear_attention": linear * t * (s["K"] * (2 * kd + vd)
                                          + 3 * s["Hv"] * s["Dk"] * s["Dv"]),
        "attention_projections": full * t * s["H"] * (
            2 * s["Hq"] * s["D"] + 2 * s["Hkv"] * s["D"] + s["Hq"] * s["D"]),
        "attention": full * pairs * 2 * s["D"] * s["Hq"],
        "moe": s["L"] * t * s["H"] * (s["E_all"] + 3 * s["Is"] + 1
                                      + held * 3 * s["I"]),
        "head": t * s["H"] * s["V"],
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
    the inputs' gradients). Held experts' matrices count once each way."""
    s = _shape(config)
    t = (config["input"]["length"] if length is None else length) * batch
    macs = forward_macs(config, t // batch)
    kd, vd = s["Hk"] * s["Dk"], s["Hv"] * s["Dv"]
    full = sum(1 for i in range(s["L"]) if (i + 1) % s["every"] == 0)
    linear = s["L"] - full
    b = act_bytes
    linear_bytes = linear * t * (
        2 * (2 * kd + vd) * b                # the convolution: in, out
        + (2 * kd + vd) * b + 2 * s["Hv"] * 4 + vd * b   # the rule: q k v, g beta, o
        + 3 * vd * b)                        # the gated norm: o, z, out
    attention_bytes = full * t * (2 * s["Hq"] * s["D"]
                                  + 2 * s["Hkv"] * s["D"]) * b
    expert_weights = (s["E_all"] * s["H"] + 3 * s["Is"] * s["H"] + s["H"]
                      + s["E"] * 3 * s["I"] * s["H"]) * b
    moe_bytes = s["L"] * (expert_weights + 2 * t * s["H"] * b)
    return {
        "linear_attention": (6 * batch * macs["linear_attention"],
                             3 * linear_bytes),
        "attention": (6 * batch * macs["attention"], 3 * attention_bytes),
        "moe": (6 * batch * macs["moe"], 3 * moe_bytes),
    }
