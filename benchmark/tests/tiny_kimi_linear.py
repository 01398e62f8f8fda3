"""A tiny Kimi Linear for the CPU tests: the program's own block and the
plain reference at sizes a test can hold. Every mechanism of the cell is
there, in the cell's order: a delta-attention layer with a dense
feed-forward, two more with experts, a latent attention layer, a fourth
delta-attention layer; a decay a key channel over two chunks of two row
blocks of 16 a sequence, keys of 8 + 4 against values of 8, four row
blocks of attention, a sigmoid router over 16 experts of which 4 are held
from the fourth on, weights scaled by 2.446, an ungated shared expert."""

KWARGS = dict(vocab_size=61, num_layers=5, hidden_size=32,
              kda_layers=[1, 2, 3, 5], full_attn_layers=[4], kda_num_heads=2,
              kda_head_dim=8, short_conv_kernel_size=4, kda_low_rank_dim=4,
              num_attention_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=4,
              v_head_dim=8, kv_lora_rank=16, first_k_dense_replace=1,
              intermediate_size=48, num_experts=16, num_experts_per_token=3,
              moe_intermediate_size=16, routed_scaling_factor=2.446,
              experts_held=4, held_start=4, rms_norm_eps=1e-5, chunk=32,
              block_q=16, expert_tile=8)

REFERENCE_KWARGS = dict(heads=2, linear_heads=2, top_k=3, held_start=4,
                        full_attn_layers=[4], first_k_dense_replace=1,
                        routed_scale=2.446, eps=1e-5)

# decays of about 0.85 to 0.98 a token, different in every channel: the
# slow ones cross the two chunks of a 64-token sequence; sigma 0.3 so that
# no path is negligible
INITIALIZER = [
    {"match": "_stats$", "fill": 0.0},
    {"match": "router_bias$", "fill": 0.0},
    {"match": "norm_weight$", "fill": 1.0},
    {"match": "dt_bias$", "normal": "sigma", "sigma": 1.0},
    {"match": "A_log$", "fill": -2.5},
    {"match": "weight$", "normal": "sigma", "sigma": 0.3}]

CONFIG = {
    "name": "tiny_kimi_linear",
    "model": {"factory": "mxnet_tpu.gluon.model_zoo.kimi_linear:KimiLinearDecoder",
              "kwargs": KWARGS},
    "input": {"kind": "tokens", "length": 64, "vocab": 61},
    "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "adam", "params": {
        "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}},
    "initializer": INITIALIZER,
    "reference": "kimi_linear_48b_a3b",
    "reference_kwargs": REFERENCE_KWARGS,
    "flops": "kimi_linear_48b_a3b",
}
