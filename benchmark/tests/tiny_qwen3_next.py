"""A tiny Qwen3-Next for the CPU tests: the program's own block and the
plain reference at sizes a test can hold. Every mechanism of the cell is
there: three Gated DeltaNet layers and one gated attention layer, key
heads shared by two value heads, grouped queries, partial rotary, a
router over 16 experts of which 4 are held from the fourth on, three
chunks of the delta rule and three row blocks of attention a sequence."""

KWARGS = dict(vocab_size=61, num_layers=4, hidden_size=32,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              partial_rotary_factor=0.5, rope_theta=1e4,
              linear_num_key_heads=2, linear_num_value_heads=4,
              linear_key_head_dim=8, linear_value_head_dim=8,
              num_experts=16, num_experts_per_tok=3, moe_intermediate_size=16,
              shared_expert_intermediate_size=16, experts_held=4,
              held_start=4, chunk=8, block_q=8, expert_tile=8)

REFERENCE_KWARGS = dict(heads=4, kv_heads=2, linear_key_heads=2,
                        linear_value_heads=4, top_k=3, held_start=4,
                        full_attention_interval=4, rotary_dim=8,
                        rope_theta=1e4, eps=1e-6)

# a decay of about 0.9 a token: the state's memory crosses the three
# chunks of a 24-token sequence; sigma 0.3 so that no path is negligible
INITIALIZER = [
    {"match": "_stats$", "fill": 0.0},
    {"match": "gdn_norm_weight$", "fill": 1.0},
    {"match": "norm_weight$", "fill": 0.0},
    {"match": "dt_bias$", "fill": 1.0},
    {"match": "A_log$", "fill": -2.5},
    {"match": "weight$", "normal": "sigma", "sigma": 0.3}]

CONFIG = {
    "name": "tiny_qwen3_next",
    "model": {"factory": "mxnet_tpu.gluon.model_zoo.qwen3_next:Qwen3NextDecoder",
              "kwargs": KWARGS},
    "input": {"kind": "tokens", "length": 24, "vocab": 61},
    "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "adam", "params": {
        "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}},
    "initializer": INITIALIZER,
    "reference": "qwen3_next_80b_a3b",
    "reference_kwargs": REFERENCE_KWARGS,
    "flops": "qwen3_next_80b_a3b",
}
