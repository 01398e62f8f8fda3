"""A tiny LFM2-MoE for the CPU tests: the program's own block and the
plain reference at sizes a test can hold. Every mechanism of the cell is
there, in the cell's order, over two periods: a short-convolution layer
with a dense feed-forward, then twice attention and three short
convolutions with experts; query heads of 8 over key/value heads two to
one, four row blocks of attention, three taps, a sigmoid router over 16
experts with a bias, top 4, of which 4 are held from the fourth on, the
weights over their sum plus 1e-6, a tied head; batches of two sequences."""

KWARGS = dict(vocab_size=61,
              layer_types=["conv", "full_attention", "conv", "conv", "conv",
                           "full_attention", "conv", "conv", "conv"],
              hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
              conv_L_cache=3, intermediate_size=48, num_dense_layers=1,
              num_experts=16, num_experts_per_tok=4, moe_intermediate_size=16,
              routed_scaling_factor=1.0, experts_held=4, held_start=4,
              rope_theta=1e6, norm_eps=1e-5, tie_word_embeddings=True,
              block_q=16, expert_tile=8)

REFERENCE_KWARGS = dict(layer_types=KWARGS["layer_types"], num_dense_layers=1,
                        heads=4, kv_heads=2, top_k=4, held_start=4,
                        routed_scale=1.0, theta=1e6, eps=1e-5)

# sigma 0.3 so that no path is negligible at width 32
INITIALIZER = [
    {"match": "_stats$", "fill": 0.0},
    {"match": "expert_bias$", "fill": 0.0},
    {"match": "norm_weight$", "fill": 1.0},
    {"match": "weight$", "normal": "sigma", "sigma": 0.3}]

CONFIG = {
    "name": "tiny_lfm2",
    "model": {"factory": "mxnet_tpu.gluon.model_zoo.lfm2_moe:Lfm2MoeDecoder",
              "kwargs": KWARGS},
    "input": {"kind": "tokens", "length": 64, "vocab": 61},
    "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "adam", "params": {
        "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}},
    "initializer": INITIALIZER,
    "reference": "lfm2_8b_a1b",
    "reference_kwargs": REFERENCE_KWARGS,
    "flops": "lfm2_8b_a1b",
}
