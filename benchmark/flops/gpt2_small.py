"""Model FLOPs of one GPT-2 training step, from the layers' shapes.

Counted, per sequence of T tokens, 2 FLOPs a multiply-add: in each layer
the fused QKV projection (T*E*3E), the scores and the weighted values over
the whole T x T square as the configuration's attention computes them
(2*T*T*E), the output projection (T*E*E) and the MLP (2*T*E*4E); the tied
output head (T*E*V). Forward once and backward twice (gradient of the
input, gradient of the weight). Not counted: embedding look-ups, layer
norm, softmax, GELU, the loss, Adam, and nothing recomputed.
"""


def forward_macs_per_sample(length=1024, width=768, layers=12, vocab=50257,
                            mlp_ratio=4):
    t, e = length, width
    layer = t * e * 3 * e + 2 * t * t * e + t * e * e + 2 * t * e * mlp_ratio * e
    return layers * layer + t * e * vocab


def train_flops_per_sample(config=None, **kw):
    if config is not None:
        m = config["model"]["kwargs"]
        kw = dict(length=config["input"]["length"], width=m["embed_dim"],
                  layers=m["num_layers"], vocab=m["vocab_size"], **kw)
    return 3 * 2 * forward_macs_per_sample(**kw)
