"""Model FLOPs of one ResNet-50 training step, from the layers' shapes.

Counted: the multiply-adds of every convolution and of the classifier, 2
FLOPs each; forward once, and in the backward pass once for the gradient
of the input and once for the gradient of the weight, except that the
first convolution needs no gradient of the image. Not counted: batch norm,
ReLU, pooling, the loss and the optimizer (elementwise: they bound by
bytes, not by FLOPs) and nothing recomputed.
"""

BLOCKS = (3, 4, 6, 3)
WIDTHS = (256, 512, 1024, 2048)


def conv_layers(height=224, width=224, channels=3, blocks=BLOCKS, classes=1000):
    """[(name, multiply-adds per image, needs input gradient)] as the
    configuration's model lays them: 7x7/2, 3x3/2 max pool, four stages of
    bottlenecks with the stride on the first 1x1, 1x1 projections."""
    layers = []
    h, w = (height + 1) // 2, (width + 1) // 2          # conv0, stride 2
    layers.append(("conv0", h * w * 64 * 7 * 7 * channels, False))
    h, w = (h + 1) // 2, (w + 1) // 2                    # max pool
    c_in = 64
    for s, (n, c_out) in enumerate(zip(blocks, WIDTHS), start=1):
        mid = c_out // 4
        for b in range(n):
            stride = 2 if (b == 0 and s > 1) else 1
            ho, wo = (h + stride - 1) // stride, (w + stride - 1) // stride
            tag = "stage%d_block%d" % (s, b)
            layers.append((tag + "_1x1a", ho * wo * mid * c_in, True))
            layers.append((tag + "_3x3", ho * wo * mid * 9 * mid, True))
            layers.append((tag + "_1x1b", ho * wo * c_out * mid, True))
            if b == 0:
                layers.append((tag + "_proj", ho * wo * c_out * c_in, True))
            h, w, c_in = ho, wo, c_out
    layers.append(("dense0", c_in * classes, True))
    return layers


def forward_macs_per_sample(**kw):
    return sum(m for _, m, _ in conv_layers(**kw))


def train_flops_per_sample(config=None, **kw):
    if config is not None:
        h, w, c = config["input"]["shape"]
        kw = dict(height=h, width=w, channels=c,
                  classes=config["input"]["classes"], **kw)
    return sum(2 * m * (3 if dgrad else 2) for _, m, dgrad in conv_layers(**kw))
