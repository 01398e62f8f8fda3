"""Plain reference: ResNet-50 (He et al., arXiv:1512.03385, table 1,
50-layer) with a 1000-way softmax cross-entropy, in `jax.numpy`.

Imports nothing of the program. Departures from the paper, all taken from
the configuration the cells run (MXNet's Gluon model zoo, `resnet50_v1`):
the stride of a down-sampling bottleneck sits on its first 1x1
convolution; the 1x1 convolutions of a bottleneck's body carry a bias
(which the batch norm behind them cancels); batch norm uses the batch's
own biased variance with eps 1e-5. Layout NHWC, weights (O, H, W, I).

Parameters come by bare name as the zoo numbers them: `conv0_weight`,
`batchnorm0_gamma`, `stage<s>_conv<i>_weight`, ..., `dense0_weight`;
within a stage convolutions and batch norms count on through the blocks,
and a block's shortcut projection comes after its body.
"""
import jax
import jax.numpy as jnp
from jax import lax

from . import precision as P

BLOCKS = (3, 4, 6, 3)     # bottlenecks in each of the four stages
EPS = 1e-5


def _conv(x, w, stride, pad, mode, bias=None):
    y = lax.conv_general_dilated(
        P.operand(x, mode), P.operand(P.weight(w, mode), mode),
        (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"),
        precision=P.matmul_precision(mode))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def _batch_norm(x, gamma, beta, tap=None, name=None):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(xf - mean), axis=(0, 1, 2))
    if tap is not None:
        tap[name + "_running_var"] = var
    a = gamma * lax.rsqrt(var + EPS)
    b = beta - mean * a
    return x * a.astype(x.dtype) + b.astype(x.dtype)


def _max_pool_3x3_s2(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1),
        (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])


def _bottleneck(p, x, stage, first_conv, first_bn, stride, project, mode,
                tap=None):
    """One bottleneck; returns its output. Convolutions `first_conv`.. and
    batch norms `first_bn`.. of `stage` are its own."""
    def conv(i, inp, s, pad):
        name = "stage%d_conv%d" % (stage, first_conv + i)
        return _conv(inp, p[name + "_weight"], s, pad, mode,
                     p.get(name + "_bias"))

    def bn(i, inp):
        name = "stage%d_batchnorm%d" % (stage, first_bn + i)
        return _batch_norm(inp, p[name + "_gamma"], p[name + "_beta"], tap,
                           name)

    y = jax.nn.relu(bn(0, conv(0, x, stride, 0)))
    y = jax.nn.relu(bn(1, conv(1, y, 1, 1)))
    y = bn(2, conv(2, y, 1, 0))
    if project:
        x = bn(3, conv(3, x, stride, 0))
    return jax.nn.relu(y + x)


def logits(p, x, mode="float32", blocks=BLOCKS, remat=True, tap=None):
    """`tap`, a dict, takes every batch norm's batch variance under the
    name of the running variance it feeds (and turns `remat` off)."""
    remat = remat and tap is None
    x = x.astype(P.act_dtype(mode))
    x = _conv(x, p["conv0_weight"], 2, 3, mode)
    x = jax.nn.relu(_batch_norm(x, p["batchnorm0_gamma"],
                                p["batchnorm0_beta"], tap, "batchnorm0"))
    x = _max_pool_3x3_s2(x)
    for s, n_blocks in enumerate(blocks, start=1):
        n_conv = n_bn = 0
        for b in range(n_blocks):
            project = b == 0
            stride = 2 if (b == 0 and s > 1) else 1
            # recompute a block's inside in the backward pass: the same
            # arithmetic, and float32 activations at the timed batch fit
            block = (lambda q, inp, s=s, c=n_conv, n=n_bn, st=stride,
                     pr=project: _bottleneck(q, inp, s, c, n, st, pr, mode,
                                             tap))
            x = (jax.checkpoint(block) if remat else block)(p, x)
            n_conv += 4 if project else 3
            n_bn += 4 if project else 3
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2)).astype(x.dtype)
    w = P.weight(p["dense0_weight"], mode)
    y = lax.dot_general(P.operand(x, mode), P.operand(w, mode),
                        (((1,), (1,)), ((), ())),
                        precision=P.matmul_precision(mode))
    return y.astype(jnp.float32) + p["dense0_bias"]


def loss(p, x, y, mode="float32", blocks=BLOCKS, remat=True):
    """Mean over the batch of the cross-entropy of `y` (class ids)."""
    logp = jax.nn.log_softmax(logits(p, x, mode, blocks, remat), axis=-1)
    picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None], axis=1)
    return -jnp.mean(picked)


def forward_variances(p, x, mode="float32", blocks=BLOCKS):
    """{running variance's name: the batch's biased variance there}: what
    the first forward pass feeds every batch norm's running variance. The
    power of the rounding noise of each layer's product lands in it."""
    tap = {}
    logits(p, x, mode, blocks, tap=tap)
    return tap


def trainable(name):
    return not name.endswith(("running_mean", "running_var"))
