"""LFM2's gated short convolution and its router's eps on the CPU at tiny
sizes: the op against the mixer written out in plain `jax.numpy`, value
and gradients, with nothing crossing from one sequence of a batch to the
next; and `route_top_k` with and without the 1e-6 of the normalisation."""
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import nd
from mxnet_tpu.ops import short_conv as sc
from mxnet_tpu.ops.moe import moe_held_ffn, route_top_k
from qwen3_next_helpers import HI, _close, _randn, _value_and_grads

B, T, H, L = 2, 24, 16, 3


def _mixer(x, w_in, w_conv, w_out):
    """[B; C; x~] = x W_in^T; z = B x~; y_t = sum_j w[:, j] z_{t-L+1+j};
    out = (C y) W_out^T: a loop over the taps, zeros before the start."""
    bcx = x @ w_in.T
    b, c, xt = bcx[..., :H], bcx[..., H:2 * H], bcx[..., 2 * H:]
    z = b * xt
    y = jnp.zeros_like(z)
    for j in range(L):
        shift = L - 1 - j
        moved = jnp.pad(z, ((0, 0), (shift, 0), (0, 0)))[:, :T]
        y = y + w_conv[:, j] * moved
    return (c * y) @ w_out.T


def _weights(seed=0):
    x, w_in, w_conv, w_out = _randn(seed, (B, T, H), (3 * H, H), (H, L),
                                    (H, H))
    return x, 0.3 * w_in, w_conv, 0.3 * w_out


def test_short_conv_matches_the_mixer_written_out():
    """Output and the four gradients in float32 at `highest`; one count
    in `short_conv.layers` a trace (the value's and the gradient's
    programs)."""
    args = _weights()
    layers0 = sc.LAYERS.total()
    with HI:
        (got, g_got), (want, g_want) = (
            _value_and_grads(fn, args) for fn in (sc.short_conv, _mixer))
    assert sc.LAYERS.total() == layers0 + 2
    assert got.shape == (B, T, H) and got.dtype == jnp.float32
    _close(got, want, 1e-5)
    for a, b in zip(g_got, g_want):
        _close(a, b, 2e-5)


def test_short_conv_does_not_leak_across_sequences():
    """The second sequence alone gives what it gives in the batch; a
    change to the first sequence's last tokens moves nothing in the
    second, and in the first moves only the tokens at and after it."""
    x, w_in, w_conv, w_out = _weights(1)
    run = jax.jit(sc.short_conv)
    with HI:
        whole = run(x, w_in, w_conv, w_out)
        alone = run(x[1:], w_in, w_conv, w_out)
        moved = run(x.at[0, T - 2:].add(1.0), w_in, w_conv, w_out)
    _close(whole[1:], alone, 1e-6)
    assert bool((moved[1] == whole[1]).all())
    assert bool((moved[0, :T - 2] == whole[0, :T - 2]).all())
    assert float(jnp.abs(moved[0, T - 2:] - whole[0, T - 2:]).max()) > 1e-3


def test_short_conv_in_bfloat16_and_through_the_registered_op():
    """bfloat16 operands, float32 sums, bfloat16 out: within bfloat16's
    rounding of the float32 mixer on the same (rounded) inputs; the
    registered op is the function."""
    args = tuple(a.astype(jnp.bfloat16) for a in _weights(2))
    with HI:
        got = jax.jit(sc.short_conv)(*args)
        want = jax.jit(_mixer)(*(a.astype(jnp.float32) for a in args))
        plain = jax.jit(sc.short_conv)(*_weights(2))
    assert got.dtype == jnp.bfloat16
    _close(got.astype(jnp.float32), want, 2e-2)
    out = nd._contrib_short_conv(*(nd.array(a) for a in _weights(2)))
    _close(out.asnumpy(), plain, 1e-5)


# -- the router's eps ---------------------------------------------------------
N, E_ALL, TOP, I_ = 40, 16, 4, 8


@pytest.mark.parametrize("eps", [0.0, 1e-6, 0.5])
def test_route_top_k_eps_joins_the_sum_only_when_given(eps):
    """Weights are s over (the sum of the k chosen + eps); at 0 the
    program is the one without the argument, bit for bit."""
    x, rw, b = _randn(3, (N, H), (E_ALL, H), (E_ALL,))
    with HI:
        top_i, top_w, _ = jax.jit(lambda x, rw, b: route_top_k(
            x, rw, TOP, "sigmoid", b, 1.0, eps))(x, rw, 0.3 * b)
        s = jax.nn.sigmoid(x @ rw.T)
    chosen = jnp.take_along_axis(s, top_i, -1)
    _close(top_w, chosen / (chosen.sum(-1, keepdims=True) + eps), 1e-6)
    plain = jax.jit(lambda x, rw, b: route_top_k(x, rw, TOP, "sigmoid", b))
    with_eps = jax.jit(lambda x, rw, b: route_top_k(
        x, rw, TOP, "sigmoid", b, 1.0, eps))
    text = lambda fn: fn.lower(x, rw, b).as_text()      # noqa: E731
    assert (text(plain) == text(with_eps)) == (eps == 0.0)


def test_held_layer_passes_eps_to_its_router():
    """The layer's program with `eps` 0 is the one without the argument,
    and another with any other `eps` (whose weights the test above
    reads)."""
    x, rw, wg, wu, wd, b = _randn(4, (N, H), (E_ALL, H), (4, I_, H),
                                  (4, I_, H), (4, H, I_), (E_ALL,))

    def text(*eps):
        return jax.jit(lambda *a: moe_held_ffn(
            *a, TOP, 4, 8, "sigmoid", b, 1.0, *eps)[0]).lower(
                x, rw, wg, wu, wd).as_text()
    assert text() == text(0.0) != text(1e-6)
