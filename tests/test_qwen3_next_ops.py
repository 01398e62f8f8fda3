"""Qwen3-Next's mixers on the CPU at tiny sizes: the gated delta rule,
the causal convolution, the norms, the blocked attention and the rotary
embedding, each against a plain form written here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu.ops.attention import (blocked_causal_attention,
                                     rotary_embedding)
from mxnet_tpu.ops.linear_attention import (causal_conv1d, gated_delta_rule,
                                            gated_rms_norm, rms_norm)
from mxnet_tpu.ops.pallas_kernels import flash_attention, _attn_reference
from qwen3_next_helpers import HI, _close, _randn, _value_and_grads


def _recurrence(q, k, v, g, beta):
    """Token by token, as the equations have it."""
    B, T, Hk, Dk = q.shape
    Hv = v.shape[2]

    def l2(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q) * Dk ** -0.5, Hv // Hk, axis=2)
    k = jnp.repeat(l2(k), Hv // Hk, axis=2)

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None, None] * S
        d = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(token, jnp.zeros((B, Hv, Dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def _delta_inputs(T, Hk, Hv, D, decay):
    q, k, v, a, b = _randn(T + Hv, (2, T, Hk, D), (2, T, Hk, D),
                           (2, T, Hv, D), (2, T, Hv), (2, T, Hv))
    # exp(g) near 1 - decay: after three chunks the first tokens still count
    return q, k, v, -decay * jax.nn.softplus(a), jax.nn.sigmoid(b)


@pytest.mark.parametrize("T,chunk,Hk,Hv,D", [(32, 8, 2, 4, 8), (48, 16, 1, 2, 4),
                                             (24, 24, 2, 2, 8)])
def test_chunked_delta_rule_matches_the_recurrence(T, chunk, Hk, Hv, D):
    args = _delta_inputs(T, Hk, Hv, D, 0.03)
    assert float(jnp.exp(3 * chunk * args[3].mean())) > 0.05
    with HI:
        (want, g_want), (got, g_got) = (
            _value_and_grads(fn, args) for fn in (
                _recurrence, lambda *a: gated_delta_rule(*a, chunk=chunk)))
    _close(got, want, 2e-5)
    for a, b in zip(g_got, g_want):
        _close(a, b, 5e-5)


@pytest.mark.parametrize("decay", [0.03, 0.3])
def test_a_state_not_carried_between_chunks_is_caught(decay):
    args = _delta_inputs(32, 2, 4, 8, decay)
    with HI:
        want = jax.jit(_recurrence)(*args)
        broken = jax.jit(lambda *a: gated_delta_rule(
            *a, chunk=8, carry_state=False))(*args)
    # the first chunk needs no carried state; every later one does
    _close(broken[:, :8], want[:, :8], 2e-5)
    gap = float(jnp.abs(broken[:, 8:] - want[:, 8:]).max())
    assert gap > 0.05 * float(jnp.abs(want).max()), gap


def test_delta_rule_refuses_a_ragged_sequence():
    args = _delta_inputs(20, 1, 1, 4, 0.03)
    with pytest.raises(ValueError, match="chunks of 8"):
        gated_delta_rule(*args, chunk=8)


def test_causal_convolution_and_norms():
    x, w, z, g = _randn(3, (2, 9, 6), (6, 4), (2, 9, 6), (6,))
    want = np.zeros((2, 9, 6), np.float32)
    xn, wn = np.asarray(x), np.asarray(w)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += wn[:, j] * xn[:, t - 3 + j]
    _close(causal_conv1d(x, w), jnp.asarray(want), 1e-5)
    _close(causal_conv1d(x, w, "silu"), jax.nn.silu(jnp.asarray(want)), 1e-5)
    rms = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    _close(rms_norm(x, g, offset=1.0), rms * (1 + g), 1e-5)
    _close(gated_rms_norm(x, z, g), rms * g * jax.nn.silu(z), 1e-5)


# -- attention ---------------------------------------------------------------
def _materialised(q, k, v):
    Hq, Hkv, D = q.shape[2], k.shape[2], q.shape[3]
    k, v = (jnp.repeat(t, Hq // Hkv, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) * D ** -0.5
    T = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("Hq,Hkv,block", [(4, 2, 8), (8, 1, 16), (2, 2, 32)])
def test_blocked_attention_and_its_backward(Hq, Hkv, block):
    q, k, v = _randn(Hq, (2, 32, Hq, 16), (2, 32, Hkv, 16), (2, 32, Hkv, 16))
    rot = lambda t: rotary_embedding(t, 8, 1e4)        # noqa: E731 partial
    blocked = lambda q, k, v: blocked_causal_attention(  # noqa: E731
        rot(q), rot(k), v, block)
    plain = lambda q, k, v: _materialised(rot(q), rot(k), v)   # noqa: E731
    with HI:
        (out, got), (ref, want) = (_value_and_grads(fn, (q, k, v))
                                   for fn in (blocked, plain))
    _close(out, ref, 2e-5)
    for a, b in zip(got, want):
        _close(a, b, 5e-5)


def test_blocked_backward_holds_no_square():
    """Neither pass of the blocked form makes a T x T array."""
    T, block = 64, 8
    q, k, v = _randn(1, (1, T, 2, 8), (1, T, 1, 8), (1, T, 1, 8))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: blocked_causal_attention(*a, block_q=block).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    text = str(jaxpr)
    assert "%d,%d]" % (T, T) not in text and "%d,%d]" % (block, T) in text


def test_rotary_turns_the_first_dimensions_only():
    (x,) = _randn(5, (1, 6, 2, 16))
    y = rotary_embedding(x, 8, 1e4)
    assert jnp.array_equal(y[..., 8:], x[..., 8:])
    assert jnp.array_equal(y[:, 0], x[:, 0])            # position 0: no turn
    _close(jnp.sum(y * y, -1), jnp.sum(x * x, -1), 1e-5)   # a rotation
    ang = 3.0 * 1e4 ** (-2.0 / 8)                       # position 3, pair 1
    _close(y[0, 3, 0, 1], x[0, 3, 0, 1] * jnp.cos(ang)
           - x[0, 3, 0, 5] * jnp.sin(ang), 1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_backward_is_blocked(causal):
    q, k, v = _randn(9, (1, 2, 64, 16), (1, 2, 64, 16), (1, 2, 64, 16))
    with HI:
        got = _value_and_grads(lambda *a: flash_attention(
            *a, causal, 16, 16), (q, k, v))[1]
        want = _value_and_grads(lambda *a: _attn_reference(*a, causal),
                                (q, k, v))[1]
    for a, b in zip(got, want):
        _close(a, b, 1e-4)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: flash_attention(
        *a, causal, 16, 16).sum(), argnums=(0, 1, 2)))(q, k, v))
    assert "64,64]" not in text

