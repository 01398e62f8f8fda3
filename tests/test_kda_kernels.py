"""The delta rule with one decay a key channel (Kimi Delta Attention)
through the Pallas kernels of `ops/delta_rule_kernels.py`, interpreted
here: against the token-by-token recurrence in float32, against the
plain path in bfloat16, and the precision each product asks for.
Small on purpose: B 1, one key head for one or two value heads of 128,
one or two grid steps."""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from mxnet_tpu.ops import delta_rule_kernels as dk
from mxnet_tpu.ops import linear_attention as la
from mxnet_tpu.ops.linear_attention import gated_delta_rule
from qwen3_next_helpers import HI, _close, _products, _randn


def _recurrence(q, k, v, g, beta):
    """Token by token, as the equations have it; g (B, T, Hv, Dk)."""
    B, T, Hk, Dk = q.shape
    Hv = v.shape[2]
    q, k = (jnp.repeat(la._l2norm(x), Hv // Hk, axis=2) for x in (q, k))

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S
        d = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0)
               for x in (q * Dk ** -0.5, k, v, g, beta))
    _, o = lax.scan(token, jnp.zeros((B, Hv, Dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def _out_and_grads(fn, args):
    """`_value_and_grads` as one compiled program: an interpreted kernel
    is a long program to compile, and the backward holds the forward."""
    (_, out), grads = jax.jit(jax.value_and_grad(
        lambda *a: (lambda o: (jnp.sum(jnp.sin(o)), o))(fn(*a)),
        tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


def _inputs(T, Hv, decay, dtype=jnp.float32, D=128):
    q, k, v, a, b = _randn(T + Hv, (1, T, 1, D), (1, T, 1, D), (1, T, Hv, D),
                           (1, T, Hv, D), (1, T, Hv))
    # every channel its own decay, a factor of ten and more apart
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype),
            -decay * jax.nn.softplus(2.0 * a), jax.nn.sigmoid(b))


def _paths():
    path = la.DELTA_PATH
    return path.get(path="kernel"), path.get(path="plain")


def _plain(chunk):
    return lambda *a: la._plain_channels(*a, chunk, True)


def _f32(fn):
    return lambda *a: fn(*a).astype(jnp.float32)


@pytest.mark.parametrize("T,chunk,Hv", [
    (128, 64, 1), (256, 64, 1), (256, 32, 1), (128, 64, 2), (256, 64, 2),
    (128, 32, 2)], ids=["one_tile", "two_tiles", "four_chunks_a_tile",
                        "two_value_heads", "two_value_heads_two_tiles",
                        "two_value_heads_four_chunks"])
def test_channel_kernels_match_the_recurrence(T, chunk, Hv):
    """Output and all five gradients, float32 at `highest`, the
    tolerances the plain path is held to. Two tiles: the state crosses a
    grid step forward and its cotangent backward. Two value heads read
    one key head's q and k, each with its own decays, and the kernels
    take them (`tiles` sends no group size to the plain path)."""
    args = _inputs(T, Hv, 0.03)
    kernel0, plain0 = _paths()
    with HI:
        (want, g_want), (got, g_got) = (
            _out_and_grads(fn, args) for fn in (
                _recurrence, lambda *a: gated_delta_rule(*a, chunk=chunk)))
    assert _paths()[1] == plain0 and _paths()[0] > kernel0
    _close(got, want, 2e-5)
    for a, b in zip(g_got, g_want):
        assert bool(jnp.isfinite(a).all())
        _close(a, b, 5e-5)


def _distance(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b).max())


@pytest.mark.parametrize("decay", [0.03, 5.0])
def test_channel_kernels_in_bfloat16(decay):
    """bfloat16 operands: the kernels round what the plain path rounds,
    so they stand no further from the float32 recurrence on the same
    rounded inputs than the plain path does, by more than 5% (of the
    largest value, which also covers what both get right to the last
    bit). At decay 5 a channel falls by e^-320 a chunk: no exponent may
    be above zero anywhere, forward or backward."""
    args = _inputs(256, 1, decay, jnp.bfloat16)
    want, g_want = _out_and_grads(
        _recurrence, [x.astype(jnp.float32) for x in args])
    plain, g_plain = _out_and_grads(_f32(_plain(64)), args)
    got, g_got = _out_and_grads(
        _f32(lambda *a: gated_delta_rule(*a, chunk=64)), args)
    assert gated_delta_rule(*args, chunk=64).dtype == jnp.bfloat16
    for mine, theirs, exact in zip((got,) + tuple(g_got),
                                   (plain,) + tuple(g_plain),
                                   (want,) + tuple(g_want)):
        assert bool(jnp.isfinite(mine.astype(jnp.float32)).all())
        scale = float(jnp.abs(exact).max())
        assert _distance(mine, exact) <= 1.05 * _distance(theirs, exact) \
            + 0.002 * scale, (_distance(mine, exact), _distance(theirs, exact),
                              scale)
        assert _distance(mine, exact) <= 4e-2 * scale
    # g's and beta's gradients are float32 and nothing rounds them after
    # the kernel (they go on to `A_log` and `dt_bias`)
    assert g_got[3].dtype == g_got[4].dtype == jnp.float32


def test_channel_kernels_catch_a_state_not_carried_between_chunks():
    args = _inputs(256, 1, 0.03)
    with HI:
        want = jax.jit(_recurrence)(*args)
        broken = jax.jit(lambda *a: gated_delta_rule(
            *a, chunk=64, carry_state=False))(*args)
        d_broken = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
            gated_delta_rule(*a, chunk=64, carry_state=False)))))(*args)
    # the first chunk needs no carried state; every later one does, the
    # second of a grid step's two as well
    _close(broken[:, :64], want[:, :64], 2e-5)
    for rows in (slice(64, 128), slice(128, 256)):
        gap = float(jnp.abs(broken[:, rows] - want[:, rows]).max())
        assert gap > 0.05 * float(jnp.abs(want).max()), gap
    assert bool(jnp.isfinite(d_broken).all())


def test_channel_kernels_on_keys_that_all_but_coincide():
    """Every k_t.k_s near 1, beta near 0.9 and hardly any decay: A is
    0.9 all over the triangle, where a sum of the tree's levels that
    lost a level or counted one twice, or an inverse with a bfloat16
    pass in it, is wrong a thousandfold."""
    q, _, v, g, _ = _inputs(128, 1, 0.002)
    noise, = _randn(11, q.shape)
    k = 1.0 + 0.05 * noise
    beta = jnp.full(g.shape[:3], 0.9)
    with HI:
        (want, g_want), (got, g_got) = (
            _out_and_grads(fn, (q, k, v, g, beta)) for fn in (
                _recurrence, lambda *a: gated_delta_rule(*a, chunk=64)))
    _close(got, want, 2e-5)
    for a, b in zip(g_got, g_want):
        _close(a, b, 1e-4)


def test_a_scalar_decay_broadcast_over_the_channels_is_the_scalar_kernels():
    """One decay a head through `gated_delta_rule_fwd` / `_bwd`, and the
    same decay written out for every channel through
    `gated_delta_rule_channels_fwd` / `_bwd`: one result."""
    q, k, v, g, beta = _inputs(256, 2, 0.1)
    g = g[..., 0]
    wide = jnp.broadcast_to(g[..., None], g.shape + (128,))
    rule = lambda decay: lambda q, k, v, beta: gated_delta_rule(  # noqa: E731
        q, k, v, decay, beta, chunk=64)
    traced = str(jax.make_jaxpr(jax.grad(
        lambda *a: rule(wide)(*a).sum()))(q, k, v, beta))
    assert "gated_delta_rule_channels_fwd" in traced
    assert "gated_delta_rule_channels_bwd" in traced
    assert "gated_delta_rule_fwd" in str(jax.make_jaxpr(rule(g))(q, k, v, beta))
    with HI:
        (scalar, g_scalar), (channel, g_channel) = (
            _out_and_grads(rule(decay), (q, k, v, beta))
            for decay in (g, wide))
    _close(channel, scalar, 2e-5)
    for a, b in zip(g_channel, g_scalar):
        _close(a, b, 5e-5)


def test_channel_kernels_round_no_pair_inside_a_block_of_16():
    """The precision rule at bfloat16, which the CPU cannot show in the
    numbers: in both kernels every product of two float32 matrices asks
    for `HIGHEST`, the products of operands in v's dtype ask for
    nothing, and of the tree's six levels a grid step (q's rows under
    k's, 256 by 128) only the two whose halves are 16 rows and more
    take bfloat16 operands."""
    args = _inputs(128, 1, 0.03, jnp.bfloat16)
    fwd = jax.make_jaxpr(
        lambda *a: gated_delta_rule(*a, chunk=64))(*args).jaxpr
    both = jax.make_jaxpr(jax.grad(
        lambda *a: gated_delta_rule(*a, chunk=64).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4)))(*args).jaxpr
    highest = (lax.Precision.HIGHEST, lax.Precision.HIGHEST)
    for traced in (fwd, both):
        for eqn in _products(traced):
            dtypes = {str(v.aval.dtype) for v in eqn.invars}
            # (a float32 cotangent by a bfloat16 operand, in the
            # backward, is the scalar kernels' too and asks for nothing)
            assert eqn.params["precision"] == (
                highest if dtypes == {"float32"} else None), eqn
    levels = [str(eqn.invars[0].aval.dtype) for eqn in _products(fwd)
              if eqn.invars[0].aval.shape == (256, 128)
              and eqn.invars[1].aval.shape == (128, 128)]
    assert sorted(levels) == ["bfloat16"] * 2 + ["float32"] * 4, levels


@pytest.mark.parametrize("b", [1, 2, 4, 16])
def test_boundary_rows_and_their_cotangent(b):
    """`_boundary`: every row reads the first row of the upper half of
    its block of 2b rows; its hand-written backward is the transpose."""
    x, = _randn(b, (64, 128))
    got, pull = jax.vjp(lambda c: dk._boundary(c, b), x)
    rows = (jnp.arange(64) // (2 * b)) * 2 * b + b
    assert bool((got == x[rows]).all())
    g, = _randn(b + 1, (64, 128))
    _close(pull(g)[0], jax.grad(lambda c: (c[rows] * g).sum())(x), 1e-6)


def test_a_chunk_that_is_no_power_of_two_takes_the_plain_path():
    """The masks and the merges halve a chunk down to single rows."""
    assert dk.tiles(256, 128, 128, 64, jnp.float32)
    assert not dk.tiles(96, 128, 128, 24, jnp.float32)
    assert not dk.tiles(96, 128, 128, 48, jnp.bfloat16)
