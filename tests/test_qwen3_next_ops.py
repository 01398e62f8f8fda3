"""Qwen3-Next's mixers on the CPU at tiny sizes: the gated delta rule,
the causal convolution, the norms, the blocked attention and the rotary
embedding, each against a plain form written here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu.ops import linear_attention as la
from mxnet_tpu.ops.attention import (blocked_causal_attention,
                                     rotary_embedding)
from mxnet_tpu.ops.linear_attention import (causal_conv1d, gated_delta_rule,
                                            gated_rms_norm, rms_norm)
from mxnet_tpu.ops.pallas_kernels import flash_attention, _attn_reference
from qwen3_next_helpers import (HI, _close, _products, _randn,
                                _value_and_grads)


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


# -- the same rule through the Pallas kernels (interpreted here) ------------
# B 1-2, T 256, one key head for two value heads of 128: the smallest
# shape that tiles as the cell's does (two chunks of 64 a grid step)
def _tiling_inputs(B=2, T=256, Hk=1, Hv=2, D=128, dtype=jnp.float32):
    q, k, v, a, b = _randn(T + B, (B, T, Hk, D), (B, T, Hk, D),
                           (B, T, Hv, D), (B, T, Hv), (B, T, Hv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype),
            -0.03 * jax.nn.softplus(a), jax.nn.sigmoid(b))


def _delta_paths():
    path = la.DELTA_PATH
    return path.get(path="kernel"), path.get(path="plain")


def _plain(chunk):
    return lambda *a: la._plain(*a, chunk, True)


@pytest.mark.parametrize("B", [1, 2])
def test_kernel_delta_rule_matches_the_recurrence_and_the_plain_path(B):
    """float32 at `highest`, the tolerances the plain path is held to:
    output and all five gradients, against the token-by-token recurrence
    and against the plain path at the same shape."""
    args = _tiling_inputs(B)
    kernel0, plain0 = _delta_paths()
    with HI:
        (want, g_want), (plain, g_plain), (got, g_got) = (
            _value_and_grads(fn, args) for fn in (
                _recurrence, _plain(64),
                lambda *a: gated_delta_rule(*a, chunk=64)))
    assert _delta_paths()[1] == plain0 and _delta_paths()[0] > kernel0
    for ref, g_ref in ((want, g_want), (plain, g_plain)):
        _close(got, ref, 2e-5)
        for a, b in zip(g_got, g_ref):
            _close(a, b, 5e-5)


@pytest.mark.parametrize("B", [1, 2])
def test_kernel_delta_rule_in_bfloat16(B):
    """bfloat16 operands: the kernels round where the plain path rounds,
    so the two stand equally far from the float32 recurrence on the same
    rounded inputs and close to one another: 1% of the largest output,
    2% of the largest gradient (a gradient is bfloat16 itself: two units
    in its last place are 1.2% at the top of a binade)."""
    args = _tiling_inputs(B, dtype=jnp.bfloat16)
    as_f32 = lambda fn: lambda *a: fn(*a).astype(jnp.float32)  # noqa: E731
    want, g_want = _value_and_grads(
        _recurrence, [x.astype(jnp.float32) for x in args])
    plain, g_plain = _value_and_grads(as_f32(_plain(64)), args)
    got, g_got = _value_and_grads(
        as_f32(lambda *a: gated_delta_rule(*a, chunk=64)), args)
    assert got.dtype == jnp.float32 and gated_delta_rule(
        *args, chunk=64).dtype == jnp.bfloat16
    for ref, g_ref in ((want, g_want), (plain, g_plain)):
        _close(got, ref, 1e-2)
        for a, b in zip(g_got, g_ref):
            _close(a.astype(jnp.float32), b.astype(jnp.float32), 2e-2)
    # g's and beta's gradients are float32 and nothing rounds them after
    # the kernel (they go on to `A_log` and `dt_bias`): closer than the
    # rest to the plain path's (2.4e-3 and 3.2e-3 read here)
    for a, b in zip(g_got[3:], g_plain[3:]):
        assert a.dtype == jnp.float32
        _close(a, b, 5e-3)


def test_kernels_multiply_float32_by_float32_at_highest():
    """The precision rule at bfloat16, which the CPU cannot show in the
    numbers (it multiplies float32 as float32 whatever it is asked): in
    both kernels every product of two float32 matrices asks for `HIGHEST`
    (the inverse's ten products, U and W, and in the backward their
    transposes and d(T^-1)), and the products of operands in v's dtype
    ask for nothing."""
    args = _tiling_inputs(1, dtype=jnp.bfloat16)
    traced = jax.make_jaxpr(jax.grad(
        lambda *a: gated_delta_rule(*a, chunk=64).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4)))(*args).jaxpr
    kinds = {}
    for eqn in _products(traced):
        dtypes = tuple(str(v.aval.dtype) for v in eqn.invars)
        kinds.setdefault(dtypes, []).append(eqn.params["precision"])
    highest = (lax.Precision.HIGHEST, lax.Precision.HIGHEST)
    both32 = kinds.pop(("float32", "float32"))
    # a value head: the inverse's ten and U|W in the forward; U|W's two
    # transposes, d(T^-1)'s two and the rebuilt U|W in the backward
    assert len(both32) == 2 * (11 + 5), len(both32)
    assert all(p == highest for p in both32), both32
    assert ("bfloat16", "bfloat16") in kinds
    assert all(p is None for ps in kinds.values() for p in ps), kinds


@pytest.mark.parametrize("C,L", [(16, 128), (64, 128), (64, 64), (128, 128)])
def test_unit_lower_inverse_on_correlated_keys(C, L):
    """(I + A)^-1 by blocks of 16 and pairwise merges, for the A of keys
    that all but coincide (every k_t.k_s near 1, beta 0.9, hardly any
    decay: entries of 0.9 all over the triangle, where the one-product
    form over a whole chunk cancels terms of size C(63, k)): float32's
    own residual, which a bfloat16 pass anywhere would break a
    thousandfold."""
    from mxnet_tpu.ops.delta_rule_kernels import _unit_lower_inverse
    noise, = _randn(C, (L, 128))
    t, s = np.indices((L, L))
    pattern = (t // C == s // C) & (s < t)
    c = -0.005 * (np.arange(L) % C + 1.0)
    eye = jnp.eye(L)

    @jax.jit          # one program, not an op at a time
    def inverse_and_residual(noise):
        k = 1.0 + 0.05 * noise
        k = k / jnp.linalg.norm(k, axis=1, keepdims=True)
        A = jnp.where(pattern, 0.9 * jnp.exp(c[:, None] - c[None, :])
                      * jnp.dot(k, k.T, precision="highest"), 0.0)
        inv = _unit_lower_inverse(A, C)
        return A, inv, jnp.dot(eye + A, inv, precision="highest") - eye

    A, inv, residual = inverse_and_residual(noise)
    assert float(A[1, 0]) > 0.85
    assert float(jnp.abs(residual).max()) < 2e-6, float(jnp.abs(residual).max())
    _close(inv, jnp.linalg.inv(np.asarray(eye + A, np.float64)), 2e-6)
    assert not bool(jnp.where(t // C == s // C, 0.0, inv).any())


@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_kernel_delta_rule_at_other_chunks(chunk):
    """Eight, four and one chunk a grid step, against the plain path."""
    args = _tiling_inputs(1)
    with HI:
        (want, g_want), (got, g_got) = (_value_and_grads(fn, args) for fn in (
            _plain(chunk), lambda *a: gated_delta_rule(*a, chunk=chunk)))
    _close(got, want, 2e-5)
    for a, b in zip(g_got, g_want):
        _close(a, b, 5e-5)


def test_kernel_path_catches_a_state_not_carried_between_chunks():
    args = _tiling_inputs(1)
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


def test_kernel_shape_still_refuses_a_ragged_sequence():
    args = _tiling_inputs(1, T=200)
    with pytest.raises(ValueError, match="chunks of 64"):
        gated_delta_rule(*args, chunk=64)


def test_delta_rule_path_is_chosen_from_the_shape_and_the_mesh():
    """`linear_attention.delta.path`: `kernel` where the heads are whole
    128-lane tiles, alone or per shard of the batch under a `dp` mesh;
    `plain` at D 8, and under a mesh with another wide axis."""
    from mxnet_tpu.parallel import make_mesh, use_mesh
    args = _tiling_inputs(2)
    rule = jax.jit(lambda *a: gated_delta_rule(*a, chunk=64))
    with HI:
        kernel0, plain0 = _delta_paths()
        alone = rule(*args)
        assert _delta_paths() == (kernel0 + 1, plain0)
        gated_delta_rule(*_delta_inputs(32, 2, 4, 8, 0.03), chunk=8)
        assert _delta_paths() == (kernel0 + 1, plain0 + 1)
        with use_mesh(make_mesh({"dp": 2}, jax.devices()[:2])):
            sharded = jax.jit(lambda *a: gated_delta_rule(*a, chunk=64))(*args)
        assert _delta_paths() == (kernel0 + 2, plain0 + 1)
        with use_mesh(make_mesh({"dp": 2, "tp": 2}, jax.devices()[:4])):
            other = jax.jit(lambda *a: gated_delta_rule(*a, chunk=64))(*args)
        assert _delta_paths() == (kernel0 + 2, plain0 + 2)
        # three devices do not divide a batch of two
        with use_mesh(make_mesh({"dp": 3}, jax.devices()[:3])):
            jax.jit(lambda *a: gated_delta_rule(*a, chunk=64))(*args)
        assert _delta_paths() == (kernel0 + 2, plain0 + 3)
    _close(sharded, alone, 1e-6)
    _close(other, alone, 2e-5)


def _xla_operations(jaxpr):
    """Every equation outside a `pallas_call`, through the nested
    programs (jit, custom_vjp, shard_map)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        inner = [getattr(p, "jaxpr", p) for v in eqn.params.values()
                 for p in (v if isinstance(v, (list, tuple)) else [v])
                 if hasattr(getattr(p, "jaxpr", p), "eqns")]
        if inner:
            for sub in inner:
                yield from _xla_operations(sub)
        else:
            yield eqn


def _large_float32(eqn, C, wide):
    """float32 arrays an operation reads or writes that are C x C (or
    two chunks square) a chunk, or `wide` (2D) a token."""
    found = []
    for var in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(var, "aval", None)
        shape = getattr(aval, "shape", ())
        if getattr(aval, "dtype", None) != jnp.float32 or len(shape) < 2:
            continue
        if shape[-2:] in ((C, C), (2 * C, 2 * C)) or (
                shape[-1] == wide and shape[-2] % C == 0):
            found.append(shape)
    return found


def test_kernel_step_holds_no_chunk_square_outside_the_kernels():
    """Forward and backward at bfloat16: no XLA operation reads or writes
    a float32 array of C x C a chunk or of 2D a token. What the backward
    is handed on purpose (every grid step's entry state and inverses)
    goes from one `pallas_call` to the other untouched. The plain path,
    traced the same way, is made of such arrays."""
    T, C, D = 256, 64, 128
    args = _tiling_inputs(1, dtype=jnp.bfloat16)

    def step(rule):
        return jax.make_jaxpr(jax.grad(
            lambda *a: rule(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4)))(*args).jaxpr

    traced = step(lambda *a: gated_delta_rule(*a, chunk=C))
    text = str(traced)
    assert "gated_delta_rule_fwd" in text and "gated_delta_rule_bwd" in text
    for eqn in _xla_operations(traced):
        assert not _large_float32(eqn, C, 2 * D), eqn
    assert any(_large_float32(eqn, C, 2 * D)
               for eqn in _xla_operations(step(_plain(C))))


def test_causal_convolution_and_norms():
    x, w, z, g = _randn(3, (2, 9, 6), (6, 4), (2, 9, 6), (6,))
    want = np.zeros((2, 9, 6), np.float32)
    xn, wn = np.asarray(x), np.asarray(w)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += wn[:, j] * xn[:, t - 3 + j]

    @jax.jit          # one program, not an op at a time
    def all_four(x, w, z, g, want):
        rms = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return ((causal_conv1d(x, w), want),
                (causal_conv1d(x, w, "silu"), jax.nn.silu(want)),
                (rms_norm(x, g, offset=1.0), rms * (1 + g)),
                (gated_rms_norm(x, z, g), rms * g * jax.nn.silu(z)))
    for got, wanted in all_four(x, w, z, g, jnp.asarray(want)):
        _close(got, wanted, 1e-5)


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
    y = jax.jit(lambda x: rotary_embedding(x, 8, 1e4))(x)
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

