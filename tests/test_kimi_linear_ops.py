"""Kimi Linear's mixers and router on the CPU at tiny sizes: the delta
rule with one decay a key channel against the token-by-token
recurrence, attention with value heads of another size than the keys',
the sigmoid router against a loop over the experts, and the shares
against the uncut reference layer."""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from mxnet_tpu.ops import linear_attention as la
from mxnet_tpu.ops.attention import LATENT_LAYERS, blocked_causal_attention
from mxnet_tpu.ops.linear_attention import gated_delta_rule, gated_rms_norm
from mxnet_tpu.ops.moe import moe_held_ffn, route_top_k, shared_expert_ffn
from qwen3_next_helpers import HI, _close, _randn, _value_and_grads


# -- the delta rule with a decay a channel -----------------------------------
def _recurrence(q, k, v, g, beta):
    """Token by token, as the equations have it; g (B, T, H, Dk)."""
    B, T, H, Dk = q.shape

    def l2(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S
        d = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0)
               for x in (l2(q) * Dk ** -0.5, l2(k), v, g, beta))
    _, o = lax.scan(token, jnp.zeros((B, H, Dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def _channel_inputs(T, H, Dk, Dv, decay, dtype=jnp.float32):
    q, k, v, a, b = _randn(T + H, (2, T, H, Dk), (2, T, H, Dk), (2, T, H, Dv),
                           (2, T, H, Dk), (2, T, H))
    # every channel its own decay, a factor of ten and more apart
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype),
            -decay * jax.nn.softplus(2.0 * a), jax.nn.sigmoid(b))


@pytest.mark.parametrize("T,chunk,decay", [
    (64, 32, 0.03), (96, 32, 0.3), (48, 16, 0.03), (24, 8, 0.03),
    (64, 64, 5.0)], ids=["two_row_blocks", "faster", "one_row_block",
                         "short_chunk", "overflow"])
def test_per_channel_delta_rule_matches_the_recurrence(T, chunk, decay):
    """Output and all five gradients, float32 at `highest`. At decay 5
    a channel falls by e^-5 and more a token, e^-320 a chunk of 64:
    factored as (k_t exp(c_t)) (k_s exp(-c_s)) the second factor is
    infinite in float32 from the eighteenth row on."""
    args = _channel_inputs(T, 2, 8, 12, decay)
    if decay == 5.0:
        assert float(-args[3].max()) * chunk > 88 or float(
            -jnp.cumsum(args[3], 1)[:, chunk - 1].min()) > 88
    with HI:
        (want, g_want), (got, g_got) = (
            _value_and_grads(fn, args) for fn in (
                _recurrence, lambda *a: gated_delta_rule(*a, chunk=chunk)))
    assert bool(jnp.isfinite(got).all())
    _close(got, want, 2e-5)
    for a, b in zip(g_got, g_want):
        assert bool(jnp.isfinite(a).all())
        _close(a, b, 5e-5)


@pytest.mark.parametrize("decay", [0.03, 5.0])
def test_per_channel_delta_rule_in_bfloat16(decay):
    """bfloat16 operands, float32 sums: within bfloat16's rounding of the
    float32 recurrence on the same (rounded) inputs."""
    args = _channel_inputs(64, 2, 8, 12, decay, jnp.bfloat16)
    as32 = tuple(a.astype(jnp.float32) for a in args)
    with HI:
        want, g_want = _value_and_grads(_recurrence, as32)
        got, g_got = _value_and_grads(
            lambda *a: gated_delta_rule(*a, chunk=32).astype(jnp.float32),
            args)
    assert got.dtype == jnp.float32 and gated_delta_rule(
        *args, chunk=32).dtype == jnp.bfloat16
    _close(got, want, 2e-2)
    for a, b in zip(g_got, g_want):
        _close(a.astype(jnp.float32), b, 4e-2)


@pytest.mark.parametrize("decay", [0.03, 0.3])
def test_per_channel_state_not_carried_between_chunks_is_caught(decay):
    args = _channel_inputs(64, 2, 8, 8, decay)
    with HI:
        want = _recurrence(*args)
        good = gated_delta_rule(*args, chunk=16)
        bad = gated_delta_rule(*args, chunk=16, carry_state=False)
    _close(good, want, 2e-5)
    # the first chunk starts from zero either way
    _close(bad[:, :16], want[:, :16], 2e-5)
    assert float(jnp.abs(bad - want).max()) > 0.02 * float(jnp.abs(want).max())


def test_a_scalar_decay_broadcast_over_the_channels_is_the_scalar_path():
    q, k, v, g, beta = _channel_inputs(64, 2, 8, 12, 0.1)
    g = g[..., 0]
    wide = jnp.broadcast_to(g[..., None], g.shape + (8,))
    with HI:
        (scalar, g_scalar), (channel, g_channel) = (
            _value_and_grads(lambda q, k, v, beta: gated_delta_rule(
                q, k, v, decay, beta, chunk=32), (q, k, v, beta))
            for decay in (g, wide))
    _close(channel, scalar, 2e-5)
    for a, b in zip(g_channel, g_scalar):
        _close(a, b, 5e-5)


def test_a_per_channel_decay_takes_the_kernels_where_its_shape_tiles():
    """`linear_attention.delta.path` for one decay a channel: `kernel`
    at a shape the kernels tile (D 128, chunks of 64), alone or per
    shard of the batch under a `dp` mesh; `plain` at D 64 and under a
    mesh with a wide axis other than `dp`; and the two paths agree."""
    from mxnet_tpu.parallel import make_mesh, use_mesh
    path = la.DELTA_PATH
    counts = lambda: (path.get(path="kernel"), path.get(path="plain"))  # noqa: E731

    def inputs(D):
        q, k, v, a, b = _randn(5, (2, 128, 1, D), (2, 128, 1, D),
                               (2, 128, 1, D), (2, 128, 1, D), (2, 128, 1))
        return q, k, v, -0.03 * jax.nn.softplus(2.0 * a), jax.nn.sigmoid(b)

    rule = lambda: jax.jit(lambda *x: gated_delta_rule(*x, chunk=64))  # noqa: E731
    args = inputs(128)
    with HI:
        kernel0, plain0 = counts()
        alone = rule()(*args)
        assert counts() == (kernel0 + 1, plain0)
        rule()(*inputs(64))
        assert counts() == (kernel0 + 1, plain0 + 1)
        with use_mesh(make_mesh({"dp": 2}, jax.devices()[:2])):
            sharded = rule()(*args)
        assert counts() == (kernel0 + 2, plain0 + 1)
        with use_mesh(make_mesh({"dp": 2, "tp": 2}, jax.devices()[:4])):
            other = rule()(*args)
        assert counts() == (kernel0 + 2, plain0 + 2)
        plain = jax.jit(lambda *x: la._plain_channels(*x, 64, True))(*args)
    _close(alone, plain, 2e-5)
    _close(sharded, alone, 1e-6)
    _close(other, plain, 1e-6)


def test_per_channel_step_holds_no_array_of_every_chunks_squares():
    """The plain path (D 8 does not tile): outside the scan over the N
    chunks nothing carries a chunk's C x C system or its decayed keys for every chunk at once
    (one chunk's constants may sit there), and nothing is larger than g."""
    T, C, D = 768, 32, 8
    args = _channel_inputs(T, 2, D, D, 0.03)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: gated_delta_rule(*a, chunk=C).sum(), argnums=(0, 1, 2, 3)))(
            *args)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    outside = [v.aval.shape for e in jaxpr.jaxpr.eqns if e not in scans
               for v in e.outvars]
    assert len(scans) == 2 and outside
    assert not any(T // C in s and s[-2:] == (C, C) for s in outside), outside
    size = lambda s: int(jnp.prod(jnp.array(s)))    # noqa: E731
    assert max(map(size, outside)) <= args[3].size, max(outside, key=size)


def test_gated_norm_with_a_sigmoid_gate():
    x, z, w = _randn(3, (2, 6, 4, 8), (2, 6, 4, 8), (8,))

    @jax.jit          # one program, not an op at a time
    def both(x, z, w):
        rms = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
        return (gated_rms_norm(x, z, w, 1e-5, "sigmoid"),
                rms * w * jax.nn.sigmoid(z), gated_rms_norm(x, z, w, 1e-5),
                rms * w * jax.nn.silu(z))
    sig, sig_want, silu, silu_want = both(x, z, w)
    _close(sig, sig_want, 1e-5)
    _close(silu, silu_want, 1e-5)
    with pytest.raises(ValueError):
        gated_rms_norm(x, z, w, 1e-5, "tanh")


# -- attention with keys wider than values ------------------------------------
def _materialised(q, k, v, scale):
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) * scale
    T = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("Dqk,Dv,block", [(12, 8, 8), (24, 16, 16), (8, 12, 32)])
def test_blocked_attention_with_values_of_another_size(Dqk, Dv, block):
    q, k, v = _randn(Dv, (2, 32, 4, Dqk), (2, 32, 4, Dqk), (2, 32, 4, Dv))
    scale = Dqk ** -0.5
    latent0 = LATENT_LAYERS.total()
    blocked = lambda *a: blocked_causal_attention(   # noqa: E731
        *a, block_q=block, scale=scale)
    with HI:
        (out, got), (ref, want) = (
            _value_and_grads(fn, (q, k, v))
            for fn in (blocked, lambda *a: _materialised(*a, scale)))
    assert out.shape == (2, 32, 4, Dv)
    # counted once a trace: the value's and the gradient's programs
    assert LATENT_LAYERS.total() == latent0 + 2
    _close(out, ref, 2e-5)
    for a, b in zip(got, want):
        _close(a, b, 5e-5)


def test_blocked_backward_with_wider_keys_holds_no_square():
    T, block = 64, 8
    q, k, v = _randn(1, (1, T, 2, 12), (1, T, 2, 12), (1, T, 2, 8))
    latent0 = LATENT_LAYERS.total()
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: blocked_causal_attention(*a, block_q=block).sum(),
        argnums=(0, 1, 2)))(q, k, v))
    assert "%d,%d]" % (T, T) not in text and "%d,%d]" % (block, T) in text
    assert LATENT_LAYERS.total() == latent0 + 1
    same = _randn(2, (1, T, 2, 8))[0]
    jax.eval_shape(lambda x: blocked_causal_attention(x, x, x, block_q=block),
                   same)
    assert LATENT_LAYERS.total() == latent0 + 1      # one head size: not counted


# -- the sigmoid router --------------------------------------------------------
N, H, I, E_ALL, TOP, SCALE = 48, 16, 8, 32, 4, 2.446


def _expert_weights(seed=7, experts=E_ALL):
    x, rw, wg, wu, wd, b = _randn(seed, (N, H), (experts, H), (experts, I, H),
                                  (experts, I, H), (experts, H, I), (experts,))
    return x, 0.5 * rw, 0.3 * wg, 0.3 * wu, 0.3 * wd, 0.3 * b


def _sigmoid_router(x, rw, b, top):
    """Scores by a sigmoid, chosen by score + bias, weighed by the score
    alone, renormalised, scaled: written with argsort and a gather."""
    s = jax.nn.sigmoid(x @ rw.T)
    top_i = jnp.argsort(-(s + b), axis=-1)[:, :top]
    w = jnp.take_along_axis(s, top_i, -1)
    return top_i, SCALE * w / w.sum(-1, keepdims=True)


def _loop_over_experts(x, rw, wg, wu, wd, b, start, held, top=TOP):
    top_i, top_w = _sigmoid_router(x, rw, b, top)
    y = jnp.zeros_like(x)
    for e in range(start, start + held):
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)
        y = y + w[:, None] * ((jax.nn.silu(x @ wg[e].T) * (x @ wu[e].T))
                              @ wd[e].T)
    return y


# the plain router's choices, one compiled program (not an op at a time)
_ROUTED = jax.jit(lambda x, rw, b: _sigmoid_router(x, rw, b, TOP)[0])


def test_sigmoid_router_chooses_with_the_bias_and_weighs_without():
    x, rw, _, _, _, b = _expert_weights()
    with HI:
        top_i, top_w, counts = jax.jit(lambda x, rw, b: route_top_k(
            x, rw, TOP, "sigmoid", b, SCALE))(x, rw, b)
        plain_i, _, _ = jax.jit(lambda x, rw: route_top_k(
            x, rw, TOP, "sigmoid"))(x, rw)
        want_i, want_w = jax.jit(_sigmoid_router, static_argnums=3)(
            x, rw, b, TOP)
    assert bool((jnp.sort(top_i, -1) == jnp.sort(want_i, -1)).all())
    # the bias moves the choice
    assert bool((jnp.sort(top_i, -1) != jnp.sort(plain_i, -1)).any())
    order = jnp.argsort(top_i, -1), jnp.argsort(want_i, -1)
    _close(jnp.take_along_axis(top_w, order[0], -1),
           jnp.take_along_axis(want_w, order[1], -1), 1e-6)
    _close(top_w.sum(-1), jnp.full((N,), SCALE), 1e-6)
    assert float(counts.sum()) == N * TOP
    with pytest.raises(ValueError):
        route_top_k(x, rw, TOP, "tanh")


@pytest.mark.parametrize("start,held,tile", [(0, 8, 8), (8, 8, 4), (24, 8, 16),
                                             (0, 32, 8), (5, 2, 8)])
def test_sigmoid_routed_layer_matches_a_loop_over_experts(start, held, tile):
    x, rw, wg, wu, wd, b = _expert_weights()
    cut = slice(start, start + held)
    layer = lambda x, rw, a, b_, c: moe_held_ffn(   # noqa: E731
        x, rw, a, b_, c, TOP, start, tile, "sigmoid", b, SCALE)
    with HI:
        y, rows, _ = jax.jit(layer)(x, rw, wg[cut], wu[cut], wd[cut])
        top_i = _ROUTED(x, rw, b)
        got = _value_and_grads(lambda *a: layer(*a)[0],
                               (x, rw, wg[cut], wu[cut], wd[cut]))[1]
        ref, want = _value_and_grads(
            lambda x, rw, wg, wu, wd: _loop_over_experts(
                x, rw, wg, wu, wd, b, start, held), (x, rw, wg, wu, wd))
    _close(y, ref, 2e-5)
    assert float(rows) == float(((top_i >= start)
                                 & (top_i < start + held)).sum())
    for a, w in zip(got, want):
        _close(a, w[cut] if w.shape[0] == E_ALL and w.ndim == 3 else w, 5e-5)


def test_no_token_is_dropped_when_the_bias_sends_every_token_to_one_expert():
    """The whole load on one held expert, by the bias alone: a capacity
    layer would drop; the weights stay the scores'."""
    x, rw, wg, wu, wd, b = _expert_weights()
    b = b.at[2].set(10.0)
    with HI:
        y, rows, load = jax.jit(lambda *a: moe_held_ffn(
            *a, TOP, 0, 8, "sigmoid", b, SCALE))(x, rw, wg[:8], wu[:8], wd[:8])
        top_i = _ROUTED(x, rw, b)
        ref = jax.jit(_loop_over_experts, static_argnums=(6, 7))(
            x, rw, wg, wu, wd, b, 0, 8)
    assert bool(jnp.all(jnp.any(top_i == 2, -1))) and float(load) > 5
    assert float(rows) >= N
    _close(y, ref, 2e-5)


def test_default_router_is_the_softmax_one_bit_for_bit():
    x, rw, wg, wu, wd, _ = _expert_weights()
    with HI:
        a = jax.jit(lambda *t: moe_held_ffn(*t, TOP, 0, 8))(
            x, rw, wg[:8], wu[:8], wd[:8])
        b = jax.jit(lambda *t: moe_held_ffn(*t, TOP, 0, 8, "softmax", None,
                                            1.0))(x, rw, wg[:8], wu[:8], wd[:8])
        p = jax.nn.softmax(x @ rw.T, -1)
    assert all(bool((u == v).all()) for u, v in zip(a, b))
    top_w = jax.jit(lambda x, rw: route_top_k(x, rw, TOP))(x, rw)[1]
    _close(jnp.sort(top_w, -1), jnp.sort(
        lax.top_k(p, TOP)[0] / lax.top_k(p, TOP)[0].sum(-1, keepdims=True), -1),
           1e-5)


def test_the_shares_add_up_to_the_uncut_reference_layer():
    """The cell's 8 experts held a chip and 8 chosen a token, at an eighth
    of the router's width (32 experts over 4 chips, where the cell has
    256 over 32; each share is a program's worth of loop to compile): the
    parts that the shares give, with the ungated shared expert counted
    once, are what the plain reference's layer gives with every expert
    held."""
    import qwen3_next_helpers  # noqa: F401  (the benchmark's path)
    from reference import kimi_linear_48b_a3b as ref
    experts, held, top = 32, 8, 8
    x, rw, wg, wu, wd, b = _expert_weights(11, experts)
    sg, su, sd = _randn(12, (I, H), (I, H), (H, I))
    p = {"l2_moe_router_weight": rw, "l2_moe_router_bias": b,
         "l2_moe_gate_weight": wg, "l2_moe_up_weight": wu,
         "l2_moe_down_weight": wd, "l2_moe_shared_gate_weight": 0.3 * sg,
         "l2_moe_shared_up_weight": 0.3 * su,
         "l2_moe_shared_down_weight": 0.3 * sd}

    @jax.jit
    def both(x, p):
        parts = [moe_held_ffn(x, rw, wg[s:s + held], wu[s:s + held],
                              wd[s:s + held], top, s, 8, "sigmoid", b, SCALE)
                 for s in range(0, experts, held)]
        shared = shared_expert_ffn(x, p["l2_moe_shared_gate_weight"],
                                   p["l2_moe_shared_up_weight"],
                                   p["l2_moe_shared_down_weight"])
        uncut = ref._experts(p, x, "l2_", "float32", dict(
            top_k=top, held_start=0, routed_scale=SCALE, remat=False))
        return (sum(part[0] for part in parts), sum(part[1] for part in parts),
                shared, uncut)

    with HI:
        summed, rows, shared, uncut = both(x, p)
        _close(shared, (jax.nn.silu(x @ p["l2_moe_shared_gate_weight"].T)
                        * (x @ p["l2_moe_shared_up_weight"].T))
               @ p["l2_moe_shared_down_weight"].T, 1e-5)
    assert float(rows) == N * top                  # no token dropped anywhere
    _close(summed + shared, uncut, 2e-5)
