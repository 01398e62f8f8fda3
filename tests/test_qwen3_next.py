"""Qwen3-Next on the CPU at tiny sizes: each new op against a plain form
written here, the expert layer's shares against the uncut layer, the
rematerialised graph against the unmarked one, and the whole model
through `ShardedTrainer` against the plain reference of the benchmark."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import gluon, graph
from mxnet_tpu.gluon.model_zoo import GPTDecoder, Qwen3NextDecoder
from mxnet_tpu.ops.attention import (blocked_causal_attention,
                                     rotary_embedding)
from mxnet_tpu.ops.linear_attention import (causal_conv1d, gated_delta_rule,
                                            gated_rms_norm, rms_norm)
from mxnet_tpu.ops.moe import moe_held_ffn, route_top_k, shared_expert_ffn
from mxnet_tpu.ops.pallas_kernels import flash_attention, _attn_reference
from mxnet_tpu.parallel import ShardedTrainer, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

HI = jax.default_matmul_precision("highest")


def _randn(seed, *shapes):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s) for k, s in zip(keys, shapes)]


def _value_and_grads(fn, args):
    """(fn(*args), the gradients of sum(sin(fn)) in every argument), each
    one compiled program."""
    n = tuple(range(len(args)))
    return (jax.jit(fn)(*args),
            jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), n))(*args))


def _close(a, b, tol):
    scale = max(float(jnp.abs(b).max()), 1e-6)
    assert float(jnp.abs(a - b).max()) <= tol * scale, (
        float(jnp.abs(a - b).max()), scale)


# -- the gated delta rule ----------------------------------------------------
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


# -- the expert layer --------------------------------------------------------
N, H, I, E_ALL, TOP = 48, 16, 8, 16, 3


def _expert_weights(seed=7):
    x, rw, wg, wu, wd = _randn(seed, (N, H), (E_ALL, H), (E_ALL, I, H),
                               (E_ALL, I, H), (E_ALL, H, I))
    return x, 0.5 * rw, 0.3 * wg, 0.3 * wu, 0.3 * wd


def _loop_over_experts(x, rw, wg, wu, wd, start, held):
    top_i, top_w, _ = route_top_k(x, rw, TOP)
    y = jnp.zeros_like(x)
    for e in range(start, start + held):
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)
        y = y + w[:, None] * ((jax.nn.silu(x @ wg[e].T) * (x @ wu[e].T))
                              @ wd[e].T)
    return y


@pytest.mark.parametrize("start,held,tile", [(0, 4, 8), (8, 4, 4), (12, 4, 16),
                                             (0, 16, 8), (5, 2, 8)])
def test_held_expert_layer_matches_a_loop_over_experts(start, held, tile):
    x, rw, wg, wu, wd = _expert_weights()
    cut = slice(start, start + held)
    layer = lambda x, rw, a, b, c: moe_held_ffn(   # noqa: E731
        x, rw, a, b, c, TOP, start, tile)
    with HI:
        y, rows, load = jax.jit(layer)(x, rw, wg[cut], wu[cut], wd[cut])
        top_i, _, counts = jax.jit(lambda x, rw: route_top_k(x, rw, TOP))(x, rw)
        got = _value_and_grads(lambda *a: layer(*a)[0],
                               (x, rw, wg[cut], wu[cut], wd[cut]))[1]
        ref, want = _value_and_grads(lambda *a: _loop_over_experts(
            *a, start, held), (x, rw, wg, wu, wd))
    _close(y, ref, 2e-5)
    assert float(rows) == float(((top_i >= start)
                                 & (top_i < start + held)).sum())
    assert float(load) == pytest.approx(float(counts.max() / counts.mean()))
    for a, b in zip(got, want):
        _close(a, b[cut] if b.shape[0] == E_ALL and b.ndim == 3 else b, 5e-5)


def test_no_token_is_dropped_when_every_token_picks_one_expert():
    """The whole load on one held expert: a capacity layer would drop."""
    x, rw, wg, wu, wd = _expert_weights()
    rw = rw.at[2].set(0.0).at[2, 0].set(50.0)
    x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 1.0)
    with HI:
        y, rows, load = jax.jit(lambda *a: moe_held_ffn(*a, TOP, 0, 8))(
            x, rw, wg[:4], wu[:4], wd[:4])
        top_i, _, _ = jax.jit(lambda x, rw: route_top_k(x, rw, TOP))(x, rw)
        ref = jax.jit(lambda *a: _loop_over_experts(*a, 0, 4))(
            x, rw, wg, wu, wd)
    assert bool(jnp.all(jnp.any(top_i == 2, -1))) and float(load) > 5
    _close(y, ref, 2e-5)


@pytest.mark.parametrize("shares", [4, 16])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Each chip of `shares` holds E_ALL / shares experts and computes its
    own part; the parts, with the shared expert counted once, are the
    uncut layer."""
    x, rw, wg, wu, wd = _expert_weights(11)
    sg, su, sd, ss = _randn(12, (I, H), (I, H), (H, I), (1, H))
    held = E_ALL // shares
    @jax.jit
    def both(x, rw, wg, wu, wd):
        parts = [moe_held_ffn(x, rw, wg[s:s + held], wu[s:s + held],
                              wd[s:s + held], TOP, s, 8)
                 for s in range(0, E_ALL, held)]
        whole, rows, _ = moe_held_ffn(x, rw, wg, wu, wd, TOP, 0, 8)
        return (sum(p[0] for p in parts), sum(p[1] for p in parts), whole,
                rows, _loop_over_experts(x, rw, wg, wu, wd, 0, E_ALL),
                shared_expert_ffn(x, sg, su, sd, ss))

    with HI:
        summed, summed_rows, whole, rows, uncut, shared = both(
            x, rw, wg, wu, wd)
        _close(shared, jax.nn.sigmoid(x @ ss.T)
               * ((jax.nn.silu(x @ sg.T) * (x @ su.T)) @ sd.T), 1e-5)
    _close(summed + shared, uncut + shared, 2e-5)
    _close(whole, uncut, 2e-5)
    assert float(summed_rows) == float(rows) == N * TOP


# -- the model ---------------------------------------------------------------
import tiny_qwen3_next as tq      # noqa: E402  (benchmark/tests)


def _trainer(net, lr=1e-3):
    return ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                          {"learning_rate": lr, "beta2": 0.95},
                          mesh=make_mesh({"dp": 1}, jax.devices()[:1]))


def _tokens(seed=0, batch=2, length=24, vocab=61):
    x = np.random.default_rng(seed).integers(0, vocab, (batch, length))
    return x.astype(np.int32), np.roll(x, -1, 1).astype(np.float32)


def _qwen(remat, seed=5):
    mx.random.seed(seed)
    net = Qwen3NextDecoder(remat=remat, prefix="q_", **tq.KWARGS)
    net.initialize(mx.init.Normal(0.3))
    return net


def test_marked_layers_train_like_unmarked_ones():
    x, y = _tokens()
    out = {}
    for remat in (False, True):
        tr = _trainer(_qwen(remat))
        losses = [float(tr.step(x, y).asscalar())]
        text = str(jax.make_jaxpr(tr._make_step_body())(
            tr._params, tr._aux, tr._opt_state,
            {"data": jnp.asarray(x), "label": jnp.asarray(y)}, None))
        out[remat] = (losses, tr.params, text.count("remat2["))
    blocks = 24 // tq.KWARGS["block_q"]       # the attention's row blocks
    assert out[False][2] == blocks
    assert out[True][2] >= blocks + tq.KWARGS["num_layers"]
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-6)
    for k, v in out[False][1].items():
        np.testing.assert_allclose(out[True][1][k], v, rtol=2e-4, atol=1e-6)


def test_an_unmarked_graph_runs_node_by_node_as_before():
    """`GPTDecoder` marks nothing: its step holds no checkpoint and its
    graph function is the plain loop over the nodes."""
    net = GPTDecoder(31, max_seq_len=8, num_layers=2, num_heads=2,
                     embed_dim=16, prefix="g_")
    net.initialize()
    tr = _trainer(net)
    x, y = _tokens(1, 2, 8, 31)
    inputs = {"data": jnp.asarray(x), "label": jnp.asarray(y)}
    text = str(jax.make_jaxpr(tr._make_step_body())(
        tr._params, tr._aux, tr._opt_state, inputs, None))
    assert "checkpoint" not in text and "remat" not in text
    assert tr._counter_vars == {}
    entries = tr._loss_sym._entries
    assert graph._remat_units(graph.topo_order(entries)) is None

    def plain(args):                          # the executor before marks
        values = {}
        for node in graph.topo_order(entries):
            if node.is_variable:
                values[id(node)] = (args[node.name],)
                continue
            raw = node.op.fn(*[values[id(n)][i] for n, i in node.inputs],
                             **graph._reg.apply_defaults(node.op, node.params))
            values[id(node)] = raw if isinstance(raw, tuple) else (raw,)
        return [values[id(n)][i] for n, i in entries]

    args = {**tr._params, **inputs}
    fn = graph.build_graph_fn(entries, "train")[0]
    strip = lambda j: str(j).replace("mx.", "")        # noqa: E731
    assert strip(jax.make_jaxpr(lambda a: fn(a, {})[0])(args)).count("\n") \
        == strip(jax.make_jaxpr(plain)(args)).count("\n")
    np.testing.assert_array_equal(fn(args, {})[0][0], plain(args)[0])


def test_a_group_that_is_not_closed_is_refused():
    a = mx.sym.var("a")
    with mx.AttrScope(__remat__="g"):
        b = mx.sym.exp(a)
    c = mx.sym.sin(b)                          # outside, between two inside
    with mx.AttrScope(__remat__="g"):
        d = b + c
    with pytest.raises(mx.MXNetError, match="not closed"):
        graph.build_graph_fn(d._entries, "train")
    fn = graph.build_graph_fn(d._entries, "predict")[0]    # no remat: runs
    x = jnp.arange(3.0)
    np.testing.assert_allclose(fn({"a": x}, {})[0][0],
                               jnp.exp(x) + jnp.sin(jnp.exp(x)), rtol=1e-6)


@pytest.mark.parametrize("what", ["losses", "gradient", "three_adam_steps"])
def test_model_against_the_plain_reference(what, _followed):
    prog, ref, shapes = _followed
    import check
    numbers, _ = check.readings(prog, ref, shapes)
    if what == "losses":
        assert max(numbers["loss_gap_%d" % i] for i in (1, 2, 3)) < 1e-5
    elif what == "gradient":
        assert numbers["grad_diff"] < 1e-4 and numbers["grad_norm_gap"] < 1e-4
        assert len(prog["grad"]) == len(ref["grad"]) >= 60
    else:
        assert numbers["change_norm_gap"] < 1e-3
        assert numbers["change_norm_gap_median"] < 1e-5


@pytest.fixture(scope="module")
def _followed():
    """The benchmark's own loop and reference at the tiny size: what a run
    of the cell compares, in float32."""
    import harness
    import tiny
    import traffic
    cell, config, seed = tiny.cell("sharded_trainer", 2), dict(tq.CONFIG), 77
    pool = traffic.make_pool(cell, config, seed)
    devices = jax.devices()[:1]
    loop = harness.load_file("loops", "sharded_trainer").Loop(
        cell, config, seed, devices)
    cell["_shapes"] = {k: tuple(v.shape) for k, v in loop.weights.items()}
    prog = harness.first_steps(loop, iter(loop.feed(traffic.cycle(pool))))
    from mxnet_tpu.observability import device_counters
    counters = device_counters.drain()
    loop.close()
    ref = harness.reference_readings(config, cell, seed, pool, devices)
    prog["counters"] = counters
    return prog, ref, cell["_shapes"]


def test_device_counters_are_published_without_a_sync(_followed):
    # the gauges keep other tests' trainers too: this loop's net alone
    counters = {name: {k: v for k, v in by_var.items()
                       if k.startswith("qwen3nextdecoder")}
                for name, by_var in _followed[0]["counters"].items()}
    chunks = counters["linear_attention.chunks"]
    assert len(chunks) == 3 and set(chunks.values()) == {2 * 24 / 8}
    held = counters["moe.assignments.held"]
    assert len(held) == 4 and all(0 < v < 2 * 24 * 3 for v in held.values())
    assert all(v >= 1 for v in counters["moe.load.max_over_mean"].values())
    from mxnet_tpu.observability import registry
    gauge = registry.REGISTRY.get("moe.assignments.held")
    assert sorted(gauge.labelsets()) and gauge.get(
        var=next(iter(held))) == next(iter(held.values()))
