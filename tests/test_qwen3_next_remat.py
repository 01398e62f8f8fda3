"""Rematerialisation by marked group: Qwen3-Next's marked layers train
like unmarked ones through `ShardedTrainer`, an unmarked graph runs as
before, and a group that is not closed is refused."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, graph
from mxnet_tpu.gluon.model_zoo import GPTDecoder, Qwen3NextDecoder
from mxnet_tpu.parallel import ShardedTrainer, make_mesh
import qwen3_next_helpers      # noqa: F401  (the benchmark's path)
import tiny_qwen3_next as tq   # noqa: E402  (benchmark/tests)


def _trainer(net, lr=1e-3):
    return ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                          {"learning_rate": lr, "beta2": 0.95},
                          mesh=make_mesh({"dp": 1}, jax.devices()[:1]))


def _tokens(seed=0, batch=2, length=24, vocab=61):
    x = np.random.default_rng(seed).integers(0, vocab, (batch, length))
    return x.astype(np.int32), np.roll(x, -1, 1).astype(np.float32)


# a Gated DeltaNet layer and an attention layer: each kind marked once,
# half the tiny model's four layers to compile twice
LAYERS = dict(tq.KWARGS, num_layers=2, full_attention_interval=2)


def _qwen(remat, seed=5):
    mx.random.seed(seed)
    net = Qwen3NextDecoder(remat=remat, prefix="q_", **LAYERS)
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
    assert out[True][2] >= blocks + LAYERS["num_layers"]
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
    # each compiled whole: node by node eagerly is a program an op
    np.testing.assert_array_equal(jax.jit(lambda a: fn(a, {})[0][0])(args),
                                  jax.jit(lambda a: plain(a)[0])(args))


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
