"""Parallelism tests on the 8-device virtual CPU mesh (conftest.py).

Mirrors the reference's strategy (SURVEY.md §4.5): multi-device semantics
validated without a cluster — here via xla_force_host_platform_device_count,
the way the reference runs dist kvstore tests with local processes.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import (make_mesh, ShardedTrainer, ring_attention,
                                local_attention, pipeline_apply,
                                PartitionSpec, shard_on, put_sharded)


def test_make_mesh():
    mesh = make_mesh({"dp": 2, "tp": -1})
    assert mesh.shape["dp"] == 2
    assert mesh.shape["tp"] == 4


def test_sharded_trainer_dp_convergence():
    np.random.seed(0)
    X = np.random.randn(64, 10).astype("float32")
    w = np.random.randn(10, 1).astype("float32")
    Y = X @ w
    net = nn.Dense(1)
    net.initialize()
    net(mx.nd.array(X[:2]))  # materialize shapes
    mesh = make_mesh({"dp": 8})
    st = ShardedTrainer(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                        "sgd", {"learning_rate": 0.2, "momentum": 0.9},
                        mesh=mesh)
    for _ in range(60):
        loss = st.step(X, Y)
    assert float(loss.asscalar()) < 1e-2
    st.copy_params_to_net()
    out = net(mx.nd.array(X)).asnumpy()
    assert np.mean((out - Y) ** 2) < 1e-2


def test_sharded_trainer_matches_single_device():
    """DP over 8 devices must equal single-device training (the
    dist_sync_kvstore.py bitwise-determinism check, tolerance-tiered)."""
    np.random.seed(1)
    X = np.random.randn(16, 6).astype("float32")
    Y = (X.sum(1, keepdims=True) > 0).astype("float32")

    def build():
        np.random.seed(42)
        net = nn.Dense(1, weight_initializer="zeros",
                       bias_initializer="zeros")
        net.initialize()
        net(mx.nd.array(X[:2]))
        return net

    losses = {}
    for name, mesh in [("single", make_mesh({"dp": 1})),
                       ("dp8", make_mesh({"dp": 8}))]:
        net = build()
        st = ShardedTrainer(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                            "sgd", {"learning_rate": 0.1, "momentum": 0.0},
                            mesh=mesh)
        for _ in range(5):
            l = st.step(X, Y)
        losses[name] = float(l.asscalar())
    assert np.isclose(losses["single"], losses["dp8"], rtol=1e-5), losses


def test_sharded_trainer_tensor_parallel():
    """Dense weight split over 'tp'; XLA inserts the collectives."""
    np.random.seed(2)
    X = np.random.randn(32, 8).astype("float32")
    Y = np.random.randn(32, 4).astype("float32")
    net = nn.HybridSequential(prefix="tpnet_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize()
    net(mx.nd.array(X[:2]))
    mesh = make_mesh({"dp": 2, "tp": 4})
    rules = [(r"dense0_weight", PartitionSpec("tp", None)),
             (r"dense0_bias", PartitionSpec("tp")),
             (r"dense1_weight", PartitionSpec(None, "tp"))]
    st = ShardedTrainer(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                        "adam", {"learning_rate": 0.05},
                        mesh=mesh, param_rules=rules)
    first = float(st.step(X, Y).asscalar())
    for _ in range(50):
        loss = st.step(X, Y)
    assert float(loss.asscalar()) < first * 0.5
    # param really is sharded over tp
    w = st.params["tpnet_dense0_weight"]
    assert w.sharding.spec == PartitionSpec("tp", None)


def test_ring_attention_matches_local():
    mesh = make_mesh({"sp": 8})
    B, H, T, D = 2, 4, 32, 16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    ref = local_attention(q, k, v)
    sh = shard_on(mesh, "sp", dim=2, ndim=4)
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh, "sp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_causal():
    mesh = make_mesh({"sp": 4})
    B, H, T, D = 1, 2, 16, 8
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    ref = local_attention(q, k, v, causal=True)
    sh = shard_on(mesh, "sp", dim=2, ndim=4)
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh, "sp", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_pipeline_apply():
    """4-stage pipeline of affine stages == sequential application."""
    mesh = make_mesh({"pp": 4})
    n_stages, D = 4, 8
    rng = np.random.RandomState(3)
    Ws = jnp.asarray(rng.randn(n_stages, D, D) * 0.5, jnp.float32)
    bs = jnp.asarray(rng.randn(n_stages, D) * 0.1, jnp.float32)

    def stage_fn(params, x):
        W, b = params
        return jnp.tanh(x @ W + b)

    x = jnp.asarray(rng.randn(16, D), jnp.float32)
    out = pipeline_apply(stage_fn, (Ws, bs), x, mesh, "pp",
                         n_microbatches=4)
    ref = x
    for i in range(n_stages):
        ref = jnp.tanh(ref @ Ws[i] + bs[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_dist_kvstore_single_process():
    kv = mx.kv.create("tpu_dist")
    assert kv.rank == 0 and kv.num_workers == 1
    kv.init(3, mx.nd.ones((2, 2)))
    kv.push(3, mx.nd.full((2, 2), 4.0))
    out = mx.nd.zeros((2, 2))
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full((2, 2), 4.0))
    kv.barrier()


def test_put_sharded_batch():
    mesh = make_mesh({"dp": 8})
    x = mx.nd.ones((16, 4))
    xs = put_sharded(x, shard_on(mesh, "dp", 0, 2))
    assert xs.shape == (16, 4)


def test_step_many_matches_sequential_steps():
    # K fused steps in one scanned program == K separate step() calls
    from mxnet_tpu.gluon import nn as gnn
    from mxnet_tpu import gluon

    def build():
        m = gnn.HybridSequential()
        m.add(gnn.Conv2D(4, 3, padding=1), gnn.BatchNorm(),
              gnn.Activation("relu"), gnn.GlobalAvgPool2D(),
              gnn.Dense(10))
        m.initialize()
        m(mx.nd.zeros((1, 3, 8, 8)))
        return m

    net = build()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh({"dp": 8})
    rng = np.random.RandomState(0)
    x = rng.randn(16, 3, 8, 8).astype("float32")
    y = (np.arange(16) % 10).astype("float32")
    kw = dict(optimizer="sgd",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
              mesh=mesh)
    st1 = ShardedTrainer(net, lambda o, l: loss(o, l), **kw)
    seq = [float(st1.step(x, y).asscalar()) for _ in range(5)]
    for unroll in (1, 3):
        st2 = ShardedTrainer(net, lambda o, l: loss(o, l), **kw)
        many = st2.step_many(x, y, n_steps=5, unroll=unroll).asnumpy()
        np.testing.assert_allclose(seq, many, rtol=1e-5, atol=1e-6)
    assert st2._step_count == 5


def test_weight_update_sharding_matches_replicated():
    # ZeRO-1-style optimizer-state sharding (SURVEY 2.3 weight-update
    # sharding): same numerics, momentum rows sharded over dp
    from mxnet_tpu.gluon import nn as gnn
    from mxnet_tpu import gluon
    from jax.sharding import PartitionSpec as P

    net = gnn.HybridSequential()
    net.add(gnn.Dense(32, activation="relu"), gnn.Dense(10))
    net.initialize()
    net(mx.nd.zeros((1, 16)))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh({"dp": 8})
    rng = np.random.RandomState(0)
    x = rng.randn(16, 16).astype("float32")
    y = (np.arange(16) % 10).astype("float32")
    kw = dict(optimizer="adam", optimizer_params={"learning_rate": 0.01},
              mesh=mesh)
    a = ShardedTrainer(net, lambda o, l: loss(o, l), **kw)
    b = ShardedTrainer(net, lambda o, l: loss(o, l),
                       shard_optimizer_state=True, **kw)
    la = [float(a.step(x, y).asscalar()) for _ in range(3)]
    lb = [float(b.step(x, y).asscalar()) for _ in range(3)]
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    # momentum for a (32,16) dense weight is actually sharded over dp
    # found by its shape: the layers' prefix numbers depend on the tests
    # that ran before in the process (dense10 sorts before dense9)
    m = next(v for k, v in b._opt_state["m"].items()
             if k.endswith("_weight") and v.shape == (32, 16))
    assert m.sharding.spec == P("dp"), m.sharding
    # params remain replicated for compute
    k0 = [k for k in b._params if k.endswith("_weight")][0]
    assert b._params[k0].sharding.spec == P()


def test_params_property_survives_next_step():
    # step() donates internal buffers; the public accessor must return
    # copies that stay valid afterwards
    from mxnet_tpu.gluon import nn as gnn
    from mxnet_tpu import gluon
    net = gnn.HybridSequential()
    net.add(gnn.Dense(4))
    net.initialize()
    net(mx.nd.zeros((1, 3)))
    loss = gluon.loss.L2Loss()
    st = ShardedTrainer(net, lambda o, l: loss(o, l), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9},
                        mesh=make_mesh({"dp": 8}))
    x = np.random.RandomState(0).randn(8, 3).astype("f")
    y = np.zeros((8, 4), "f")
    st.step(x, y)
    snap = st.params
    st.step(x, y)
    for v in snap.values():
        assert np.isfinite(np.asarray(v)).all()  # not deleted


def test_sgd_momentum_zero_carries_no_state_and_trains():
    from mxnet_tpu.gluon import nn as gnn
    from mxnet_tpu import gluon
    net = gnn.HybridSequential()
    net.add(gnn.Dense(8, activation="relu"), gnn.Dense(10))
    net.initialize()
    net(mx.nd.zeros((1, 4)))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    st = ShardedTrainer(net, lambda o, l: loss(o, l), "sgd",
                        {"learning_rate": 0.2},
                        mesh=make_mesh({"dp": 8}))
    assert st._opt_state == {}
    rng = np.random.RandomState(0)
    x = rng.randn(16, 4).astype("f")
    y = (np.arange(16) % 10).astype("f")
    ls = [float(st.step(x, y).asscalar()) for _ in range(5)]
    assert ls[-1] < ls[0]


def test_batch_axis_one_with_rank1_labels():
    # TNC-layout data (batch on axis 1) alongside (B,) labels: the label
    # sharding must clamp to its own rank instead of erroring
    from mxnet_tpu.gluon import nn as gnn, HybridBlock
    from mxnet_tpu import gluon

    class MeanDense(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.out = gnn.Dense(10)

        def hybrid_forward(self, F, x):  # x: (T, B, C)
            return self.out(F.mean(x, axis=0))

    net = MeanDense()
    net.initialize()
    net(mx.nd.zeros((5, 2, 4)))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    st = ShardedTrainer(net, lambda o, l: loss(o, l), "sgd",
                        {"learning_rate": 0.1}, batch_axis=1,
                        mesh=make_mesh({"dp": 8}))
    x = np.random.RandomState(0).randn(5, 16, 4).astype("f")
    y = (np.arange(16) % 10).astype("f")
    l = float(st.step(x, y).asscalar())
    assert np.isfinite(l)


def test_compressed_step_predict_mode_and_rng_net():
    # compressed path with (a) a BN net in predict aux_mode (no aux
    # updates emitted) and (b) a dropout net (per-shard folded RNG)
    from mxnet_tpu.gluon import nn as gnn
    from mxnet_tpu import gluon
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    gc = {"gradient_compression": {"type": "2bit", "threshold": 0.1}}
    rng = np.random.RandomState(0)
    x = rng.randn(16, 6).astype("f")
    y = (np.arange(16) % 4).astype("f")

    bn_net = gnn.HybridSequential()
    bn_net.add(gnn.Dense(8), gnn.BatchNorm(), gnn.Dense(4))
    bn_net.initialize()
    bn_net(mx.nd.zeros((1, 6)))
    st = ShardedTrainer(bn_net, lambda o, l: loss(o, l), "sgd",
                        {"learning_rate": 0.1}, aux_mode="predict",
                        mesh=make_mesh({"dp": 8}), **gc)
    assert np.isfinite(float(st.step(x, y).asscalar()))

    do_net = gnn.HybridSequential()
    do_net.add(gnn.Dense(8, activation="relu"), gnn.Dropout(0.5),
               gnn.Dense(4))
    do_net.initialize()
    do_net(mx.nd.zeros((1, 6)))
    st2 = ShardedTrainer(do_net, lambda o, l: loss(o, l), "sgd",
                         {"learning_rate": 0.1},
                         mesh=make_mesh({"dp": 8}), **gc)
    ls = [float(st2.step(x, y).asscalar()) for _ in range(3)]
    assert all(np.isfinite(v) for v in ls)


def test_batch_axis_one_rank1_labels_with_compression():
    # the compressed path's jit in_shardings must clamp too
    from mxnet_tpu.gluon import nn as gnn, HybridBlock
    from mxnet_tpu import gluon

    class MeanDense(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.out = gnn.Dense(10)

        def hybrid_forward(self, F, x):
            return self.out(F.mean(x, axis=0))

    net = MeanDense()
    net.initialize()
    net(mx.nd.zeros((5, 2, 4)))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    st = ShardedTrainer(net, lambda o, l: loss(o, l), "sgd",
                        {"learning_rate": 0.1}, batch_axis=1,
                        mesh=make_mesh({"dp": 8}),
                        gradient_compression={"type": "2bit",
                                              "threshold": 0.1})
    x = np.random.RandomState(0).randn(5, 16, 4).astype("f")
    y = (np.arange(16) % 10).astype("f")
    assert np.isfinite(float(st.step(x, y).asscalar()))


def test_sgd_update_passes_state_through_at_zero_momentum():
    from mxnet_tpu.parallel.data_parallel import sgd_update
    import jax.numpy as jnp
    params = {"w": jnp.ones((3,))}
    grads = {"w": jnp.full((3,), 0.5)}
    state = {"w": jnp.zeros((3,))}
    new_p, new_s = sgd_update(params, grads, state, lr=0.1, momentum=0.0)
    assert new_s is state  # structure preserved for schedule callers
    np.testing.assert_allclose(np.asarray(new_p["w"]), 0.95)


def test_remat_matches_plain_step():
    """remat=True (jax.checkpoint around the traced graph) must change
    memory behavior only — identical numerics to the plain step
    (reference analog: MXNET_BACKWARD_DO_MIRROR)."""
    import numpy as np
    import jax
    from mxnet_tpu import nd, gluon
    from mxnet_tpu.parallel import make_mesh, ShardedTrainer

    rng = np.random.RandomState(0)
    X = rng.rand(16, 6).astype("float32")
    y = (X.sum(1) > 3).astype("float32")
    mesh = make_mesh({"dp": len(jax.devices())})

    def train(remat):
        import mxnet_tpu as mx
        mx.random.seed(42)  # identical init across variants
        net = gluon.nn.Sequential()
        net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(2))
        net.initialize()
        net(nd.zeros((1, 6)))
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        st = ShardedTrainer(net, lambda o, l: loss(o, l), "sgd",
                            {"learning_rate": 0.1}, mesh=mesh,
                            remat=remat)
        return [float(st.step(nd.array(X), nd.array(y)).asnumpy())
                for _ in range(4)]

    plain = train(False)
    remat = train(True)
    assert np.allclose(plain, remat, rtol=1e-5), (plain, remat)
    sel = train("dots_with_no_batch_dims_saveable")
    assert np.allclose(plain, sel, rtol=1e-5), (plain, sel)


def test_input_specs_override_matches_default():
    """input_specs shards the sequence axis of the inputs over 'sp' at
    ingest; numerics must equal the batch-default sharding."""
    import jax
    np.random.seed(0)
    B, T, D = 4, 16, 8
    X = np.random.randn(B, T, D).astype("float32")
    Y = np.random.randn(B, T, 1).astype("float32")

    net = nn.Dense(1, flatten=False)
    net.initialize()
    net(mx.nd.array(X[:1]))

    def build(input_specs=None):
        mesh = make_mesh({"dp": 2, "sp": 4})
        return ShardedTrainer(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                              "sgd", {"learning_rate": 0.1}, mesh=mesh,
                              input_specs=input_specs)

    a = build()
    b = build(input_specs={"data": ("dp", "sp"),
                           "label": ("dp", "sp")})
    la = [float(a.step(X, Y).asscalar()) for _ in range(3)]
    lb = [float(b.step(X, Y).asscalar()) for _ in range(3)]
    assert np.allclose(la, lb, rtol=1e-6), (la, lb)
    # the staged input really is sequence-sharded
    sh = b._input_sharding("data", 3)
    assert "sp" in str(sh.spec)
