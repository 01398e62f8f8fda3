"""NHWC (channels-last) layout + mixed-precision tests.

The reference grew NHWC support for tensor cores
(src/operator/nn/convolution.cc layout param, docs/faq/perf.md fp16
guidance); on TPU channels-last is the MXU-native layout. These tests pin
the NCHW<->NHWC numerical equivalence for every layout-aware op and the
compute_dtype="bfloat16" mixed-precision path of ShardedTrainer.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def _to_nhwc(x):
    return np.transpose(x, (0, 2, 3, 1)).copy()


class TestConvLayout:
    def test_conv_nhwc_matches_nchw(self):
        rng = np.random.RandomState(0)
        x = rng.randn(2, 8, 10, 10).astype("float32")
        w = rng.randn(16, 8, 3, 3).astype("float32")
        b = rng.randn(16).astype("float32")
        y1 = nd.Convolution(nd.array(x), nd.array(w), nd.array(b),
                            kernel=(3, 3), num_filter=16, pad=(1, 1))
        # NHWC weight is (O, kh, kw, I)
        y2 = nd.Convolution(nd.array(_to_nhwc(x)),
                            nd.array(np.transpose(w, (0, 2, 3, 1)).copy()),
                            nd.array(b), kernel=(3, 3), num_filter=16,
                            pad=(1, 1), layout="NHWC")
        np.testing.assert_allclose(_to_nhwc(y1.asnumpy()), y2.asnumpy(),
                                   rtol=1e-5, atol=1e-5)

    def test_conv_nhwc_strided_grouped(self):
        rng = np.random.RandomState(1)
        x = rng.randn(2, 8, 9, 9).astype("float32")
        w = rng.randn(8, 4, 3, 3).astype("float32")
        y1 = nd.Convolution(nd.array(x), nd.array(w), kernel=(3, 3),
                            num_filter=8, stride=(2, 2), num_group=2,
                            no_bias=True)
        y2 = nd.Convolution(nd.array(_to_nhwc(x)),
                            nd.array(np.transpose(w, (0, 2, 3, 1)).copy()),
                            kernel=(3, 3), num_filter=8, stride=(2, 2),
                            num_group=2, no_bias=True, layout="NHWC")
        np.testing.assert_allclose(_to_nhwc(y1.asnumpy()), y2.asnumpy(),
                                   rtol=1e-5, atol=1e-5)


class TestPoolingLayout:
    @pytest.mark.parametrize("pool_type", ["max", "avg"])
    def test_pool_nhwc(self, pool_type):
        rng = np.random.RandomState(2)
        x = rng.randn(2, 4, 8, 8).astype("float32")
        y1 = nd.Pooling(nd.array(x), kernel=(3, 3), stride=(2, 2),
                        pad=(1, 1), pool_type=pool_type)
        y2 = nd.Pooling(nd.array(_to_nhwc(x)), kernel=(3, 3), stride=(2, 2),
                        pad=(1, 1), pool_type=pool_type, layout="NHWC")
        np.testing.assert_allclose(_to_nhwc(y1.asnumpy()), y2.asnumpy(),
                                   rtol=1e-6, atol=1e-6)

    def test_pool_nhwc_ceil_mode(self):
        rng = np.random.RandomState(3)
        x = rng.randn(1, 3, 7, 7).astype("float32")
        y1 = nd.Pooling(nd.array(x), kernel=(3, 3), stride=(2, 2),
                        pooling_convention="full")
        y2 = nd.Pooling(nd.array(_to_nhwc(x)), kernel=(3, 3), stride=(2, 2),
                        pooling_convention="full", layout="NHWC")
        np.testing.assert_allclose(_to_nhwc(y1.asnumpy()), y2.asnumpy(),
                                   rtol=1e-6, atol=1e-6)

    def test_global_pool_nhwc(self):
        rng = np.random.RandomState(4)
        x = rng.randn(2, 5, 6, 6).astype("float32")
        y1 = nd.Pooling(nd.array(x), global_pool=True, pool_type="avg")
        y2 = nd.Pooling(nd.array(_to_nhwc(x)), global_pool=True,
                        pool_type="avg", layout="NHWC")
        np.testing.assert_allclose(_to_nhwc(y1.asnumpy()), y2.asnumpy(),
                                   rtol=1e-6, atol=1e-6)


class TestBatchNormAxis:
    def test_bn_axis_last_matches_axis1(self):
        rng = np.random.RandomState(5)
        x = rng.randn(4, 6, 5, 5).astype("float32")
        gamma = rng.rand(6).astype("float32") + 0.5
        beta = rng.randn(6).astype("float32")
        mm = np.zeros(6, "float32")
        mv = np.ones(6, "float32")
        with mx.autograd.train_mode():
            y1 = nd.BatchNorm(nd.array(x), nd.array(gamma), nd.array(beta),
                              nd.array(mm), nd.array(mv), fix_gamma=False)
            y2 = nd.BatchNorm(nd.array(_to_nhwc(x)), nd.array(gamma),
                              nd.array(beta), nd.array(mm), nd.array(mv),
                              fix_gamma=False, axis=3)
        np.testing.assert_allclose(_to_nhwc(y1.asnumpy()), y2.asnumpy(),
                                   rtol=1e-4, atol=1e-4)

    def test_bn_stats_fp32_under_bf16(self):
        # bf16 input: statistics must be computed in fp32 (single-pass
        # E[x^2]-E[x]^2), output dtype preserved
        rng = np.random.RandomState(6)
        x = (rng.randn(8, 4, 4, 16) * 3 + 5).astype("float32")
        import jax.numpy as jnp
        xb = nd.array(x).astype("bfloat16")
        gamma = nd.ones((16,))
        beta = nd.zeros((16,))
        with mx.autograd.train_mode():
            y = nd.BatchNorm(xb, gamma, beta, nd.zeros((16,)),
                             nd.ones((16,)), fix_gamma=False, axis=3)
        assert y.dtype == np.dtype("bfloat16") or str(y.dtype) == "bfloat16"
        ref = (x - x.mean((0, 1, 2))) / np.sqrt(x.var((0, 1, 2)) + 1e-3)
        np.testing.assert_allclose(y.asnumpy().astype("float32"), ref,
                                   atol=0.15)


class TestResNetNHWC:
    def test_resnet18_nhwc_forward_parity(self):
        from mxnet_tpu.gluon.model_zoo import vision
        rng = np.random.RandomState(7)
        x_nchw = rng.randn(2, 3, 32, 32).astype("float32")

        n1 = vision.resnet18_v1(classes=10)
        n1.initialize()
        y1 = n1(mx.nd.array(x_nchw))

        n2 = vision.resnet18_v1(classes=10, layout="NHWC")
        n2.initialize()

        def strip(n):
            return n.split("_", 1)[1]
        p1 = {strip(p.name): p for p in n1.collect_params().values()}
        p2 = {strip(p.name): p for p in n2.collect_params().values()}
        assert set(p1) == set(p2)
        for name, p in p2.items():
            v = p1[name].data().asnumpy()
            if v.ndim == 4:
                v = np.transpose(v, (0, 2, 3, 1)).copy()
            p.set_data(mx.nd.array(v))
        y2 = n2(mx.nd.array(_to_nhwc(x_nchw)))
        np.testing.assert_allclose(y1.asnumpy(), y2.asnumpy(),
                                   rtol=1e-4, atol=1e-4)


class TestComputeDtype:
    def test_sharded_trainer_bf16_converges(self):
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.model_zoo import vision
        from mxnet_tpu.parallel import ShardedTrainer
        import jax.numpy as jnp

        net = vision.resnet18_v1(classes=10, layout="NHWC")
        net.initialize()
        net(mx.nd.zeros((1, 32, 32, 3)))
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        st = ShardedTrainer(net, lambda o, l: loss(o, l), "sgd",
                            {"learning_rate": 0.1},
                            compute_dtype="bfloat16")
        rng = np.random.RandomState(8)
        x = rng.randn(8, 32, 32, 3).astype("float32")
        y = (np.arange(8) % 10).astype("float32")
        l0 = float(st.step(x, y).asnumpy())
        for _ in range(15):
            l = st.step(x, y)
        l1 = float(l.asnumpy())
        assert l1 < l0, (l0, l1)
        # master params stay fp32
        assert all(v.dtype == jnp.float32 for v in st.params.values())

    def test_bf16_matches_fp32_first_step_loss(self):
        # first-step loss of the bf16 path must track the fp32 path
        from mxnet_tpu import gluon
        from mxnet_tpu.parallel import ShardedTrainer
        from mxnet_tpu.gluon import nn as gnn

        def build():
            net = gnn.HybridSequential()
            net.add(gnn.Conv2D(8, 3, padding=1, layout="NHWC"),
                    gnn.BatchNorm(axis=3), gnn.Activation("relu"),
                    gnn.GlobalAvgPool2D(layout="NHWC"), gnn.Dense(5))
            return net

        rng = np.random.RandomState(9)
        x = rng.randn(8, 8, 8, 3).astype("float32")
        y = (np.arange(8) % 5).astype("float32")
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        losses = {}
        for cd in (None, "bfloat16"):
            np.random.seed(0)
            net = build()
            net.initialize()
            net(mx.nd.zeros((1, 8, 8, 3)))
            st = ShardedTrainer(net, lambda o, l: loss(o, l), "sgd",
                                {"learning_rate": 0.0}, compute_dtype=cd)
            losses[cd] = float(st.step(x, y).asnumpy())
        assert abs(losses[None] - losses["bfloat16"]) < 0.05, losses
