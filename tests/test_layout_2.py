"""NHWC layout parity of the model zoo beyond ResNet: cases of
tests/test_layout.py in a file of their own, because a file is one unit of
work under `--dist loadfile` and these eager zoo forwards are minutes on
the CPU."""
import numpy as np
import pytest

import mxnet_tpu as mx

from test_layout import _to_nhwc


class TestMobileNetNHWC:
    def test_mobilenet_v1_nhwc_parity(self):
        from mxnet_tpu.gluon.model_zoo import vision
        rng = np.random.RandomState(10)
        x = rng.randn(2, 3, 32, 32).astype("float32")
        n1 = vision.mobilenet0_25(classes=10)
        n1.initialize()
        y1 = n1(mx.nd.array(x))
        n2 = vision.mobilenet0_25(classes=10, layout="NHWC")
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
        y2 = n2(mx.nd.array(_to_nhwc(x)))
        np.testing.assert_allclose(y1.asnumpy(), y2.asnumpy(),
                                   rtol=2e-4, atol=2e-4)

    def test_mobilenet_v2_nhwc_runs(self):
        from mxnet_tpu.gluon.model_zoo import vision
        net = vision.mobilenet_v2_0_25(classes=10, layout="NHWC")
        net.initialize()
        y = net(mx.nd.zeros((2, 32, 32, 3)))
        assert y.shape == (2, 10)


class TestOtherModelsNHWC:
    @pytest.mark.slow   # minutes on the CPU: densenet121 twice, eagerly
    def test_densenet_nhwc_parity(self):
        from mxnet_tpu.gluon.model_zoo import vision
        rng = np.random.RandomState(12)
        x = rng.randn(1, 3, 224, 224).astype("float32")
        n1 = vision.densenet121(classes=10)
        n1.initialize()
        y1 = n1(mx.nd.array(x))
        n2 = vision.densenet121(classes=10, layout="NHWC")
        n2.initialize()
        n2(mx.nd.zeros((1, 224, 224, 3)))

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
        y2 = n2(mx.nd.array(_to_nhwc(x)))
        np.testing.assert_allclose(y1.asnumpy(), y2.asnumpy(),
                                   rtol=3e-4, atol=3e-4)

    def test_squeezenet_vgg_alexnet_nhwc_run(self):
        from mxnet_tpu.gluon.model_zoo import vision
        for ctor, size in [(vision.squeezenet1_1, 64),
                           (vision.vgg11, 64),
                           (vision.alexnet, 224)]:
            net = ctor(classes=7, layout="NHWC")
            net.initialize()
            y = net(mx.nd.zeros((2, size, size, 3)))
            assert y.shape == (2, 7), ctor.__name__


class TestInceptionNHWC:
    @pytest.mark.slow   # ~400 s in the tier-1 run: 299x299, twice, eagerly
    def test_inception_nhwc_parity(self):
        from mxnet_tpu.gluon.model_zoo import vision
        rng = np.random.RandomState(11)
        x = rng.randn(1, 3, 299, 299).astype("float32")
        n1 = vision.inception_v3(classes=10)
        n1.initialize()
        y1 = n1(mx.nd.array(x))
        n2 = vision.inception_v3(classes=10, layout="NHWC")
        n2.initialize()
        n2(mx.nd.zeros((1, 299, 299, 3)))  # materialize deferred Dense

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
        y2 = n2(mx.nd.array(_to_nhwc(x)))
        np.testing.assert_allclose(y1.asnumpy(), y2.asnumpy(),
                                   rtol=3e-4, atol=3e-4)
