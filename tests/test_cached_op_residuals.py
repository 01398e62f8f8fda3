"""A hybridized block's recorded forward keeps the outputs of its
convolutions and matrix products, and the backward program reads them in
place of running the forward again (cached_op.py, docs/performance.md
"What a recorded forward keeps"): a tiny conv + batch norm + ReLU +
residual add + dropout + dense net on the CPU."""
import contextlib

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu import random as _random
from mxnet_tpu.cached_op import (CachedOp, _JIT_BUILDS, _PACK_LIMIT,
                                 _RES_BYTES, _RES_OUTPUTS)
from mxnet_tpu.graph import build_graph_fn

nn = gluon.nn
# 2 x 192 x 192 images: the first convolution's output (4 channels) is
# over `_PACK_LIMIT` and leaves as a buffer of its own, the second's (3)
# is packed with the sums
SIDE = 192
_RNG = np.random.RandomState(4)
X1 = _RNG.rand(2, SIDE, SIDE, 3).astype("float32")
X2 = _RNG.rand(2, SIDE, SIDE, 3).astype("float32")
N_CONV = 2                       # forward convolutions of the net


class _Net(gluon.HybridBlock):
    def __init__(self, remat=False, **kw):
        super().__init__(**kw)
        self._remat = remat
        with self.name_scope():
            self.conv0 = nn.Conv2D(4, 3, padding=1, use_bias=False,
                                   layout="NHWC", in_channels=3)
            self.bn = nn.BatchNorm(axis=3, in_channels=4)
            self.conv1 = nn.Conv2D(3, 3, padding=1, layout="NHWC",
                                   in_channels=4)
            self.drop = nn.Dropout(0.5)
            self.fc = nn.Dense(5, in_units=SIDE * SIDE * 3)

    def hybrid_forward(self, F, x):
        with (self.remat_scope("body") if self._remat
              else contextlib.nullcontext()):
            y = self.conv1(F.relu(self.bn(self.conv0(x)))) + x
        return self.fc(self.drop(y))


def _net(hybrid=True, remat=False):
    """The same names and weights every time, so that two nets of one
    kind compile to one program (the persistent cache holds it)."""
    net = _Net(remat=remat, prefix="net_")
    net.initialize(mx.init.Zero())
    rng = np.random.RandomState(3)
    for p in net.collect_params().values():
        if p.grad_req != "null":
            p.set_data(mx.nd.array(0.3 * rng.randn(*p.shape)))
    if hybrid:
        net.hybridize()
    return net


def _forward(net, x, seed, train_mode=True):
    mx.random.seed(seed)
    with autograd.record(train_mode=train_mode):
        out = net(mx.nd.array(x))
        return (out * out).sum()


def _grads(net):
    return [p.grad().asnumpy() for p in net.collect_params().values()
            if p.grad_req != "null"]


def _close(got, want, rtol):
    for g, w in zip(got, want):
        assert np.linalg.norm(g - w) <= rtol * np.linalg.norm(w)


@pytest.fixture(scope="module")
def hybrid():
    """One hybridized net, one recorded step: its gradients, and the
    backward program's call as the tape made it."""
    calls, build = [], CachedOp._bwd

    def spy(self, mode):
        jit = build(self, mode)
        return lambda *a: calls.append((jit, a)) or jit(*a)

    net = _net()
    CachedOp._bwd = spy
    try:
        _forward(net, X1, 11).backward()
    finally:
        CachedOp._bwd = build
    assert 2 * SIDE * SIDE * 3 <= _PACK_LIMIT < 2 * SIDE * SIDE * 4
    assert _RES_OUTPUTS.get(op=net._cached_op._stub.name, mode="train") == 2
    return {"net": net, "grads": _grads(net), "call": calls[0]}


def test_gradients_match_the_imperative_tape(hybrid, monkeypatch):
    """The graph splits its key once before the dropout draws from it;
    the tape's dropout is given that same sub-key."""
    net = _net(hybrid=False)
    draw = _random.next_key
    monkeypatch.setattr(_random, "next_key",
                        lambda: jax.random.split(draw())[1])
    _forward(net, X1, 11).backward()
    _close(hybrid["grads"], _grads(net), 1e-5)


def test_the_backward_program_runs_no_forward_convolution(hybrid):
    jit, args = hybrid["call"]
    text = jit.lower(*args).as_text()
    assert text.count("stablehlo.convolution") == 2 * N_CONV


@pytest.mark.parametrize("order", ["interleaved", "retain_graph"])
def test_each_recorded_forward_keeps_its_own_residuals(hybrid, order):
    net = hybrid["net"]
    if order == "interleaved":
        want = []
        for x, seed in ((X1, 11), (X2, 12)):
            _forward(net, x, seed).backward()
            want.append(_grads(net))
        first, second = _forward(net, X1, 11), _forward(net, X2, 12)
    else:
        want = [hybrid["grads"]] * 2
        first = second = _forward(net, X1, 11)
    got = []
    for loss in (first, second):
        loss.backward(retain_graph=True)
        got.append(_grads(net))
    for g, w in zip(got, want):
        _close(g, w, 1e-6)


@pytest.mark.parametrize("train_mode", [False, True])
def test_a_forward_outside_record_returns_no_residuals(train_mode):
    net = _net()
    x = mx.nd.array(X1)
    scope = autograd.train_mode() if train_mode else autograd.predict_mode()
    with scope:
        net(x)
    op, mode = net._cached_op, "train" if train_mode else "predict"
    assert list(op._fwd_jits) == [(mode, False)]
    fwd, needs_rng = op._fwd_jits[mode, False]
    params = {p.name: p.data()._data for p in net.collect_params().values()}
    args = {n: (x._data if n == "data" else params[n])
            for n in op._arg_names}
    aux = {n: params[n] for n in op._aux_names}
    key = _random.next_key() if needs_rng else None
    assert len(jax.eval_shape(fwd, args, aux, key)) == 2  # outs, aux updates
    plain, _, _, _ = build_graph_fn(op.symbol._entries, mode)
    plain.__name__ = fwd.__name__
    assert (fwd.lower(args, aux, key).as_text()
            == jax.jit(plain).lower(args, aux, key).as_text())


@pytest.mark.parametrize("train_mode", [True, False])
def test_one_build_of_each_program_a_mode(train_mode):
    def builds():
        return {k: _JIT_BUILDS.get(**dict(k))
                for k in _JIT_BUILDS.labelsets()}

    net, before = _net(), builds()
    mode = "train" if train_mode else "predict"
    for seed in (1, 2, 3):
        _forward(net, X1, seed, train_mode).backward()
    op = net._cached_op._stub.name
    added = {k: n - before.get(k, 0) for k, n in builds().items()}
    assert {k: n for k, n in added.items() if n} == {
        tuple(sorted(dict(op=op, mode=mode, direction=d).items())): 1
        for d in ("fwd", "bwd")}


def test_a_marked_group_keeps_fewer_residual_bytes():
    kept = {}
    for remat in (False, True):
        net = _net(remat=remat)
        _forward(net, X1, 11).backward()
        kept[remat] = _RES_BYTES.get(op=net._cached_op._stub.name,
                                     mode="train")
    assert 0 < kept[True] < kept[False]
