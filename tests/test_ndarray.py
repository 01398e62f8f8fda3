"""NDArray unit tests (reference model: tests/python/unittest/test_ndarray.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def test_creation():
    a = nd.zeros((2, 3))
    assert a.shape == (2, 3)
    assert a.dtype == np.float32
    assert a.asnumpy().sum() == 0

    b = nd.ones((4,), dtype="int32")
    assert b.dtype == np.int32
    assert b.asnumpy().tolist() == [1, 1, 1, 1]

    c = nd.full((2, 2), 7.0)
    assert (c.asnumpy() == 7).all()

    d = nd.array([[1, 2], [3, 4]])
    assert d.shape == (2, 2)
    assert d.dtype == np.float32

    e = nd.arange(0, 10, 2)
    assert e.asnumpy().tolist() == [0, 2, 4, 6, 8]


def test_elementwise_arithmetic():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([4.0, 5.0, 6.0])
    assert ((a + b).asnumpy() == [5, 7, 9]).all()
    assert ((b - a).asnumpy() == [3, 3, 3]).all()
    assert ((a * b).asnumpy() == [4, 10, 18]).all()
    assert np.allclose((b / a).asnumpy(), [4, 2.5, 2])
    assert ((a + 1).asnumpy() == [2, 3, 4]).all()
    assert ((1 + a).asnumpy() == [2, 3, 4]).all()
    assert ((2 - a).asnumpy() == [1, 0, -1]).all()
    assert np.allclose((2 / a).asnumpy(), [2, 1, 2.0 / 3])
    assert ((a ** 2).asnumpy() == [1, 4, 9]).all()
    assert ((-a).asnumpy() == [-1, -2, -3]).all()


def test_comparison_ops():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([2.0, 2.0, 2.0])
    assert ((a > b).asnumpy() == [0, 0, 1]).all()
    assert ((a >= b).asnumpy() == [0, 1, 1]).all()
    assert ((a == 2).asnumpy() == [0, 1, 0]).all()
    assert ((a != 2).asnumpy() == [1, 0, 1]).all()


def test_broadcast():
    a = nd.ones((2, 1, 3))
    b = nd.ones((1, 4, 3))
    assert (a + b).shape == (2, 4, 3)
    c = nd.broadcast_to(nd.ones((1, 3)), shape=(5, 3))
    assert c.shape == (5, 3)


def test_reduce():
    a = nd.array(np.arange(24).reshape(2, 3, 4))
    assert a.sum().asscalar() == np.arange(24).sum()
    assert a.sum(axis=1).shape == (2, 4)
    assert a.sum(axis=(0, 2)).shape == (3,)
    assert a.mean().asscalar() == pytest.approx(11.5)
    assert a.max().asscalar() == 23
    assert a.min().asscalar() == 0
    s = nd.sum(a, axis=1, keepdims=True)
    assert s.shape == (2, 1, 4)
    e = nd.sum(a, axis=1, exclude=True)
    assert e.shape == (3,)


def test_reshape_codes():
    a = nd.zeros((2, 3, 4))
    assert a.reshape((24,)).shape == (24,)
    assert a.reshape((-1, 4)).shape == (6, 4)
    assert a.reshape((0, -1)).shape == (2, 12)
    assert nd.Reshape(a, shape=(-3, 4)).shape == (6, 4)
    assert nd.Reshape(a, shape=(0, 0, -1)).shape == (2, 3, 4)
    assert nd.Reshape(a, shape=(-2,)).shape == (2, 3, 4)
    assert nd.Reshape(a, shape=(-4, 1, 2, 0, 0)).shape == (1, 2, 3, 4)


def test_transpose_and_shape_ops():
    a = nd.zeros((2, 3, 4))
    assert a.T.shape == (4, 3, 2)
    assert nd.transpose(a, axes=(1, 0, 2)).shape == (3, 2, 4)
    assert nd.expand_dims(a, axis=1).shape == (2, 1, 3, 4)
    assert a.flatten().shape == (2, 12)
    assert nd.squeeze(nd.zeros((1, 3, 1)), axis=0).shape == (3, 1)
    assert nd.swapaxes(a, dim1=0, dim2=2).shape == (4, 3, 2)
    assert nd.tile(nd.ones((2, 2)), reps=(2, 3)).shape == (4, 6)
    assert nd.repeat(nd.ones((2,)), repeats=3).shape == (6,)
    assert nd.flip(nd.array([1, 2, 3]), axis=0).asnumpy().tolist() == [3, 2, 1]


def test_concat_split_stack():
    a, b = nd.ones((2, 3)), nd.zeros((2, 3))
    c = nd.Concat(a, b, dim=0)
    assert c.shape == (4, 3)
    c2 = nd.concat(a, b, dim=1)
    assert c2.shape == (2, 6)
    s = nd.stack(a, b, axis=0)
    assert s.shape == (2, 2, 3)
    parts = nd.split(nd.ones((4, 6)), num_outputs=3, axis=1)
    assert len(parts) == 3 and parts[0].shape == (4, 2)
    parts = nd.split(nd.ones((4, 6)), num_outputs=2, axis=0, squeeze_axis=False)
    assert parts[0].shape == (2, 6)


def test_slicing_indexing():
    a = nd.array(np.arange(12).reshape(3, 4))
    assert a[1].asnumpy().tolist() == [4, 5, 6, 7]
    assert a[0:2].shape == (2, 4)
    assert a[1, 2].asscalar() == 6
    assert nd.slice(a, begin=(0, 1), end=(2, 3)).shape == (2, 2)
    assert nd.slice_axis(a, axis=1, begin=1, end=3).shape == (3, 2)
    a[0] = 9.0
    assert (a[0].asnumpy() == 9).all()
    a[1, 1] = -1.0
    assert a.asnumpy()[1, 1] == -1


def test_take_embedding_onehot():
    w = nd.array(np.arange(12).reshape(4, 3))
    idx = nd.array([0, 2])
    t = nd.take(w, idx)
    assert t.shape == (2, 3)
    emb = nd.Embedding(idx, w, input_dim=4, output_dim=3)
    assert (emb.asnumpy() == t.asnumpy()).all()
    oh = nd.one_hot(nd.array([0, 1, 2]), depth=4)
    assert oh.shape == (3, 4)
    assert oh.asnumpy().sum() == 3


def test_dot():
    a = nd.array(np.random.rand(3, 4))
    b = nd.array(np.random.rand(4, 5))
    c = nd.dot(a, b)
    assert np.allclose(c.asnumpy(), a.asnumpy() @ b.asnumpy(), atol=1e-5)
    ct = nd.dot(a, nd.array(b.asnumpy().T), transpose_b=True)
    assert np.allclose(ct.asnumpy(), c.asnumpy(), atol=1e-5)
    bd = nd.batch_dot(nd.ones((2, 3, 4)), nd.ones((2, 4, 5)))
    assert bd.shape == (2, 3, 5)


def test_ordering():
    a = nd.array([[3.0, 1.0, 2.0], [0.5, 2.5, 1.5]])
    top = nd.topk(a, k=2, ret_typ="value")
    assert top.asnumpy()[0].tolist() == [3, 2]
    s = nd.sort(a, axis=-1)
    assert s.asnumpy()[0].tolist() == [1, 2, 3]
    ags = nd.argsort(a, axis=-1)
    assert ags.asnumpy()[0].tolist() == [1, 2, 0]
    assert nd.argmax(a, axis=1).asnumpy().tolist() == [0, 1]
    assert nd.argmin(a, axis=1).asnumpy().tolist() == [1, 0]


def test_cast_astype():
    a = nd.array([1.5, 2.5])
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = a.astype(np.float16)
    assert c.dtype == np.float16
    d = nd.Cast(a, dtype="bfloat16")
    assert d.asnumpy().astype(np.float32).tolist() == [1.5, 2.5]


def test_context_placement():
    a = nd.ones((2, 2), ctx=mx.cpu(0))
    assert a.context.device_type == "cpu"
    b = a.as_in_context(mx.cpu(0))
    assert b is a
    c = a.copyto(mx.cpu(1))
    assert c.context.device_id in (0, 1)  # single-device fallback allowed


@pytest.mark.parametrize("make", [mx.tpu, mx.gpu])
def test_accelerator_context_raises_off_the_accelerator(make):
    """No fallback that hides the device: on the CPU backend a context
    that names an accelerator raises, naming the backend it found."""
    import jax
    assert jax.default_backend() == "cpu"
    with pytest.raises(mx.MXNetError, match="backend is 'cpu'"):
        make(0).jax_device
    with pytest.raises(mx.MXNetError, match="backend is 'cpu'"):
        nd.ones((2,), ctx=make(0))


def test_serialization(tmp_path):
    fname = str(tmp_path / "arrs.npz")
    data = {"w": nd.array(np.random.rand(3, 3)), "b": nd.ones((3,))}
    nd.save(fname, data)
    loaded = nd.load(fname)
    assert set(loaded) == {"w", "b"}
    assert np.allclose(loaded["w"].asnumpy(), data["w"].asnumpy())

    fname2 = str(tmp_path / "arrs_list.npz")
    nd.save(fname2, [nd.zeros((2,)), nd.ones((3,))])
    ll = nd.load(fname2)
    assert len(ll) == 2 and ll[1].shape == (3,)


def test_wait_and_async():
    a = nd.ones((100, 100))
    b = nd.dot(a, a)
    b.wait_to_read()
    nd.waitall()
    assert b.asnumpy()[0, 0] == 100


def test_inplace_ops():
    a = nd.ones((3,))
    aid = id(a)
    a += 1
    assert id(a) == aid and (a.asnumpy() == 2).all()
    a *= 3
    assert (a.asnumpy() == 6).all()
    a -= 1
    assert (a.asnumpy() == 5).all()
    a /= 5
    assert (a.asnumpy() == 1).all()


def test_unary_math():
    a = nd.array([1.0, 4.0, 9.0])
    assert np.allclose(nd.sqrt(a).asnumpy(), [1, 2, 3])
    assert np.allclose(nd.square(a).asnumpy(), [1, 16, 81])
    assert np.allclose(nd.exp(nd.zeros((2,))).asnumpy(), [1, 1])
    assert np.allclose(nd.log(a).asnumpy(), np.log([1, 4, 9]), atol=1e-6)
    assert np.allclose(nd.rsqrt(a).asnumpy(), 1 / np.sqrt([1, 4, 9]))
    assert np.allclose(nd.abs(nd.array([-1.0, 2.0])).asnumpy(), [1, 2])
    assert np.allclose(nd.sign(nd.array([-5.0, 0.0, 3.0])).asnumpy(), [-1, 0, 1])
    assert np.allclose(nd.clip(a, a_min=2, a_max=5).asnumpy(), [2, 4, 5])
    assert np.allclose(nd.relu(nd.array([-1.0, 1.0])).asnumpy(), [0, 1])
    assert np.allclose(nd.sigmoid(nd.zeros((1,))).asnumpy(), [0.5])


def test_where():
    cond = nd.array([1.0, 0.0, 1.0])
    x = nd.array([1.0, 2.0, 3.0])
    y = nd.array([10.0, 20.0, 30.0])
    assert nd.where(cond, x, y).asnumpy().tolist() == [1, 20, 3]


def test_sequence_ops():
    data = nd.array(np.arange(24).reshape(4, 2, 3))  # (T=4, B=2, 3)
    length = nd.array([2, 3])
    masked = nd.SequenceMask(data, length, use_sequence_length=True, value=-1)
    npd = masked.asnumpy()
    assert (npd[2, 0] == -1).all() and (npd[3, 1] == -1).all()
    assert (npd[1, 0] != -1).all()
    last = nd.SequenceLast(data, length, use_sequence_length=True)
    assert last.shape == (2, 3)
    assert np.allclose(last.asnumpy()[0], data.asnumpy()[1, 0])
    rev = nd.SequenceReverse(data, length, use_sequence_length=True)
    assert np.allclose(rev.asnumpy()[0, 0], data.asnumpy()[1, 0])


def test_gather_scatter():
    data = nd.array(np.arange(9).reshape(3, 3))
    idx = nd.array([[0, 2], [1, 0]])
    g = nd.gather_nd(data, idx)
    assert g.asnumpy().tolist() == [1, 6]
    s = nd.scatter_nd(nd.array([9.0, 8.0]), idx, shape=(3, 3))
    assert s.asnumpy()[0, 1] == 9 and s.asnumpy()[2, 0] == 8


def test_strict_fence(monkeypatch):
    """wait_to_read/wait_to_write/waitall share ONE fence (_fence), and
    the strict mode one can ask for device_gets a dependent slice on top
    of block_until_ready (docs/faq/env_var.md, MXTPU_STRICT_FENCE;
    reference WaitToRead semantics, include/mxnet/ndarray.h:315)."""
    import jax
    from mxnet_tpu.ndarray import ndarray as nd_mod

    gets = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (gets.append(1), real_get(x))[1])

    monkeypatch.setenv("MXTPU_STRICT_FENCE", "1")
    a = nd.ones((4, 4)) * 3
    a.wait_to_read()
    assert len(gets) == 1          # one tiny dependent-slice fetch
    assert a.asnumpy()[0, 0] == 3  # value untouched by the fence
    a.wait_to_write()
    assert len(gets) == 2

    n_before = len(gets)
    nd.waitall()
    assert len(gets) > n_before    # waitall fences strictly too

    # scalars and empty arrays fence without error
    nd.array(7.0).wait_to_read()
    nd.zeros((0, 3)).wait_to_read()

    # forced off: no device_get
    monkeypatch.setenv("MXTPU_STRICT_FENCE", "0")
    n = len(gets)
    a.wait_to_read()
    assert len(gets) == n

    # both user entry points route through the shared implementation
    # (_fence_many; waitall batches its strict leg into one device_get)
    fenced = []
    monkeypatch.setattr(nd_mod, "_fence_many",
                        lambda ds: fenced.extend(id(d) for d in ds))
    a.wait_to_read()
    assert fenced == [id(a._data)]
    nd.waitall()
    assert fenced.count(id(a._data)) >= 2
