"""Pallas kernel tests (interpret mode on the CPU mesh; the same kernels
compile natively on TPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops.pallas_kernels import (flash_attention,
                                          pallas_layer_norm,
                                          _attn_reference)
import mxnet_tpu as mx


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    r = np.random.RandomState(0)
    B, H, T, D = 2, 2, 256, 64
    q, k, v = (jnp.asarray(r.randn(B, H, T, D), jnp.float32)
               for _ in range(3))
    out = flash_attention(q, k, v, causal)
    ref = _attn_reference(q, k, v, causal)
    assert float(jnp.abs(out - ref).max()) < 2e-4


def test_flash_attention_grad():
    r = np.random.RandomState(1)
    B, H, T, D = 1, 2, 128, 32
    q, k, v = (jnp.asarray(r.randn(B, H, T, D), jnp.float32)
               for _ in range(3))
    g1 = jax.grad(lambda a, b, c: flash_attention(a, b, c, True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda a, b, c: _attn_reference(a, b, c, True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 2e-3


def _out_and_grads(attn, q, k, v, w):
    """attn(q, k, v) and the three gradients of its w-weighted sum, in
    float32, from one program."""
    def both(q, k, v):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(w.astype(out.dtype))
    return [x.astype(jnp.float32) for x in jax.jit(both)(q, k, v)]


# bfloat16: both sides read the same rounded operands; the kernel then
# rounds p and dS to bfloat16 for its products (the reference keeps them
# float32), so an element of o is off by up to an ulp of bfloat16 at
# |o| <= 4, and a gradient, a sum over up to 256 such terms, by a few
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 6e-2)])
@pytest.mark.parametrize("T", [128, 256])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_attention_kernels_match_reference(causal, T, dtype, tol):
    """Forward and all three gradients of the kernels (blocks of 128, so
    T 256 sweeps a skipped, a masked and an unmasked block) against
    `_attn_reference`."""
    r = np.random.RandomState(4)
    q, k, v = (jnp.asarray(r.randn(1, 2, T, 64), dtype) for _ in range(3))
    w = jnp.asarray(r.randn(1, 2, T, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _out_and_grads(
            lambda *a: flash_attention(*a, causal, 128, 128), q, k, v, w)
        want = _out_and_grads(
            lambda *a: _attn_reference(*a, causal), q, k, v, w)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < tol


def test_flash_attention_residuals_hold_no_square():
    """What the backward keeps is q, k, v, o and the rows' log-sum-exp."""
    B, H, T, D = 1, 2, 256, 64
    r = np.random.RandomState(5)
    q, k, v = (jnp.asarray(r.randn(B, H, T, D), jnp.float32)
               for _ in range(3))
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, True, 128, 128),
                       q, k, v)
    kept = sorted(x.shape for x in jax.tree.leaves(vjp))
    assert kept == sorted([(B, H, T, D)] * 4 + [(B * H, 1, T)])
    assert any(x.shape == out.shape and bool((x == out).all())
               for x in jax.tree.leaves(vjp))


def _obs():
    from mxnet_tpu.observability import registry
    return registry.REGISTRY


def _flash_paths():
    path = _obs().get("attention.flash.path")
    return path.get(path="kernel"), path.get(path="plain")


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_attention_op_plain_where_the_shape_does_not_tile(causal):
    """T 48, D 16: the op is batch_dot, softmax over the scores plus the
    additive mask, batch_dot, bit for bit, and counts `plain`."""
    r = np.random.RandomState(6)
    q, k, v = (mx.nd.array(r.randn(2, 2, 48, 16).astype("float32"))
               for _ in range(3))
    kernel0, plain0 = _flash_paths()
    got = mx.nd.contrib.flash_attention(q, k, v, causal=causal)
    assert _flash_paths() == (kernel0, plain0 + 1)
    scores = mx.nd.batch_dot(q, k, transpose_b=True) * (1.0 / np.sqrt(16))
    if causal:
        pos = mx.nd.arange(48)
        allowed = mx.nd.broadcast_lesser_equal(
            pos.reshape((1, 48)), pos.reshape((48, 1)))
        scores = mx.nd.broadcast_add(
            scores, ((allowed - 1.0) * 1e30).reshape((1, 1, 48, 48)))
    want = mx.nd.batch_dot(mx.nd.softmax(scores, axis=-1), v)
    assert np.array_equal(got.asnumpy(), want.asnumpy())


def test_flash_attention_op_kernel_where_the_shape_tiles():
    r = np.random.RandomState(7)
    q, k, v = (mx.nd.array(r.randn(1, 2, 128, 64).astype("float32"))
               for _ in range(3))
    kernel0, plain0 = _flash_paths()
    got = mx.nd.contrib.flash_attention(q, k, v, causal=True)
    assert _flash_paths() == (kernel0 + 1, plain0)
    ref = _attn_reference(q._data, k._data, v._data, True)
    assert float(jnp.abs(got._data - ref).max()) < 2e-4


def _tiling_gpt_step(through):
    """Loss and every gradient of one step of a GPTDecoder whose shape
    tiles (1 layer, 2 heads of 64, T 128): through `autograd` on the
    hybridized block, or as what one step of plain SGD at rate 1 takes
    off the weights in a `ShardedTrainer` over `through` devices."""
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo.gpt import GPTDecoder
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh
    mx.random.seed(5)
    net = GPTDecoder(97, max_seq_len=128, num_layers=1, num_heads=2,
                     embed_dim=128, prefix="t_")
    net.initialize(mx.init.Normal(0.1))
    x = np.random.RandomState(2).randint(0, 97, (4, 128)).astype(np.int32)
    y = np.roll(x, -1, 1).astype(np.float32)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    if through == "autograd":
        net.hybridize()
        with autograd.record():
            loss = loss_fn(net(mx.nd.array(x)), mx.nd.array(y)).mean()
        loss.backward()
        return float(loss.asscalar()), {
            k: p.grad().asnumpy() for k, p in net.collect_params().items()}
    tr = ShardedTrainer(net, loss_fn, "sgd", {"learning_rate": 1.0},
                        mesh=make_mesh({"dp": through},
                                       jax.devices()[:through]))
    before = {k: np.asarray(v) for k, v in tr.params.items()}
    loss = float(tr.step(x, y).asscalar())
    return loss, {k: before[k] - np.asarray(v) for k, v in tr.params.items()}


@pytest.fixture(scope="module")
def plain_tiling_gpt_step():
    """The same step with the attention op held to its three-op
    composition: the test steers the op, no option of the program does."""
    from mxnet_tpu.ops import pallas_kernels as pk, registry
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            registry.get("_contrib_flash_attention"), "fn",
            lambda q, k, v, causal, **_: pk._attention_plain(q, k, v, causal))
        return _tiling_gpt_step("autograd")


@pytest.mark.parametrize("through", ["autograd", 1, 4],
                         ids=["autograd", "dp1", "dp4"])
def test_gpt_kernel_attention_trains_like_the_plain_path(
        through, plain_tiling_gpt_step):
    """float32 at the CPU's default precision on both sides: the two
    paths differ by the order of their sums, 1e-5 of a loss near ln 97
    and 2e-5 of gradients of up to 0.3. Under the dp mesh the kernels
    run per shard of the batch (`shard_map`)."""
    want_loss, want = plain_tiling_gpt_step
    path = _obs().get("attention.flash.path")
    kernel0, plain0 = path.get(path="kernel"), path.get(path="plain")
    loss, grads = _tiling_gpt_step(through)
    assert path.get(path="kernel") > kernel0
    assert path.get(path="plain") == plain0
    assert abs(loss - want_loss) < 1e-5 * want_loss
    assert len(grads) == len(want) == 16
    for k, g in want.items():
        assert np.abs(g).max() > 0, k
        np.testing.assert_allclose(grads[k], g, atol=2e-5, rtol=1e-4,
                                   err_msg=k)


def test_pallas_layer_norm():
    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(37, 100), jnp.float32)
    g = jnp.asarray(r.randn(100), jnp.float32)
    b = jnp.asarray(r.randn(100), jnp.float32)
    out = pallas_layer_norm(x, g, b)
    mean = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    ref = (x - mean) / jnp.sqrt(var + 1e-5) * g + b
    assert float(jnp.abs(out - ref).max()) < 1e-4


def test_flash_attention_nd_op():
    r = np.random.RandomState(3)
    q = mx.nd.array(r.randn(1, 2, 64, 16).astype("float32"))
    k = mx.nd.array(r.randn(1, 2, 64, 16).astype("float32"))
    v = mx.nd.array(r.randn(1, 2, 64, 16).astype("float32"))
    out = mx.nd.contrib.flash_attention(q, k, v, causal=True,
                                        block_q=64, block_k=64)
    ref = _attn_reference(q._data, k._data, v._data, True)
    assert float(jnp.abs(out._data - ref).max()) < 2e-4


def test_fused_sgd_momentum_matches_reference():
    """Pallas fused momentum-SGD vs the plain jnp update — both the
    lane-aligned zero-copy path and the padded general path."""
    import numpy as np
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import fused_sgd_momentum

    rng = np.random.RandomState(0)
    for shape in [(512, 128), (3, 3, 7, 11), (1000,)]:
        w = rng.randn(*shape).astype("float32")
        g = rng.randn(*shape).astype("float32")
        m = rng.randn(*shape).astype("float32")
        lr, mom, wd, rs = 0.05, 0.9, 1e-4, 0.5
        ow, om = fused_sgd_momentum(jnp.asarray(w), jnp.asarray(g),
                                    jnp.asarray(m), lr, mom, wd, rs)
        m_ref = mom * m + rs * g + wd * w
        w_ref = w - lr * m_ref
        assert np.allclose(np.asarray(om), m_ref, atol=1e-5), shape
        assert np.allclose(np.asarray(ow), w_ref, atol=1e-5), shape


def test_fused_sgd_momentum_mixed_dtype():
    """bf16 weights + fp32 momentum (the mixed-precision pairing):
    accumulate in fp32, outputs keep their input dtypes."""
    import numpy as np
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import fused_sgd_momentum

    rng = np.random.RandomState(1)
    w = rng.randn(64, 128).astype("float32")
    g = rng.randn(64, 128).astype("float32")
    m = rng.randn(64, 128).astype("float32")
    ow, om = fused_sgd_momentum(jnp.asarray(w, jnp.bfloat16),
                                jnp.asarray(g, jnp.bfloat16),
                                jnp.asarray(m), 0.1, 0.9)
    assert ow.dtype == jnp.bfloat16 and om.dtype == jnp.float32
    m_ref = 0.9 * m + np.asarray(jnp.asarray(g, jnp.bfloat16), "float32")
    assert np.allclose(np.asarray(om), m_ref, atol=2e-2)


def test_conv1x1_bn_stats_fusion():
    """Fused matmul+BN-stat epilogue matches the two-pass oracle,
    including the padded-rows path."""
    from mxnet_tpu.ops.pallas_kernels import conv1x1_bn_stats
    rng = np.random.RandomState(0)
    for M, Cin, Cout in [(512, 16, 32), (300, 8, 8)]:   # 300: pad path
        x = jnp.asarray(rng.randn(M, Cin), jnp.float32)
        w = jnp.asarray(rng.randn(Cin, Cout) * 0.2, jnp.float32)
        y, mean, var = conv1x1_bn_stats(x, w, block_rows=128)
        ref = np.asarray(x) @ np.asarray(w)
        assert np.allclose(np.asarray(y), ref, atol=1e-4)
        assert np.allclose(np.asarray(mean), ref.mean(0), atol=1e-4)
        assert np.allclose(np.asarray(var), ref.var(0), atol=1e-3)
