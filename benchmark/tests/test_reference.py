"""Each plain reference against the system on the CPU at a small size, in
float32: the loss, every leaf's gradient and one update, element by
element, so that a disagreement on the chip is about precision and size
and not about the mathematics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import model
import reference_train
import tiny
import traffic

SEED = 2147483659


def _program(config, batch):
    """Loss, gradients and the parameters after one `gluon.Trainer` step
    of the program's own net, by bare name."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    cell = {"batch": batch, "pool": 1}
    (x, y), = traffic.make_pool(cell, config, SEED)
    net, w0 = model.build(config, SEED, jax.devices()[0])
    w0 = {k: np.asarray(v) for k, v in w0.items()}
    opt = config["optimizer"]
    trainer = gluon.Trainer(net.collect_params(), opt["name"],
                            dict(opt["params"]))
    loss_fn = getattr(gluon.loss, config["loss"])()
    with autograd.record():
        loss = loss_fn(net(mx.nd.array(x, dtype=x.dtype)), mx.nd.array(y))
    loss.backward()
    params = {model.bare(net, p.name): p
              for p in net.collect_params().values() if p.grad_req != "null"}
    grads = {k: np.asarray(p.grad()._data) / batch for k, p in params.items()}
    trainer.step(batch)
    after = {k: np.asarray(p.data()._data) for k, p in params.items()}
    return float(loss.mean().asscalar()), grads, after, w0, (x, y)


@pytest.mark.parametrize("config,batch", [(tiny.RESNET, 16), (tiny.GPT, 4)],
                         ids=["resnet", "gpt"])
def test_reference_agrees_with_the_program(config, batch):
    loss, grads, after, w0, (x, y) = _program(config, batch)
    ref = harness.load_file("reference", config["reference"])
    kw = config.get("reference_kwargs", {})
    w = {k: jnp.asarray(v) for k, v in w0.items()}
    p = {k: v for k, v in w.items() if ref.trainable(k)}
    frozen = {k: v for k, v in w.items() if not ref.trainable(k)}
    ref_loss, ref_grads = jax.value_and_grad(
        lambda q: ref.loss({**frozen, **q}, x, y, "float32", **kw))(p)
    assert loss == pytest.approx(float(ref_loss), rel=2e-5)
    assert set(grads) == set(ref_grads)
    scale = np.median([np.linalg.norm(np.asarray(g)) for g in ref_grads.values()])
    for k, g in ref_grads.items():
        g = np.asarray(g)
        tol = 2e-4 * max(np.linalg.norm(g), scale) / np.sqrt(g.size)
        np.testing.assert_allclose(grads[k], g, rtol=2e-3, atol=tol,
                                   err_msg=k)
    init, update = reference_train.OPTIMIZERS[config["optimizer"]["name"]](
        config["optimizer"]["params"])
    stepped, _ = update(p, ref_grads, init(p), jnp.float32(1))
    moved = {k: np.linalg.norm(np.asarray(v) - w0[k])
             for k, v in stepped.items()}
    typical = np.median(list(moved.values()))
    for k, v in stepped.items():
        # by the norm of the leaf's difference: under Adam the few elements
        # whose gradient is near eps move by anything up to the rate, and a
        # bias that batch norm cancels moves by round-off alone, so a leaf
        # is held to the typical leaf's movement where its own is smaller
        # (a key's bias, whose gradient softmax makes nought, is a third of
        # `attn_qkv_bias` and reads 2% there under Adam)
        tol = 5e-2 if config["optimizer"]["name"] == "adam" else 2e-3
        gap = np.linalg.norm(after[k] - np.asarray(v))
        assert gap <= tol * max(moved[k], typical), (k, gap, moved[k])


def test_follow_reads_what_it_says():
    """`follow` on a one-leaf quadratic: losses, the first gradient's norm
    and the change's norm by hand."""
    class Quadratic:
        @staticmethod
        def loss(p, x, y, mode):
            return 0.5 * jnp.sum(jnp.square(p["w"])) * x[0]

        @staticmethod
        def trainable(name):
            return True

    w = {"w": jnp.array([3.0, 4.0])}
    out = reference_train.follow(
        Quadratic, w, [(jnp.ones(1), None)] * 2,
        {"name": "sgd", "params": {"learning_rate": 0.1, "momentum": 0.0}})
    assert out["losses"] == pytest.approx([12.5, 0.5 * 25 * 0.81])
    assert out["grad_norms"]["w"] == pytest.approx(5.0)
    assert out["change_norms"]["w"] == pytest.approx(5.0 * (1 - 0.81))


def test_grad_diff_is_the_norm_of_the_difference():
    import check
    ref = {"a": np.array([3.0, 0.0], np.float32), "b": np.array([[4.0]], np.float32)}
    assert check.grad_diff(ref, ref) == 0
    turned = {"a": np.array([0.0, 3.0], np.float32), "b": ref["b"]}
    assert check.grad_diff(turned, ref) == pytest.approx(np.sqrt(18.0) / 5.0)
    # a leaf the program lacks counts as nought
    assert check.grad_diff({"a": ref["a"]}, ref) == pytest.approx(4.0 / 5.0)
