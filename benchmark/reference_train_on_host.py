"""`reference_train.follow` for a reference that fills the chip.

The same steps, optimizers and readings, number for number
(`benchmark/tests/test_kimi_linear_cell.py` holds the two to one another
bit for bit); what differs is where the arrays live between the steps:

  * the parameters and the optimizer's state are donated to the update,
    so an update holds four float32 copies of the parameters and not
    seven (the caller's `weights` are consumed: the trainable ones are
    deleted by the first update);
  * the seed's parameters and the first gradient wait on the host and not
    on the chip; the parameters come back once, for the change's norms,
    when the optimizer's state is gone. `grad` is returned as host arrays.

`follow` holds nine copies at its update, 36 bytes a parameter, where the
program under test holds 18 to 22: a configuration of more than some 430M
parameters cannot be judged on one chip of 16 GB with it (PERF.md section
6, PR 37). This one peaks at five copies and the reference's own
activations. The loop `sharded_trainer_net_on_host` puts it in `follow`'s
place when it closes.
"""
import jax
import jax.numpy as jnp

import reference_train as _plain


def follow(ref, weights, batches, optimizer, mode="float32", ref_kwargs=None,
           rows=None, unchanged=False):
    """As `reference_train.follow`, which see."""
    kw = dict(ref_kwargs or {})
    init, update = _plain.OPTIMIZERS[optimizer["name"]](optimizer["params"])
    frozen = {k: v for k, v in weights.items() if not ref.trainable(k)}
    p = {k: v for k, v in weights.items() if ref.trainable(k)}
    variances = {}
    if hasattr(ref, "forward_variances"):     # while `weights` are whole
        x = batches[0][0] if rows is None else batches[0][0][:rows]
        variances = jax.jit(lambda w, x: ref.forward_variances(
            w, x, mode, **{k: v for k, v in kw.items() if k != "remat"}))(
                weights, x)
    p0 = jax.device_get(p)

    @jax.jit
    def value_and_grad(p, x, y):
        return jax.value_and_grad(
            lambda q: ref.loss({**frozen, **q}, x, y, mode, **kw))(p)

    update = jax.jit(update, donate_argnums=(0, 2))
    state, losses, first, grad_norms = jax.jit(init)(p), [], None, None
    for t, (x, y) in enumerate(batches, start=1):
        if rows is not None:
            x, y = x[:rows], y[:rows]
        loss, g = value_and_grad(p, x, y)
        if first is None:
            g32 = {k: v.astype(jnp.float32) for k, v in g.items()}
            grad_norms = jax.jit(_plain._norms)(g32)
            first = jax.device_get(g32)
            del g32
        if not unchanged:
            p, state = update(p, g, state, jnp.float32(t))
        del g
        losses.append(float(loss))
    del state
    change = jax.jit(lambda a, b: _plain._norms(
        {k: a[k] - b[k] for k in a}))(p, p0)
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "change_norms": {k: float(v) for k, v in change.items()},
            "grad": first, "variances": variances}
