"""The plain reference's three training steps, and the readings that
`correct` compares.

Given a reference's `loss(params, x, y, mode)`, the weights by bare name
and the first batches, follow plain SGD with momentum or Adam in float32
and return what the program is held to:

  losses        the loss of each step, before its update
  grad_norms    per leaf, the norm of the first step's gradient
  change_norms  per leaf, the norm of the parameters' change after the
                last step
  grad          the first step's gradient itself, float32 on the device
  variances     where the reference has batch norms: each one's batch
                variance in the first forward pass, on the device

Nothing here is the program's: the optimizers are written from their
papers (Sutskever et al. 2013 momentum in its MXNet form with the weight
decay added to the gradient; Kingma & Ba 2015 with bias correction).
"""
import jax
import jax.numpy as jnp


def _zeros(p):
    return {k: jnp.zeros_like(v) for k, v in p.items()}


def _sgd(hp):
    lr, mom, wd = hp["learning_rate"], hp.get("momentum", 0.0), hp.get("wd", 0.0)

    def update(p, g, state, t):
        new_p, new_m = {}, {}
        for k in p:
            new_m[k] = mom * state[k] + g[k] + wd * p[k]
            new_p[k] = p[k] - lr * new_m[k]
        return new_p, new_m

    return _zeros, update


def _adam(hp):
    lr = hp["learning_rate"]
    b1, b2 = hp.get("beta1", 0.9), hp.get("beta2", 0.999)
    eps, wd = hp.get("epsilon", 1e-8), hp.get("wd", 0.0)

    def update(p, g, state, t):
        new_p, new_m, new_v = {}, {}, {}
        for k in p:
            gk = g[k] + wd * p[k]
            new_m[k] = b1 * state[0][k] + (1 - b1) * gk
            new_v[k] = b2 * state[1][k] + (1 - b2) * gk * gk
            mhat = new_m[k] / (1 - b1 ** t)
            vhat = new_v[k] / (1 - b2 ** t)
            new_p[k] = p[k] - lr * mhat / (jnp.sqrt(vhat) + eps)
        return new_p, (new_m, new_v)

    return (lambda p: (_zeros(p), _zeros(p))), update


OPTIMIZERS = {"sgd": _sgd, "adam": _adam}


def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def follow(ref, weights, batches, optimizer, mode="float32", ref_kwargs=None,
           rows=None, unchanged=False):
    """Run len(batches) steps. `weights`: {bare name: float32 array};
    `batches`: [(x, y)] device or host arrays; `optimizer`: {"name",
    "params"}. For the planted faults, `rows` keeps only the first `rows`
    rows of every batch and `unchanged` leaves the state as it was after
    every step. Returns plain floats, and the first gradient."""
    kw = dict(ref_kwargs or {})
    init, update = OPTIMIZERS[optimizer["name"]](optimizer["params"])
    frozen = {k: v for k, v in weights.items() if not ref.trainable(k)}
    p0 = {k: v for k, v in weights.items() if ref.trainable(k)}

    @jax.jit
    def value_and_grad(p, x, y):
        return jax.value_and_grad(
            lambda q: ref.loss({**frozen, **q}, x, y, mode, **kw))(p)

    update = jax.jit(update)
    p, state, losses, first = p0, jax.jit(init)(p0), [], None
    for t, (x, y) in enumerate(batches, start=1):
        if rows is not None:
            x, y = x[:rows], y[:rows]
        loss, g = value_and_grad(p, x, y)
        if first is None:
            first = {k: v.astype(jnp.float32) for k, v in g.items()}
        if not unchanged:
            p, state = update(p, g, state, jnp.float32(t))
        losses.append(float(loss))
    grad_norms = jax.jit(_norms)(first)
    variances = {}
    if hasattr(ref, "forward_variances"):
        x = batches[0][0] if rows is None else batches[0][0][:rows]
        variances = jax.jit(lambda w, x: ref.forward_variances(
            w, x, mode, **{k: v for k, v in kw.items() if k != "remat"}))(
                weights, x)
    change = jax.jit(lambda a, b: _norms({k: a[k] - b[k] for k in a}))(p, p0)
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "change_norms": {k: float(v) for k, v in change.items()},
            "grad": first, "variances": variances}
