"""The loop a Gluon script writes: `mx.nd.array(x, ctx=mx.tpu(0))`, a
hybridized net under `autograd.record()`, `loss.backward()`,
`gluon.Trainer.step(batch)`, and `loss.mean().asscalar()` every batch."""
import jax
import jax.numpy as jnp

import model


class Loop:
    def __init__(self, cell, config, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon

        self._mx = mx
        # a rehearsal on the CPU backend has no mx.tpu(0)
        self.ctx = mx.tpu(0) if devices[0].platform == "tpu" else mx.cpu(0)
        self.net, self.weights = model.build(config, seed, devices[0],
                                             ctx=self.ctx)
        self.net.hybridize()
        opt = config["optimizer"]
        self.trainer = gluon.Trainer(self.net.collect_params(), opt["name"],
                                     dict(opt["params"]))
        self.loss_fn = getattr(gluon.loss, config["loss"])()
        self.batch = int(cell["batch"])
        self._momentum = float(config.get("batch_norm", {}).get("momentum", 0))
        # the net's parameters ARE the seed's arrays, and the fused update
        # donates them: keep copies for the change's norm
        self._p0 = {k: jnp.array(v, copy=True)
                    for k, v in self.weights.items()}

    def feed(self, batches):
        nd = self._mx.nd
        return ((nd.array(x, ctx=self.ctx), nd.array(y, ctx=self.ctx))
                for x, y in batches)

    def step(self, staged):
        from mxnet_tpu import autograd
        x, y = staged
        with autograd.record():
            loss = self.loss_fn(self.net(x), y)
        loss.backward()
        self.trainer.step(self.batch)
        return loss

    def fetch(self, handle):
        return float(handle.mean().asscalar())

    def _trainable(self):
        return {model.bare(self.net, p.name): p
                for p in self.net.collect_params().values()
                if p.grad_req != "null"}

    def first_gradient(self):
        """After step 1: the gradient the optimizer got, which is the
        parameter's gradient buffer times `rescale_grad` (1/batch); the
        fused update does not donate it. Host arrays by bare name."""
        scale = 1.0 / self.batch
        grads = {k: p.grad(self.ctx)._data for k, p in self._trainable().items()}
        return jax.device_get(jax.jit(
            lambda g: {k: scale * v for k, v in g.items()})(grads))

    def first_variances(self):
        """After step 1: the batch variance that each batch norm's running
        variance took in (new = m*old + (1-m)*batch), by bare name."""
        m = self._momentum
        new = {model.bare(self.net, p.name): p.data(self.ctx)._data
               for p in self.net.collect_params().values()
               if p.name.endswith("running_var")}
        if not new:
            return {}
        old = {k: self._p0[k] for k in new}
        return jax.device_get(jax.jit(lambda new, old: {
            k: (new[k] - m * old[k]) / (1.0 - m) for k in new})(new, old))

    def change_norms(self):
        now = {k: p.data(self.ctx)._data for k, p in self._trainable().items()}
        p0 = {k: self._p0[k] for k in now}
        got = jax.device_get(jax.jit(lambda a, b: {
            k: jnp.linalg.norm((a[k] - b[k]).ravel()) for k in a})(now, p0))
        self._p0 = None
        return {k: float(v) for k, v in got.items()}

    def close(self):
        self.trainer = self.net = self.weights = self._p0 = None
