"""The loop of `ShardedTrainer.fit`: batches staged by
`ShardedTrainer.prefetched(..., depth=2)`, one `step(x, y)` a batch on
`make_mesh({"dp": chips})`, the loss fetched every step."""
import jax
import jax.numpy as jnp

import model


class Loop:
    def __init__(self, cell, config, seed, devices):
        from mxnet_tpu import gluon
        from mxnet_tpu.parallel import ShardedTrainer, make_mesh

        self.net, self.weights = model.build(config, seed, devices[0])
        opt = config["optimizer"]
        self._opt = opt
        # the configuration names Adam's epsilon as the paper does
        params = {{"epsilon": "eps"}.get(k, k): v
                  for k, v in opt["params"].items()}
        mesh = make_mesh({"dp": len(devices)}, list(devices))
        self.trainer = ShardedTrainer(
            self.net, getattr(gluon.loss, config["loss"])(), opt["name"],
            params, mesh=mesh,
            compute_dtype=cell.get("compute_dtype"))
        self._feed = None
        self._momentum = float(config.get("batch_norm", {}).get("momentum", 0))

    # -- the window's own call and feed ---------------------------------
    def feed(self, batches):
        self._feed = self.trainer.prefetched(batches, depth=2)
        return self._feed

    def step(self, staged):
        return self.trainer.step(*staged)

    def fetch(self, handle):
        return float(handle.asscalar())

    # -- what `correct` reads from the program's state -------------------
    def _initial(self):
        """The seed's weights, placed as the trainer places its own."""
        live = self.trainer._params
        return {k: jax.device_put(self.weights[model.bare(self.net, k)],
                                  live[k].sharding) for k in live}

    def first_gradient(self):
        """After step 1: the gradient as the optimizer got it, worked out
        from the optimizer's state (momentum from zero is g + wd*p0;
        Adam's first moment from zero is (1-beta1)*(g + wd*p0)). Host
        arrays by bare name, so that nothing of it stays on the chip."""
        hp = self._opt["params"]
        wd = hp.get("wd", 0.0)
        state = self.trainer._opt_state
        if self._opt["name"] == "adam":
            scale = 1.0 / (1.0 - hp.get("beta1", 0.9))
            moment = state["m"]
        else:
            scale, moment = 1.0, state

        @jax.jit
        def gradient(moment, p0):
            return {k: scale * moment[k] - wd * p0[k] for k in moment}

        got = jax.device_get(gradient(moment, self._initial()))
        return {model.bare(self.net, k): v for k, v in got.items()}

    def first_variances(self):
        """After step 1: the batch variance that each batch norm's running
        variance took in (new = m*old + (1-m)*batch), by bare name."""
        m = self._momentum
        aux = {k: v for k, v in self.trainer._aux.items()
               if k.endswith("running_var")}
        if not aux:
            return {}
        old = {k: jax.device_put(self.weights[model.bare(self.net, k)],
                                 v.sharding) for k, v in aux.items()}
        got = jax.device_get(jax.jit(lambda new, old: {
            k: (new[k] - m * old[k]) / (1.0 - m) for k in new})(aux, old))
        return {model.bare(self.net, k): v for k, v in got.items()}

    def change_norms(self):
        """The norm of each parameter's change since the seed's weights."""
        @jax.jit
        def norms(p, p0):
            return {k: jnp.linalg.norm((p[k] - p0[k]).ravel()) for k in p}

        got = jax.device_get(norms(self.trainer._params, self._initial()))
        return {model.bare(self.net, k): float(v) for k, v in got.items()}

    def close(self):
        if self._feed is not None:
            self._feed.close()
        self.trainer = self.net = self.weights = self._feed = None
