"""Build the system under test from a configuration's file: the program's
own model, holding weights the benchmark made from the seed."""
import importlib

import weights as _weights


def bare(net, name):
    """A parameter's name without the prefix the net gives it (two nets of
    one process differ in it)."""
    return name[len(net.prefix):] if name.startswith(net.prefix) else name


def build(config, seed, device, ctx=None):
    """(net, {bare name: float32 weight on `device`}). The net's
    parameters ARE those arrays: no initializer runs, nothing is copied.
    Shapes come from symbolic inference where the net defers them, so no
    eager forward compiles a program for every layer."""
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray

    spec = config["model"]
    module, factory = spec["factory"].split(":")
    mx.random.seed(int(seed) & 0x7FFFFFFF)
    net = getattr(importlib.import_module(module), factory)(**spec["kwargs"])
    net.initialize(ctx=ctx)
    if "infer_shape" in spec:
        net.infer_shape(mx.nd.zeros(tuple(spec["infer_shape"])))
    params = net.collect_params()
    shapes = {bare(net, p.name): tuple(p.shape) for p in params.values()}
    made = _weights.make_weights(shapes, config["initializer"], seed, device)
    for p in params.values():
        p.set_data(NDArray(made[bare(net, p.name)]))
        p._finish_deferred_init()
    return net, made
