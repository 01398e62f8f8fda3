"""Whether a training configuration of the benchmark fits one chip, from
shapes alone: no chip, no weights, nothing runs.

    JAX_PLATFORMS=cpu python3 tools/fit_probe.py benchmark/configs/<name>.json
        [--mode float32] [--topology v5e:2x2] [--batch 1]

Compiles, with the TPU's compiler for a described chip (the
`on-chip-measurement` guide's third rehearsal), the three programs that
decide it, and prints each one's `memory_analysis()` as one JSON line:

  step        the program's training step as `ShardedTrainer` builds it
              (bfloat16 copies of the matrices, value and gradient, Adam,
              the guard's selects; parameters, aux and state donated)
  reference   `value_and_grad` of the configuration's plain reference
              (`benchmark/reference/<name>.py`), as
              `benchmark/reference_train.py:follow` jits it
  update      `reference_train`'s optimizer update, nothing donated, as
              `follow` jits it, and donated for comparison

and the sums that `benchmark/harness.py`'s run has to hold: beside the
step, the net's own copy of the weights (not with the loop
`sharded_trainer_net_on_host`); in `follow`, p0, the first gradient, p
and the optimizer's state beside each program; in
`reference_train_on_host.follow` (which that loop puts in `follow`'s
place) the state alone. The configuration's net
has to know its shapes without a forward pass (the decoders do).
"""
import argparse
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
for _p in (os.path.join(ROOT, "benchmark"), ROOT):
    sys.path.insert(0, _p)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding


def _bytes(compiled):
    ma = compiled.memory_analysis()
    out = {"arguments": ma.argument_size_in_bytes,
           "outputs": ma.output_size_in_bytes,
           "temporaries": ma.temp_size_in_bytes,
           "aliased": ma.alias_size_in_bytes}
    out["in_all"] = (out["arguments"] + out["outputs"] + out["temporaries"]
                     - out["aliased"])
    return {k: round(v / 1e9, 3) for k, v in out.items()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--mode", default="float32")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--batch", type=int, default=1,
                    help="sequences a step (the cell's `batch`)")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)

    from jax.experimental import topologies
    import harness
    import reference_train
    from mxnet_tpu import gluon, symbol
    from mxnet_tpu.graph import build_graph_fn
    from mxnet_tpu.ops import delta_rule_kernels, moe_kernels, pallas_kernels
    from mxnet_tpu.parallel import data_parallel
    jax.config.update("jax_enable_compilation_cache", False)
    for kernels in (pallas_kernels, delta_rule_kernels, moe_kernels):
        kernels._interpret = lambda: False         # compiled, not interpreted
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    chip = SingleDeviceSharding(topo.devices[0])

    def described(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    module, factory = config["model"]["factory"].split(":")
    net = getattr(importlib.import_module(module), factory)(
        **config["model"]["kwargs"])
    spec = config["input"]
    if spec["kind"] != "tokens":
        sys.exit("fit_probe: only token inputs are described here")
    data, label = described((args.batch, spec["length"]), jnp.int32), \
        described((args.batch, spec["length"]))
    loss = getattr(gluon.loss, config["loss"])()(
        net(symbol.var("data")), symbol.var("label"))
    fn, arg_names, aux_names, _ = build_graph_fn(loss._entries, "train")
    shapes = {p.name: p.shape for p in net.collect_params().values()}
    if any(s is None or not np.prod(s) for s in shapes.values()):
        sys.exit("fit_probe: the net defers its shapes to a forward pass")
    params = {n: described(shapes[n]) for n in arg_names
              if n not in ("data", "label")}
    aux = {n: described(shapes[n]) for n in aux_names}
    opt = config["optimizer"]
    opt_init, opt_update, defaults = data_parallel._OPTIMIZERS[opt["name"]]
    renamed = {"learning_rate": "lr", "epsilon": "eps"}
    hp = {**defaults, **{renamed.get(k, k): v
                         for k, v in opt["params"].items()}}

    def step(params, aux, state, data, label):
        def loss_fn(p):
            p = {k: v.astype(jnp.bfloat16) if v.ndim >= 2 else v
                 for k, v in p.items()}
            outs, auxup = fn({**p, "data": data, "label": label}, aux, None)
            return jnp.mean(outs[0].astype(jnp.float32)), auxup
        (value, auxup), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        new_p, new_s = opt_update(params, grads, state, **hp)
        ok = data_parallel._grads_finite(grads)
        keep = lambda n, o: jnp.where(ok, n, o)          # noqa: E731
        return (jax.tree.map(keep, new_p, params), {**aux, **(auxup or {})},
                jax.tree.map(keep, new_s, state), value)

    state = jax.tree.map(lambda a: described(a.shape, a.dtype),
                         jax.eval_shape(opt_init, params))
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    copy = 4 * n_params / 1e9
    out = {"config": config["name"], "parameters": n_params,
           "float32_copy_gb": round(copy, 3)}
    out["step"] = _bytes(jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        params, aux, state, data, label).compile())
    print(json.dumps({"step": out["step"]}), flush=True)

    ref = harness.load_file("reference", config["reference"])
    bare = {n[len(net.prefix):]: v for n, v in {**params, **aux}.items()}
    p = {k: v for k, v in bare.items() if ref.trainable(k)}
    frozen = {k: v for k, v in bare.items() if not ref.trainable(k)}
    kw = config.get("reference_kwargs") or {}

    def value_and_grad(p, frozen, x, y):
        return jax.value_and_grad(lambda q: ref.loss(
            {**frozen, **q}, x, y, args.mode, **kw))(p)

    out["reference"] = _bytes(jax.jit(value_and_grad).lower(
        p, frozen, data, label).compile())
    print(json.dumps({"reference": out["reference"]}), flush=True)
    init, update = reference_train.OPTIMIZERS[opt["name"]](opt["params"])
    ref_state = jax.eval_shape(init, p)
    for name, donate in (("update", ()), ("update_donated", (0, 2))):
        out[name] = _bytes(jax.jit(update, donate_argnums=donate).lower(
            p, p, ref_state, described((), jnp.float32)).compile())
    state_copies = len(jax.tree.leaves(ref_state)) / max(len(p), 1)
    # what `follow` holds beside each of its programs from step 2 on: p0
    # and the first gradient; beside value_and_grad also the state
    out["harness_holds_gb"] = {
        "step_with_the_nets_copy": round(out["step"]["in_all"] + copy, 3),
        "follow_value_and_grad": round(
            out["reference"]["in_all"] + (2 + state_copies) * copy, 3),
        "follow_update": round(out["update"]["in_all"] + 2 * copy, 3),
        # `reference_train_on_host.follow`: p0 and the first gradient wait
        # on the host, p and the state are donated to the update
        "on_host_value_and_grad": round(
            out["reference"]["in_all"] + state_copies * copy, 3),
        "on_host_update": out["update_donated"]["in_all"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
