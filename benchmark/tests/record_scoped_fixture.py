#!/usr/bin/env python3
"""Record the fixture of `test_program_trace.py` on a chip: a tiny
conv + batch-norm + dense net through `ShardedTrainer.step`, four steps
under the harness's profiler options, the loss fetched every step.

    chiprun -- python3 benchmark/tests/record_scoped_fixture.py

writes `chiprun_out/fixture/scoped_v5e.xplane.pb` (the device trace) and
`scoped_v5e.json` (the program table's maps for the programs that ran,
its snapshot, and the ring's spans of the traced steps); copy both to
`benchmark/tests/data/`. Nothing is measured here."""
import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

STEPS = 4


def main():
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.compile import programs
    from mxnet_tpu.observability import trace
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("record_scoped_fixture: no TPU (%s)" % dev.platform)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, layout="NHWC", in_channels=3),
            gluon.nn.BatchNorm(axis=3, in_channels=8),
            gluon.nn.Activation("relu"), gluon.nn.Flatten(),
            gluon.nn.Dense(10))
    net.initialize(ctx=mx.tpu(0))
    rng = np.random.RandomState(7)
    x = rng.rand(16, 16, 16, 3).astype("float32")
    y = rng.randint(0, 10, (16,)).astype("float32")
    net(mx.nd.array(x, ctx=mx.tpu(0)))
    trainer = ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=make_mesh({"dp": 1}, [dev]), compute_dtype="bfloat16")
    feed = trainer.prefetched(((x, y) for _ in range(3 + STEPS)), depth=2)
    for _ in range(3):
        float(trainer.step(*next(feed)).asscalar())
    out = os.path.join(ROOT, "chiprun_out", "fixture")
    tdir = os.path.join(out, "trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
    trace.reset_ring()
    jax.profiler.start_trace(tdir, profiler_options=opts)
    for _ in range(STEPS):
        float(trainer.step(*next(feed)).asscalar())
    jax.profiler.stop_trace()
    feed.close()
    pb = sorted(glob.glob(os.path.join(
        tdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(pb, os.path.join(out, "scoped_v5e.xplane.pb"))
    shutil.rmtree(tdir)
    snap = programs.snapshot()
    with open(os.path.join(out, "scoped_v5e.json"), "w") as f:
        json.dump({"steps": STEPS, "snapshot": snap,
                   "owners": {n: programs.owners(n) for n in snap},
                   "spans": trace.ring_spans()}, f, sort_keys=True)
    print("recorded", os.path.getsize(os.path.join(
        out, "scoped_v5e.xplane.pb")), "bytes of trace,", len(snap),
        "programs")


if __name__ == "__main__":
    main()
