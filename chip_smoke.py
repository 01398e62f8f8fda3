#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path runs on the chip.

One process, the entry points a user calls, the full width of a model the
repo ships (ResNet-50 v1, 1000 classes, NHWC, 224x224, random weights from
a seed):

  python chip_smoke.py            one chip: five `ShardedTrainer.step`s at
                                  batch 128 in bf16 (fp32 master weights),
                                  then three `gluon.Trainer` steps at batch
                                  32 with `ctx=mx.tpu(0)`
  python chip_smoke.py --chips 4  four chips, and nothing else: three
                                  dp=4 steps against the same three dp=1
                                  steps from the same seed
  python chip_smoke.py --rehearse [--chips 4]
                                  CPU rehearsal at a tiny size, for the
                                  sandbox that has no chip; its last line
                                  never says "ok": true

Every phase asserts its own results; an exception anywhere is a non-zero
exit and no `"ok": true`. Without an accelerator the script fails before
it does any work. The seconds it prints are a smoke reading, not a
benchmark. The last line of standard output is one JSON object.
"""
import argparse
import json
import math
import sys
import time

import numpy as np

CLASSES = 1000
# the rate at which the CPU rehearsal (and a batch-32 224x224 CPU run)
# shows the loss falling on the repeated batch at every step
LR = 0.01
MOMENTUM = 0.9
# --chips 4: how far dp=4 may be from dp=1 (see phase_four_chips)
LOSS_TOL_STEP1 = 0.1
LOSS_TOL_LATER = 0.25
HEAD_GAP_RATIO = 0.5


def _args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp=4 against dp=1 comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights and the synthetic batch")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU rehearsal; never reports ok")
    return ap.parse_args()


def _batch(seed, batch, img):
    rng = np.random.RandomState(seed)
    x = rng.randn(batch, img, img, 3).astype("float32")
    y = rng.randint(0, CLASSES, size=batch).astype("float32")
    return x, y


def _resnet50(mx, seed, img, ctx=None):
    """A fresh ResNet-50 whose weights come from `seed`; with no `ctx`
    it is materialised as the README's quick start does."""
    from mxnet_tpu.gluon.model_zoo import vision
    mx.random.seed(seed)
    net = vision.resnet50_v1(classes=CLASSES, layout="NHWC")
    if ctx is None:
        net.initialize()
        net(mx.nd.zeros((1, img, img, 3)))
    else:
        net.initialize(ctx=ctx)
    return net


def _sharded_steps(mx, devices, seed, batch, img, n_steps):
    """`n_steps` of `ShardedTrainer.step` on a dp mesh over `devices`.
    Returns (losses, seconds per step, params before, params after, the
    staged batch)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh

    mesh = make_mesh({"dp": len(devices)}, devices)
    net = _resnet50(mx, seed, img)
    st = ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                        {"learning_rate": LR, "momentum": MOMENTUM},
                        mesh=mesh, compute_dtype="bfloat16")
    def bare(params):   # two nets in one process differ in the prefix
        return {k[len(net.prefix):]: v for k, v in params.items()}

    before = bare({k: np.asarray(v) for k, v in st.params.items()})
    x, y = _batch(seed, batch, img)
    by_dp = NamedSharding(mesh, PartitionSpec("dp"))
    x, y = jax.device_put(x, by_dp), jax.device_put(y, by_dp)
    losses, secs = [], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        losses.append(float(st.step(x, y).asscalar()))   # fetched: a fence
        secs.append(time.perf_counter() - t0)
    return losses, secs, before, bare(st.params), (x, y)


def _check_training(losses, before, after, devices, what,
                    expect_fall=True, above=4.0):
    """The assertions both trainers share. `after` holds device arrays;
    `above` is how far over ln C the first loss may start."""
    assert all(math.isfinite(v) for v in losses), (what, losses)
    # a fresh C-way classifier starts at ln C plus what the spread of its
    # logits adds: `net.initialize()`'s default Uniform(0.07) gives this
    # net logits of std ~2, so ~9 and not 6.9 (CPU runs at 224x224: 8.93
    # at b16 fp32, 9.38 at b32 bf16; the 32x32 rehearsal, whose last
    # feature map is 1x1, starts higher); far outside is a broken model
    first = losses[0] - math.log(CLASSES)
    assert -0.5 < first < above, \
        "%s: a fresh %d-way classifier starts between %.2f and %.2f, " \
        "got %r" % (what, CLASSES, math.log(CLASSES) - 0.5,
                    math.log(CLASSES) + above, losses)
    if expect_fall:
        assert losses[-1] < losses[0], \
            "%s: loss did not fall on the repeated batch: %r" % (what,
                                                                losses)
    assert set(before) == set(after) and after
    moved = [k for k in after
             if not np.array_equal(before[k], np.asarray(after[k]))]
    assert len(moved) > len(after) // 2, \
        "%s: only %d of %d parameters changed" % (what, len(moved),
                                                  len(after))
    for k, v in after.items():
        assert np.isfinite(np.asarray(v)).all(), (what, k)
        assert set(v.devices()) == set(devices), \
            "%s: parameter %s is on %s, not on %s" % (
                what, k, sorted(map(str, v.devices())), devices)


def phase_sharded(mx, devices, seed, batch, img, above):
    losses, secs, before, after, _ = _sharded_steps(
        mx, devices[:1], seed, batch, img, n_steps=5)
    print("sharded_trainer: resnet50_v1 NHWC %dx%d b%d bf16, 5 steps, "
          "losses %s" % (img, img, batch,
                         " ".join("%.4f" % v for v in losses)))
    print("sharded_trainer: step 1 (with compile) %.2f s, steps 2-5 "
          "%.3f s in all (a smoke reading, not a benchmark)"
          % (secs[0], sum(secs[1:])))
    _check_training(losses, before, after, devices[:1], "sharded_trainer",
                    above=above)
    print("sharded_trainer: %d parameters, all on %s"
          % (len(after), devices[0]))


def phase_gluon(mx, ctx, device, seed, batch, img, above):
    from mxnet_tpu import autograd, gluon

    net = _resnet50(mx, seed + 1, img, ctx=ctx)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": LR, "momentum": MOMENTUM})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _batch(seed + 1, batch, img)
    x, y = mx.nd.array(x, ctx=ctx), mx.nd.array(y, ctx=ctx)
    losses, secs, before = [], [], None
    for _ in range(3):
        t0 = time.perf_counter()
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        if before is None:      # shapes are known after the first forward
            before = {p.name: p.data(ctx).asnumpy()
                      for p in net.collect_params().values()}
        trainer.step(batch)
        losses.append(float(loss.mean().asscalar()))
        secs.append(time.perf_counter() - t0)
    after = {p.name: p.data(ctx)._data
             for p in net.collect_params().values()}
    print("gluon_trainer: resnet50_v1 NHWC %dx%d b%d fp32 on %s, 3 steps, "
          "losses %s" % (img, img, batch, ctx,
                         " ".join("%.4f" % v for v in losses)))
    print("gluon_trainer: step 1 (with compile) %.2f s, steps 2-3 %.3f s "
          "in all (a smoke reading, not a benchmark)"
          % (secs[0], sum(secs[1:])))
    _check_training(losses, before, after, [device], "gluon_trainer",
                    above=above)
    print("gluon_trainer: %d parameters, all on %s" % (len(after), device))


def phase_four_chips(mx, devices, seed, batch, img, above):
    """dp=4 over all four devices against dp=1 on the first: one program
    with the same meaning, so the losses, and the parameters that bf16
    leaves well determined, agree."""
    assert len(devices) == 4, devices
    l4, s4, before, p4, (x4, _) = _sharded_steps(
        mx, devices, seed, batch, img, n_steps=3)
    l1, s1, _, p1, _ = _sharded_steps(
        mx, devices[:1], seed, batch, img, n_steps=3)
    print("dp=4 losses %s (step 1 %.2f s)"
          % (" ".join("%.4f" % v for v in l4), s4[0]))
    print("dp=1 losses %s (step 1 %.2f s)"
          % (" ".join("%.4f" % v for v in l1), s1[0]))
    _check_training(l4, before, p4, devices, "dp=4", expect_fall=False,
                    above=above)
    shard_devices = {s.device for s in x4.addressable_shards}
    assert shard_devices == set(devices), shard_devices
    assert all(s.data.shape[0] == batch // 4
               for s in x4.addressable_shards)
    spread = {d for a in p4.values() for d in a.devices()}
    assert len(spread) == 4, spread
    # parameters: the distance between the two runs against the distance
    # either of them moved in three steps
    def ratio(keys):
        gap = math.sqrt(sum(float(np.sum(
            (np.asarray(p4[k]) - np.asarray(p1[k])) ** 2)) for k in keys))
        moved = math.sqrt(sum(float(np.sum(
            (np.asarray(p1[k]) - before[k]) ** 2)) for k in keys))
        return gap / moved

    head = [k for k in p1 if k.startswith("dense")]    # the classifier
    assert len(head) == 2, head
    print("dp=4 against dp=1 after step 3, |p4-p1| / |p1-p0|: classifier "
          "%.4g, all parameters %.4g" % (ratio(head), ratio(list(p1))))
    # Step 1 runs the same weights on the same batch: only bf16 and the
    # order of the reductions differ, and the losses stay together. The
    # parameters are held to agree in the classifier only. In bf16 the
    # gradients of a fresh ResNet-50 below its last block are dominated
    # by rounding: ONE device, the same batch scaled by 1+1e-4 (which
    # batch norm undoes), moves every earlier layer further from the
    # unscaled run than the step moved it (CPU, 96x96 b32; classifier
    # 0.17). So the ratio over all parameters is near 1 on any number of
    # devices (CPU, 224x224 b128: 1.07; classifier 0.05) and is printed,
    # not asserted. A gradient summed where it should be averaged would
    # put the classifier's ratio near 3.
    assert abs(l4[0] - l1[0]) < LOSS_TOL_STEP1, (l4, l1)
    assert np.allclose(l4, l1, rtol=0, atol=LOSS_TOL_LATER), (l4, l1)
    assert ratio(head) < HEAD_GAP_RATIO, ratio(head)
    print("dp=4: batch shards of %d on %d devices, parameters on %d"
          % (batch // 4, len(shard_devices), len(spread)))


def main():
    args = _args()
    import jax
    devices = jax.devices()
    dev = devices[0]
    print("devices: platform=%s kind=%s count=%d"
          % (dev.platform, dev.device_kind, len(devices)))
    if not args.rehearse and dev.platform != "tpu":
        sys.exit("chip_smoke: no accelerator: jax found platform %r (%s); "
                 "use --rehearse for the CPU rehearsal" % (
                     dev.platform, dev.device_kind))
    if len(devices) < args.chips:
        sys.exit("chip_smoke: --chips %d needs %d devices, jax found %d"
                 % (args.chips, args.chips, len(devices)))

    import mxnet_tpu as mx
    from mxnet_tpu.compile import cache
    print("compile cache: %s" % cache.enable_cache())

    if args.chips == 4:
        # the rehearsal keeps 96x32: at 32x32 the last feature map is 1x1
        # and bf16 alone moves the first loss by 0.2
        img, batch, above = (96, 32, 4.0) if args.rehearse \
            else (224, 128, 4.0)
        phase_four_chips(mx, devices[:4], args.seed, batch, img, above)
    else:
        img, batch, gluon_batch, above = (32, 16, 8, 8.0) if args.rehearse \
            else (224, 128, 32, 4.0)
        phase_sharded(mx, devices, args.seed, batch, img, above)
        ctx = mx.cpu(0) if args.rehearse else mx.tpu(0)
        phase_gluon(mx, ctx, dev, args.seed, gluon_batch, img, above)

    stats = cache.cache_stats()
    print("compile.cache.hits=%d compile.cache.misses=%d"
          % (stats["hits"], stats["misses"]))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": args.chips}
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
