"""Build / inspect / garbage-collect AOT compilation artifacts.

The release-time half of docs/compilation.md: compile a model's fixed
program set ahead of time (`jit(...).lower().compile()`), serialize the
executables into an `ArtifactStore` directory, and ship that directory
with the release. A serving process pointed at it via
``MXTPU_AOT_STORE=<dir>`` (or ``ModelServer(artifacts=...)``) loads the
executables before first dispatch — warmup and restart downtime stop
paying compile; any fingerprint mismatch falls back to JIT.

    # build the serve_bench MLP's padding-bucket programs
    python tools/aot_build.py --out /releases/r42/aot --mlp \
        --features 256 --hidden 256 --max-batch 32

    # plus a GPT decoder's two-program decode set
    python tools/aot_build.py --out /releases/r42/aot --decode

    # capture fused-update kernels by running a tiny training loop
    # under MXTPU_AOT_EXPORT (your real training job captures its own
    # kernels the same way: MXTPU_AOT_STORE=<dir> MXTPU_AOT_EXPORT=1)
    python tools/aot_build.py --out /releases/r42/aot --train

    # inspect / garbage-collect (kill_stale-style: REFUSES while a
    # live process holds the store; exit 2 so callers know GC is
    # blocked rather than silently skipped)
    python tools/aot_build.py --list /releases/r42/aot
    python tools/aot_build.py --gc /releases/r42/aot \
        --max-bytes 268435456

``--gc`` on a directory *without* a manifest treats it as a raw
persistent-XLA-cache directory: scrub corrupt husks, then LRU-evict
past ``--max-bytes`` (the offline mirror of the cache's own bound).

Exit codes: 0 done; 2 refused (live holder) or error. The last stdout
line is one JSON record describing what happened.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _env_int(name, default):
    return int(os.environ.get(name, default))


def build_mlp(store, args):
    """Freeze serve_bench's MLP and export its padding-bucket forward
    programs."""
    from serve_bench import _build_model
    from mxnet_tpu.serving import InferenceEngine
    sym, params = _build_model(args.features, args.hidden,
                               depth=args.depth)
    engine = InferenceEngine.from_symbol(
        sym, params, {}, {"data": (args.features,)},
        max_batch_size=args.max_batch, name=args.name)
    exported = engine.aot_export(store)
    return {"model": "mlp", "engine": engine.name,
            "buckets": [b for b, _ in exported]}


def build_decode(store, args):
    """Freeze a GPTDecoder into a DecodeEngine and export its whole
    program set (prefill buckets + admit + step)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import GPTDecoder
    from mxnet_tpu.serving import DecodeEngine
    np.random.seed(13)
    block = GPTDecoder(args.vocab, max_seq_len=args.max_seq_len,
                       num_layers=args.layers, num_heads=args.heads,
                       embed_dim=args.embed)
    block.initialize(mx.init.Xavier(magnitude=2.5))
    engine = DecodeEngine(block, max_slots=args.slots,
                          name=args.decode_name)
    exported = engine.aot_export(store)
    return {"model": "gpt_decode", "engine": engine.name,
            "programs": [n for n, _ in exported]}


def build_train(store, args):
    """Capture the training-step programs: run a few optimizer steps
    with the export env armed, so every program signature that fires
    compiles ahead of time into the store (the same mechanism a real
    training job uses via MXTPU_AOT_STORE + MXTPU_AOT_EXPORT=1). What
    is captured is what the trainer built here runs
    (docs/performance.md "Fused train step & ZeRO-1"): ONE fused_step/
    exchange+update program for SGD and Adam, the staged fused/
    per-group kernels for `--optimizer rmsprop` or `adagrad`."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn

    net = nn.Dense(args.hidden, in_units=args.features)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), args.optimizer,
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(0)
    for _ in range(2):
        x = mx.nd.array(rng.rand(8, args.features).astype(np.float32))
        y = mx.nd.array(rng.rand(8, args.hidden).astype(np.float32))
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(8)
    return {"model": "train_capture", "optimizer": args.optimizer}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="build/inspect/GC AOT compilation artifacts")
    ap.add_argument("--out", default=None,
                    help="artifact store directory to build into")
    ap.add_argument("--gc", default=None, metavar="DIR",
                    help="garbage-collect an artifact store (or raw "
                         "XLA cache dir)")
    ap.add_argument("--list", default=None, metavar="DIR",
                    help="print a store's manifest and exit")
    ap.add_argument("--max-bytes", type=int, default=None,
                    help="with --gc: LRU-evict past this byte budget")
    ap.add_argument("--dry-run", action="store_true",
                    help="with --gc: report only, evict nothing")
    ap.add_argument("--mlp", action="store_true",
                    help="export the serve_bench MLP program set")
    ap.add_argument("--decode", action="store_true",
                    help="export a GPTDecoder decode program set")
    ap.add_argument("--train", action="store_true",
                    help="capture fused-update kernels from a tiny "
                         "training run")
    ap.add_argument("--name", default="serve_bench")
    ap.add_argument("--features", type=int,
                    default=_env_int("MXTPU_SERVE_BENCH_FEATURES", 256))
    ap.add_argument("--hidden", type=int,
                    default=_env_int("MXTPU_SERVE_BENCH_HIDDEN", 256))
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--decode-name", default="decode")
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--max-seq-len", type=int, default=28)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--embed", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    args = ap.parse_args(argv)

    if sum(x is not None for x in (args.out, args.gc, args.list)) != 1:
        ap.error("need exactly one of --out / --gc DIR / --list DIR")

    if args.list is not None:
        from mxnet_tpu.compile import ArtifactStore
        store = ArtifactStore(args.list)
        print(json.dumps({"dir": store.root,
                          "entries": store.entries(),
                          "holders": len(store.live_holders())},
                         sort_keys=True))
        return 0

    if args.gc is not None:
        from mxnet_tpu.compile import (ArtifactStore, StoreHeld,
                                       gc_cache_dir)
        if os.path.isfile(os.path.join(args.gc, "manifest.json")):
            store = ArtifactStore(args.gc)
            try:
                report = store.gc(max_bytes=args.max_bytes,
                                  dry_run=args.dry_run)
            except StoreHeld as err:
                print(json.dumps({"dir": args.gc, "refused": True,
                                  "error": str(err)}))
                print("aot_build: %s" % err, file=sys.stderr)
                return 2
            report["kind"] = "store"
        else:
            report = gc_cache_dir(args.gc, max_bytes=args.max_bytes,
                                  dry_run=args.dry_run)
            report["kind"] = "xla_cache"
        print(json.dumps(report, sort_keys=True))
        return 0

    # --out: build. Arm the capture env BEFORE the framework imports so
    # --train's fused kernels land in the same store.
    os.environ["MXTPU_AOT_STORE"] = os.path.abspath(args.out)
    os.environ["MXTPU_AOT_EXPORT"] = "1"
    from mxnet_tpu.compile import ArtifactStore
    store = ArtifactStore(args.out, create=True)
    built = []
    if not (args.mlp or args.decode or args.train):
        args.mlp = True     # something must be built
    if args.mlp:
        built.append(build_mlp(store, args))
    if args.decode:
        built.append(build_decode(store, args))
    if args.train:
        built.append(build_train(store, args))
    # prove every blob loads in a fresh interpreter; prune the ones
    # that don't (a warm persistent cache in THIS process can yield
    # symbol-referencing blobs only this process could read)
    verified = store.verify_and_prune()
    entries = store.entries()
    print(json.dumps({
        "dir": store.root, "built": built,
        "entries": len(entries),
        "verified": sum(1 for ok in verified.values() if ok),
        "pruned": sorted(n for n, ok in verified.items() if not ok),
        "bytes": sum(int(e.get("bytes", 0)) for e in entries.values()),
    }, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
