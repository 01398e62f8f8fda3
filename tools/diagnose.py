"""Environment diagnosis (reference: tools/diagnose.py — prints
platform, versions, and connectivity so bug reports carry context).

    python tools/diagnose.py
"""
import os
import platform
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Platform     :", platform.platform())
    print("Processor    :", platform.processor() or "n/a")
    print("CPU count    :", os.cpu_count())

    print("----------Framework Info----------")
    t0 = time.time()
    import mxnet_tpu as mx
    print("mxnet_tpu    :", mx.__version__,
          "(import %.2fs)" % (time.time() - t0))
    try:
        print("native lib   :", mx.libinfo.find_lib_path()[0])
    except Exception as e:
        print("native lib   : NOT BUILT (%s)" % e)

    print("----------JAX / Device Info----------")
    import jax
    import jaxlib
    print("jax          :", jax.__version__)
    print("jaxlib       :", jaxlib.__version__)
    t0 = time.time()
    from mxnet_tpu.base import probe_devices
    devs, err = probe_devices(timeout_s=30)
    if devs is not None:
        print("devices      : %s (probe %.2fs)"
              % ([str(d) for d in devs], time.time() - t0))
    else:
        print("devices      : UNAVAILABLE (%s)" % err)
        print("  recovery   : python tools/kill_stale.py --kill  "
              "(reaps init-hung holders of the device)")
        try:
            from tools.kill_stale import find_candidates
            for c in find_candidates():
                print("  suspect    : pid %d age %.0fs %s"
                      % (c["pid"], c["age_s"], c["cmd"][:80]))
        except Exception as e:  # /proc-less host: keep the report going
            print("  suspects   : unavailable (%s)" % e)

    print("----------Deps----------")
    for name in ("numpy", "flax", "optax", "orbax.checkpoint", "PIL",
                 "torch"):
        try:
            m = __import__(name)
            print("%-12s : %s" % (name, getattr(m, "__version__", "ok")))
        except ImportError:
            print("%-12s : absent" % name)

    print("----------Telemetry Counters----------")
    # live snapshot of the process-wide registry (docs/observability.md):
    # in a fresh diagnose process this shows what importing the
    # framework alone recorded (e.g. warm-up XLA compiles); inside a
    # training process it is the full runtime counter state
    from mxnet_tpu.observability import REGISTRY, stream_path
    print("MXTPU_TELEMETRY :", stream_path() or "(unset: step records off)")
    rows = REGISTRY.snapshot()
    if not rows:
        print("(no metrics recorded)")
    for name, kind, labels, value in rows:
        tag = "{%s}" % ",".join("%s=%s" % kv
                                for kv in sorted(labels.items())) \
            if labels else ""
        if kind == "histogram":
            print("%-44s count=%d sum=%.4f"
                  % (name + tag, value["count"], value["sum"]))
        else:
            print("%-44s %g" % (name + tag, value))

    print("----------Environment----------")
    for k, v in sorted(os.environ.items()):
        if k.startswith(("MXTPU_", "MXNET_", "JAX_", "XLA_", "DMLC_")):
            print("%s=%s" % (k, v))


if __name__ == "__main__":
    main()
