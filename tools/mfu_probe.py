"""Round-4 MFU probes (PERF.md §5 follow-ups; run ON THE REAL CHIP in
one generously-timed process that exits normally — never wrap in
`timeout`, never SIGKILL: a killed holder loses every result).

Probes, each isolated so one failure doesn't cost the rest:
  1. b128 headline sanity (round-3 ladder said 2762 img/s)
  2. batch ladder b192/b256 plain — r3 saw b256 regress (HBM spill)
  3. b256 with remat=True / selective remat — the single-chip memory
     lever (ZeRO-1 shards optimizer state across dp, which is a no-op
     at dp=1; recorded as a reasoned negative, not a measurement)
  4. fused-update roofline: XLA's fused momentum-SGD vs the Pallas
     fused_sgd_momentum kernel on a resnet50-sized buffer, GB/s each —
     if XLA already sits at HBM spec (~819 GB/s/chip v5e), the Pallas
     path can't win and the negative closes PERF.md §5's question.

Writes PROBE_MFU.json and prints one JSON line.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

RESULTS = {}


def _out_path():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "PROBE_MFU.json")


def _record(name, fn):
    t0 = time.time()
    try:
        RESULTS[name] = fn()
    except Exception as e:  # noqa: BLE001 — probe isolation
        RESULTS[name] = {"error": str(e)[:300]}
    RESULTS[name + "_wall_s"] = round(time.time() - t0, 1)
    _flush()


def _flush():
    """Snapshot RESULTS after every probe: a later probe wedging in the
    compile hangs the process, but completed
    results survive on disk. Atomic via os.replace so a kill mid-write
    can't truncate what was already saved."""
    out = _out_path()
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(RESULTS, f, indent=1)
    os.replace(tmp, out)


def _resnet():
    from mxnet_tpu.gluon.model_zoo import vision
    return vision.resnet50_v1(classes=1000, layout="NHWC")


def batch_probe(batch, **kw):
    def run():
        import bench
        from mxnet_tpu.observability import goodput
        r, _ = bench._train_tput(lambda: _resnet(), batch, 224, 50, 10,
                                 **kw)
        # same denominator the StepTimer MFU uses: the shared goodput
        # peak-FLOPs table (MXTPU_PEAK_FLOPS override respected), so
        # probe MFU and telemetry MFU are directly comparable (None:
        # the device's kind has no published peak there)
        peak = goodput.peak_flops()
        return {"img_s": round(r, 2),
                "mfu": (round(r * 3 * 4.089e9 / peak, 4) if peak
                        else None)}
    return run


def optimizer_phase_cost():
    """Host-only accounting: FLOPs/bytes of the fused update phase at
    ResNet-50 scale (parallel/fused_update.update_cost), so MFU numbers
    can include the optimizer phase instead of silently excluding it.
    Per-step fwd+bwd FLOPs for resnet50 b128 ~ 3 * 4.1 GFLOP * 128."""
    from mxnet_tpu import optimizer as mxopt
    from mxnet_tpu.parallel.fused_update import update_cost

    n_params = 25_557_032  # resnet50_v1 classes=1000
    fwd_bwd_flops = 3 * 4.089e9 * 128
    out = {"n_params": n_params}
    for name, kw in (("sgd_momentum", dict(momentum=0.9)),
                     ("adam", dict())):
        opt_name = "sgd" if name == "sgd_momentum" else name
        cost = update_cost(mxopt.create(opt_name, **kw), n_params, 4)
        out[name] = {
            "flops": cost["flops"], "bytes": cost["bytes"],
            "reads_per_elem": cost["reads"],
            "writes_per_elem": cost["writes"],
            # how much the optimizer phase adds to a b128 train step's
            # FLOP count if excluded from the MFU denominator
            "share_of_b128_step_flops": round(
                cost["flops"] / (fwd_bwd_flops + cost["flops"]), 6),
        }
    return out


def update_roofline():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import fused_sgd_momentum
    from mxnet_tpu import optimizer as mxopt
    from mxnet_tpu.parallel.fused_update import update_cost

    rows, cols = 199680, 128  # ~25.6M fp32 params, lane-aligned
    rng = np.random.RandomState(0)
    w = jax.device_put(rng.randn(rows, cols).astype("float32"))
    g = jax.device_put(rng.randn(rows, cols).astype("float32"))
    m = jax.device_put(rng.randn(rows, cols).astype("float32"))
    lr, mom = 0.05, 0.9
    iters = 50
    # the fused update's cost model (3R+2W, 5 flops/elem for
    # momentum-SGD) — the same accounting the MFU summary uses
    cost = update_cost(mxopt.create("sgd", momentum=mom,
                                    learning_rate=lr), rows * cols, 4)

    def xla_step(w, g, m):
        m2 = mom * m + g
        return w - lr * m2, m2

    def timed(step):
        @jax.jit
        def loop(w, g, m):
            def body(i, c):
                w, m = c
                w, m = step(w, g + i * 0.0, m)
                return (w, m)
            return jax.lax.fori_loop(0, iters, body, (w, m))
        out = loop(w, g, m)
        np.asarray(jax.device_get(out[0][:1, :1]))  # compile+fence
        t0 = time.perf_counter()
        out = loop(w, g, m)
        np.asarray(jax.device_get(out[0][:1, :1]))
        dt = time.perf_counter() - t0
        return (cost["bytes"] * iters / dt / 1e9,
                cost["flops"] * iters / dt / 1e9)

    xla, xla_gf = timed(xla_step)
    pallas, pallas_gf = timed(
        lambda w, g, m: fused_sgd_momentum(w, g, m, lr, mom))
    return {"xla_gb_s": round(xla, 1), "pallas_gb_s": round(pallas, 1),
            "xla_gflop_s": round(xla_gf, 1),
            "pallas_gflop_s": round(pallas_gf, 1),
            "update_bytes_per_step": cost["bytes"],
            "update_flops_per_step": cost["flops"],
            "buffer_mb": round(rows * cols * 4 / 2**20, 1),
            "note": "3R+2W bytes/iter; v5e HBM spec ~819 GB/s"}


def bn_fusion_probe():
    """Fused 1x1-conv + BN-stat epilogue vs the XLA two-pass schedule,
    at a representative ResNet-50 interior shape (56x56, C=64->256,
    b128 -> M=401408 rows). Keep the kernel only if pallas wins here
    (VERDICT r4 #5c)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import conv1x1_bn_stats

    M, Cin, Cout = 128 * 56 * 56, 64, 256
    rng = np.random.RandomState(0)
    x = jax.device_put(rng.randn(M, Cin).astype("float32"))
    w = jax.device_put((rng.randn(Cin, Cout) * 0.1).astype("float32"))
    iters = 30

    def xla_version(x, w):
        y = x @ w
        mean = jnp.mean(y, axis=0)
        var = jnp.mean(y * y, axis=0) - mean * mean
        return y, mean, var

    def timed(fn):
        @jax.jit
        def loop(x, w):
            def body(i, c):
                y, mean, var = fn(x, w + 0.0 * i)
                return (y[:1, :1] + mean[:1] + var[:1],)
            return jax.lax.fori_loop(0, iters, body,
                                     (jnp.zeros((1, 1)),))
        np.asarray(jax.device_get(loop(x, w)[0]))
        t0 = time.perf_counter()
        np.asarray(jax.device_get(loop(x, w)[0]))
        dt = time.perf_counter() - t0
        return dt / iters * 1e3

    xla_ms = timed(xla_version)
    pallas_ms = timed(lambda x, w: conv1x1_bn_stats(x, w))
    return {"xla_ms": round(xla_ms, 3), "pallas_ms": round(pallas_ms, 3),
            "shape": "M=%d Cin=%d Cout=%d" % (M, Cin, Cout),
            "winner": "pallas" if pallas_ms < xla_ms else "xla"}


def main():
    from bench import _enable_compile_cache
    _enable_compile_cache()   # share executables with bench runs
    from mxnet_tpu.base import probe_devices
    devs, err = probe_devices(timeout_s=240)
    if devs is None:
        print(json.dumps({"error": "backend unreachable: %s" % err}))
        return 1
    import jax
    jax.config.update("jax_default_matmul_precision", "bfloat16")
    RESULTS["devices"] = [str(d) for d in devs]

    # smallest programs FIRST (bench-ladder lesson): the
    # batch-ladder probes each compile a full 50-step train program —
    # the longest phase on the device — so the cheap kernel
    # probes must already be on disk if one of those wedges
    RESULTS["zero1_note"] = (
        "shard_optimizer_state (ZeRO-1) shards over the dp mesh axis; "
        "with ONE real chip dp=1 so there is nothing to shard — "
        "a single-chip b256 memory fix must come from remat instead")
    _flush()   # devices + the reasoned negative survive even a
    _record("optimizer_phase_cost", optimizer_phase_cost)  # host-only
    _record("update_roofline", update_roofline)  # first-probe wedge
    _record("bn_fusion", bn_fusion_probe)
    _record("b128_headline", batch_probe(128))
    _record("b192", batch_probe(192))
    _record("b256", batch_probe(256))
    _record("b256_remat_full", batch_probe(256, remat=True))
    _record("b256_remat_dots",
            batch_probe(256, remat="dots_with_no_batch_dims_saveable"))

    print(json.dumps(RESULTS))
    return 0


if __name__ == "__main__":
    sys.exit(main())
