"""Collective-bandwidth measurement (reference: tools/bandwidth/
measure.py — measures kvstore push+pull bus bandwidth across GPUs;
README reports 11.1 GB/s on 2 GPUs, 4.4-4.6 GB/s on 8).

Two modes:

1. Single-process psum (original): times a jitted all-reduce at
   ResNet-50-gradient scale over an in-process device mesh and reports
   algorithm bandwidth per device.

       python tools/bandwidth.py [--size-mb 100] [--devices N] [--cpu]

2. Bucket-size sweep over REAL processes: self-launches ``--nproc N``
   workers joined via jax.distributed, builds a synthetic gradient set
   (harmonic size split, like a real net's few-big-many-small mix),
   and times the DistKVStore bucketed exchange (`push_all`) at each
   fusion-bucket size — including 0 = per-key — so MXTPU_BUCKET_MB can
   be tuned per fabric (docs/performance.md).

       python tools/bandwidth.py --cpu --nproc 4 \\
           --sweep-bucket-mb 0,1,4,16,64 [--params 64] [--total-mb 16]

On a CPU mesh this measures memcpy-through-XLA plus dispatch overhead
(which is exactly what bucketing amortizes — the per-key row should be
visibly slower); on real chips the same program measures ICI/DCN.
"""
import argparse
import os
import socket
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--size-mb", type=float, default=100.0,
                   help="payload per device (ResNet-50 grads ~ 100MB; "
                        "single-process psum mode)")
    p.add_argument("--devices", type=int, default=0,
                   help="mesh size (default: all; single-process mode)")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--sweep-bucket-mb", default=None,
                   help="comma-separated bucket sizes in MB to sweep "
                        "(0 = per-key exchange), e.g. 0,1,4,16,64")
    p.add_argument("--nproc", type=int, default=0,
                   help="spawn N real processes for the sweep (sweep "
                        "mode only)")
    p.add_argument("--params", type=int, default=64,
                   help="synthetic gradient count for the sweep")
    p.add_argument("--total-mb", type=float, default=16.0,
                   help="total synthetic gradient payload for the sweep")
    return p.parse_args(argv)


def _synthetic_shapes(n_params, total_mb):
    """Deterministic harmonic size split: a few large tensors carry
    most of the bytes, a long tail of small ones carries the dispatch
    count — the shape mix bucketing exists for."""
    total_elems = max(n_params, int(total_mb * (1 << 20) / 4))
    weights = [1.0 / (i + 1) for i in range(n_params)]
    scale = total_elems / sum(weights)
    return [(max(4, int(w * scale)),) for w in weights]


# ---------------------------------------------------------------------------
# sweep mode (multi-process DistKVStore)
# ---------------------------------------------------------------------------
def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_sweep(args):
    """Parent: spawn --nproc copies of this script as dist workers and
    relay rank 0's report."""
    coordinator = "127.0.0.1:%d" % _free_port()
    env_base = dict(os.environ)
    env_base.pop("XLA_FLAGS", None)  # workers use their own 1-device CPU
    if args.cpu:
        env_base["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env_base["PYTHONPATH"] = repo_root + os.pathsep + \
        env_base.get("PYTHONPATH", "")
    procs = []
    for rank in range(args.nproc):
        env = dict(env_base)
        env["MXTPU_BW_COORD"] = coordinator
        env["MXTPU_BW_NPROC"] = str(args.nproc)
        env["MXTPU_BW_RANK"] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)] + sys.argv[1:],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env))
    rc = 0
    try:
        for rank, proc in enumerate(procs):
            try:
                out, _ = proc.communicate(timeout=600)
            except subprocess.TimeoutExpired:
                # one wedged rank (e.g. a peer died before rendezvous)
                # must not leak the rest of the fleet
                rc = 1
                sys.stderr.write("worker %d timed out\n" % rank)
                continue
            if proc.returncode != 0:
                rc = proc.returncode or 1
                sys.stderr.write("worker %d failed (rc=%d):\n%s\n"
                                 % (rank, proc.returncode,
                                    out.decode(errors="replace")[-3000:]))
            elif rank == 0:
                sys.stdout.write(out.decode(errors="replace"))
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
    return rc


def _run_sweep_worker(args):
    """Child: join the dist runtime and time push_all per bucket size."""
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from mxnet_tpu.parallel.kvstore_dist import _enable_cpu_collectives
    _enable_cpu_collectives()
    coordinator = os.environ["MXTPU_BW_COORD"]
    nproc = int(os.environ["MXTPU_BW_NPROC"])
    rank = int(os.environ["MXTPU_BW_RANK"])
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=nproc, process_id=rank)
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.observability import registry as obs

    kv = mx.kv.create("dist_sync")
    nw = kv.num_workers
    shapes = _synthetic_shapes(args.params, args.total_mb)
    keys = ["g%d" % i for i in range(len(shapes))]
    grads, total_bytes = [], 0
    for i, (key, shape) in enumerate(zip(keys, shapes)):
        kv.init(key, mx.nd.zeros(shape))
        grads.append(mx.nd.full(shape, float((rank + i) % 7 + 1)))
        total_bytes += int(np.prod(shape)) * 4
    prios = [-i for i in range(len(keys))]
    calls = obs.REGISTRY.get("kvstore.allreduce.calls")
    # update phase: consume the reduced grads (bucket-layout slices out
    # of pull_all) with the fused optimizer step, so the sweep shows
    # exchange AND update cost per bucket size in one table — the
    # pack-layout reuse of parallel/fused_update.py is the delta
    from mxnet_tpu import optimizer as mxopt
    updater = mxopt.get_updater(
        mxopt.create("sgd", learning_rate=0.01, momentum=0.9))
    weights = [mx.nd.zeros(shape) for shape in shapes]
    pulled = [mx.nd.zeros(shape) for shape in shapes]
    idxs = list(range(len(keys)))

    if rank == 0:
        print("sweep: %d procs  %d params  %.1f MB total payload  "
              "%d iters" % (nw, len(keys), total_bytes / 1e6, args.iters))
    for mb in [float(v) for v in args.sweep_bucket_mb.split(",")]:
        kv.set_bucket_size_mb(mb)
        kv.push_all(keys, grads, priorities=prios)  # warmup + compile
        jax.block_until_ready([kv._data[k]._data for k in keys])
        kv.barrier()
        c0 = calls.total()
        t0 = time.perf_counter()
        for _ in range(args.iters):
            kv.push_all(keys, grads, priorities=prios)
        jax.block_until_ready([kv._data[k]._data for k in keys])
        dt = (time.perf_counter() - t0) / args.iters
        n_collectives = (calls.total() - c0) // args.iters
        # ring-allreduce convention: 2*(n-1)/n of the payload per device
        eff_bw = total_bytes * 2 * (nw - 1) / nw / dt
        kv.pull_all(keys, pulled, priorities=prios)
        updater.update_all(idxs, pulled, weights)  # warmup + compile
        jax.block_until_ready([w._data for w in weights])
        u0 = time.perf_counter()
        for _ in range(args.iters):
            updater.update_all(idxs, pulled, weights)
        jax.block_until_ready([w._data for w in weights])
        ut = (time.perf_counter() - u0) / args.iters
        # fused one-program step (parallel/fused_step.py): the SAME
        # exchange+update work as the two staged phases above, in ONE
        # donated program — the per-row delta is the whole point of
        # docs/performance.md "Fused train step & ZeRO-1". Bucket size
        # doesn't change its layout (one flat per lane), so the column
        # is constant across rows: the staged columns converge toward
        # it as buckets grow.
        fupdater = mxopt.get_updater(
            mxopt.create("sgd", learning_rate=0.01, momentum=0.9))
        from mxnet_tpu.parallel import fused_step as _fstep
        ran = _fstep.step(fupdater, idxs, grads, weights,
                          kvstore=kv)          # warmup + compile
        if not ran:       # not inside assert: python -O must still warm
            raise RuntimeError("fused step refused the sweep set")
        jax.block_until_ready([w._data for w in weights])
        f0 = time.perf_counter()
        for _ in range(args.iters):
            _fstep.step(fupdater, idxs, grads, weights, kvstore=kv)
        jax.block_until_ready([w._data for w in weights])
        ft = (time.perf_counter() - f0) / args.iters
        if rank == 0:
            label = "per-key" if mb <= 0 else "%g MB" % mb
            print("bucket %-8s  collectives/step %3d  exchange %8.2f ms  "
                  "effective %6.3f GB/s  update %7.2f ms  "
                  "fused-step %7.2f ms"
                  % (label, n_collectives, dt * 1e3, eff_bw / 1e9,
                     ut * 1e3, ft * 1e3))
        kv.barrier()
    return 0


# ---------------------------------------------------------------------------
# single-process psum mode (original)
# ---------------------------------------------------------------------------
def _run_psum(args):
    if args.cpu:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh, shard_on
    from mxnet_tpu.parallel.mesh import shard_map_compat

    n = args.devices or len(jax.devices())
    mesh = make_mesh({"dp": n}, jax.devices()[:n])
    count = max(1, int(args.size_mb * 1e6 / 4))
    x = jnp.ones((n, count), jnp.float32)

    def local_fn(xl):
        return jax.lax.psum(xl, "dp")

    fn = jax.jit(shard_map_compat(local_fn, mesh, (P("dp"),), P("dp")))
    xs = jax.device_put(x, shard_on(mesh, "dp", 0))
    r = fn(xs)
    float(np.asarray(jax.device_get(r[0, :1])))  # compile + fence
    t0 = time.perf_counter()
    for _ in range(args.iters):
        r = fn(r)
    float(np.asarray(jax.device_get(r[0, :1])))
    dt = (time.perf_counter() - t0) / args.iters
    # ring-allreduce moves 2*(n-1)/n of the payload per device
    payload = count * 4
    algo_bw = payload * 2 * (n - 1) / n / dt
    print("devices %d  payload/device %.1f MB  allreduce %.2f ms  "
          "algo b/w %.2f GB/s/device"
          % (n, payload / 1e6, dt * 1e3, algo_bw / 1e9))
    return algo_bw


def main(argv=None):
    args = _parse_args(argv)
    if args.sweep_bucket_mb is not None:
        if "MXTPU_BW_RANK" in os.environ:
            return _run_sweep_worker(args)
        if args.nproc < 2:
            sys.stderr.write("--sweep-bucket-mb needs --nproc >= 2\n")
            return 2
        return _launch_sweep(args)
    _run_psum(args)
    return 0


if __name__ == "__main__":
    sys.exit(main() or 0)
