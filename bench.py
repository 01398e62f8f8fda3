"""Headline benchmark: ResNet-50 v1 training throughput (img/s).

Baseline (BASELINE.md, docs/faq/perf.md:214-217 of the reference):
MXNet 1.2 ResNet-50 fp32 training on one V100, batch 128 = 363.69 img/s.
Secondary (docs/faq/perf.md:155,171): ResNet-50 *scoring*, V100 fp16,
batch 32 = 2085.51 img/s — measured here as `extra.score_*`.

TPU-native configuration (see PERF.md for the trace-driven derivation):
  - layout NHWC: channels ride the 128-lane minor dim; no layout
    transposes around convs (vs ~11% slower NCHW, measured)
  - mixed precision via ShardedTrainer(compute_dtype="bfloat16"):
    weights/activations bf16 on the MXU, fp32 master params, fp32 BN
    statistics, fp32 softmax inner (measured 1.9x vs fp32)
  - one fused XLA program per step (fwd+bwd+SGD update) built by
    parallel.ShardedTrainer; synthetic data staged on-device, like the
    reference's `--benchmark 1` mode (image-classification/common/fit.py)

Prints a best-so-far JSON line after every ladder rung; the LAST
{-prefixed stdout line is the result:
{"metric", "value", "unit", "vs_baseline", "extra"} — with
extra.ladder recording each rung's img/s or failure status.
"""
import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 363.69
SCORE_BASELINE_FP16 = 2085.51
INCEPTION_BASELINE = 253.68   # docs/faq/perf.md:216, V100 b128
ALEXNET_BASELINE = 2994.32    # docs/faq/perf.md:212, V100 b256
# env overrides exist for CI smoke only; the driver runs the defaults
BATCH = int(os.environ.get("MXTPU_BENCH_BATCH", 128))
SCORE_BATCH = int(os.environ.get("MXTPU_BENCH_SCORE_BATCH", 32))
IMG = int(os.environ.get("MXTPU_BENCH_IMG", 224))
STEPS = int(os.environ.get("MXTPU_BENCH_STEPS", 50))
UNROLL = int(os.environ.get("MXTPU_BENCH_UNROLL", 10))


def _flag(name, default="1"):
    return os.environ.get(name, default) not in ("0", "false")


# device-lease bookkeeping for the BENCH record (ISSUE 7): a failed
# round must be diagnosable from the record alone — how many probes it
# took, whether a stale lease was taken over, and who held it
_LEASE = None
_PROBE_INFO = {"probes": 0, "takeovers": 0, "lease_holder": None}


def _acquire_device_lease():
    """The probe path owns device acquisition now: a cooperative
    on-disk lease (resilience/lease.py) with hard-timeout takeover
    replaces the old skip-and-pray kill_stale ladder. A wedged previous
    holder (stale heartbeat) is reclaimed — SIGTERM→SIGKILL with grace,
    no --force — while a LIVE holder with a fresh heartbeat becomes a
    clean diagnosable exit instead of 35 minutes of doomed retries."""
    global _LEASE
    from mxnet_tpu.resilience.lease import DeviceLease, LeaseHeld
    if os.environ.get("MXTPU_LEASE", "") in ("0", "false"):
        return None      # explicit opt-out; bench otherwise ALWAYS
        # leases — even a cpu-pinned run wants measurement exclusivity
    if _LEASE is not None and _LEASE.held():
        return _LEASE
    lease = DeviceLease(what="bench")
    try:
        lease.acquire()      # MXTPU_LEASE_ACQUIRE_S bounds the wait
    except LeaseHeld as err:
        _PROBE_INFO["lease_holder"] = err.holder
        raise SystemExit("bench: %s" % err)
    _LEASE = lease
    import atexit
    atexit.register(lease.release)
    _PROBE_INFO["takeovers"] = lease.takeovers
    if lease.taken_over_from:
        # the party that mattered: who was wedged on the device before
        # this run reclaimed it (trim to the diagnosable fields)
        _PROBE_INFO["lease_holder"] = {
            k: lease.taken_over_from.get(k)
            for k in ("pid", "host", "what", "cmdline", "heartbeat")}
    else:
        _PROBE_INFO["lease_holder"] = lease.state().get("holder")
    return lease


def _apply_platform_override():
    """MXTPU_BENCH_PLATFORM=cpu pins the backend via jax.config (the
    stated choice of the CI smoke runs; without it a host that has no
    accelerator is an error, not a smaller measurement)."""
    plat = os.environ.get("MXTPU_BENCH_PLATFORM")
    if plat:
        import jax
        jax.config.update("jax_platforms", plat)


def _probe_devices(timeout_s=180, parent_init=True, retries=1):
    """Probe the device backend once (the recorded metric must be a
    real measurement or a clean error, never a hang).

    The probe runs in a FRESH interpreter: a PJRT init that timed out
    leaves this process's jax wedged on the init lock, so an in-process
    attempt could never be retried. The probe first ACQUIRES the host
    device lease (stale holders are taken over — resilience/lease.py;
    a live fresh holder is a clean diagnosable exit). With a local chip
    one probe decides; MXTPU_BENCH_PROBE_RETRIES asks for more.
    """
    import subprocess
    _acquire_device_lease()
    retries = int(os.environ.get("MXTPU_BENCH_PROBE_RETRIES", retries))
    plat = os.environ.get("MXTPU_BENCH_PLATFORM")
    pin = ("import jax; jax.config.update('jax_platforms', %r); " % plat
           if plat else "")
    # the child probes through the health watchdog: a trip reports the
    # typed DeviceUnreachable WITH the lease-holder + /proc diagnostics
    # on stderr, so the failure record names the culprit
    code = (pin + "import sys\n"
            "from mxnet_tpu.resilience.watchdog import (HealthWatchdog, "
            "DeviceUnreachable)\n"
            "try:\n"
            "    d = HealthWatchdog(init_timeout_s=%d).init_devices()\n"
            "except DeviceUnreachable as e:\n"
            "    sys.stderr.write(str(e))\n"
            "    sys.exit(1)\n"
            "sys.stdout.write(d[0].platform)\n" % timeout_s)
    err = "?"
    here = os.path.dirname(os.path.abspath(__file__))
    for attempt in range(max(retries, 1)):
        _PROBE_INFO["probes"] += 1
        try:
            # belt over the in-child deadline: if the child itself wedges
            # (e.g. PJRT init stuck in a C call holding the GIL so even
            # interpreter shutdown hangs), reap it here
            r = subprocess.run([sys.executable, "-c", code], cwd=here,
                               capture_output=True, text=True,
                               timeout=timeout_s + 60)
        except subprocess.TimeoutExpired:
            err = "probe child wedged past %ds" % (timeout_s + 60)
        else:
            if r.returncode == 0:
                # the child reports its backend platform on stdout so
                # the caller can notice a TPU-less (cpu-only) host
                plat = (r.stdout or "").strip() or "unknown"
                if not parent_init:
                    # ladder mode: measurement runs in child processes,
                    # and a parent that inits PJRT would HOLD the device
                    # lease for the whole ladder, blocking every rung
                    # child's init (kill_stale.py's holder model)
                    return plat
                # do the PARENT's backend init under the same deadline:
                # this process hasn't attempted init yet, so the probe
                # both guards and performs it (a wedge in the window
                # after the child's clean exit would otherwise hang the
                # unguarded jax.devices() below)
                from mxnet_tpu.base import probe_devices
                devs, perr = probe_devices(timeout_s)
                if devs is not None:
                    return plat
                raise SystemExit(
                    "bench: probe child ok but parent init failed (%s)"
                    % perr)
            err = ((r.stderr or "").strip().splitlines() or ["?"])[-1]
        sys.stderr.write("bench: probe %d failed (%s)\n"
                         % (attempt + 1, err))
    raise SystemExit("bench: device backend unreachable after %d probes "
                     "(%s)" % (max(retries, 1), err))


def _materialize(net, img, nhwc=True):
    """Finish deferred param init WITHOUT an eager forward (which would
    trigger ~180 separate accelerator compiles over the device link):
    symbolic shape inference + deferred-init finish. The initializer
    ops run on the host CPU backend when the process has one (it is
    absent under JAX_PLATFORMS=tpu)."""
    import contextlib
    import jax
    import mxnet_tpu as mx
    try:
        mat_ctx = jax.default_device(jax.local_devices(backend="cpu")[0])
    except RuntimeError:    # "Unknown backend cpu": a tpu-only pin
        mat_ctx = contextlib.nullcontext()
    with mat_ctx:
        net.initialize()
        shp = (1, img, img, 3) if nhwc else (1, 3, img, img)
        net.infer_shape(mx.nd.zeros(shp))
        for p in net.collect_params().values():
            p._finish_deferred_init()


def _train_tput(ctor, batch, img, steps, unroll, lr=0.1,
                flops_per_img=None, **trainer_kw):
    """Train throughput of one model: ALL timed steps run inside ONE
    jitted lax.scan (step_many) — one dispatch per window, fenced by
    fetching the losses to host."""
    import jax
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import make_mesh, ShardedTrainer

    mesh = make_mesh({"dp": len(jax.devices())})
    net = ctor()
    _materialize(net, img)
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    st = ShardedTrainer(net, lambda o, l: loss(o, l), "sgd",
                        {"learning_rate": lr, "momentum": 0.9},
                        mesh=mesh, compute_dtype="bfloat16",
                        **trainer_kw)
    rng = np.random.RandomState(0)
    # stage the synthetic batch on-device ONCE (the input pipeline's
    # job; re-uploading per step would measure the host link, not the
    # TPU — the reference's --benchmark 1 mode does the same)
    sh = st._batch_sharding()
    x = jax.device_put(rng.randn(batch, img, img, 3).astype("float32"),
                       sh)
    y = jax.device_put((rng.rand(batch) * 1000).astype("float32"), sh)

    def run_window(n):
        losses = st.step_many(x, y, n_steps=n, unroll=min(unroll, n))
        out = np.asarray(jax.device_get(losses._data))
        assert np.isfinite(out).all(), "non-finite loss in bench window"
        return out

    # numerics accounting (ISSUE 10): the in-graph guard records one ok
    # flag per step; a silently-skipping run must be visible in the
    # BENCH record, not post a fake throughput number
    from mxnet_tpu.resilience import numerics as _numerics

    run_window(steps)  # compile + warm (same shape/unroll as timed run)
    _numerics.drain_flags()
    t0 = time.perf_counter()
    run_window(steps)
    dt = time.perf_counter() - t0
    guard = _numerics.drain_flags()     # timed window's verdicts
    st.bench_skipped_steps = guard["skipped_steps"]
    st.bench_anomalies = guard["anomalies"]
    if flops_per_img:
        # charge the timed window's analytic model FLOPs (fwd+bwd) to
        # the goodput counter and derive the headline MFU — step_many's
        # scanned window never dispatches per-step costed programs, so
        # the fused step only self-charges its optimizer phase
        from mxnet_tpu.observability import goodput as _goodput
        flops = float(flops_per_img) * batch * steps
        if _goodput.enabled():
            _goodput.note_flops(flops, n_dispatches=steps)
        # None when the device's kind has no published peak: the
        # record then says null ("not measured"), never a guess
        st.bench_mfu = _goodput.mfu_value(flops, dt, source="bench")
    return batch * steps / dt, st


def _score_tput(score_fn, tree, xs, batch, n_score=30):
    """Inference throughput: n_score forwards in ONE jitted fori_loop;
    each iteration perturbs the input by a function of the previous
    logits so XLA cannot collapse the loop. The weights ride as jit
    ARGUMENTS (a pytree), not closure constants — closure capture would
    embed ~25M params into the jaxpr and pin their current (possibly
    host) placement into the compiled module."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def window(tree, xb):
        def body(i, carry):
            xb, acc = carry
            out = score_fn(tree, xb)
            return (xb + out.mean().astype(xb.dtype) * 1e-12,
                    acc + out.astype(jnp.float32).mean())
        _, acc = jax.lax.fori_loop(0, n_score, body,
                                   (xb, jnp.float32(0)))
        return acc

    np.asarray(jax.device_get(window(tree, xs)))  # compile
    t0 = time.perf_counter()
    np.asarray(jax.device_get(window(tree, xs)))
    return batch * n_score / (time.perf_counter() - t0)


def _extra_metrics(rng, t_start):
    """Secondary BASELINE.md rows (docs/faq/perf.md:155,212-216):
    inception-v3 train b128, alexnet train b256, int8 resnet50
    scoring. Each is fenced in try/except so one failure can't cost
    the others, and a soft deadline keeps extras from eating a driver
    timeout that would lose the already-computed headline."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    extras = {}
    steps = int(os.environ.get("MXTPU_BENCH_EXTRA_STEPS", 20))
    budget = float(os.environ.get("MXTPU_BENCH_BUDGET_S", 1200))

    def over_budget(name):
        if time.perf_counter() - t_start > budget:
            extras[name + "_skipped"] = "time budget (%ds) spent" % budget
            return True
        return False
    # size overrides exist for CI smoke only; the driver runs defaults
    inc_batch = int(os.environ.get("MXTPU_BENCH_INCEPTION_BATCH", BATCH))
    alex_batch = int(os.environ.get("MXTPU_BENCH_ALEX_BATCH", 256))

    def inception():
        # Inception-v3 train, b128 @299^2 (V100 baseline 253.68; the
        # 299^2 input is structural: the v3 tail pools an 8x8 map)
        r, _ = _train_tput(
            lambda: vision.inception_v3(classes=1000, layout="NHWC"),
            inc_batch, 299, steps, 5)
        extras["inception_v3_train_b%d_img_s" % inc_batch] = round(r, 2)
        extras["inception_v3_vs_v100"] = round(r / INCEPTION_BASELINE,
                                               3)

    def alexnet():
        # AlexNet train, b256 (V100 baseline 2994.32 at batch 16x16);
        # small lr: no BN anywhere, lr=0.1 diverges within the window
        r, _ = _train_tput(
            lambda: vision.alexnet(classes=1000, layout="NHWC"),
            alex_batch, 224, steps, 5, lr=1e-3)
        extras["alexnet_train_b%d_img_s" % alex_batch] = round(r, 2)
        extras["alexnet_vs_v100"] = round(r / ALEXNET_BASELINE, 3)

    def int8_score():
        # int8-quantized resnet50 scoring, b32 (the int8 subsystem's
        # one unmeasured perf story; fp16 V100 score row = 2085.51)
        net = vision.resnet50_v1(classes=1000)  # NCHW: quantizer's form
        _materialize(net, IMG, nhwc=False)
        out = net(mx.sym.var("data"))
        aux_names = set(out.list_auxiliary_states())
        args = {p.name: p.data() for p in net.collect_params().values()
                if p.name not in aux_names}
        auxs = {p.name: p.data() for p in net.collect_params().values()
                if p.name in aux_names}
        calib = rng.randn(SCORE_BATCH, 3, IMG, IMG).astype("float32")

        from mxnet_tpu.io import NDArrayIter
        from mxnet_tpu.contrib.quantization import quantize_model
        qsym, qargs, qauxs = quantize_model(
            out, args, auxs,
            calib_data=NDArrayIter(calib, batch_size=SCORE_BATCH),
            calib_mode="naive", quantize_mode="full", label_names=None)
        from mxnet_tpu.graph import build_graph_fn
        qfn, _, _, _ = build_graph_fn(qsym._entries, "predict")
        # weights were materialized on the host backend: re-stage them
        # on the accelerator so the jit doesn't mix device commitments
        dev = jax.devices()[0]
        qa = {k: jax.device_put(v._data, dev) for k, v in qargs.items()}
        qx = {k: jax.device_put(v._data, dev) for k, v in qauxs.items()}

        def score_fn(tree, xb):
            a, x_ = tree
            outs, _ = qfn({**a, "data": xb}, x_)
            return outs[0]

        xs = jax.device_put(calib, dev)
        r = _score_tput(score_fn, (qa, qx), xs, SCORE_BATCH)
        extras["int8_resnet50_score_b%d_img_s" % SCORE_BATCH] = round(r, 2)
        extras["int8_score_vs_v100_fp16"] = round(
            r / SCORE_BASELINE_FP16, 3)

    for name, fn in (("inception_v3", inception), ("alexnet", alexnet),
                     ("int8_score", int8_score)):
        if over_budget(name):
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001 -- recorded, not fatal
            extras[name + "_error"] = str(e)[:200]
    return extras


def _rungs():
    """Escalation ladder for the headline measurement: a single
    in-process measurement of the full-size program (50-step scan,
    unroll=10) that hangs records nothing at all. Rungs run
    smallest-first in separate deadline-fenced child processes: the
    first secures *a* chip number cheaply, later ones upgrade it. CI
    size overrides apply inside each rung (min with the rung's cap).
    """
    deadlines = [float(x) for x in os.environ.get(
        "MXTPU_BENCH_DEADLINES", "900,900,1500,2400").split(",")
        if x.strip()]
    if len(deadlines) == 3:
        # pre-round-5 spelling (secure,mid,full): keep its semantics —
        # the score rung borrows secure's fence rather than silently
        # shifting mid/full to looser bounds
        deadlines = [deadlines[0]] + deadlines
    specs = [
        # (name, steps, unroll, score?, extras?) — round-5 chip lesson:
        # the rung that bundled the train upgrade WITH the score compile
        # wedged and took the lease with it, so train-upgrade and score
        # are now separate rungs (score reuses the secure-size train
        # program, which the persistent compile cache makes nearly free)
        ("secure", min(8, STEPS), 1, False, False),
        ("score", min(8, STEPS), 1, True, False),
        ("mid", STEPS, min(2, UNROLL), False, False),
        ("full", STEPS, UNROLL, True, True),
    ]
    while len(deadlines) < len(specs):  # a short list bounds the rest
        deadlines.append(deadlines[-1] if deadlines else 900.0)
    rungs = [s + (d,) for s, d in zip(specs, deadlines)]
    if not _flag("MXTPU_BENCH_SCORE"):
        # with scoring masked off, the score rung would be an exact
        # duplicate of secure — don't spend a chip-window child on it
        # (deadlines are zipped first so the others keep their slots)
        rungs = [r for r in rungs if r[0] != "score"]
    return rungs


def fence_child(p, graces=None):
    """Reap a deadline-struck child with SIGINT -> SIGTERM -> SIGKILL
    escalation: the clean KeyboardInterrupt unwind closes the PJRT
    client and releases the device lease, where a blunt kill leaves it
    behind. Used by the bench rungs.
    Returns (stdout_so_far, signal_name|'unreaped') — output the child
    printed before wedging is real and must be kept. stdout is always
    str: TimeoutExpired.stdout is bytes even under text=True, so it is
    decoded here — both callers can strip/concatenate without a
    TypeError in exactly the wedge scenario they exist to survive."""
    import signal
    import subprocess

    def _text(b):
        return b.decode("utf-8", "replace") if isinstance(b, bytes) else b

    graces = graces or ((signal.SIGINT, 120), (signal.SIGTERM, 30),
                        (signal.SIGKILL, 30))
    out = None
    for sig, grace in graces:
        p.send_signal(sig)
        try:
            got, _ = p.communicate(timeout=grace)
            return (_text(got) if got is not None else out,
                    signal.Signals(sig).name)
        except subprocess.TimeoutExpired as e:
            if e.stdout is not None:
                out = _text(e.stdout)
            continue
    return out, "unreaped"


def _run_rung(name, steps, unr, score, extras, deadline):
    """One ladder rung in a fresh interpreter. Returns (result|None,
    status). On deadline the child is reaped via fence_child (SIGINT
    first; escalating only if it is stuck in a C call)."""
    import subprocess
    import sys
    env = dict(os.environ)
    # a caller's explicit SCORE=0/EXTRAS=0 wins over the rung spec
    score &= _flag("MXTPU_BENCH_SCORE")
    extras &= _flag("MXTPU_BENCH_EXTRAS")
    env.update(MXTPU_BENCH_CHILD="1", MXTPU_BENCH_STEPS=str(steps),
               MXTPU_BENCH_UNROLL=str(unr),
               MXTPU_BENCH_SCORE="1" if score else "0",
               MXTPU_BENCH_EXTRAS="1" if extras else "0")
    here = os.path.dirname(os.path.abspath(__file__))
    p = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                         cwd=here, env=env, stdout=subprocess.PIPE,
                         stderr=sys.stderr, text=True)
    out, timed_out = "", False
    try:
        out, _ = p.communicate(timeout=deadline)
    except subprocess.TimeoutExpired as e:
        timed_out = True
        fenced, _sig = fence_child(p)
        if fenced is not None:
            out = fenced
        elif isinstance(e.stdout, bytes):
            out = e.stdout.decode("utf-8", "replace")
        else:
            out = e.stdout or ""

    def parse():
        text = out or ""  # always str: fence_child decodes
        lines = [l for l in text.splitlines()
                 if l.startswith("{")]
        if not lines:
            return None
        try:
            return json.loads(lines[-1])
        except ValueError:
            return None

    if timed_out:
        # the child may have finished the measurement and printed its
        # line BEFORE wedging in teardown — that result is real; keep
        # it (the caller still stops escalating: the lease is suspect)
        return parse(), "timeout after %ds" % deadline
    r = parse()
    if p.returncode != 0 or r is None:
        return None, "rc=%s" % p.returncode
    return r, "ok"


def _enable_compile_cache():
    """Persistent XLA compile cache shared by every child interpreter
    (and by later bench runs on this host): reusing executables across
    rungs and across runs keeps the big compiles out of the rung
    deadlines. The directory is the framework's own
    (mxnet_tpu.compile.cache.resolve_cache_dir — importing the package
    starts no backend); it is exported so the children agree with the
    parent by construction. MXTPU_XLA_CACHE=0 disables."""
    from mxnet_tpu.compile.cache import resolve_cache_dir
    d = resolve_cache_dir()
    if d:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", d)


def main():
    _enable_compile_cache()
    if os.environ.get("MXTPU_BENCH_CHILD"):
        return _measure_main()
    _apply_platform_override()
    ladder_mode = _flag("MXTPU_BENCH_LADDER")
    plat = _probe_devices(parent_init=not ladder_mode)
    if plat == "cpu" and os.environ.get("MXTPU_BENCH_PLATFORM") != "cpu":
        # no fallback that hides the device: a CPU number is recorded
        # only where the caller asked for the CPU by name
        raise SystemExit("bench: the backend is the cpu and no "
                         "accelerator was found; set "
                         "MXTPU_BENCH_PLATFORM=cpu to measure the CPU "
                         "on purpose")
    if not ladder_mode:
        return _measure_main()
    best, extra, ladder = None, {}, {}

    def emit():
        rec = dict(best)
        # probe/lease outcome ride every emitted record: a failed or
        # degraded round is diagnosable from the BENCH json alone
        rec["extra"] = dict(extra, ladder=dict(ladder), **_PROBE_INFO)
        print(json.dumps(rec), flush=True)

    for name, steps, unr, score, extras, deadline in _rungs():
        r, status = _run_rung(name, steps, unr, score, extras, deadline)
        ladder[name] = (r["value"] if status == "ok"
                        else status if r is None
                        else "%s (%s)" % (r["value"], status))
        if r is not None:
            extra.update(r.get("extra") or {})
            # a later rung ran the higher-fidelity configuration:
            # its number replaces the quick secure estimate even when
            # lower (the headline must describe the documented config)
            best = r
            # best-so-far line NOW: if the driver's own timeout fires
            # mid-ladder, the last complete line printed still stands
            emit()
        if "timeout" in status:
            # a wedged (even if reaped) holder means the lease is
            # suspect; bigger programs won't fare better — stop
            break
    if best is None:
        raise SystemExit("bench: all ladder rungs failed: %s" % ladder)
    # final line carries the COMPLETE ladder record, including any
    # failure entry from a rung that came after the last success
    emit()


def _numerics_overhead_pct(steps=150, warmup=30):
    """Happy-path cost of the training numerics guard on the fused
    update path (the ISSUE-10 acceptance number): time a small gluon
    Trainer step loop with MXTPU_NUMERICS on vs off and report the
    overhead percentage. Small on purpose — a dispatch-bound loop is
    the WORST case for the guard (one extra fused reduce + select per
    group, plus the host-side flag drain), so the recorded number
    upper-bounds the big-model cost. MXTPU_BENCH_NUMERICS_PROBE=0
    skips it."""
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.resilience import numerics as _numerics

    rng = np.random.RandomState(0)
    shapes = [(64, 64)] * 6 + [(64,)] * 6

    def loop(env_on):
        os.environ["MXTPU_NUMERICS"] = "1" if env_on else "0"
        try:
            ws = [mx.nd.array(rng.randn(*s).astype("float32"))
                  for s in shapes]
            gs = [mx.nd.array(rng.randn(*s).astype("float32"))
                  for s in shapes]
            upd = opt.get_updater(opt.create("sgd", learning_rate=1e-6,
                                             momentum=0.9))
            idx = list(range(len(ws)))
            for _ in range(warmup):
                upd.update_all(idx, gs, ws)
            _numerics.drain_flags()
            import jax
            jax.block_until_ready([w._data for w in ws])
            t0 = time.perf_counter()
            for _ in range(steps):
                upd.update_all(idx, gs, ws)
                _numerics.drain_flags()    # the guard's host-side cost
            jax.block_until_ready([w._data for w in ws])
            return time.perf_counter() - t0
        finally:
            os.environ.pop("MXTPU_NUMERICS", None)
    prev = os.environ.get("MXTPU_NUMERICS")
    try:
        # interleaved min-of-5: single reps on a busy CI core are
        # noise-dominated (±5% observed); alternating the modes cancels
        # slow drift and the minimum is the least-perturbed run of each
        t_on, t_off = [], []
        for _ in range(5):
            t_off.append(loop(False))
            t_on.append(loop(True))
        t_off, t_on = min(t_off), min(t_on)
    finally:
        if prev is not None:
            os.environ["MXTPU_NUMERICS"] = prev
    return round(100.0 * (t_on - t_off) / t_off, 2)


def _ledger_mb():
    """HBM-ledger resident MiB at call time (0.0 when the plane is
    off): the BENCH record's model-footprint field."""
    from mxnet_tpu.observability import memory as _memory
    return _memory.total_bytes() / (1024.0 * 1024.0)


def _memledger_overhead_pct(steps=120, warmup=20):
    """Happy-path cost of the HBM-ledger/goodput plane (the ISSUE-17
    acceptance number): time a dispatch-bound fused-step loop with
    MXTPU_MEMLEDGER on vs off and report the overhead percentage. The
    plane's per-dispatch cost is an oom_guard enter/exit, a cost-table
    lookup, and two counter bumps — so a tiny one-dispatch-per-call
    loop upper-bounds the big-model cost exactly like the numerics
    probe above. MXTPU_BENCH_MEMLEDGER_PROBE=0 skips it."""
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.parallel import fused_step as _fstep

    rng = np.random.RandomState(0)
    shapes = [(64, 64)] * 6 + [(64,)] * 6

    def loop(env_on):
        os.environ["MXTPU_MEMLEDGER"] = "1" if env_on else "0"
        try:
            ws = [mx.nd.array(rng.randn(*s).astype("float32"))
                  for s in shapes]
            gs = [mx.nd.array(rng.randn(*s).astype("float32"))
                  for s in shapes]
            upd = opt.get_updater(opt.create("sgd", learning_rate=1e-6,
                                             momentum=0.9))
            idx = list(range(len(ws)))
            for _ in range(warmup):
                if not _fstep.step(upd, idx, gs, ws):
                    raise RuntimeError("fused step refused — the "
                                       "memledger probe measures its "
                                       "dispatch wrapper")
            import jax
            jax.block_until_ready([w._data for w in ws])
            t0 = time.perf_counter()
            for _ in range(steps):
                _fstep.step(upd, idx, gs, ws)
            jax.block_until_ready([w._data for w in ws])
            return time.perf_counter() - t0
        finally:
            os.environ.pop("MXTPU_MEMLEDGER", None)
    prev = os.environ.get("MXTPU_MEMLEDGER")
    try:
        # interleaved min-of-5, same rationale as the numerics probe
        t_on, t_off = [], []
        for _ in range(5):
            t_off.append(loop(False))
            t_on.append(loop(True))
        t_off, t_on = min(t_off), min(t_on)
    finally:
        if prev is not None:
            os.environ["MXTPU_MEMLEDGER"] = prev
    return round(100.0 * (t_on - t_off) / t_off, 2)


def _measure_main():
    t_start = time.perf_counter()
    _apply_platform_override()
    import jax
    jax.config.update("jax_default_matmul_precision", "bfloat16")
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.graph import build_graph_fn

    rng = np.random.RandomState(0)
    unroll = int(os.environ.get("MXTPU_BENCH_UNROLL", 10))
    img_s, st = _train_tput(
        lambda: vision.resnet50_v1(classes=1000, layout="NHWC"),
        BATCH, IMG, STEPS, unroll,
        # resnet50 @224 fwd ~4.089 GFLOP/img, train ~3x fwd (the same
        # accounting tools/mfu_probe.py documents); conv FLOPs scale
        # with spatial area, so shrunk-IMG CI rungs scale the constant
        # instead of posting a fantasy MFU
        flops_per_img=3 * 4.089e9 * (IMG / 224.0) ** 2)
    net = st._net

    extra = {}
    if _flag("MXTPU_BENCH_SCORE"):
        # secondary: inference scoring at the reference's
        # benchmark_score.py config (batch 32), bf16 like the V100
        # fp16 row
        import jax.numpy as jnp
        params = {k: (v.astype(jnp.bfloat16) if v.ndim >= 2 else v)
                  for k, v in st.params.items()}
        aux = dict(st._aux)
        out_sym = net(mx.sym.var("data"))
        score_fn, _, _, _ = build_graph_fn(out_sym._entries, "predict")

        def fp_score(tree, xb):
            p, a = tree
            outs, _ = score_fn({**p, "data": xb.astype(jnp.bfloat16)},
                               a)
            return outs[0]

        xs = jax.device_put(
            rng.randn(SCORE_BATCH, IMG, IMG, 3).astype("float32"))
        score_img_s = _score_tput(fp_score, (params, aux), xs,
                                  SCORE_BATCH)
        extra.update({
            "score_b%d_img_s" % SCORE_BATCH: round(score_img_s, 2),
            "score_vs_v100_fp16": round(
                score_img_s / SCORE_BASELINE_FP16, 3),
        })
    if _flag("MXTPU_BENCH_EXTRAS"):
        extra.update(_extra_metrics(rng, t_start))
    if _flag("MXTPU_BENCH_NUMERICS_PROBE") and STEPS >= 10:
        # CI smoke runs (shrunk MXTPU_BENCH_STEPS) skip the probe: its
        # number is only meaningful — and only recorded — on the
        # driver's default-size runs
        try:
            extra["numerics_overhead_pct"] = _numerics_overhead_pct()
        except Exception as e:  # noqa: BLE001 — recorded, not fatal
            extra["numerics_overhead_error"] = str(e)[:200]
    if _flag("MXTPU_BENCH_MEMLEDGER_PROBE") and STEPS >= 10:
        try:
            extra["memledger_overhead_pct"] = _memledger_overhead_pct()
        except Exception as e:  # noqa: BLE001 — recorded, not fatal
            extra["memledger_overhead_error"] = str(e)[:200]
    if _PROBE_INFO["probes"]:
        # non-ladder parent measured in-process: its record carries the
        # probe/lease outcome directly (rung children never probe —
        # the ladder parent merges _PROBE_INFO at emit instead)
        extra.update(_PROBE_INFO)

    print(json.dumps({
        "metric": "resnet50_v1_train_throughput_b%d" % BATCH,
        "value": round(img_s, 2), "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        # what the number was measured on, as jax reports it: a CPU
        # record must never be mistaken for a chip measurement
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        # numerics-guard verdicts over the TIMED window (ISSUE 10): a
        # throughput number from silently-skipped steps is a fake —
        # tools/perf_gate.py --max-skipped-steps turns these into a CI
        # failure
        "skipped_steps": int(getattr(st, "bench_skipped_steps", 0)),
        "anomalies": int(getattr(st, "bench_anomalies", 0)),
        # fused-step provenance (docs/performance.md "Fused train step
        # & ZeRO-1"): the measured loop is the one-program-per-step
        # ShardedTrainer path; zero1 records whether optimizer state
        # was ZeRO-1-sharded over dp (MXTPU_ZERO1) for this number
        "fused_step": True,
        "zero1": bool(getattr(st, "_shard_opt", False)),
        # goodput/memory plane (docs/observability.md "Goodput & MFU" /
        # "Memory ledger"): model-FLOPs utilization of the timed window
        # against the platform's peak, and the HBM ledger's resident
        # bytes at record time — 0.0 with MXTPU_MEMLEDGER=0. mfu is
        # null ("not measured") where the device's kind has no peak
        "mfu": (None if getattr(st, "bench_mfu", None) is None
                else round(float(st.bench_mfu), 4)),
        "hbm_mb": round(_ledger_mb(), 2),
        "extra": extra}))


if __name__ == "__main__":
    main()
