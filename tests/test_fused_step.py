"""One compiled program per training step + ZeRO-1
(parallel/fused_step.py; docs/performance.md "Fused train step &
ZeRO-1").

The contract under test:

1. bit parity: the fused one-program step behind gluon.Trainer /
   Module update produces byte-identical weights AND optimizer state
   vs the staged bucketed path (exchange then update) — SGD, momentum,
   Adam, fp16-under-fp32-master multi-precision. The staged side is
   reached through the API: `allreduce_grads()` + `update()`;
2. dispatch count: the fused path issues exactly ONE device program
   per step (train.step.dispatches metric + program-cache census),
   the staged path O(buckets)+O(groups);
3. numerics-guard composition: chaos kind=nan at grad.post inside the
   fused step skips in-graph with weights/opt state preserved
   bit-identically, and the verdict reaches the watchdog/telemetry
   exactly once;
4. ZeRO-1 checkpoint round-trip: dp-sharded optimizer state saves
   through TrainerCheckpoint two-phase commit and restores
   bit-identically into sharded AND replicated topologies of a
   different replica count;
5. plan signatures: bucket-layout changes re-fingerprint AOT programs.

Multi-process (gloo, 4 ranks) ZeRO-1 == replicated == staged parity is
asserted in tests/dist_kvstore_worker.py (ZERO1_PARITY_OK markers).
"""
import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu import optimizer as opt
from mxnet_tpu.observability import registry as obs
from mxnet_tpu.parallel import fused_step as fs
from mxnet_tpu.resilience import chaos

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _update(tr, batch_size, staged=False):
    """One update through `step()`, or through the staged halves that
    `allreduce_grads()` + `update()` always run."""
    if staged:
        tr.allreduce_grads()
        tr.update(batch_size)
    else:
        tr.step(batch_size)


def _train_gluon(optname, optkw, steps=4, dtype="float32", seed=0,
                 staged=False):
    """A tiny gluon loop: returns (param arrays, pickled updater
    states) after `steps` iterations of autograd + `_update`."""
    mx.random.seed(seed)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    x0 = mx.nd.array(np.random.RandomState(1).randn(4, 5).astype("f"))
    net(x0)
    if dtype != "float32":
        net.cast(dtype)
        net(mx.nd.array(np.random.RandomState(1).randn(4, 5)
                        .astype(dtype)))
    tr = gluon.Trainer(net.collect_params(), optname, dict(optkw))
    loss_fn = gluon.loss.L2Loss()
    for s in range(steps):
        x = mx.nd.array(np.random.RandomState(10 + s).randn(4, 5)
                        .astype(dtype))
        y = mx.nd.array(np.random.RandomState(20 + s).randn(4, 3)
                        .astype(dtype))
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        _update(tr, 4, staged)
    params = [p.data().asnumpy() for p in net.collect_params().values()]
    states = pickle.loads(tr._updaters[0].get_states())
    return params, states, tr


def _state_bytes(states):
    out = []
    for k in sorted(states):
        st = states[k]
        stack = [st]
        while stack:
            s = stack.pop()
            if s is None:
                continue
            if isinstance(s, (list, tuple)):
                stack.extend(s)
            else:
                out.append(np.asarray(s.asnumpy()).tobytes())
    return out


@pytest.mark.parametrize("name,kw,dtype", [
    ("sgd", dict(learning_rate=0.1), "float32"),
    ("sgd", dict(learning_rate=0.1, momentum=0.9, wd=0.01), "float32"),
    ("adam", dict(learning_rate=0.01, wd=0.001), "float32"),
    ("sgd", dict(learning_rate=0.1, momentum=0.9,
                 multi_precision=True), "float16"),
    ("adam", dict(learning_rate=0.01,
                  multi_precision=True), "float16"),
])
def test_fused_step_bit_parity(name, kw, dtype):
    a_p, a_s, _ = _train_gluon(name, kw, dtype=dtype)
    b_p, b_s, _ = _train_gluon(name, kw, dtype=dtype, staged=True)
    for a, b in zip(a_p, b_p):
        assert a.dtype == b.dtype
        assert a.tobytes() == b.tobytes()
    assert _state_bytes(a_s) == _state_bytes(b_s)


def test_fused_step_one_dispatch_per_step():
    disp = obs.REGISTRY.counter("train.step.dispatches")
    d0 = disp.total()
    _, _, tr = _train_gluon("sgd", dict(learning_rate=0.1,
                                        momentum=0.9), steps=5)
    assert disp.total() - d0 == 5          # exactly ONE program/step
    # jit-cache census: steady-state training holds exactly one
    # compiled step program (the PR-6 two-program-assert analog)
    owner = tr._updaters[0]._fused_step_owner
    assert owner is not None and owner.program_count() == 1
    # staged path: O(groups) per step (two lanes here: weight wd_mult
    # lane + bias lane collapse into one fp32 bucket per cohort)
    d0 = disp.total()
    _train_gluon("sgd", dict(learning_rate=0.1, momentum=0.9), steps=5,
                 staged=True)
    staged = disp.total() - d0
    assert staged >= 5                     # at least one per step


def test_fused_step_telemetry_record_and_phase(tmp_path, monkeypatch):
    tel = tmp_path / "t.jsonl"
    monkeypatch.setenv("MXTPU_TELEMETRY", str(tel))
    _train_gluon("sgd", dict(learning_rate=0.1), steps=3)
    from mxnet_tpu.observability.telemetry import close_stream
    close_stream()
    recs = [json.loads(line) for line in tel.read_text().splitlines()]
    steps = [r for r in recs if r.get("source") == "gluon.trainer"]
    assert steps
    # one "step.launch" phase, no host allreduce/optimizer phases, and
    # the dispatch budget field reads 1 (acceptance: the host-side
    # Python between phases is gone from the trace); the phase's time
    # is a part of the iteration's, not written over it
    for r in steps[1:]:
        assert r.get("step_dispatches") == 1
        assert 0 < r["step.launch_time"] < r["step_time"]
        assert "allreduce_time" not in r and "optimizer_time" not in r


def test_perf_gate_dispatch_budget(tmp_path, monkeypatch):
    tel = tmp_path / "t.jsonl"
    monkeypatch.setenv("MXTPU_TELEMETRY", str(tel))
    _train_gluon("adam", dict(learning_rate=0.01), steps=3)
    from mxnet_tpu.observability.telemetry import close_stream
    close_stream()
    gate = os.path.join(ROOT, "tools", "perf_gate.py")
    r = subprocess.run([sys.executable, gate, str(tel),
                        "--max-dispatches-per-step", "1"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    # tighter than 1 program/step is unachievable: breach
    r = subprocess.run([sys.executable, gate, str(tel),
                        "--max-dispatches-per-step", "0.5"],
                       capture_output=True, text=True)
    assert r.returncode == 1
    assert "dispatches_per_step" in r.stdout
    # a stream without the metric must breach, not pass silently
    legacy = tmp_path / "legacy.jsonl"
    legacy.write_text(json.dumps(
        {"ts": 0, "source": "train", "step": 0, "step_time": 0.1}) +
        "\n")
    r = subprocess.run([sys.executable, gate, str(legacy),
                        "--max-dispatches-per-step", "1"],
                       capture_output=True, text=True)
    assert r.returncode == 1


def test_guard_composition_chaos_nan():
    """kind=nan at grad.post INSIDE the fused step: the lax.cond skip
    preserves weights + opt state bit-identically and the verdict
    reaches the watchdog/telemetry exactly once."""
    mx.random.seed(0)
    net = gluon.nn.Dense(3)
    net.initialize()
    x = mx.nd.array(np.random.RandomState(1).randn(4, 5).astype("f"))
    y = mx.nd.array(np.random.RandomState(2).randn(4, 3).astype("f"))
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    loss_fn = gluon.loss.L2Loss()

    def one_step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        tr.step(4)

    one_step()                      # clean step: program + state exist
    pre_w = [p.data().asnumpy().copy()
             for p in net.collect_params().values()]
    pre_s = tr._updaters[0].get_states()
    anom0 = obs.REGISTRY.get("numerics.anomalies").total()
    skip0 = obs.REGISTRY.get("numerics.skipped_steps").total()
    bad0 = tr.numerics.watchdog.bad_streak
    chaos.configure("grad.post:kind=nan,n=1", seed=7)
    try:
        one_step()
    finally:
        chaos.reset()
    for a, b in zip(pre_w, [p.data().asnumpy()
                            for p in net.collect_params().values()]):
        assert a.tobytes() == b.tobytes()
    assert pre_s == tr._updaters[0].get_states()
    rep = tr.numerics.last_report
    assert rep["skipped_steps"] == 1 and rep["anomalies"] == 1
    # exactly once: metric deltas of 1, watchdog streak advanced by 1
    assert obs.REGISTRY.get("numerics.anomalies").total() - anom0 == 1
    assert (obs.REGISTRY.get("numerics.skipped_steps").total()
            - skip0 == 1)
    assert tr.numerics.watchdog.bad_streak == bad0 + 1
    one_step()                      # clean step: streak resets
    assert tr.numerics.last_report["anomalies"] == 0
    assert tr.numerics.watchdog.bad_streak == 0


def test_step_and_staged_halves_alternate_mid_run():
    """`step()` and `allreduce_grads()` + `update()` alternating mid-run
    keep training exact: the fused and staged paths share updater
    state."""
    mx.random.seed(3)
    net = gluon.nn.Dense(4)
    net.initialize()
    x = mx.nd.array(np.random.RandomState(5).randn(4, 6).astype("f"))
    y = mx.nd.array(np.random.RandomState(6).randn(4, 4).astype("f"))
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    loss_fn = gluon.loss.L2Loss()

    def steps(n, staged):
        for _ in range(n):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            _update(tr, 4, staged)

    path = obs.REGISTRY.get("train.step.fused_path")
    fused0 = path.total()
    steps(2, staged=False)
    steps(2, staged=True)
    steps(2, staged=False)
    assert path.total() - fused0 == 4
    mixed = [p.data().asnumpy() for p in net.collect_params().values()]
    b_p, _, _ = _train_gluon_fixed_dense(net_seed=3, steps=6)
    for a, b in zip(mixed, b_p):
        assert a.tobytes() == b.tobytes()


def _train_gluon_fixed_dense(net_seed, steps):
    mx.random.seed(net_seed)
    net = gluon.nn.Dense(4)
    net.initialize()
    x = mx.nd.array(np.random.RandomState(5).randn(4, 6).astype("f"))
    y = mx.nd.array(np.random.RandomState(6).randn(4, 4).astype("f"))
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    loss_fn = gluon.loss.L2Loss()
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        _update(tr, 4, staged=True)
    return ([p.data().asnumpy() for p in net.collect_params().values()],
            None, tr)


def test_module_fit_fused_parity(monkeypatch):
    path = obs.REGISTRY.get("train.step.fused_path")

    def fit(fused):
        # the reference: `Module.update` with the per-key
        # `optimizer.Updater`, which `fused_step.step` leaves to the
        # staged push/pull + update path
        if not fused:
            monkeypatch.setattr(opt, "get_updater", opt.Updater)
        mx.random.seed(0)
        np.random.seed(0)
        data = mx.sym.var("data")
        s = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
        s = mx.sym.Activation(s, act_type="relu")
        s = mx.sym.FullyConnected(s, num_hidden=4, name="fc2")
        s = mx.sym.SoftmaxOutput(s, name="softmax")
        X = np.random.RandomState(3).randn(16, 10).astype("f")
        Y = np.random.RandomState(4).randint(0, 4, (16,)).astype("f")
        it = mx.io.NDArrayIter(X, Y, batch_size=8,
                               label_name="softmax_label")
        mod = mx.mod.Module(s, data_names=("data",),
                            label_names=("softmax_label",))
        mod.fit(it, num_epoch=2, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1,
                                  "momentum": 0.9})
        args, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in args.items()}

    n0 = path.total()
    a = fit(True)
    n1 = path.total()
    b = fit(False)
    assert n1 - n0 == 4 and path.total() == n1     # 2 epochs x 2 batches
    for k in sorted(a):
        assert a[k].tobytes() == b[k].tobytes(), k


def test_staged_oracle_unused_paths_intact():
    """allreduce_grads()/update() keep the staged halves (facade
    contract)."""
    mx.random.seed(1)
    net = gluon.nn.Dense(2)
    net.initialize()
    x = mx.nd.array(np.random.RandomState(0).randn(2, 3).astype("f"))
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    tr.allreduce_grads()
    tr.update(2)
    # params moved; no fused program was built for these facades
    assert tr._updaters[0]._fused_step_owner is None


# -- the decision: which path a step takes, from what it observes ----------

def _two_process_store(monkeypatch):
    """A `DistKVStore` that believes it has a peer: the cross-process
    sum is replaced by the identity (test_bucketing's stand-in)."""
    from mxnet_tpu.parallel.kvstore_dist import DistKVStore
    kv = DistKVStore("dist_sync")   # single process: init is a no-op
    kv._nproc = 2
    monkeypatch.setattr(kv, "_cross_process_sum", lambda x: x)
    return kv


# (optimizer, Trainer arguments, step arguments) -> the path `step()`
# took, read from the counters. Adam on the single-worker `device`
# store -> fused is `test_fused_step_leaves_alive_and_path_counter`'s.
_DECISIONS = {
    "sgd-no-store": ("sgd", dict(kvstore=None), {}, "fused"),
    "rmsprop": ("rmsprop", {}, {}, "grouped"),
    "adagrad": ("adagrad", {}, {}, "grouped"),
    "no-fused-kernel": ("nag", {}, {}, "per-key"),
    "update-on-kvstore": ("sgd", dict(update_on_kvstore=True), {},
                          "on the store"),
    "compressing-store": ("sgd", dict(compression_params={
        "type": "2bit", "threshold": 0.5}), {}, "grouped"),
    "two-processes-ignore-stale": (
        "sgd", dict(kvstore=_two_process_store),
        dict(ignore_stale_grad=True), "grouped"),
}


@pytest.mark.parametrize("case", sorted(_DECISIONS))
def test_step_decides_the_path_from_what_it_observes(case, monkeypatch):
    optname, trainer_kw, step_kw, want = _DECISIONS[case]
    trainer_kw = dict(trainer_kw)
    if callable(trainer_kw.get("kvstore")):
        trainer_kw["kvstore"] = trainer_kw["kvstore"](monkeypatch)
    mx.random.seed(0)
    net = gluon.nn.Dense(3)
    net.initialize()
    x = mx.nd.array(np.random.RandomState(1).randn(4, 5).astype("f"))
    net(x)
    tr = gluon.Trainer(net.collect_params(), optname,
                       {"learning_rate": 0.01}, **trainer_kw)
    fused = obs.REGISTRY.get("train.step.fused_path")
    grouped = obs.REGISTRY.get("optimizer.fused.groups")
    per_key = obs.REGISTRY.get("optimizer.update.dispatches")
    programs = obs.REGISTRY.get("train.step.dispatches")
    before = [c.total() for c in (fused, grouped, per_key, programs)]
    n_steps, n_params = 2, len(net.collect_params())
    for _ in range(n_steps):
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        tr.step(4, **step_kw)
    d_fused, d_grouped, d_updates, d_programs = (
        c.total() - b for c, b in
        zip((fused, grouped, per_key, programs), before))
    if want == "fused":
        assert (d_fused, d_grouped, d_programs) == (n_steps, 0, n_steps)
    elif want == "grouped":     # the staged exchange + grouped update
        assert d_fused == 0 and d_grouped == d_updates >= n_steps
    elif want == "per-key":
        assert (d_fused, d_grouped) == (0, 0)
        assert d_updates == n_steps * n_params
    else:                       # the store's own updater applied it
        assert d_fused == 0 and d_updates >= n_steps
        assert tr._updaters[0].states == {}


def test_row_sparse_key_stages_once_then_is_latched(monkeypatch):
    """A key set with a row-sparse gradient is found out by `_collect`
    once (nothing mutated), and from then on refused by the rule
    before anything is collected."""
    upd = opt.get_updater(opt.create("sgd", learning_rate=0.1,
                                     momentum=0.9))
    ws = [mx.nd.array(np.ones((4, 3), "f")), mx.nd.array(np.ones(5, "f"))]
    gs = [mx.nd.NDArray(mx.nd.array(np.ones((4, 3), "f"))._data,
                        _stype="row_sparse"),
          mx.nd.array(np.ones(5, "f"))]
    probes = []
    collect = upd._collect

    def spy(*args, **kw):
        probes.append(kw.get("require_all", False))
        return collect(*args, **kw)

    monkeypatch.setattr(upd, "_collect", spy)
    fused = obs.REGISTRY.get("train.step.fused_path")
    f0 = fused.total()
    for _ in range(3):
        assert not fs.step(upd, [0, 1], gs, ws)
    assert probes == [True]                 # collected once, latched
    assert fused.total() == f0
    assert upd.optimizer._index_update_count == {}      # not mutated
    # another key set of the same updater is judged on its own
    assert fs.step(upd, [1], gs[1:], ws[1:])
    assert fused.total() == f0 + 1


# -- the program's boundary: leaves in, leaves out --------------------------

_LEAF_CASES = [
    ("sgd", dict(learning_rate=0.1, momentum=0.9, wd=0.01), "float32"),
    ("adam", dict(learning_rate=0.01, multi_precision=True), "float16"),
]


def _hybrid_loop(optname, optkw, dtype):
    """A small hybridized net under gluon.Trainer; returns (net,
    trainer, one_iteration) with the programs of step 1 built."""
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    if dtype != "float32":
        net.cast(dtype)
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(1).randn(4, 5).astype(dtype))
    y = mx.nd.array(np.random.RandomState(2).randn(4, 3).astype(dtype))
    tr = gluon.Trainer(net.collect_params(), optname, dict(optkw))
    loss_fn = gluon.loss.L2Loss()

    def fwd_bwd():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()

    fwd_bwd()
    tr.step(4)
    return net, tr, fwd_bwd


def _executions(tmp_path, fn):
    """Compiled-program executions while `fn` runs, counted from a
    `jax.profiler` trace of the host: the CPU client records one
    `PjRtCpuExecutable::Execute` a run of a compiled program, through
    the C++ fast path too (neither the program table nor
    `jax.monitoring` sees a run, only builds)."""
    import glob
    import jax

    def count(fn, d):
        jax.profiler.start_trace(str(d))
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        pb = glob.glob(str(d / "plugins" / "profile" / "*" / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(pb[0])
        return sum(e.name == "PjRtCpuExecutable::Execute"
                   for plane in data.planes for line in plane.lines
                   for e in line.events)

    double = jax.jit(lambda a: a * 2)
    one = double(jax.numpy.ones(3))

    def three():
        for _ in range(3):
            double(one).block_until_ready()

    if count(three, tmp_path / "calibrate") != 3:
        pytest.skip("this jaxlib's host trace does not name executions")
    return count(fn, tmp_path / "measured")


@pytest.mark.parametrize("name,kw,dtype", _LEAF_CASES)
def test_fused_step_is_one_program_no_eager_pack(name, kw, dtype,
                                                 monkeypatch, tmp_path):
    """Steps 2-4: `Trainer.step` runs ONE compiled program and never
    calls `Bucket.pack` / `Bucket.unpack` (steady state retraces
    nothing, so any call would be an eager one)."""
    from mxnet_tpu.parallel.bucketing import Bucket
    _net, tr, fwd_bwd = _hybrid_loop(name, kw, dtype)
    calls = []
    for meth in ("pack", "unpack"):
        orig = getattr(Bucket, meth)

        def spy(self, arg, _orig=orig, _meth=meth):
            calls.append(_meth)
            return _orig(self, arg)
        monkeypatch.setattr(Bucket, meth, spy)
    for i in range(3):
        fwd_bwd()
        assert _executions(tmp_path / str(i), lambda: tr.step(4)) == 1
    assert calls == []
    assert tr._updaters[0]._fused_step_owner.program_count() == 1


@pytest.mark.parametrize("name,kw,dtype", _LEAF_CASES)
def test_fused_step_leaves_alive_and_path_counter(name, kw, dtype,
                                                  tmp_path):
    """After fused steps every weight, master and state NDArray holds a
    live array of its own shape and dtype though masters and states are
    donated, and an NDArray that shared a weight's buffer (`detach()`)
    still reads: weights are the one class of leaf the program never
    donates."""
    path = obs.REGISTRY.get("train.step.fused_path")
    net, tr, fwd_bwd = _hybrid_loop(name, kw, dtype)
    params = list(net.collect_params().values())
    shapes = [(p.data().shape, p.data().dtype) for p in params]
    held = [p.data().detach() for p in params]
    before = [h.asnumpy() for h in held]
    leaves0, flats0 = path.get(path="leaves"), path.get(path="flats")
    for _ in range(3):
        fwd_bwd()
        tr.step(4)
    assert path.get(path="leaves") - leaves0 == 3
    assert path.get(path="flats") == flats0

    def alive(nd, shape, dtype):
        assert not nd._data.is_deleted()
        assert (nd.shape, nd.dtype) == (shape, dtype)
        assert np.isfinite(nd.asnumpy().astype("float64")).all()

    for p, (shape, dt), h, b in zip(params, shapes, held, before):
        alive(p.data(), shape, dt)
        alive(p.grad(), shape, dt)
        assert h.asnumpy().tobytes() == b.tobytes()      # not donated
        assert p.data().asnumpy().tobytes() != b.tobytes()
    upd = tr._updaters[0]
    mp = kw.get("multi_precision", False)
    for i, p in enumerate(params):
        stack = [upd.states[i]]
        n = 0
        while stack:
            st = stack.pop()
            if isinstance(st, (list, tuple)):
                stack.extend(st)
            elif st is not None:
                alive(st, p.data().shape,
                      np.float32 if mp else p.data().dtype)
                n += 1
        assert n == (3 if mp else 1)     # (master,) + m, v | momentum
    f = tmp_path / "states"
    tr.save_states(str(f))
    tr.load_states(str(f))
    fwd_bwd()
    tr.step(4)
    assert path.get(path="leaves") - leaves0 == 4


def test_armed_corruption_site_takes_the_flat_boundary():
    """An armed `grad.post` / `weight.post` site must fire on the flat
    itself: while one is armed the step packs eagerly around its
    program, and goes back to the leaves when it is disarmed."""
    path = obs.REGISTRY.get("train.step.fused_path")
    net, tr, fwd_bwd = _hybrid_loop(*_LEAF_CASES[0])
    pre = [p.data().asnumpy() for p in net.collect_params().values()]
    leaves0, flats0 = path.get(path="leaves"), path.get(path="flats")
    chaos.configure("weight.post:kind=bitflip,n=1", seed=3)
    try:
        fwd_bwd()
        tr.step(4)
        assert chaos.trip_count("weight.post") == 1
    finally:
        chaos.reset()
    assert (path.get(path="leaves") - leaves0,
            path.get(path="flats") - flats0) == (0, 1)
    fwd_bwd()
    tr.step(4)
    assert (path.get(path="leaves") - leaves0,
            path.get(path="flats") - flats0) == (1, 1)
    for a, p in zip(pre, net.collect_params().values()):
        assert a.tobytes() != p.data().asnumpy().tobytes()


# -- ZeRO-1 ---------------------------------------------------------------

def test_zero1_env_defaults_sharded_trainer(monkeypatch):
    from mxnet_tpu.parallel import make_mesh, ShardedTrainer

    def step_env(zero1):
        monkeypatch.setenv("MXTPU_ZERO1", "1" if zero1 else "0")

    step_env(zero1=True)
    mx.random.seed(0)
    net = gluon.nn.Dense(8)
    net.initialize()
    net(mx.nd.array(np.zeros((8, 4), "f")))
    st = ShardedTrainer(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                        "sgd", {"learning_rate": 0.1, "momentum": 0.9},
                        mesh=make_mesh({"dp": 8}))
    assert st._shard_opt
    g = obs.REGISTRY.get("zero1.shard_params")
    assert g is not None
    step_env(zero1=False)
    st2 = ShardedTrainer(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                         "sgd", {"learning_rate": 0.1,
                                 "momentum": 0.9},
                         mesh=make_mesh({"dp": 8}))
    assert not st2._shard_opt
    # explicit bool wins over env
    step_env(zero1=True)
    st3 = ShardedTrainer(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                         "sgd", {"learning_rate": 0.1,
                                 "momentum": 0.9},
                         mesh=make_mesh({"dp": 8}),
                         shard_optimizer_state=False)
    assert not st3._shard_opt


def _make_sharded_trainer(n_dp, zero1, seed=0, prefix="z1ckpt_"):
    from mxnet_tpu.parallel import make_mesh, ShardedTrainer
    import jax
    mx.random.seed(seed)
    # fixed prefix: every instance names its params identically, so
    # checkpoints restore across instances and runs compare by key
    net = gluon.nn.Dense(8, prefix=prefix)   # (8, 8): shardable at 8 & 4
    net.initialize()
    net(mx.nd.array(np.zeros((8, 8), "f")))
    mesh = make_mesh({"dp": n_dp}, jax.devices()[:n_dp])
    st = ShardedTrainer(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                        "sgd", {"learning_rate": 0.1, "momentum": 0.9},
                        mesh=mesh, shard_optimizer_state=zero1)
    return st


def test_zero1_checkpoint_roundtrip_elastic(tmp_path):
    """Sharded optimizer state saves through TrainerCheckpoint's
    two-phase commit and restores bit-identically into BOTH a sharded
    trainer of a different replica count (elastic 8 -> 4) and a
    replicated one."""
    from mxnet_tpu.parallel import checkpoint as ckpt
    import jax
    st = _make_sharded_trainer(8, zero1=True)
    x = mx.nd.array(np.random.RandomState(0).randn(8, 8).astype("f"))
    y = mx.nd.array(np.random.RandomState(1).randn(8, 8).astype("f"))
    for _ in range(3):
        st.step(x, y)
    # momentum state really is dp-sharded (the ZeRO-1 memory claim)
    from jax.sharding import PartitionSpec
    sharded = [v for v in st._opt_state.values()
               if v.sharding.spec == PartitionSpec("dp")]
    assert sharded, "no opt-state leaf was dp-sharded"
    want_state = {k: np.asarray(jax.device_get(v)).tobytes()
                  for k, v in st._opt_state.items()}
    want_params = {k: np.asarray(jax.device_get(v)).tobytes()
                   for k, v in st._params.items()}
    mngr = ckpt.TrainerCheckpoint(tmp_path, async_save=False)
    mngr.save(st._step_count, st, wait=True)
    # two-phase commit sealed the step
    assert mngr.commit_manifest(st._step_count) is not None

    for n_dp, zero1 in ((4, True), (8, False)):
        tgt = _make_sharded_trainer(n_dp, zero1=zero1)
        step = mngr.restore_latest(tgt)
        assert step == st._step_count
        got_state = {k: np.asarray(jax.device_get(v)).tobytes()
                     for k, v in tgt._opt_state.items()}
        got_params = {k: np.asarray(jax.device_get(v)).tobytes()
                      for k, v in tgt._params.items()}
        assert got_state == want_state, (n_dp, zero1)
        assert got_params == want_params, (n_dp, zero1)
    mngr.close()


def test_zero1_matches_replicated_sharded_trainer():
    """MXTPU_ZERO1 sharding changes memory layout, never numerics."""
    a = _make_sharded_trainer(8, zero1=True, seed=5)
    b = _make_sharded_trainer(8, zero1=False, seed=5)
    x = mx.nd.array(np.random.RandomState(2).randn(8, 8).astype("f"))
    y = mx.nd.array(np.random.RandomState(3).randn(8, 8).astype("f"))
    import jax
    for _ in range(3):
        a.step(x, y)
        b.step(x, y)
    for k in a._params:
        assert np.asarray(jax.device_get(a._params[k])).tobytes() == \
            np.asarray(jax.device_get(b._params[k])).tobytes(), k


# -- plan signatures ------------------------------------------------------

def test_plan_signature_stability_and_layout_sensitivity():
    from mxnet_tpu.parallel.bucketing import GradBucketer
    bk = GradBucketer(target_bytes=1 << 62)
    items = (("a", (4, 4), "float32", 0, None),
             ("b", (7,), "float32", -1, None))
    sig1 = bk.plan_signature(items)
    sig2 = bk.plan_signature(items)
    assert sig1 == sig2 and len(sig1) == 16
    # layout change (key order/priority) -> different signature
    flipped = (("a", (4, 4), "float32", -1, None),
               ("b", (7,), "float32", 0, None))
    assert bk.plan_signature(flipped) != sig1
    # an already-planned bucket list fingerprints identically
    assert bk.plan_signature(bk.plan(items)) == sig1


def test_fused_update_aot_sig_covers_layout():
    from mxnet_tpu.parallel import fused_update as fu
    import jax.numpy as jnp
    o = opt.create("sgd", learning_rate=0.1)
    spec = fu._SUPPORTED[type(o)]
    w = jnp.zeros((10,), jnp.float32)
    g = jnp.zeros((10,), jnp.float32)
    s1 = fu._aot_sig(spec, True, w, g, (), 0.0, (1, None, 0.0),
                     layout="aaaa")
    s2 = fu._aot_sig(spec, True, w, g, (), 0.0, (1, None, 0.0),
                     layout="bbbb")
    assert s1 != s2
