"""Resilience layer: fault injection, retry/deadline policies, and
preemption-safe training (mxnet_tpu/resilience/, docs/fault_tolerance.md).

Tier-1-safe: everything runs on the virtual CPU mesh, chaos is armed
programmatically (seeded — every run replays identically), and the
SIGTERM path delivers the signal in-process via os.kill.
"""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu import recordio as rio
from mxnet_tpu import resilience
from mxnet_tpu.resilience import (chaos, metrics, atomic_write,
                                  Deadline, DeadlineExceeded,
                                  InjectedFault, InjectedFailure,
                                  PreemptionGuard, RetryPolicy,
                                  TrainingPreempted, TransientError,
                                  retry, retry_call, run_with_deadline)


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.configure("")          # disarm, whatever the ambient env says
    metrics.reset_counters()
    yield
    chaos.reset()


# -- chaos spec / injector ------------------------------------------------

def test_parse_spec():
    spec = chaos.parse_spec(
        "kvstore.push:p=0.1,kind=raise;io.read:p=0.05;"
        "dist.init:kind=sleep,secs=0.5,n=3,after=2")
    assert spec["kvstore.push"] == {"p": 0.1, "kind": "raise"}
    assert spec["io.read"] == {"p": 0.05}
    assert spec["dist.init"] == {"kind": "sleep", "secs": 0.5,
                                 "n": 3, "after": 2}
    assert chaos.parse_spec("") == {}
    with pytest.raises(mx.MXNetError):
        chaos.parse_spec("site:bogus=1")
    with pytest.raises(mx.MXNetError):
        chaos.parse_spec("site:kind=explode")


def test_seeded_draws_replay_identically():
    def pattern(seed):
        chaos.configure("s:p=0.5", seed=seed)
        out = []
        for _ in range(64):
            try:
                chaos.chaos_point("s")
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    a, b = pattern(7), pattern(7)
    assert a == b and sum(a) > 0
    assert pattern(8) != a


def test_wildcard_site_and_trip_budget():
    chaos.configure("kvstore.*:p=1,n=2")
    with pytest.raises(InjectedFault):
        chaos.chaos_point("kvstore.push")
    with pytest.raises(InjectedFault):
        chaos.chaos_point("kvstore.pull")
    chaos.chaos_point("kvstore.push")  # budget n=2 spent: no more trips
    assert chaos.trip_count("kvstore.push") == 2
    chaos.chaos_point("io.read")       # unarmed site: never trips


def test_env_driven_configuration(monkeypatch):
    monkeypatch.setenv("MXTPU_CHAOS", "x:p=1,n=1")
    monkeypatch.setenv("MXTPU_CHAOS_SEED", "3")
    chaos.reset()                      # next point re-reads the env
    with pytest.raises(InjectedFault):
        chaos.chaos_point("x")
    chaos.chaos_point("x")
    assert chaos.trip_count("x") == 1


def test_sleep_kind_exercises_deadlines():
    chaos.configure("slow:kind=sleep,secs=0.05")
    t0 = time.monotonic()
    chaos.chaos_point("slow")          # does not raise, just stalls
    assert time.monotonic() - t0 >= 0.04


# -- retry / deadline toolkit ---------------------------------------------

def test_retry_call_absorbs_transients_then_succeeds():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientError("transient %d" % calls["n"])
        return "ok"

    assert retry_call(flaky, policy=RetryPolicy(
        max_attempts=5, base_delay=0.001)) == "ok"
    assert calls["n"] == 3


def test_retry_exhaustion_reraises_last_error():
    calls = {"n": 0}

    def always_fails():
        calls["n"] += 1
        raise TransientError("still down")

    with pytest.raises(TransientError, match="still down"):
        retry_call(always_fails, policy=RetryPolicy(
            max_attempts=3, base_delay=0.001))
    assert calls["n"] == 3


def test_retry_decorator_and_give_up_on():
    class Fatal(Exception):
        pass

    calls = {"n": 0}

    @retry(RetryPolicy(max_attempts=5, base_delay=0.001,
                       retry_on=(Exception,), give_up_on=(Fatal,)))
    def fails_fatally():
        calls["n"] += 1
        raise Fatal("do not retry me")

    with pytest.raises(Fatal):
        fails_fatally()
    assert calls["n"] == 1


def test_deadline_expiry():
    dl = Deadline(0.02, what="unit test op")
    dl.check()                         # fresh: fine
    time.sleep(0.03)
    assert dl.expired()
    with pytest.raises(DeadlineExceeded, match="unit test op"):
        dl.check()


def test_retry_respects_deadline():
    calls = {"n": 0}

    def always_fails():
        calls["n"] += 1
        raise TransientError("down")

    # generous attempts but a deadline too short for the backoff: the
    # loop must stop early rather than sleep past the budget
    with pytest.raises((TransientError, DeadlineExceeded)):
        retry_call(always_fails, policy=RetryPolicy(
            max_attempts=50, base_delay=0.05,
            deadline=Deadline(0.05, what="bounded retries")))
    assert calls["n"] < 50


def test_run_with_deadline():
    assert run_with_deadline(lambda: 42, 5.0, what="quick") == 42
    with pytest.raises(ValueError):
        run_with_deadline(lambda: (_ for _ in ()).throw(ValueError("x")),
                          5.0, what="raising")
    with pytest.raises(DeadlineExceeded, match="wedged barrier"):
        run_with_deadline(lambda: time.sleep(10), 0.05,
                          what="wedged barrier")


# -- kvstore.push site ----------------------------------------------------

def test_kvstore_push_injection_is_absorbed_by_retry():
    chaos.configure("kvstore.push:p=1,n=2")
    kv = mx.kv.create("device")
    kv.init(0, mx.nd.ones((4,)))
    kv.push(0, mx.nd.full((4,), 3.0))
    out = mx.nd.zeros((4,))
    kv.pull(0, out)
    np.testing.assert_allclose(out.asnumpy(), 3.0)
    assert chaos.trip_count("kvstore.push") == 2
    assert metrics.get("chaos.injected.kvstore.push") == 2


def test_kvstore_push_retry_exhaustion(monkeypatch):
    monkeypatch.setenv("MXTPU_KV_PUSH_RETRIES", "3")
    monkeypatch.setenv("MXTPU_RETRY_BASE_DELAY_S", "0.001")
    chaos.configure("kvstore.push:p=1")
    kv = mx.kv.create("device")
    kv.init(0, mx.nd.ones((4,)))
    with pytest.raises(InjectedFault):
        kv.push(0, mx.nd.ones((4,)))
    assert chaos.trip_count("kvstore.push") == 3


def test_kvstore_push_fatal_injection_not_retried():
    chaos.configure("kvstore.push:p=1,kind=fatal")
    kv = mx.kv.create("device")
    kv.init(0, mx.nd.ones((4,)))
    with pytest.raises(InjectedFailure):
        kv.push(0, mx.nd.ones((4,)))
    assert chaos.trip_count("kvstore.push") == 1


# -- dist.init site -------------------------------------------------------

def test_dist_init_retry_exhaustion(monkeypatch):
    from mxnet_tpu.parallel import kvstore_dist
    monkeypatch.setenv("MXTPU_DIST_INIT_RETRIES", "3")
    monkeypatch.setenv("MXTPU_DIST_INIT_BACKOFF_S", "0.001")
    chaos.configure("dist.init:p=1")
    # every attempt trips before jax.distributed.initialize runs, so
    # the bogus coordinator is never actually contacted
    with pytest.raises(InjectedFault):
        kvstore_dist.init_distributed(
            coordinator_address="127.0.0.1:1",
            num_processes=2, process_id=0)
    assert chaos.trip_count("dist.init") == 3
    assert not kvstore_dist._dist_initialized


# -- io.read site ---------------------------------------------------------

def test_io_read_chaos_preserves_the_batch_stream():
    X = np.arange(48, dtype="float32").reshape(12, 4)
    Y = (np.arange(12) % 3).astype("float32")

    def epoch():
        it = mx.io.NDArrayIter(X, Y, batch_size=4)
        return [(b.data[0].asnumpy().copy(), b.label[0].asnumpy().copy())
                for b in it]

    clean = epoch()
    chaos.configure("io.read:p=0.5", seed=11)
    chaotic = epoch()
    assert chaos.trip_count("io.read") > 0
    assert len(clean) == len(chaotic)
    for (xa, ya), (xb, yb) in zip(clean, chaotic):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


# -- corrupt-record budget ------------------------------------------------

def _write_plain_rec(path, payloads, monkeypatch):
    """Write records via the pure-python framing (native lib bypassed)
    and return each record's byte offset."""
    monkeypatch.setattr(rio, "_native_lib", lambda: None)
    w = rio.MXRecordIO(path, "w")
    offsets = [w.write(p) for p in payloads]
    w.close()
    return offsets


def test_recordio_bad_magic_resync_within_budget(tmp_path, monkeypatch):
    path = str(tmp_path / "x.rec")
    payloads = [b"rec-%d-" % i + bytes(range(8)) for i in range(5)]
    offsets = _write_plain_rec(path, payloads, monkeypatch)
    with open(path, "r+b") as f:      # corrupt record 3's magic word
        f.seek(offsets[3])
        f.write(b"\xde\xad\xbe\xef")

    r = rio.MXRecordIO(path, "r", bad_record_budget=2)
    got = []
    while True:
        rec = r.read()
        if rec is None:
            break
        got.append(rec)
    r.close()
    assert got == [payloads[0], payloads[1], payloads[2], payloads[4]]
    assert r.bad_records == 1
    assert metrics.get("io.bad_records") == 1

    strict = rio.MXRecordIO(path, "r")  # default budget 0: reference
    assert strict.read() == payloads[0]
    assert strict.read() == payloads[1]
    assert strict.read() == payloads[2]
    with pytest.raises(IOError, match="Invalid RecordIO magic"):
        strict.read()
    strict.close()


def test_recordio_truncated_tail_is_warned_eof_even_at_budget_zero(
        tmp_path, monkeypatch):
    # a torn TRAILING record (crashed/concurrent writer) must read as
    # EOF whatever the budget — the pre-budget reader ended there too;
    # the counter just makes the damage visible
    path = str(tmp_path / "t.rec")
    payloads = [b"a" * 40, b"b" * 40]
    _write_plain_rec(path, payloads, monkeypatch)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:      # tear the last record's payload
        f.truncate(size - 20)
    r = rio.MXRecordIO(path, "r")     # default budget 0
    assert r.read() == payloads[0]
    assert r.read() is None           # torn record reads as EOF
    assert r.bad_records == 1
    r.close()


def test_io_read_exhaustion_surfaces_instead_of_truncating(monkeypatch):
    # only the injection gate is retried: when retries exhaust, the
    # fault must surface from __next__ — NOT consume iterator state or
    # decay into a silent early StopIteration
    monkeypatch.setenv("MXTPU_IO_RETRIES", "3")
    monkeypatch.setenv("MXTPU_RETRY_BASE_DELAY_S", "0.001")
    chaos.configure("io.read:p=1")
    it = mx.io.NDArrayIter(np.zeros((8, 2), "float32"),
                           np.zeros(8, "float32"), batch_size=4)
    with pytest.raises(InjectedFault):
        next(it)
    chaos.configure("")               # iterator state untouched: the
    batches = list(it)                # full epoch is still there
    assert len(batches) == 2


def test_image_record_iter_skips_bad_records_within_budget(tmp_path):
    path = str(tmp_path / "img.rec")
    w = rio.MXRecordIO(path, "w")
    n_good = 8
    for i in range(n_good):
        img = np.full((6, 5, 3), i * 9, np.uint8)
        w.write(rio.pack_img(rio.IRHeader(0, float(i), i, 0), img))
        if i == 3:                    # a record whose decode must fail
            w.write(rio.pack(rio.IRHeader(0, 99.0, 99, 0),
                             b"NOT-AN-IMAGE"))
    w.close()

    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 6, 5),
                               batch_size=4, preprocess_threads=2,
                               bad_record_budget=2)
    labels = []
    for batch in it:
        labels.extend(batch.label[0].asnumpy()[:4 - batch.pad].tolist())
    it.close()
    assert sorted(labels) == sorted(float(i) for i in range(n_good))
    assert it.bad_record_count == 1

    strict = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 6, 5),
                                   batch_size=4, preprocess_threads=2)
    with pytest.raises(mx.MXNetError, match="bad-record budget"):
        for _ in strict:
            pass
    strict.close()


# -- crash-consistent writes ----------------------------------------------

def test_atomic_write_failure_leaves_target_untouched(tmp_path):
    target = tmp_path / "state.params"
    with atomic_write(str(target)) as f:
        f.write(b"generation-1")
    with pytest.raises(RuntimeError, match="mid-write crash"):
        with atomic_write(str(target)) as f:
            f.write(b"gener")        # partial second generation...
            raise RuntimeError("mid-write crash")
    assert target.read_bytes() == b"generation-1"
    assert os.listdir(str(tmp_path)) == ["state.params"]  # no tmp litter


def test_nd_save_is_crash_consistent(tmp_path):
    fname = str(tmp_path / "w.params")
    mx.nd.save(fname, {"w": mx.nd.ones((3, 3))})
    loaded = mx.nd.load(fname)
    np.testing.assert_allclose(loaded["w"].asnumpy(), 1.0)
    assert os.listdir(str(tmp_path)) == ["w.params"]


# -- checkpoint.save site + preemption ------------------------------------

def _sharded(net):
    from mxnet_tpu.parallel import make_mesh, ShardedTrainer
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    return ShardedTrainer(net, lambda o, l: loss(o, l), "sgd",
                          {"learning_rate": 0.05},
                          mesh=make_mesh({"dp": 8}))


def _small_net():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(10))
    net.initialize()
    net(mx.nd.zeros((1, 8)))
    return net


def _batch(rng):
    return (rng.randn(16, 8).astype("float32"),
            (np.arange(16) % 10).astype("float32"))


def test_checkpoint_save_injection_retried(tmp_path):
    from mxnet_tpu.parallel.checkpoint import TrainerCheckpoint
    rng = np.random.RandomState(0)
    net = _small_net()
    x, y = _batch(rng)
    tr = _sharded(net)
    tr.step(x, y)
    chaos.configure("checkpoint.save:p=1,n=2")
    with TrainerCheckpoint(str(tmp_path / "ck")) as ck:
        ck.save(1, tr, wait=True)    # two injected faults absorbed
        assert chaos.trip_count("checkpoint.save") == 2
        fresh = _sharded(net)
        assert ck.restore_latest(fresh) == 1


def test_sigterm_checkpoints_at_next_step_boundary(tmp_path):
    from mxnet_tpu.parallel.checkpoint import TrainerCheckpoint
    rng = np.random.RandomState(1)
    net = _small_net()
    x, y = _batch(rng)
    tr = _sharded(net)
    old = signal.getsignal(signal.SIGTERM)
    with TrainerCheckpoint(str(tmp_path / "ck")) as ck:
        with pytest.raises(TrainingPreempted) as ei:
            with PreemptionGuard.for_trainer(ck, tr) as guard:
                for i in range(100):
                    tr.step(x, y)
                    if i == 2:       # preemption arrives mid-run...
                        os.kill(os.getpid(), signal.SIGTERM)
        # ...and fires at the NEXT step boundary: 3 completed steps
        assert ei.value.step == 3
        assert guard.preempted and guard.saved_step == 3
        assert signal.getsignal(signal.SIGTERM) is old  # restored
        resumed = _sharded(net)
        assert ck.restore_latest(resumed) == 3
        assert resumed._step_count == 3
        # the resumed run continues training from exactly there
        assert float(resumed.step(x, y).asscalar()) > 0
        assert resumed._step_count == 4


def test_second_signal_escalates_to_keyboard_interrupt():
    # a wedged loop never reaches a boundary; the second signal must
    # escape with the clean unwind the reaping ladders rely on
    with PreemptionGuard(reraise=False):
        os.kill(os.getpid(), signal.SIGTERM)
        with pytest.raises(KeyboardInterrupt):
            os.kill(os.getpid(), signal.SIGTERM)
            time.sleep(0.1)  # let the pending signal be delivered


def test_preemption_guard_cooperative_mode():
    with PreemptionGuard(reraise=False) as guard:
        os.kill(os.getpid(), signal.SIGTERM)
        resilience.at_step_boundary()
        assert guard.preempted
    assert not resilience.preemption_requested()


# -- engine.host_push site ------------------------------------------------

def test_host_push_site():
    from mxnet_tpu import engine
    if engine.host_engine() is None:
        assert engine.host_push(lambda: 5) == 5  # inline fallback path
    chaos.configure("engine.host_push:p=1,kind=fatal")
    with pytest.raises(InjectedFailure):
        engine.host_push(lambda: 5)


# -- acceptance: chaos training run ---------------------------------------

def _train_losses(net, init_params, n_epochs=3):
    params = net.collect_params()
    for k, v in init_params.items():
        params[k].set_data(mx.nd.array(v))
    trainer = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                            kvstore="device")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)
    X = rng.randn(24, 8).astype("float32")
    Y = (np.arange(24) % 10).astype("float32")
    losses = []
    for _ in range(n_epochs):
        it = mx.io.NDArrayIter(X, Y, batch_size=8)
        for batch in it:
            with autograd.record():
                l = loss_fn(net(batch.data[0]), batch.label[0])
            l.backward()
            trainer.step(8)
            losses.append(float(l.mean().asscalar()))
    return losses


def test_training_identical_loss_under_chaos(monkeypatch):
    """Acceptance: 10% transient injection at kvstore.push and io.read
    is fully absorbed — the loss trajectory is identical to the
    fault-free run (every site precedes mutation, so retries replay
    bit-identically)."""
    monkeypatch.setenv("MXTPU_RETRY_BASE_DELAY_S", "0.001")
    net = _small_net()
    init = {k: p.data().asnumpy().copy()
            for k, p in net.collect_params().items()}
    clean = _train_losses(net, init)
    chaos.configure("kvstore.push:p=0.1;io.read:p=0.1", seed=5)
    chaotic = _train_losses(net, init)
    trips = (chaos.trip_count("kvstore.push") +
             chaos.trip_count("io.read"))
    assert trips > 0, "chaos must actually have fired for this to mean anything"
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(chaotic))
    # and training actually trains: an epoch is the same three batches,
    # so epochs compare like with like (the last batch's loss against
    # the first batch's hung on what the earlier tests drew from the
    # global RNG before this net was initialized)
    assert np.mean(clean[-3:]) < np.mean(clean[:3])


# -- chaos_run harness -----------------------------------------------------

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _chaos_run(*args, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(TOOLS, "chaos_run.py")] + list(args),
        capture_output=True, text=True, timeout=timeout, env=env)


def test_chaos_run_completion_and_clean_error():
    r = _chaos_run("--chaos", "io.read:p=0", "--timeout", "90",
                   "--expect", "complete", "--",
                   sys.executable, "-c", "print('done')")
    assert r.returncode == 0, r.stdout + r.stderr
    assert '"outcome": "COMPLETED"' in r.stdout

    r = _chaos_run("--chaos", "io.read:p=0", "--timeout", "90",
                   "--expect", "error", "--",
                   sys.executable, "-c",
                   "import sys; sys.exit('diagnosable boom')")
    assert r.returncode == 0, r.stdout + r.stderr
    assert '"outcome": "CLEAN_ERROR"' in r.stdout


def test_chaos_run_flags_hangs():
    r = _chaos_run("--chaos", "io.read:p=0", "--timeout", "1",
                   "--grace", "2", "--",
                   sys.executable, "-c", "import time; time.sleep(120)")
    assert r.returncode == 3, r.stdout + r.stderr
    assert '"outcome": "HANG"' in r.stdout
