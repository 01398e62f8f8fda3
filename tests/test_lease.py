"""Device lease & health subsystem (ISSUE 7, docs/fault_tolerance.md).

Covers the acceptance surface: contended acquire has exactly one
winner; a SIGKILLed holder is taken over within the hard timeout with
no orphan lease file; a wedged LIVE holder (stale heartbeat) is
recovered without --force; a fresh live holder is never killed (by the
lease, by kill_stale --force, or by bench's probe path); the health
watchdog trips typed errors with holder diagnostics; and
tools/perf_gate.py turns a telemetry stream into a CI exit code.

Everything runs on the CPU mesh. Subprocess workers import the real
package (the lease is cross-process by nature); the wedged-holder
stand-ins are plain sleepers whose lease records carry their /proc
starttime — the same identity DeviceLease verifies before signalling.
"""
import importlib.util
import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

import mxnet_tpu as mx
from mxnet_tpu.observability import registry as obs
from mxnet_tpu.resilience import chaos
from mxnet_tpu.resilience.atomic import exclusive_create
from mxnet_tpu.resilience.lease import (DeviceLease, LeaseHeld,
                                        _proc_starttime, read_lease)
from mxnet_tpu.resilience.watchdog import (DeviceUnreachable,
                                           HealthWatchdog, diagnostics)
from mxnet_tpu.resilience.retry import DeadlineExceeded

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.configure("")
    yield
    chaos.reset()


@pytest.fixture()
def lease_path(tmp_path):
    return str(tmp_path / "dev.lease")


def _sleeper():
    """A wedged-holder stand-in: plain sleeper, no framework import."""
    return subprocess.Popen([sys.executable, "-S", "-c",
                             "import time; time.sleep(600)"])


def _lease_record(pid, heartbeat_age=0.0, takeover_s=2.0, starttime=...):
    if starttime is ...:
        starttime = _proc_starttime(pid)
    return {"pid": pid, "host": socket.gethostname(),
            "boot_id": open("/proc/sys/kernel/random/boot_id")
            .read().strip(),
            "starttime": starttime, "what": "wedged",
            "created": time.time() - heartbeat_age - 1.0,
            "heartbeat": time.time() - heartbeat_age,
            "heartbeat_s": 0.5, "takeover_s": takeover_s}


def _write_lease(path, rec):
    with open(path, "w") as f:
        f.write(json.dumps(rec))


# -- primitives -----------------------------------------------------------

def test_exclusive_create(tmp_path):
    p = str(tmp_path / "x")
    assert exclusive_create(p, "one")
    assert not exclusive_create(p, "two")
    assert open(p).read() == "one"


def test_acquire_release_roundtrip(lease_path):
    dl = DeviceLease(path=lease_path, takeover_s=5.0, what="test")
    with dl:
        rec = read_lease(lease_path)
        assert rec["pid"] == os.getpid()
        assert rec["what"] == "test"
        assert rec["starttime"] == _proc_starttime(os.getpid())
        hb0 = rec["heartbeat"]
        assert dl.refresh()
        assert read_lease(lease_path)["heartbeat"] >= hb0
    # no orphan file after release
    assert not os.path.exists(lease_path)
    assert not dl.held()


def test_reacquire_same_instance_is_idempotent(lease_path):
    dl = DeviceLease(path=lease_path, takeover_s=5.0)
    dl.acquire(timeout=5)
    assert dl.acquire(timeout=5) is dl       # held: no second create
    dl.release()


# -- staleness / takeover -------------------------------------------------

def test_fresh_live_holder_blocks_acquire(lease_path):
    holder = _sleeper()
    try:
        time.sleep(0.2)
        _write_lease(lease_path, _lease_record(holder.pid,
                                               takeover_s=60.0))
        with pytest.raises(LeaseHeld) as ei:
            DeviceLease(path=lease_path, takeover_s=60.0).acquire(
                timeout=0.8)
        assert ei.value.holder["pid"] == holder.pid
        # the holder was never signalled
        assert holder.poll() is None
        assert read_lease(lease_path)["pid"] == holder.pid
    finally:
        holder.kill()
        holder.wait()


def test_wedged_live_holder_taken_over_and_killed(lease_path):
    """The hung-holder mode: the holder is alive but stopped
    heartbeating past the hard timeout — SIGTERM→SIGKILL, then the
    lease changes hands. No --force anywhere."""
    holder = _sleeper()
    try:
        time.sleep(0.2)
        _write_lease(lease_path, _lease_record(holder.pid,
                                               heartbeat_age=100.0))
        dl = DeviceLease(path=lease_path, takeover_s=2.0,
                         kill_grace_s=1.0, what="taker")
        t0 = time.monotonic()
        dl.acquire(timeout=20)
        took = time.monotonic() - t0
        assert dl.takeovers == 1
        assert dl.taken_over_from["pid"] == holder.pid
        assert took < 10.0            # well within the hard timeout
        assert _proc_starttime(holder.pid) is None   # holder reaped
        assert read_lease(lease_path)["pid"] == os.getpid()
        dl.release()
        assert not os.path.exists(lease_path)
    finally:
        holder.kill()
        holder.wait()


def test_dead_holder_reclaimed_even_with_fresh_heartbeat(lease_path):
    """A dead pid holds nothing, whatever the timestamps say."""
    rec = _lease_record(os.getpid(), heartbeat_age=0.0)
    rec["pid"] = 2 ** 22 + 1              # vanishingly unlikely to exist
    rec["starttime"] = 12345
    _write_lease(lease_path, rec)
    dl = DeviceLease(path=lease_path, takeover_s=60.0)
    dl.acquire(timeout=10)
    assert dl.takeovers == 1
    dl.release()


def test_recycled_pid_never_blindly_killed(lease_path):
    """Stale lease whose pid now belongs to a DIFFERENT process
    (starttime mismatch): the lease is reclaimed but the innocent
    process is never signalled."""
    bystander = _sleeper()
    try:
        time.sleep(0.2)
        _write_lease(lease_path, _lease_record(
            bystander.pid, heartbeat_age=100.0, starttime=1))
        dl = DeviceLease(path=lease_path, takeover_s=2.0,
                         kill_grace_s=1.0)
        dl.acquire(timeout=10)
        assert dl.takeovers == 1
        assert bystander.poll() is None   # untouched
        dl.release()
    finally:
        bystander.kill()
        bystander.wait()


def test_refresh_detects_loss_and_stands_down(lease_path):
    """A holder that was (rightly) taken over after going silent must
    not stomp the new holder's lease on wakeup."""
    dl = DeviceLease(path=lease_path, takeover_s=5.0)
    dl.acquire(timeout=5)
    foreign = _lease_record(os.getpid())
    foreign["created"] = time.time() + 1   # a different lease identity
    _write_lease(lease_path, foreign)
    assert dl.refresh() is False
    assert dl.lost and not dl.held()
    dl.release()
    # the usurper's lease survives our release
    assert read_lease(lease_path)["created"] == foreign["created"]
    os.unlink(lease_path)


def test_chaos_lease_acquire_site(lease_path):
    chaos.configure("lease.acquire:kind=raise,n=1")
    from mxnet_tpu.resilience import InjectedFault
    with pytest.raises(InjectedFault):
        DeviceLease(path=lease_path).acquire(timeout=1)
    assert chaos.trip_count("lease.acquire") == 1
    assert not os.path.exists(lease_path)   # failed acquire left nothing
    chaos.configure("")
    dl = DeviceLease(path=lease_path)
    dl.acquire(timeout=5)
    dl.release()


# -- multi-process contention (the acceptance test) -----------------------

_WORKER = r'''
import os, sys, time
sys.path.insert(0, %r)
from mxnet_tpu.resilience.lease import DeviceLease, LeaseHeld
path, takeover, mode, timeout = (sys.argv[1], float(sys.argv[2]),
                                 sys.argv[3], float(sys.argv[4]))
dl = DeviceLease(path=path, takeover_s=takeover, kill_grace_s=1.0,
                 what=mode)
try:
    dl.acquire(timeout=timeout)
except LeaseHeld:
    print("LOST", flush=True)
    sys.exit(3)
print("WON %%d %%d" %% (os.getpid(), dl.takeovers), flush=True)
if mode == "hold":
    time.sleep(600)
else:
    dl.release()
    print("RELEASED", flush=True)
''' % ROOT


def _spawn_worker(path, takeover, mode, timeout):
    return subprocess.Popen(
        [sys.executable, "-c", _WORKER, path, str(takeover), mode,
         str(timeout)],
        cwd=ROOT, stdout=subprocess.PIPE, text=True, bufsize=1)


def _read_line(proc, deadline=60.0):
    end = time.monotonic() + deadline
    line = ""
    while time.monotonic() < end:
        line = proc.stdout.readline()
        if line:
            return line.strip()
    raise AssertionError("worker produced no output within %ss: %s"
                         % (deadline, line))


def test_multiprocess_contention_and_takeover(lease_path):
    """Two processes race: exactly one wins. SIGKILL the winner: the
    waiter takes over within the hard timeout, the lease file names
    the new holder, and release leaves no orphan file behind."""
    holder = _spawn_worker(lease_path, 2.0, "hold", 30)
    try:
        won = _read_line(holder)
        assert won.startswith("WON %d" % holder.pid)
        # contended acquire: the second process must LOSE, not co-hold
        loser = _spawn_worker(lease_path, 2.0, "take", 1.0)
        assert _read_line(loser) == "LOST"
        assert loser.wait(timeout=30) == 3
        assert read_lease(lease_path)["pid"] == holder.pid

        # now a patient waiter + a SIGKILLed holder
        waiter = _spawn_worker(lease_path, 2.0, "take", 30.0)
        time.sleep(0.5)                   # let it reach the wait loop
        t0 = time.monotonic()
        holder.kill()
        holder.wait()
        won = _read_line(waiter, deadline=30.0)
        took = time.monotonic() - t0
        assert won.startswith("WON %d" % waiter.pid), won
        assert took < 15.0                # hard timeout is 2s + margin
        assert _read_line(waiter) == "RELEASED"
        assert waiter.wait(timeout=30) == 0
        # no orphan/stale lease file left behind
        assert not os.path.exists(lease_path)
        assert not os.path.exists(lease_path + ".takeover")
    finally:
        for p in (holder,):
            if p.poll() is None:
                p.kill()
                p.wait()


# -- health watchdog ------------------------------------------------------

def _trips(kind):
    return obs.REGISTRY.get("resilience.watchdog.trips").get(kind=kind)


def test_watchdog_init_trip_fake_backend(lease_path):
    _write_lease(lease_path, _lease_record(os.getpid()))
    wd = HealthWatchdog(init_timeout_s=0.2, lease_path=lease_path)
    before = _trips("init")
    with pytest.raises(DeviceUnreachable) as ei:
        wd.init_devices(probe=lambda t: (None, "device init timed out"))
    assert _trips("init") == before + 1
    # the trip names the probe error AND the lease holder
    assert "device init timed out" in str(ei.value)
    assert str(os.getpid()) in str(ei.value)
    os.unlink(lease_path)


def test_watchdog_init_ok_real_backend():
    devs = HealthWatchdog(init_timeout_s=60).init_devices()
    assert devs and devs[0].platform == "cpu"


def test_watchdog_collective_trip():
    wd = HealthWatchdog(collective_timeout_s=0.2)
    before = _trips("collective")
    with pytest.raises(DeadlineExceeded):
        wd.guard_collective(lambda: time.sleep(5), what="fake barrier")
    assert _trips("collective") == before + 1
    # unguarded (0) runs inline
    assert wd.guard_collective(lambda: 7, timeout_s=0) == 7
    # within budget returns the value
    assert wd.guard_collective(lambda: 9, timeout_s=5.0) == 9


def test_device_init_chaos_site():
    chaos.configure("device.init:kind=fatal,n=1")
    from mxnet_tpu.resilience import InjectedFailure
    with pytest.raises(InjectedFailure):
        HealthWatchdog(init_timeout_s=1).init_devices(
            probe=lambda t: (["dev"], None))
    assert chaos.trip_count("device.init") == 1


def test_diagnostics_names_holder(lease_path):
    rec = _lease_record(os.getpid(), heartbeat_age=3.0)
    _write_lease(lease_path, rec)
    d = diagnostics(lease_path)
    assert str(os.getpid()) in d and "heartbeat" in d
    os.unlink(lease_path)
    assert "no holder" in diagnostics(lease_path)


def test_dist_lease_skipped_on_cpu():
    """Multi-process CPU runs (tests, gloo) share the backend: the
    training path must not serialize them on one lease."""
    from mxnet_tpu.parallel.kvstore_dist import _lease_wanted
    assert _lease_wanted() is False       # conftest pins jax to cpu


def test_lease_wanted_policy(monkeypatch):
    """Explicit MXTPU_LEASE wins; otherwise only a PRIMARY cpu platform
    skips — "tpu,cpu" (accelerator with cpu fallback) must lease."""
    from mxnet_tpu.resilience.lease import lease_wanted
    monkeypatch.setenv("MXTPU_LEASE", "0")
    assert lease_wanted(_platforms="tpu,cpu") is False
    monkeypatch.setenv("MXTPU_LEASE", "1")
    assert lease_wanted(_platforms="cpu") is True
    monkeypatch.delenv("MXTPU_LEASE")
    monkeypatch.delenv("MXNET_LEASE", raising=False)
    assert lease_wanted(_platforms="cpu") is False
    assert lease_wanted(_platforms="tpu,cpu") is True
    assert lease_wanted(_platforms="") is True    # unknown: could be accel


def test_hold_refcount_survives_reacquire(lease_path, monkeypatch):
    """Re-acquiring the process-wide hold after the old lease was
    usurped must keep the outstanding refcount: the first rider's
    release_hold() must not drop the fresh lease out from under the
    later holders."""
    from mxnet_tpu.resilience import lease as L
    monkeypatch.setenv("MXTPU_LEASE_PATH", lease_path)
    try:
        L.hold(what="first", timeout=5)
        # usurp: a foreign record replaces ours; the holder notices on
        # its next heartbeat and stands down
        foreign = _lease_record(os.getpid())
        foreign["created"] = time.time() + 1
        _write_lease(lease_path, foreign)
        assert L._process["lease"].refresh() is False
        os.unlink(lease_path)
        L.hold(what="second", timeout=5)      # re-acquire: refs now 2
        L.release_hold()                      # first rider leaves
        assert L.held_state() is not None     # second STILL holds
        assert read_lease(lease_path)["what"] == "second"
        L.release_hold()
        assert L.held_state() is None
        assert not os.path.exists(lease_path)
    finally:
        while L.held_state() is not None:
            L.release_hold()


# -- telemetry / observability -------------------------------------------

def test_lease_events_feed_telemetry_report(lease_path, tmp_path,
                                            monkeypatch):
    stream = str(tmp_path / "tele.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY", stream)
    holder = _sleeper()
    try:
        time.sleep(0.2)
        _write_lease(lease_path, _lease_record(holder.pid,
                                               heartbeat_age=100.0))
        dl = DeviceLease(path=lease_path, takeover_s=2.0,
                         kill_grace_s=1.0)
        dl.acquire(timeout=20)
        dl.release()
    finally:
        holder.kill()
        holder.wait()
    from mxnet_tpu.observability import telemetry
    telemetry.close_stream()
    monkeypatch.delenv("MXTPU_TELEMETRY")
    events = [json.loads(l) for l in open(stream)]
    kinds = {e["event"] for e in events}
    assert {"lease_acquire", "lease_takeover"} <= kinds
    # the report renders a lease section from the same stream
    spec = importlib.util.spec_from_file_location(
        "telemetry_report_t", os.path.join(ROOT, "tools",
                                           "telemetry_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    s = rep.summarize(rep.load_records(stream))
    assert s["lease_acquires"] == 1 and s["lease_takeovers"] == 1
    assert s["lease_stale_heartbeat_max_s"] > 50.0
    assert "lease" in rep.format_summary(s)


def test_lease_metrics_registered():
    for name, kind in (("resilience.lease.acquire.seconds", "histogram"),
                       ("resilience.lease.takeovers", "counter"),
                       ("resilience.lease.heartbeat.age", "gauge"),
                       ("resilience.watchdog.trips", "counter")):
        m = obs.REGISTRY.get(name)
        assert m is not None and m.kind == kind, name


# -- tools/kill_stale.py --------------------------------------------------

def _kill_stale(*args):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "kill_stale.py")]
        + list(args), capture_output=True, text=True, timeout=120)


def _kill_stale_seeing(pids, monkeypatch, *args):
    """`tools/kill_stale.py` run in this process with /proc's listing
    cut to `pids`; returns its exit code. Under --force the tool
    signals EVERY python process whose command line names the
    repository, and a suite under xdist is full of those (another
    worker's beacon and sleeper children, the workers themselves once
    they have mapped libtpu): a forced call may see only what its test
    started. The calls without --force can reach an expired holder of
    their own lease file and an accelerator-mapped process that idled
    past the ten-minute grace, which no test leaves behind."""
    spec = importlib.util.spec_from_file_location(
        "kill_stale_seeing", os.path.join(ROOT, "tools", "kill_stale.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    listdir = os.listdir
    with monkeypatch.context() as m:
        m.setattr(tool.os, "listdir", lambda path=".": (
            [str(p) for p in pids] if path == "/proc" else listdir(path)))
        return tool.main(list(args))


def test_kill_stale_refuses_fresh_holder_even_forced(lease_path,
                                                     monkeypatch, capsys):
    holder = _sleeper()
    try:
        time.sleep(0.2)
        _write_lease(lease_path, _lease_record(holder.pid,
                                               takeover_s=600.0))
        rc = _kill_stale_seeing([holder.pid], monkeypatch, "--kill",
                                "--force", "--lease-path", lease_path)
        out = capsys.readouterr().out
        assert rc == 2, out
        assert "refused" in out
        assert holder.poll() is None          # still alive
        assert os.path.exists(lease_path)     # lease intact
        # the old dead-end wording is gone for good
        assert "holds the device lease?" not in out
    finally:
        holder.kill()
        holder.wait()


def test_kill_stale_reaps_expired_holder_and_clears_lease(lease_path):
    holder = _sleeper()
    try:
        time.sleep(0.2)
        _write_lease(lease_path, _lease_record(holder.pid,
                                               heartbeat_age=100.0))
        r = _kill_stale("--kill", "--lease-path", lease_path)
        holder.wait(timeout=10)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "-> killed" in r.stdout
        assert not os.path.exists(lease_path), r.stdout
    finally:
        if holder.poll() is None:
            holder.kill()
            holder.wait()


def test_kill_stale_never_clears_foreign_host_lease(lease_path):
    """A holder on another host (shared-filesystem lease path) can't be
    inspected from here: a fresh one blocks recovery (exit 2), and the
    lease file is never cleared either way."""
    rec = _lease_record(2 ** 22 + 1, heartbeat_age=0.0, starttime=1)
    rec["host"] = "some-other-host"
    _write_lease(lease_path, rec)
    r = _kill_stale("--kill", "--lease-path", lease_path)
    assert r.returncode == 2, r.stdout + r.stderr
    assert os.path.exists(lease_path)
    assert "cannot recover" in r.stdout


def test_kill_stale_foreign_holder_pid_never_hits_local_process(
        lease_path):
    """A foreign-host holder's pid means nothing in OUR /proc: a local
    process that happens to share the number must not be tagged (or
    killed) as the expired holder."""
    bystander = _sleeper()
    try:
        time.sleep(0.2)
        rec = _lease_record(bystander.pid, heartbeat_age=100.0)
        rec["host"] = "some-other-host"
        _write_lease(lease_path, rec)
        r = _kill_stale("--kill", "--lease-path", lease_path)
        assert bystander.poll() is None       # untouched
        assert os.path.exists(lease_path)     # not ours to clear
        assert "-> killed" not in r.stdout
    finally:
        bystander.kill()
        bystander.wait()


def test_kill_stale_clears_orphan_lease(lease_path):
    rec = _lease_record(2 ** 22 + 1, heartbeat_age=100.0, starttime=1)
    _write_lease(lease_path, rec)
    r = _kill_stale("--kill", "--lease-path", lease_path)
    assert r.returncode == 0
    assert not os.path.exists(lease_path)
    assert "cleared" in r.stdout


# -- bench.py probe path --------------------------------------------------

@pytest.fixture()
def bench(monkeypatch, tmp_path, lease_path):
    monkeypatch.setenv("MXTPU_XLA_CACHE", str(tmp_path / "cache"))
    monkeypatch.setenv("MXTPU_LEASE_PATH", lease_path)
    monkeypatch.setenv("MXTPU_BENCH_PLATFORM", "cpu")
    spec = importlib.util.spec_from_file_location(
        "bench_lease_test", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    yield mod
    if mod._LEASE is not None:
        mod._LEASE.release()


def test_bench_probe_runs_through_lease(bench, lease_path):
    plat = bench._probe_devices(timeout_s=120, parent_init=False,
                                retries=1)
    assert plat == "cpu"
    assert bench._PROBE_INFO["probes"] == 1
    assert bench._PROBE_INFO["takeovers"] == 0
    assert bench._PROBE_INFO["lease_holder"]["pid"] == os.getpid()
    assert read_lease(lease_path)["pid"] == os.getpid()


def test_bench_probe_recovers_wedged_holder_without_force(
        bench, lease_path, monkeypatch):
    """ISSUE 7 acceptance: a simulated wedged holder (live, silent
    heartbeat) is recovered by the probe path itself — no kill_stale
    --force, no skip-and-pray ladder."""
    monkeypatch.setenv("MXTPU_LEASE_TAKEOVER_S", "2")
    monkeypatch.setenv("MXTPU_LEASE_KILL_GRACE_S", "1")
    holder = _sleeper()
    try:
        time.sleep(0.2)
        _write_lease(lease_path, _lease_record(holder.pid,
                                               heartbeat_age=100.0))
        plat = bench._probe_devices(timeout_s=120, parent_init=False,
                                    retries=1)
        assert plat == "cpu"
        assert bench._PROBE_INFO["takeovers"] == 1
        assert bench._PROBE_INFO["lease_holder"]["pid"] == holder.pid
        assert _proc_starttime(holder.pid) is None   # wedge cleared
        assert read_lease(lease_path)["pid"] == os.getpid()
    finally:
        holder.kill()
        holder.wait()


def test_bench_probe_live_holder_is_clean_exit(bench, lease_path,
                                               monkeypatch):
    """A holder doing real work: bench exits with a diagnosable error
    naming it instead of a doomed multi-probe retry ladder."""
    monkeypatch.setenv("MXTPU_LEASE_ACQUIRE_S", "1")
    holder = _sleeper()
    try:
        time.sleep(0.2)
        _write_lease(lease_path, _lease_record(holder.pid,
                                               takeover_s=600.0))
        with pytest.raises(SystemExit) as ei:
            bench._probe_devices(timeout_s=30, parent_init=False,
                                 retries=1)
        assert "live holder" in str(ei.value)
        assert str(holder.pid) in str(ei.value)
        assert holder.poll() is None
    finally:
        holder.kill()
        holder.wait()


def test_bench_probe_lease_optout(bench, lease_path, monkeypatch):
    """MXTPU_LEASE=0 is the documented escape hatch: bench probes
    without touching the lease file."""
    monkeypatch.setenv("MXTPU_LEASE", "0")
    plat = bench._probe_devices(timeout_s=120, parent_init=False,
                                retries=1)
    assert plat == "cpu"
    assert not os.path.exists(lease_path)


# -- serving lease hold ---------------------------------------------------

def test_model_server_reports_lease(lease_path, monkeypatch):
    from mxnet_tpu.serving import InferenceEngine, ModelServer
    import numpy as np
    monkeypatch.setenv("MXTPU_LEASE", "1")       # CPU backend: opt in
    monkeypatch.setenv("MXTPU_LEASE_PATH", lease_path)
    rng = np.random.RandomState(0)
    data = mx.sym.var("data")
    sym = mx.sym.FullyConnected(data=data, num_hidden=3, name="fc")
    params = {"fc_weight": mx.nd.array(rng.randn(3, 4).astype("float32")),
              "fc_bias": mx.nd.zeros((3,))}
    engine = InferenceEngine.from_symbol(sym, params, {}, {"data": (4,)},
                                         max_batch_size=4)
    server = ModelServer(engine, num_workers=1)
    server.start()
    try:
        st = server.stats()
        assert st["lease"] is not None and st["lease"]["held"]
        assert read_lease(lease_path)["pid"] == os.getpid()
        assert read_lease(lease_path)["what"] == "serving"
    finally:
        assert server.drain(timeout=30)
    from mxnet_tpu.resilience.lease import held_state
    assert held_state() is None
    assert not os.path.exists(lease_path)
    assert server.stats()["lease"] is None


def test_model_server_releases_lease_on_start_failure(lease_path,
                                                      monkeypatch):
    """A failed warmup must not keep squatting on the device lease for
    the process's remaining lifetime."""
    from mxnet_tpu.serving import InferenceEngine, ModelServer
    monkeypatch.setenv("MXTPU_LEASE", "1")
    monkeypatch.setenv("MXTPU_LEASE_PATH", lease_path)
    data = mx.sym.var("data")
    sym = mx.sym.FullyConnected(data=data, num_hidden=2, name="fc")
    params = {"fc_weight": mx.nd.ones((2, 3)), "fc_bias": mx.nd.zeros((2,))}
    engine = InferenceEngine.from_symbol(sym, params, {}, {"data": (3,)},
                                         max_batch_size=4)

    def boom(*a, **k):
        raise RuntimeError("warmup boom")

    monkeypatch.setattr(engine, "warmup", boom)
    server = ModelServer(engine, num_workers=1, warmup=True)
    with pytest.raises(RuntimeError, match="warmup boom"):
        server.start()
    from mxnet_tpu.resilience.lease import held_state
    assert held_state() is None
    assert not os.path.exists(lease_path)


def test_model_server_skips_lease_on_cpu_by_default(monkeypatch,
                                                    lease_path):
    from mxnet_tpu.serving import InferenceEngine, ModelServer
    import numpy as np
    monkeypatch.delenv("MXTPU_LEASE", raising=False)
    monkeypatch.setenv("MXTPU_LEASE_PATH", lease_path)
    data = mx.sym.var("data")
    sym = mx.sym.FullyConnected(data=data, num_hidden=2, name="fc")
    params = {"fc_weight": mx.nd.ones((2, 3)), "fc_bias": mx.nd.zeros((2,))}
    engine = InferenceEngine.from_symbol(sym, params, {}, {"data": (3,)},
                                         max_batch_size=4)
    with ModelServer(engine, num_workers=1) as server:
        assert server.stats()["lease"] is None
        assert not os.path.exists(lease_path)


# -- chaos_run exercises the new sites ------------------------------------

@pytest.mark.slow
def test_chaos_run_lease_acquire_site(tmp_path):
    """tools/chaos_run.py drives the lease.acquire site end to end: a
    fatal injection makes the wrapped acquire fail CLEANLY (no hang)."""
    lease = str(tmp_path / "dev.lease")
    prog = ("import os, sys; sys.path.insert(0, %r); "
            "from mxnet_tpu.resilience.lease import DeviceLease; "
            "DeviceLease(path=%r).acquire(timeout=5)" % (ROOT, lease))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "chaos_run.py"),
         "--chaos", "lease.acquire:kind=fatal", "--timeout", "120",
         "--expect", "error", "--", sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=180, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.splitlines()[-1])
    assert out["outcome"] == "CLEAN_ERROR" and out["ok"]


# -- tools/perf_gate.py ---------------------------------------------------

def _write_stream(path, n=5, step_time=0.01, compile_seconds=0.05,
                  batch_size=8):
    with open(path, "w") as f:
        for i in range(n):
            f.write(json.dumps({
                "source": "train", "step": i, "step_time": step_time,
                "compile_count": 1, "compile_seconds": compile_seconds,
                "batch_size": batch_size}) + "\n")


def _perf_gate(*args):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "perf_gate.py")]
        + list(args), capture_output=True, text=True, timeout=120)


def test_perf_gate_passes_healthy_stream(tmp_path):
    p = str(tmp_path / "t.jsonl")
    _write_stream(p)
    r = _perf_gate(p, "--max-step-p95-s", "0.5",
                   "--max-compile-stall-s", "10",
                   "--min-samples-per-sec", "1", "--min-steps", "5")
    assert r.returncode == 0, r.stdout + r.stderr
    verdict = json.loads(r.stdout.splitlines()[-1])
    assert verdict["ok"] and verdict["breaches"] == []
    assert verdict["checks"]["step_p95_s"]["observed"] == 0.01


def test_perf_gate_fails_on_injected_breach(tmp_path):
    p = str(tmp_path / "t.jsonl")
    _write_stream(p, step_time=1.0)       # injected step-time regression
    r = _perf_gate(p, "--max-step-p95-s", "0.1")
    assert r.returncode == 1
    verdict = json.loads(r.stdout.splitlines()[-1])
    assert verdict["breaches"] == ["step_p95_s"]
    assert "BREACH step_p95_s" in r.stderr
    # compile-stall budget breaches too
    _write_stream(p, compile_seconds=10.0)
    r = _perf_gate(p, "--max-compile-stall-s", "1.0")
    assert r.returncode == 1
    assert "compile_stall_s" in json.loads(
        r.stdout.splitlines()[-1])["breaches"]


def test_perf_gate_rejects_malformed_and_missing(tmp_path):
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write('{"step_time": 0.1}\nnot json\n')
    assert _perf_gate(bad, "--max-step-p95-s", "1").returncode == 2
    assert _perf_gate(str(tmp_path / "absent.jsonl"),
                      "--max-step-p95-s", "1").returncode == 2
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert _perf_gate(empty, "--max-step-p95-s", "1").returncode == 2


def test_perf_gate_requires_budgets_and_enough_steps(tmp_path):
    p = str(tmp_path / "t.jsonl")
    _write_stream(p, n=2)
    assert _perf_gate(p).returncode == 2          # no budgets: no gate
    r = _perf_gate(p, "--max-step-p95-s", "1", "--min-steps", "10")
    assert r.returncode == 1                      # truncated stream
    assert "steps" in json.loads(r.stdout.splitlines()[-1])["breaches"]
