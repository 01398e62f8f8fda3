"""Cooperative on-disk device lease: one holder per accelerator host.

A hung previous process can keep the PJRT device grant: recovery
tooling can *see* it but not safely clear it, and the next run records
"device backend unreachable". The fix is the stance the paper's layer
map implies — L5 execution owns device acquisition as explicit runtime
state (the TensorFlow device-layer position, PAPERS.md
arXiv:1605.08695) — not ad-hoc /proc forensics after the fact.

`DeviceLease` is that state, as a file:

* **acquire** is an atomic O_EXCL create (`resilience.atomic.
  exclusive_create`): exactly one of N racing processes wins. The file
  body is one JSON record naming the holder (pid, host, boot id,
  /proc starttime — the pid-reuse defense), its role, and a heartbeat
  timestamp.
* a **daemon heartbeat thread** refreshes the timestamp every
  `heartbeat_s` via `atomic_write` (readers never see a torn record).
  A holder that stops heartbeating has, by contract, wedged or died.
* **hard-timeout takeover**: a lease whose heartbeat is older than
  `MXTPU_LEASE_TAKEOVER_S` is reclaimed — after proving the holder is
  dead (gone pid, recycled pid, previous boot) or, for a live-but-
  silent holder, escalating SIGTERM → SIGKILL with a post-kill grace.
  A holder with a *fresh* heartbeat is never signalled: acquire waits,
  then raises a diagnosable `LeaseHeld` naming it. Takeover is
  arbitrated through a second O_EXCL side file so concurrent waiters
  elect exactly one reclaimer and never unlink a just-written lease.

The lease is cooperative and host-local (default file in /tmp, keyed
by uid): it serializes *our* processes against each other, which is
exactly the wedge class the bench history shows. Multi-process SPMD
runs on the CPU backend (tests, gloo collectives) skip it — N
cooperating processes per host legitimately share that backend.

Env knobs (docs/fault_tolerance.md):
  MXTPU_LEASE_PATH         lease file (default
                           $TMPDIR/mxtpu_device_<uid>.lease)
  MXTPU_LEASE_TAKEOVER_S   heartbeat age that makes a lease stale (60)
  MXTPU_LEASE_HEARTBEAT_S  refresh interval (takeover/4, capped at 5)
  MXTPU_LEASE_ACQUIRE_S    default acquire timeout (600)
  MXTPU_LEASE_KILL_GRACE_S per-signal grace in the takeover kill (5)
  MXTPU_LEASE              =0 disables the process-wide hold()
"""
from __future__ import annotations

import json
import os
import signal
import socket
import sys
import tempfile
import threading
import time

from ..base import MXNetError, getenv
from ..observability import registry as _obs
from ..observability import telemetry as _tele
from .atomic import atomic_write, exclusive_create
from .chaos import chaos_point

__all__ = ["DeviceLease", "LeaseHeld", "default_lease_path", "read_lease",
           "reclaim_stale", "hold", "release_hold", "held_state",
           "lease_wanted"]

ACQUIRE_SECONDS = _obs.histogram(
    "resilience.lease.acquire.seconds",
    "Wall time one DeviceLease.acquire spent winning the lease "
    "(including any takeover)")
TAKEOVERS = _obs.counter(
    "resilience.lease.takeovers",
    "Stale leases reclaimed (holder dead or heartbeat past the hard "
    "timeout)")
HEARTBEAT_AGE = _obs.gauge(
    "resilience.lease.heartbeat.age",
    "Last observed lease heartbeat age in seconds (holder refresh and "
    "waiter polls both update it)")
HELD = _obs.gauge(
    "resilience.lease.held",
    "1 while this process holds the device lease (label path)")


def default_lease_path():
    """MXTPU_LEASE_PATH, or the per-uid /tmp default. tools/
    kill_stale.py mirrors this computation (it must work with stdlib
    only, even when the framework env is broken)."""
    return os.environ.get("MXTPU_LEASE_PATH") or os.path.join(
        tempfile.gettempdir(), "mxtpu_device_%d.lease" % os.getuid())


def lease_wanted(_platforms=None):
    """Should this process hold the device lease? Explicit MXTPU_LEASE
    wins (=0 forbids, =1 forces); otherwise accelerator targets yes,
    explicit-CPU targets no — N cooperating CPU processes per host
    (tests, gloo collectives) legitimately share that backend. Decided
    from config/env, NEVER from backend state: querying the backend
    would initialize the very thing the lease gates. Only the PRIMARY
    platform counts — "tpu,cpu" (an accelerator with a cpu fallback)
    is an accelerator target. `_platforms` injects the platform spec
    for tests."""
    env = os.environ.get("MXTPU_LEASE", os.environ.get("MXNET_LEASE"))
    if env is not None and env != "":
        return env not in ("0", "false")
    if _platforms is None:
        try:
            import jax
            _platforms = jax.config.jax_platforms or os.environ.get(
                "JAX_PLATFORMS", "")
        except (ImportError, AttributeError):
            _platforms = os.environ.get("JAX_PLATFORMS", "")
    primary = (_platforms or "").split(",")[0].strip()
    return primary != "cpu"


def _boot_id():
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            return f.read().strip()
    except OSError:
        return ""


def _proc_starttime(pid):
    """The /proc starttime tick of `pid`, or None when the pid is gone
    or a zombie (dead-but-unreaped holds no lease and can't be killed
    further). (pid, starttime) identifies a process across pid reuse —
    the same field tools/kill_stale.py ages candidates by."""
    try:
        with open("/proc/%d/stat" % pid, "rb") as f:
            stat = f.read().decode("utf-8", "replace")
        fields = stat.rsplit(")", 1)[1].split()
        if fields[0] in ("Z", "X", "x"):
            return None
        return int(fields[19])
    except (OSError, IndexError, ValueError):
        return None


def read_lease(path=None):
    """Parse the lease file into its holder record, or None when the
    file is absent or unreadable/torn (the caller falls back to file
    mtime for staleness in that case)."""
    path = path or default_lease_path()
    try:
        with open(path) as f:
            raw = f.read()
    except OSError:
        return None
    try:
        rec = json.loads(raw)
    except ValueError:
        return None
    return rec if isinstance(rec, dict) else None


def _holder_alive(rec):
    """Best-effort holder liveness. True means "may still be running"
    (conservative); False means provably dead: gone pid, recycled pid
    (starttime mismatch), or a lease from a previous boot. A holder on
    another host can't be inspected — only its heartbeat age counts."""
    pid = rec.get("pid")
    if not isinstance(pid, int) or pid <= 0:
        return False
    if rec.get("host") and rec["host"] != socket.gethostname():
        return True
    bid = _boot_id()
    if bid and rec.get("boot_id") and rec["boot_id"] != bid:
        return False
    st = _proc_starttime(pid)
    if st is None:
        return False
    recorded = rec.get("starttime")
    if isinstance(recorded, int) and st != recorded:
        return False
    return True


def _heartbeat_age(rec):
    return max(0.0, time.time() - float(rec.get("heartbeat",
                                                rec.get("created", 0.0))))


class LeaseHeld(MXNetError):
    """acquire() ran out of budget: a LIVE holder with a FRESH
    heartbeat owns the device. `.holder` carries its lease record —
    the diagnosable replacement for the old skip-and-pray retry."""

    def __init__(self, msg, holder=None):
        super().__init__(msg)
        self.holder = holder


class DeviceLease:
    """Cooperative on-disk lease with heartbeat and hard-timeout
    takeover (module docstring). Context-manager:

        with DeviceLease(what="bench") as dl:
            ... exclusive device access ...
    """

    def __init__(self, path=None, takeover_s=None, heartbeat_s=None,
                 kill_grace_s=None, what="device"):
        self.path = os.fspath(path) if path else default_lease_path()
        self.takeover_s = float(
            takeover_s if takeover_s is not None
            else getenv("MXTPU_LEASE_TAKEOVER_S", 60.0))
        if heartbeat_s is None:
            heartbeat_s = getenv("MXTPU_LEASE_HEARTBEAT_S", 0.0)
        self.heartbeat_s = (float(heartbeat_s) if heartbeat_s
                            else max(0.05, min(5.0, self.takeover_s / 4.0)))
        self.kill_grace_s = float(
            kill_grace_s if kill_grace_s is not None
            else getenv("MXTPU_LEASE_KILL_GRACE_S", 5.0))
        self.what = what
        self.takeovers = 0
        self.taken_over_from = None   # last evicted holder's record
        self.lost = False
        self._record = None
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread = None

    # -- state ----------------------------------------------------------
    def held(self):
        return self._record is not None

    def state(self):
        """Snapshot for observability / the BENCH record: current file
        holder (maybe us), its heartbeat age, our takeover count."""
        out = {"path": self.path, "held": self.held(),
               "takeovers": self.takeovers}
        cur = read_lease(self.path)
        if cur is not None:
            out["holder"] = {k: cur.get(k) for k in
                             ("pid", "host", "what", "created")}
            out["heartbeat_age_s"] = round(_heartbeat_age(cur), 3)
        return out

    def _my_record(self):
        pid = os.getpid()
        return {"pid": pid, "host": socket.gethostname(),
                "boot_id": _boot_id(), "starttime": _proc_starttime(pid),
                "what": self.what,
                "cmdline": " ".join(sys.argv)[:200],
                "created": time.time(), "heartbeat": time.time(),
                "heartbeat_s": self.heartbeat_s,
                "takeover_s": self.takeover_s}

    # -- acquire / release ---------------------------------------------
    def acquire(self, timeout=None):
        """Win the lease or raise. Waiters poll; a stale holder (dead,
        or live with a heartbeat past `takeover_s`) is taken over; a
        fresh live holder makes acquire block until `timeout`, then
        raise `LeaseHeld` with the holder record."""
        if self.held():
            return self
        chaos_point("lease.acquire")
        if timeout is None:
            timeout = getenv("MXTPU_LEASE_ACQUIRE_S", 600.0)
        timeout = float(timeout)
        t0 = time.monotonic()
        poll = max(0.05, min(1.0, self.takeover_s / 10.0))
        holder = None
        while True:
            rec = self._my_record()
            if exclusive_create(self.path,
                                json.dumps(rec, sort_keys=True)):
                with self._lock:
                    self._record = rec
                    self.lost = False
                self._start_heartbeat()
                dt = time.monotonic() - t0
                ACQUIRE_SECONDS.observe(dt)
                HELD.set(1, path=self.path)
                _tele.emit({"ts": time.time(), "source": "resilience",
                            "event": "lease_acquire", "step_time": dt,
                            "what": self.what, "path": self.path,
                            "takeovers": self.takeovers})
                return self
            holder = read_lease(self.path)
            if holder is None:
                # unreadable/torn record (a non-atomic foreign writer):
                # only the file mtime can age it
                try:
                    age = time.time() - os.stat(self.path).st_mtime
                except OSError:
                    continue       # released under us: retry the create
                if age > self.takeover_s and self._reclaim({},
                                                           kill=False):
                    continue
            else:
                hb_age = _heartbeat_age(holder)
                HEARTBEAT_AGE.set(hb_age, path=self.path)
                if not _holder_alive(holder):
                    if self._reclaim(holder, kill=False):
                        continue
                elif hb_age > self.takeover_s:
                    # live pid, silent heartbeat: the wedged-holder mode
                    if self._reclaim(holder, kill=True):
                        continue
            if time.monotonic() - t0 >= timeout:
                raise LeaseHeld(
                    "device lease %s held by a live holder (pid %s on "
                    "%s, role %r, heartbeat %.1fs ago, takeover at "
                    "%.6gs) — it is doing real work; not killed"
                    % (self.path,
                       holder.get("pid") if holder else "?",
                       holder.get("host") if holder else "?",
                       holder.get("what") if holder else "?",
                       _heartbeat_age(holder) if holder else 0.0,
                       self.takeover_s), holder=holder)
            time.sleep(poll)

    def release(self):
        """Stop the heartbeat and remove the lease file — but only if
        it is still OURS: a taker that (rightly) reclaimed after we
        went silent must not lose its fresh lease to our unlink."""
        self._stop.set()
        th = self._thread
        if th is not None and th is not threading.current_thread():
            th.join(timeout=2.0 * self.heartbeat_s + 2.0)
        with self._lock:
            rec, self._record = self._record, None
            if rec is None:
                return
            cur = read_lease(self.path)
            if cur is not None and cur.get("pid") == rec["pid"] \
                    and cur.get("created") == rec["created"]:
                try:
                    os.unlink(self.path)
                except OSError:
                    pass
        HELD.set(0, path=self.path)

    def __enter__(self):
        return self.acquire()

    def __exit__(self, *exc):
        self.release()
        return False

    # -- heartbeat ------------------------------------------------------
    def _start_heartbeat(self):
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._heartbeat_loop, daemon=True,
            name="lease-heartbeat:%s" % self.what)
        self._thread.start()

    def _heartbeat_loop(self):
        while not self._stop.wait(self.heartbeat_s):
            self.refresh()

    def refresh(self):
        """One heartbeat write (the daemon thread's body; callable
        synchronously in tests). Verifies ownership first: if the file
        now names someone else we were taken over — mark the lease
        lost and stand down rather than stomping the new holder."""
        with self._lock:
            rec = self._record
            if rec is None:
                return False
            cur = read_lease(self.path)
            if cur is None or cur.get("pid") != rec["pid"] \
                    or cur.get("created") != rec["created"]:
                self.lost = True
                self._record = None
                self._stop.set()
                HELD.set(0, path=self.path)
                return False
            HEARTBEAT_AGE.set(_heartbeat_age(rec), path=self.path)
            rec = dict(rec, heartbeat=time.time())
            try:
                with atomic_write(self.path, "w") as f:
                    f.write(json.dumps(rec, sort_keys=True))
            except OSError:
                return False
            self._record = rec
            return True

    # -- takeover -------------------------------------------------------
    def _reclaim(self, stale, kill):
        """Clear a stale lease. Guarded by an O_EXCL side file so N
        waiters elect exactly one reclaimer; the re-reads below make
        sure a lease that changed hands (or heartbeat) mid-decision is
        left alone. Returns True when the file was cleared — the
        caller then races the O_EXCL create like everyone else."""
        guard = self.path + ".takeover"
        t0 = time.monotonic()
        if not exclusive_create(guard, json.dumps(
                {"pid": os.getpid(), "ts": time.time()})):
            # another claimant is mid-takeover; break ITS guard only if
            # it died mid-reclaim (guard older than the full kill budget)
            try:
                gage = time.time() - os.stat(guard).st_mtime
            except OSError:
                return False
            if gage > max(30.0, self.takeover_s + 2 * self.kill_grace_s):
                try:
                    os.unlink(guard)
                except OSError:
                    pass
            return False
        try:
            cur = read_lease(self.path)
            if cur is not None and stale and (
                    cur.get("pid") != stale.get("pid")
                    or cur.get("created") != stale.get("created")):
                return False   # changed hands while we decided
            ref = cur if cur is not None else stale
            if kill and ref and _holder_alive(ref):
                if not self._kill_holder(ref):
                    return False
            # last look before the unlink: a holder that heartbeat in
            # the window keeps its lease (it was slow, not wedged)
            cur = read_lease(self.path)
            if cur is not None and _holder_alive(cur) \
                    and _heartbeat_age(cur) <= self.takeover_s:
                return False
            try:
                os.unlink(self.path)
            except OSError:
                return False
            self.takeovers += 1
            self.taken_over_from = ref or None
            TAKEOVERS.inc()
            _tele.emit({"ts": time.time(), "source": "resilience",
                        "event": "lease_takeover",
                        "step_time": time.monotonic() - t0,
                        "path": self.path, "what": self.what,
                        "holder_pid": (ref or {}).get("pid"),
                        "killed": bool(kill),
                        "heartbeat_age_s": (_heartbeat_age(ref)
                                            if ref else None)})
            return True
        finally:
            try:
                os.unlink(guard)
            except OSError:
                pass

    def _kill_holder(self, rec):
        """SIGTERM → SIGKILL escalation with a per-signal grace, after
        verifying the target really is the recorded holder: matching
        /proc starttime when the record carries one (the strong check —
        that pid wrote this lease), else the kill_stale cmdline/
        accelerator-marker heuristics. An unverifiable pid is never
        signalled. Returns True once the holder is provably gone."""
        pid = rec.get("pid")
        if not isinstance(pid, int) or pid <= 0:
            return True
        if rec.get("host") and rec["host"] != socket.gethostname():
            return False           # cannot signal a foreign host
        st = _proc_starttime(pid)
        if st is None:
            return True            # already gone
        recorded = rec.get("starttime")
        if isinstance(recorded, int):
            if st != recorded:
                return True        # pid recycled: holder is gone
        elif not _looks_like_ours(pid):
            return False
        for sig, grace in ((signal.SIGTERM, self.kill_grace_s),
                           (signal.SIGKILL, self.kill_grace_s)):
            try:
                os.kill(pid, sig)
            except ProcessLookupError:
                return True
            except PermissionError:
                return False
            end = time.monotonic() + max(0.2, grace)
            while time.monotonic() < end:
                if _proc_starttime(pid) != st:
                    return True
                time.sleep(0.05)
        return _proc_starttime(pid) != st


def _looks_like_ours(pid):
    """tools/kill_stale.py's target test: a framework/bench cmdline or
    an accelerator .so in the maps. Only used for lease records without
    a starttime (foreign or pre-starttime writers)."""
    def _read(path):
        try:
            with open(path, "rb") as f:
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""
    cmd = _read("/proc/%d/cmdline" % pid).replace("\0", " ")
    if any(m in cmd for m in ("bench.py", "mxnet_tpu")):
        return True
    maps = _read("/proc/%d/maps" % pid)
    return any(m in maps for m in ("libtpu",))


def reclaim_stale(path=None):
    """Out-of-band takeover for tools (kill_stale): clear the lease at
    `path` iff it is stale by the lease's own recorded contract —
    holder dead, or live with a heartbeat past its takeover window (the
    wedged holder is killed with the same SIGTERM→SIGKILL ladder).
    Returns True when the lease file is gone afterwards, False when a
    fresh live holder keeps it."""
    dl = DeviceLease(path=path, what="reclaim")
    rec = read_lease(dl.path)
    if rec is None:
        return not os.path.exists(dl.path)
    if isinstance(rec.get("takeover_s"), (int, float)):
        dl.takeover_s = float(rec["takeover_s"])
    alive = _holder_alive(rec)
    if alive and _heartbeat_age(rec) <= dl.takeover_s:
        return False
    dl._reclaim(rec, kill=alive)
    return not os.path.exists(dl.path)


# -- process-wide shared hold (serving / training) ----------------------
_process = {"lease": None, "refs": 0}
_process_lock = threading.Lock()


def hold(what="device", timeout=None, path=None):
    """Refcounted process-wide lease: the first caller acquires, later
    callers ride along — one process is one device grant, however many
    servers/trainers it runs. Pair with `release_hold()`."""
    with _process_lock:
        dl = _process["lease"]
        if dl is None or not dl.held():
            # re-acquiring after the old lease was LOST (usurped) must
            # keep the outstanding refcount: earlier holders still ride
            # the process-wide grant, and their release_hold() must not
            # drop the fresh lease out from under everyone else
            if dl is None:
                _process["refs"] = 0
            dl = DeviceLease(path=path, what=what)
            dl.acquire(timeout=timeout)
            _process["lease"] = dl
        _process["refs"] += 1
        return dl


def release_hold():
    """Drop one reference on the process-wide lease; the last drop
    releases the file."""
    with _process_lock:
        if _process["lease"] is None:
            return
        _process["refs"] -= 1
        if _process["refs"] <= 0:
            _process["lease"].release()
            _process["lease"] = None
            _process["refs"] = 0


def held_state():
    """The process-wide lease's `state()` snapshot, or None when no
    hold is active (what ModelServer.stats reports)."""
    with _process_lock:
        dl = _process["lease"]
    return dl.state() if dl is not None and dl.held() else None
