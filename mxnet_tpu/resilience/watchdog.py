"""Deadline-bounded device init and hung-collective monitoring.

The two hang modes a run on the device can meet are (1) PJRT backend
init blocking forever behind a hung lease holder, and (2) a cross-process collective blocking forever because a
peer died mid-run. `HealthWatchdog` bounds both:

* `init_devices()` wraps `base.probe_devices` (the daemon-thread
  probe) with a deadline; on trip it dumps the lease holder plus its
  /proc state and raises a typed `DeviceUnreachable` — callers
  (`Context` backend init, bench's probe child, `init_distributed`)
  get a diagnosable error instead of a hang.
* `guard_collective()` runs a collective (`DistKVStore.barrier`, one
  bucketed allreduce) under `resilience.retry.run_with_deadline`; a
  trip dumps the same diagnostics, bumps `resilience.watchdog.trips`,
  and re-raises the `DeadlineExceeded` so the caller aborts cleanly.

Every trip is counted (`resilience.watchdog.trips{kind=...}`) and, when
``MXTPU_TELEMETRY`` streams, recorded as a `source="resilience"`
`watchdog_trip` event — so a failed round is diagnosable from the
telemetry file alone (tools/telemetry_report.py's lease/watchdog
section).

* `guard_dispatch()` bounds one SERVING engine dispatch (ISSUE 14,
  docs/fault_tolerance.md "Serving resilience"): a wedged XLA dispatch
  trips as a typed `DeviceUnreachable` in bounded time — the replica
  health machinery in `serving.server`/`serving.scheduler` quarantines
  the replica instead of letting every request on it hang forever.

Env knobs (docs/fault_tolerance.md):
  MXTPU_WATCHDOG_INIT_S        device-init deadline (180; 0 disables)
  MXTPU_WATCHDOG_COLLECTIVE_S  default collective deadline when the
                               call site doesn't pass one (0 = off)
  MXTPU_SERVE_DISPATCH_TIMEOUT_S
                               serving-dispatch deadline (0 = off; the
                               default — the watchdog-off path is the
                               plain direct call, bit-identical)
"""
from __future__ import annotations

import threading
import time

from ..base import MXNetError, getenv, probe_devices
from ..observability import registry as _obs
from ..observability import telemetry as _tele
from . import lease as _lease
from .chaos import chaos_point
from .retry import DeadlineExceeded, run_with_deadline

__all__ = ["DeviceUnreachable", "HealthWatchdog", "diagnostics"]

TRIPS = _obs.counter(
    "resilience.watchdog.trips",
    "Watchdog deadline trips (label kind: init / collective / "
    "dispatch)")

_log = None


def _logger():
    global _log
    if _log is None:
        from ..log import get_logger
        _log = get_logger("mxnet_tpu.resilience")
    return _log


class DeviceUnreachable(MXNetError):
    """Device backend init failed or timed out. The message carries the
    probe error plus the lease/holder diagnostics; `.diagnostics` holds
    the dump alone for machine consumers."""

    def __init__(self, msg, diagnostics=None):
        super().__init__(msg + ("\n" + diagnostics if diagnostics else ""))
        self.diagnostics = diagnostics


def _read_proc(pid, name):
    try:
        with open("/proc/%d/%s" % (pid, name), "rb") as f:
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


def diagnostics(lease_path=None):
    """One-string dump for a tripped watchdog: the lease holder (the
    prime suspect for an init hang) and its /proc state — enough for a
    post-mortem without a live session."""
    path = lease_path or _lease.default_lease_path()
    lines = []
    rec = _lease.read_lease(path)
    if rec is None:
        lines.append("lease %s: no holder recorded" % path)
    else:
        age = time.time() - float(rec.get("heartbeat",
                                          rec.get("created", 0.0)))
        lines.append(
            "lease %s: holder pid %s on %s (role %r), heartbeat %.1fs "
            "ago (takeover at %.6gs)"
            % (path, rec.get("pid"), rec.get("host"), rec.get("what"),
               age, rec.get("takeover_s", 0.0)))
        pid = rec.get("pid")
        if isinstance(pid, int) and pid > 0:
            stat = _read_proc(pid, "stat")
            if stat:
                fields = stat.rsplit(")", 1)[-1].split()
                state = fields[0] if fields else "?"
                lines.append("holder /proc: state %s  cmdline %r  "
                             "wchan %s"
                             % (state,
                                _read_proc(pid, "cmdline")
                                .replace("\0", " ").strip()[:120],
                                _read_proc(pid, "wchan").strip() or "?"))
            else:
                lines.append("holder /proc: pid %d is gone" % pid)
    return "\n".join(lines)


class HealthWatchdog:
    """Deadline policies for the two hang-prone device paths (module
    docstring). One instance per subsystem is fine — state is just the
    configured budgets."""

    def __init__(self, init_timeout_s=None, collective_timeout_s=None,
                 lease_path=None):
        self.init_timeout_s = float(
            init_timeout_s if init_timeout_s is not None
            else getenv("MXTPU_WATCHDOG_INIT_S", 180.0))
        self.collective_timeout_s = float(
            collective_timeout_s if collective_timeout_s is not None
            else getenv("MXTPU_WATCHDOG_COLLECTIVE_S", 0.0))
        self.dispatch_timeout_s = float(
            getenv("MXTPU_SERVE_DISPATCH_TIMEOUT_S", 0.0))
        self.lease_path = lease_path
        # persistent guard worker (peer-checked collectives run every
        # bucket through here — a fresh thread per call would tax the
        # hot allreduce path); lazily started, single-slot
        self._worker_lock = threading.Lock()
        self._worker_q = None
        self._worker_busy = False

    # -- guard worker ---------------------------------------------------
    def _worker_loop(self, q):
        while True:
            fn, box, done = q.get()
            try:
                box["result"] = fn()
            except BaseException as err:  # delivered via the box
                box["error"] = err
            # the WORKER clears its own busy flag (a guard that gave
            # up on this collective is long gone; the worker must
            # become reusable the moment the stuck call returns), and
            # clears it BEFORE done.set() so the waiter's very next
            # guarded collective finds it free instead of racing into
            # the ephemeral-thread fallback
            with self._worker_lock:
                self._worker_busy = False
            done.set()

    def _submit(self, fn, what):
        """Run `fn` off-thread, returning its (box, done) pair. Reuses
        ONE persistent daemon worker; when that worker is wedged
        holding a previous collective that never returned (a tripped
        deadline — the process is suspect but may still be unwinding),
        falls back to an ephemeral thread so the guard itself never
        blocks."""
        box, done = {}, threading.Event()
        with self._worker_lock:
            if not self._worker_busy:
                if self._worker_q is None:
                    import queue
                    self._worker_q = queue.Queue()
                    threading.Thread(
                        target=self._worker_loop,
                        args=(self._worker_q,), daemon=True,
                        name="watchdog-guard-worker").start()
                self._worker_busy = True
                self._worker_q.put((fn, box, done))
                return box, done

        def target():
            try:
                box["result"] = fn()
            except BaseException as err:
                box["error"] = err
            done.set()
        threading.Thread(target=target, daemon=True,
                         name="deadline:%s" % what).start()
        return box, done

    def init_devices(self, timeout_s=None, probe=None):
        """Deadline-bounded backend init: returns the device list or
        raises `DeviceUnreachable` with holder diagnostics. `probe` is
        `(timeout_s) -> (devices|None, err)` — `base.probe_devices` by
        default, injectable for tests (the fake backend)."""
        chaos_point("device.init")
        t = float(timeout_s if timeout_s is not None
                  else self.init_timeout_s)
        probe = probe or probe_devices
        if t <= 0:      # watchdog disabled: direct (possibly hanging) init
            import jax
            return jax.devices()
        devs, err = probe(t)
        if devs is not None:
            return devs
        diag = self._trip("init", "device backend init", t)
        raise DeviceUnreachable(
            "device backend unreachable: %s (init bounded at %.6gs)"
            % (err, t), diag)

    def guard_collective(self, fn, what="collective", timeout_s=None,
                         peer_check=None):
        """Run `fn()` under a deadline; a trip dumps diagnostics and
        re-raises the `DeadlineExceeded` (clean abort — the process
        state is suspect, never silently retried). `timeout_s` 0/None
        falls back to the instance default; 0 there means unguarded.

        `peer_check` is the gang-supervision fast path
        (`resilience.supervisor.peer_checker`): a callable polled every
        `MXTPU_GANG_PEER_POLL_S` while the collective waits, raising a
        typed `PeerLost` naming the dead rank — survivors abort in
        seconds instead of waiting out the whole collective budget,
        and a deadline trip gets one final peer check so a dead peer
        is reported as `PeerLost`, never a generic `DeadlineExceeded`.
        With a peer_check, the collective is monitored even when no
        deadline is configured (a supervised gang must never block
        forever on a dead peer)."""
        return self._guard(fn, what, timeout_s,
                           self.collective_timeout_s, "collective",
                           peer_check=peer_check)

    def guard_init(self, fn, what="backend init", timeout_s=None,
                   peer_check=None):
        """Like guard_collective but for init-shaped work (trips count
        under kind=init): bounds calls such as
        `jax.distributed.initialize` that can block forever on a dead
        coordinator."""
        return self._guard(fn, what, timeout_s, self.init_timeout_s,
                           "init", peer_check=peer_check)

    def guard_dispatch(self, fn, what="engine dispatch",
                       timeout_s=None):
        """Run one serving engine dispatch under a deadline; a trip
        raises a typed `DeviceUnreachable` (kind=dispatch) with holder
        diagnostics — the wedged-device signal the serving replica
        health machinery quarantines on. `timeout_s` None falls back
        to ``MXTPU_SERVE_DISPATCH_TIMEOUT_S``; <= 0 means unguarded:
        the plain direct call, bit-identical to the pre-watchdog path.

        Same execution shape as `guard_collective`: the dispatch runs
        on the persistent daemon guard worker (a wedged XLA call
        cannot be cancelled from Python — it keeps blocking its
        thread, and later guards fall back to ephemeral threads while
        the worker is held)."""
        t = float(timeout_s if timeout_s is not None
                  else self.dispatch_timeout_s)
        if t <= 0:
            return fn()
        box, done = self._submit(fn, what)
        if not done.wait(timeout=t):
            diag = self._trip("dispatch", what, t)
            raise DeviceUnreachable(
                "%s did not complete within %.6gs — the device "
                "dispatch is wedged (the call still blocks a daemon "
                "thread; see docs/fault_tolerance.md \"Serving "
                "resilience\")" % (what, t), diag)
        if "error" in box:
            raise box["error"]
        return box.get("result")

    def _guard(self, fn, what, timeout_s, default_t, kind,
               peer_check=None):
        t = float(timeout_s if timeout_s is not None else default_t)
        if t <= 0 and peer_check is None:
            return fn()
        try:
            if peer_check is None:
                return run_with_deadline(fn, t, what=what)
            return self._guard_with_peers(fn, t, what, peer_check)
        except DeadlineExceeded as err:
            diag = self._trip(kind, what, t)
            raise DeadlineExceeded("%s\n%s" % (err, diag)) from err

    def _guard_with_peers(self, fn, t, what, peer_check):
        """run_with_deadline with a peer poll: `fn` runs on the
        persistent guard worker (a blocked collective cannot be
        cancelled from Python) while this thread waits in short
        slices, calling `peer_check` each slice. A raised `PeerLost`
        (or any peer_check error) propagates immediately — the
        collective stays blocked on its worker, the process state is
        suspect, and the caller aborts with a *named* culprit (later
        guards fall back to ephemeral threads while the worker is
        wedged). `t` <= 0 means no deadline: only the peer poll
        bounds the wait."""
        poll = max(0.05, float(getenv("MXTPU_GANG_PEER_POLL_S", 0.5)))
        box, finished = self._submit(fn, what)
        end = (time.monotonic() + t) if t > 0 else None
        while True:
            # never sleep past the deadline: a sub-poll budget must
            # trip on time, not be rounded up to the poll interval
            slice_s = poll if end is None else \
                min(poll, max(0.0, end - time.monotonic()))
            if finished.wait(timeout=slice_s):
                break
            try:
                peer_check()
            except MXNetError:
                TRIPS.inc(kind="peer")
                raise
            if end is not None and time.monotonic() >= end:
                try:
                    peer_check()   # last look: name the culprit if any
                except MXNetError:
                    TRIPS.inc(kind="peer")
                    raise
                raise DeadlineExceeded(
                    "%s did not complete within %.6gs and every gang "
                    "peer still heartbeats — a peer process likely "
                    "wedged without dying (the call is still blocked "
                    "on a daemon thread; see docs/fault_tolerance.md)"
                    % (what, t))
        if "error" in box:
            # a collective that ERRORS while a peer is dead (gloo
            # connection reset, coordinator gone) is diagnosed as the
            # dead peer — PeerLost, with the transport error chained
            try:
                peer_check()
            except MXNetError as lost:
                TRIPS.inc(kind="peer")
                raise lost from box["error"]
            raise box["error"]
        return box.get("result")

    def _trip(self, kind, what, budget):
        TRIPS.inc(kind=kind)
        diag = diagnostics(self.lease_path)
        _logger().error("watchdog trip (%s): %s exceeded %.6gs\n%s",
                        kind, what, budget, diag)
        _tele.emit({"ts": time.time(), "source": "resilience",
                    "event": "watchdog_trip", "kind": kind,
                    "what": what, "step_time": float(budget)})
        return diag
