"""Find (and optionally kill) stale framework processes that could be
holding or blocking the accelerator lease.

Reference analog: tools/kill-mxnet.py — a cluster-wide `pkill` over a
hostfile. The TPU-native redesign is single-host (the device belongs
to one process at a time) and far more careful, because the failure
mode differs: SIGKILLing a process that has an *active* device lease
kills real work and can leave the device in a bad state — exactly the
outage this tool exists to recover from. So:

  * processes merely *hung in PJRT init* (dialing the pool, no grant
    yet) are safe to kill and are this tool's main target;
  * a process that plausibly HOLDS the lease (accelerator .so mapped
    AND old enough to have finished init) is only killed under
    --force, with a loud warning.

Usage:
    python tools/kill_stale.py            # list candidates
    python tools/kill_stale.py --kill     # kill init-hung candidates
    python tools/kill_stale.py --kill --force   # accel-mapped too
    python tools/kill_stale.py --kill --force --expired
                                          # even a fresh lease holder

Serving front doors (mxnet_tpu/serving/gateway/, ISSUE 12) hold the
lease with role "gateway"; kill_stale surfaces that role (tag GATEWAY
/ GATEWAY-EXPIRED) and reaps a wedged one by the SAME ladder as
training/serving holders: fresh heartbeat refused (exit 2), expired
heartbeat reaped and the lease cleared.

Supervised gangs (resilience/supervisor.py, ISSUE 8) are recognized by
the MXTPU_GANG_DIR tag in a candidate's environment: when the gang's
supervisor is alive (pid + starttime + boot id from
<gang_dir>/supervisor.json, heartbeat fresh), the worker is tagged
SUPERVISED and NEVER reaped — killing it would only trigger a
supervisor restart (reap the supervisor instead if the gang itself is
the problem). A refused supervised worker exits 2 like a refused lease
holder. Workers whose supervisor is dead fall through to the normal
heuristics.

The on-disk device lease (mxnet_tpu/resilience/lease.py, ISSUE 7) is
read FIRST and is ground truth over every /proc heuristic:

  * a recorded holder with a FRESH heartbeat is working — it is never
    killed, not even under --force (that kill is the very wedge this
    tool exists to recover from); overriding requires BOTH --force and
    --expired, and a refused live holder makes the run exit 2 so
    callers know recovery is blocked;
  * a holder whose heartbeat is past its takeover window is stale by
    the lease's own contract: --kill reaps it and clears the lease
    file (the out-of-band twin of DeviceLease's takeover);
  * an orphan lease file (holder dead) is removed under --kill.

Heuristics (all /proc-based, no deps — the lease file is plain JSON,
parsed with stdlib so this tool works even when the framework env is
broken):
  * candidate = a python process, not us/our ancestors, whose cmdline
    mentions this repo, bench.py, or whose maps include the PJRT
    plugin (libtpu).
  * "init-hung" requires POSITIVE evidence the process is still
    dialing: old enough to judge (> --init-grace seconds) yet with
    negligible lifetime CPU (a process that completed init and did any
    real work burns far more). A bare probe one-liner is also safe at
    any age. Everything else accel-mapped — including processes too
    young to judge — is treated as a potential lease holder and only
    killed under --force: killing an active holder is the very wedge
    this tool exists to recover from.

Remote cleanup over a DMLC hostfile (the reference's use case) rides
tools/launch.py's ssh plumbing:
`tools/launch.py -H hostfile --cleanup --kill` (list-only without
--kill).
"""
import argparse
import json
import os
import signal
import socket
import sys
import tempfile
import time

ACCEL_SO_MARKERS = ("libtpu",)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CMD_MARKERS = ("bench.py", _REPO_ROOT, "mxnet_tpu")


def default_lease_path():
    """Mirror of resilience.lease.default_lease_path (stdlib-only on
    purpose: this tool must run when the framework env is broken)."""
    return os.environ.get("MXTPU_LEASE_PATH") or os.path.join(
        tempfile.gettempdir(), "mxtpu_device_%d.lease" % os.getuid())


def read_lease(path):
    """The lease record at `path`, or None (absent/torn file)."""
    try:
        with open(path) as f:
            rec = json.loads(f.read())
    except (OSError, ValueError):
        return None
    return rec if isinstance(rec, dict) else None


def lease_state(path=None):
    """(record, fresh, alive) for the lease at `path`. `fresh` means
    the heartbeat is within the record's own takeover window (or
    MXTPU_LEASE_TAKEOVER_S / 60s when the record lacks one); `alive`
    means the recorded pid still exists with the recorded /proc
    starttime (pid-reuse safe)."""
    path = path or default_lease_path()
    rec = read_lease(path)
    if rec is None:
        return None, False, False
    takeover = rec.get("takeover_s")
    if not isinstance(takeover, (int, float)) or takeover <= 0:
        takeover = float(os.environ.get("MXTPU_LEASE_TAKEOVER_S", 60))
    hb_age = time.time() - float(rec.get("heartbeat",
                                         rec.get("created", 0.0)))
    fresh = hb_age <= float(takeover)
    pid = rec.get("pid")
    if rec.get("host") and rec["host"] != socket.gethostname():
        # a holder on another host (shared-filesystem lease path) can't
        # be inspected from here — treat it as alive so only its own
        # heartbeat can age it out (mirrors lease._holder_alive)
        return rec, fresh, True
    alive = False
    if isinstance(pid, int) and pid > 0:
        stat = _read("/proc/%d/stat" % pid)
        try:
            fields = stat.rsplit(")", 1)[1].split()
            # a zombie holds no lease (dead, just unreaped)
            start = None if fields[0] in ("Z", "X", "x") \
                else int(fields[19])
        except (IndexError, ValueError):
            start = None
        recorded = rec.get("starttime")
        alive = start is not None and (
            not isinstance(recorded, int) or start == recorded)
    return rec, fresh, alive


def _read(path):
    try:
        with open(path, "rb") as f:
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


def gang_state(pid):
    """(gang_dir, supervisor_alive) for a supervised worker: the gang
    dir comes from MXTPU_GANG_DIR in the candidate's environment, and
    the supervisor record from <gang_dir>/supervisor.json — the same
    identity/heartbeat record shape as the device lease, so liveness
    and freshness reuse `lease_state` verbatim (one pid-reuse defense,
    not two). Alive means the recorded pid still exists with the
    recorded starttime AND its heartbeat is fresh; a foreign-host
    record can only be aged out by its own heartbeat — a stale record
    from a reimaged host must not protect orphan workers forever. A
    dead or silent supervisor protects nothing."""
    gdir = None
    for chunk in _read("/proc/%d/environ" % pid).split("\0"):
        if chunk.startswith("MXTPU_GANG_DIR="):
            gdir = chunk.split("=", 1)[1] or None
    if gdir is None:
        return None, False
    rec, fresh, alive = lease_state(os.path.join(gdir,
                                                 "supervisor.json"))
    if rec is None:
        return gdir, False
    return gdir, alive and fresh


def _ancestors_of_self():
    pids = set()
    pid = os.getpid()
    while pid > 1:
        pids.add(pid)
        stat = _read("/proc/%d/stat" % pid)
        try:  # field 4 is ppid; comm (field 2) may contain spaces
            pid = int(stat.rsplit(")", 1)[1].split()[1])
        except (IndexError, ValueError):
            break
    pids.add(1)
    return pids


def find_candidates(init_grace=600, lease_path=None):
    """Yield dicts describing stale-process candidates. The lease file
    is read first: its holder is tagged (`lease_holder`/`lease_fresh`)
    and surfaced even when the /proc heuristics would miss it."""
    lrec, lfresh, lalive = lease_state(lease_path)
    holder_pid = lrec.get("pid") if lrec else None
    if lrec is not None and lrec.get("host") \
            and lrec["host"] != socket.gethostname():
        # a foreign-host holder's pid means nothing in OUR /proc: an
        # unrelated local process with the same number must never be
        # tagged (let alone killed) as the holder
        holder_pid = None
    skip = _ancestors_of_self()
    now = time.time()
    boot = None
    for line in _read("/proc/stat").splitlines():
        if line.startswith("btime"):
            boot = float(line.split()[1])
    hz = os.sysconf("SC_CLK_TCK")
    out = []
    for ent in os.listdir("/proc"):
        if not ent.isdigit():
            continue
        pid = int(ent)
        if pid in skip:
            continue
        cmdline = _read("/proc/%d/cmdline" % pid).replace("\0", " ").strip()
        is_holder = (pid == holder_pid and lalive)
        if "python" not in cmdline and not is_holder:
            continue
        # the driver (claude ...) and shells are in `skip` via ancestry;
        # also never touch anything that doesn't look like ours — the
        # recorded lease holder always counts as ours (it wrote the file)
        maps_has_accel = any(
            m in _read("/proc/%d/maps" % pid) for m in ACCEL_SO_MARKERS)
        cmd_is_ours = any(m in cmdline for m in CMD_MARKERS)
        if not (maps_has_accel or cmd_is_ours or is_holder):
            continue
        stat = _read("/proc/%d/stat" % pid)
        try:
            fields = stat.rsplit(")", 1)[1].split()
            starttime = int(fields[19])
            age = now - (boot + starttime / hz) if boot else None
            cpu_s = (int(fields[11]) + int(fields[12])) / hz  # utime+stime
        except (IndexError, ValueError):
            age = None
            cpu_s = None
        # a bare probe one-liner never does real work after init: safe
        # to reap at any age (it is the very thing bench's recovery
        # must be able to clear)
        bare_probe = "probe_devices" in cmdline
        # positive evidence of init-hung: lived past the grace window
        # while accumulating almost no CPU — a process that finished
        # init and did ANY device work (tracing, dispatch, compile)
        # burns orders of magnitude more. Anything else accel-mapped,
        # including young or unknown-age processes, sits on the
        # hazardous side and needs --force.
        init_hung = (age is not None and cpu_s is not None
                     and age > init_grace and cpu_s < 10.0
                     and cpu_s < 0.05 * age)
        gdir, sup_alive = gang_state(pid)
        out.append({
            "pid": pid, "cmd": cmdline[:160],
            "gang_dir": gdir,
            "supervised": sup_alive,
            # the holder's recorded role ("gateway", "serving",
            # "bench", ...) — a wedged front door is diagnosed by
            # name, not by guessing from the cmdline
            "lease_role": (str(lrec.get("what", ""))
                           if is_holder and lrec else ""),
            "age_s": round(age, 1) if age is not None else -1.0,
            "cpu_s": round(cpu_s, 1) if cpu_s is not None else -1.0,
            "accel_mapped": maps_has_accel,
            "lease_holder": is_holder,
            "lease_fresh": is_holder and lfresh,
            "lease_risk": (maps_has_accel and not bare_probe
                           and not init_hung and not is_holder),
        })
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kill", action="store_true",
                    help="SIGTERM (then SIGKILL) init-hung candidates "
                         "and expired lease holders")
    ap.add_argument("--force", action="store_true",
                    help="also kill accel-mapped non-holders (HAZARD: "
                         "can leave the device unusable)")
    ap.add_argument("--expired", action="store_true",
                    help="with --force: kill even a lease holder whose "
                         "heartbeat is still fresh (last resort — the "
                         "holder is doing real work)")
    ap.add_argument("--lease-path", default=None,
                    help="device lease file (default MXTPU_LEASE_PATH "
                         "or the per-uid /tmp lease)")
    ap.add_argument("--init-grace", type=int, default=600,
                    help="minimum age (s) before an accel-mapped process "
                         "with negligible CPU is judged init-hung; "
                         "younger processes are never auto-killed")
    args = ap.parse_args(argv)

    lease_path = args.lease_path or default_lease_path()
    lrec, lfresh, lalive = lease_state(lease_path)
    if lrec is not None:
        print("lease %s: holder pid %s role %r (%s, heartbeat %s)"
              % (lease_path, lrec.get("pid"),
                 lrec.get("what", "?"),
                 "alive" if lalive else "dead",
                 "fresh" if lfresh else "EXPIRED"))
    cands = find_candidates(args.init_grace, lease_path=lease_path)
    if not cands and lrec is None:
        print("kill_stale: no stale framework processes found")
        return 0
    killed = 0
    blocked = 0
    supervised_blocked = 0
    for c in cands:
        if c["supervised"]:
            tag = "SUPERVISED"
        elif c["lease_holder"] and c.get("lease_role") == "gateway":
            # the serving front door: same refusal/reap ladder as any
            # holder, but named — a wedged gateway is a customer-facing
            # outage and the operator should know what they're reaping
            tag = "GATEWAY" if c["lease_fresh"] else "GATEWAY-EXPIRED"
        elif c["lease_holder"]:
            tag = "LEASE-HOLDER" if c["lease_fresh"] else "LEASE-EXPIRED"
        elif c["lease_risk"]:
            tag = "ACCEL-MAPPED"
        elif c["accel_mapped"]:
            tag = "init-hung"
        else:
            tag = "host-only"
        print("pid %-7d age %-8s cpu %-7s %-12s %s"
              % (c["pid"], "%.0fs" % c["age_s"], "%.1fs" % c["cpu_s"],
                 tag, c["cmd"]))
        if not args.kill:
            continue
        if c["supervised"]:
            # the supervisor owns this worker's lifecycle: killing it
            # only triggers a gang restart — never a recovery. Reap the
            # SUPERVISOR if the gang itself is the problem.
            print("  -> refused (supervised worker, gang supervisor "
                  "alive in %s; kill the supervisor to stop the gang)"
                  % c["gang_dir"])
            blocked += 1
            supervised_blocked += 1
            continue
        if c["lease_fresh"] and not (args.force and args.expired):
            # lease ground truth: a fresh heartbeat means the holder is
            # WORKING. Killing it is the wedge, not the recovery.
            print("  -> refused (lease holder with a fresh heartbeat; "
                  "it will be reclaimed automatically if it wedges — "
                  "--force --expired to override)")
            blocked += 1
            continue
        if c["lease_risk"] and not args.force:
            print("  -> skipped (accel-mapped but not the lease "
                  "holder and not init-hung; --force to kill anyway)")
            continue
        if not c["accel_mapped"] and not c["lease_holder"] \
                and not args.force:
            # host-only work can't be blocking the accelerator lease;
            # killing it wouldn't help recovery, so require --force
            print("  -> skipped (host-only, not a lease blocker; "
                  "--force to kill anyway)")
            continue
        try:
            os.kill(c["pid"], signal.SIGTERM)
            time.sleep(1.0)
            os.kill(c["pid"], 0)  # still alive?
            os.kill(c["pid"], signal.SIGKILL)
        except ProcessLookupError:
            pass
        except PermissionError:
            print("  -> EPERM")
            continue
        killed += 1
        print("  -> killed")
    if args.kill and lrec is not None and lfresh and lalive \
            and lrec.get("host") and lrec["host"] != socket.gethostname():
        # live fresh holder on ANOTHER host (shared-filesystem lease):
        # nothing this host can or should do — recovery is blocked
        print("lease %s: live holder on host %s — cannot recover from "
              "here" % (lease_path, lrec["host"]))
        blocked += 1
    if args.kill and lrec is not None \
            and blocked == supervised_blocked:
        # holder dead (was dead, or reaped above): clear the orphan
        # lease so the next acquire wins O_EXCL immediately instead of
        # waiting out the takeover window. A refused SUPERVISED worker
        # does not block the clear — it says nothing about the lease.
        if killed:
            time.sleep(0.2)   # let a just-SIGKILLed holder leave /proc
        _, _, still_alive = lease_state(lease_path)
        if not still_alive:
            try:
                os.unlink(lease_path)
                print("lease %s: cleared (holder gone)" % lease_path)
            except OSError:
                pass
    if args.kill:
        print("kill_stale: killed %d/%d" % (killed, len(cands)))
        if blocked:
            print("kill_stale: %d live lease holder(s)/supervised "
                  "worker(s) refused — recovery blocked" % blocked)
            return 2
    else:
        print("kill_stale: %d candidate(s) listed (no --kill)" % len(cands))
    return 0


if __name__ == "__main__":
    sys.exit(main())
