"""The host's account of every step the span ring still holds: what each
thread did, what the collector and the OS took, which steps stalled and
what covered them; and, where a device trace exists, the idle gaps of the
traced steps by the span that covers them on every thread, laid by a
clock that each traced step bounds. Read by the metric readers
`host_gc_ms`, `stall_ms`, `feed_wait_ms` and `step_host_ms`.

A program without the spans this reads (no `gc0` on its roots: the
commits before the collector's spans) gives `host_gc_ms` nothing and it
returns None; the other three read any program that records step roots,
`fence` and `input.wait`.

**The causality clock.** Host spans carry `perf_counter` stamps; the
trace has its own clock. Each traced step bounds the shift between them:
its step program's run (`XLA Modules`) cannot start before its
`step.launch` began, so shift <= run start - launch start; and its loss
(the first `fence` that ends after the launch returns) cannot be in hand
before the run ended, so shift >= run end - fence end. The lower bound is
the step whose fence returned soonest, and the one used; the interval's
width says how well the spans are placed. `program_trace.clock_shift`
lets the last fence end where the last device operation ends, one point
that a late last fence moves.

Intervals are (start, end) in seconds; everything below `analyse` works
on plain lists of span dicts, so hand-made ones test it.
"""
import bisect
import statistics
from collections import defaultdict

import program_trace as pt
import trace_reduce as tr

say = pt.say
STALL = 1.2                      # a root over this times the median stalled
WAITS = ("fence", "input.wait")  # the two waits the program names
STEP_PROGRAMS = ("jit_sharded_step", "jit_fused_step_")
ROOT_ATTRS = ("cpu_ms", "nvcsw", "nivcsw", "majflt", "minflt", "gc0",
              "gc0_ms")
EMPTY = {"host_gc_ms": None, "stall_ms": None, "feed_wait_ms": None,
         "step_host_ms": None}


def _length(s):
    return s["t1"] - s["t0"]


def _meet(a, b):
    """The parts of merged `a` that merged `b` covers."""
    return tr.subtract(a, tr.subtract(a, b))


def _overlap(s, lo, hi):
    return max(0.0, min(s["t1"], hi) - max(s["t0"], lo))


# -- the steps the ring holds ------------------------------------------------
def held_roots(spans, window):
    """The training thread's step roots that close inside `window` (host
    clock), in order: one for each step of the window the ring holds.
    The first opened before the window did (where the step before it
    returned) and is cut at the window's opening, as the window's first
    step is: what the harness ran between the two is no step's."""
    tid = pt.consumer_tid(spans)
    return [dict(s, t0=max(s["t0"], window[0])) for s in sorted(
        (s for s in spans if s["name"] == pt.ROOT and not s.get("parent_id")
         and s["tid"] == tid and window[0] < s["t1"] <= window[1]),
        key=lambda s: s["t0"])]


def stalled(roots):
    """(median root seconds, [(index, root)] of the roots over STALL times
    it)."""
    med = statistics.median(_length(r) for r in roots)
    return med, [(i, r) for i, r in enumerate(roots)
                 if _length(r) > STALL * med]


def account(spans, roots):
    """The four metrics over the held `roots` (ms a step), from `spans`."""
    n = len(roots)
    tid = roots[0]["tid"]
    lo, hi = roots[0]["t0"], roots[-1]["t1"]
    mine = [s for s in spans if s["tid"] == tid]
    own = pt.self_intervals(mine)
    med, slow = stalled(roots)
    out = {"stall_ms": 1e3 * sum(_length(r) - med for _i, r in slow) / n}

    if any("gc0" in r for r in roots):
        gc_s = sum(_length(s) for s in spans
                   if s["name"] == "gc" and lo <= s["t0"] < hi)
        out["host_gc_ms"] = (1e3 * gc_s + sum(r.get("gc0_ms", 0.0)
                                             for r in roots)) / n
    else:
        out["host_gc_ms"] = None

    waits = [s for s in mine if s["name"] == "input.wait"
             and lo <= s["t0"] < hi]
    out["feed_wait_ms"] = (1e3 * sum(tr.total(own.get(s["span_id"], []))
                                     for s in waits) / n
                           if waits else None)

    blocked = sorted((s["t0"], s["t1"]) for s in mine if s["name"] in WAITS)
    starts = [b[0] for b in blocked]
    host = 0.0
    for r in roots:
        i = bisect.bisect_left(starts, r["t0"])
        j = bisect.bisect_left(starts, r["t1"])
        inside = tr.union(tr.clip(blocked[i:j], (r["t0"], r["t1"])))
        host += _length(r) - tr.total(inside)
    out["step_host_ms"] = 1e3 * host / n
    return out


# -- the causality clock -------------------------------------------------------
def causality_clock(spans, device):
    """(lower bound, upper bound, steps paired) of the shift from the
    host's clock to `device`'s, or None where no step program ran: the
    k-th last `step.launch` of the training thread is paired with the
    k-th last run of a step program."""
    tid = pt.consumer_tid(spans)
    launches = sorted((s for s in spans if s["name"] == "step.launch"
                       and s["tid"] == tid), key=lambda s: s["t0"])
    fences = sorted((s for s in spans if s["name"] == "fence"
                     and s["tid"] == tid), key=lambda s: s["t0"])
    starts = [f["t0"] for f in fences]
    runs = [m for m in device.modules
            if pt.module_name(m[0]).startswith(STEP_PROGRAMS)]
    pairs = list(zip(reversed(launches), reversed(runs)))
    if not pairs:
        return None
    lo, hi = float("-inf"), float("inf")
    for launch, (_name, start, end) in pairs:
        hi = min(hi, start - launch["t0"])
        k = bisect.bisect_left(starts, launch["t1"])
        if k < len(fences):
            lo = max(lo, end - fences[k]["t1"])
    return lo, hi, len(pairs)


def idle_by_cover(spans, shift, gaps, tid):
    """{(thread, span name): idle seconds that span's self time covers},
    every thread; the `gc` spans of all threads as one row and the idle
    time under no span but a root as another. Rows of different threads
    may cover the same gap."""
    gaps = tr.union(gaps)
    rows = defaultdict(float)
    threads = defaultdict(list)
    for s in spans:
        threads[s["tid"]].append(s)
    shifted = lambda parts: tr.union(                         # noqa: E731
        [(a + shift, b + shift) for a, b in parts])
    for thread, mine in threads.items():
        label = "train" if thread == tid else "thread %d" % thread
        own = pt.self_intervals(mine)
        for s in mine:
            if s["name"] != "gc" and s["span_id"] in own:
                rows[(label, s["name"])] += tr.total(
                    _meet(gaps, shifted(own[s["span_id"]])))
    rows[("any", "gc")] = tr.total(_meet(gaps, shifted(
        [(s["t0"], s["t1"]) for s in spans if s["name"] == "gc"])))
    covered = shifted([(s["t0"], s["t1"]) for s in spans
                       if s.get("parent_id") or s["name"] != pt.ROOT])
    rows[("none", "no span but a root")] = tr.total(
        tr.subtract(gaps, covered))
    return {k: v for k, v in rows.items() if v > 0}


# -- the stall table -------------------------------------------------------------
def _median_by(rows):
    keys = {k for r in rows for k in r}
    return {k: statistics.median(r.get(k, 0.0) for r in rows) for k in keys}


def stall_table(spans, roots):
    """One block of lines a stalled root (standard error's table)."""
    if not roots:
        return []
    med, slow = stalled(roots)
    tid = roots[0]["tid"]
    mine = [s for s in spans if s["tid"] == tid]
    own = pt.self_intervals(mine)
    by_trace = defaultdict(list)
    for s in mine:
        by_trace[s["trace_id"]].append(s)
    others = [s for s in spans if s["tid"] != tid and s["name"] != "gc"]

    def self_ms(root):
        out = defaultdict(float)
        for s in by_trace[root["trace_id"]]:
            if s["span_id"] != root["span_id"]:
                out[s["name"]] += 1e3 * tr.total(own.get(s["span_id"], []))
        out["(root)"] = 1e3 * tr.total(own.get(root["span_id"], []))
        return out

    def other_ms(root):
        out = defaultdict(float)
        for s in others:
            cover = _overlap(s, root["t0"], root["t1"])
            if cover > 0:
                out["%s on thread %d" % (s["name"], s["tid"])] += 1e3 * cover
        return out

    typical_self = _median_by([self_ms(r) for r in roots])
    typical_other = _median_by([other_ms(r) for r in roots])
    typical_nivcsw = statistics.median(r.get("nivcsw", 0) for r in roots)
    lines = []
    for i, root in slow:
        ms, excess = 1e3 * _length(root), 1e3 * (_length(root) - med)
        lines.append("stall: step %d of %d held (root step %s): %.1f ms, "
                     "%.2f times the median %.1f ms"
                     % (i + 1, len(roots), root.get("step", "?"), ms,
                        _length(root) / med, 1e3 * med))
        mine_ms = self_ms(root)
        lines.append("  self ms on the training thread: " + ", ".join(
            "%s %.1f" % kv for kv in sorted(mine_ms.items(),
                                            key=lambda kv: -kv[1])))
        theirs = other_ms(root)
        lines.append("  other threads' spans over it, ms: " + (", ".join(
            "%s %.1f" % kv for kv in sorted(theirs.items(),
                                            key=lambda kv: -kv[1])[:6])
            or "none"))
        gcs = [s for s in spans if s["name"] == "gc"
               and _overlap(s, root["t0"], root["t1"]) > 0]
        lines.append("  gc: " + (", ".join(
            "generation %s %.1f ms (collected %s, thread %d)"
            % (s.get("generation"), 1e3 * _length(s), s.get("collected"),
               s["tid"]) for s in gcs) or "none"))
        lines.append("  root: " + (" ".join(
            "%s %s" % (k, ("%.1f" % root[k]) if isinstance(root[k], float)
                       else root[k]) for k in ROOT_ATTRS if k in root)
            or "no OS account (a program without it)"))
        gc_ms = 1e3 * sum(_overlap(s, root["t0"], root["t1"]) for s in gcs)
        grew = {k: v - typical_self.get(k, 0.0) for k, v in mine_ms.items()}
        name, most = max(grew.items(), key=lambda kv: kv[1])
        other = {k: v - typical_other.get(k, 0.0) for k, v in theirs.items()}
        oname, omost = (max(other.items(), key=lambda kv: kv[1])
                        if other else ("", 0.0))
        cpu = root.get("cpu_ms")
        if gc_ms >= 0.5 * excess:
            why = "a collection (generation %s, %.1f ms)" % (
                max(s.get("generation", 0) for s in gcs), gc_ms)
        elif root.get("majflt", 0) > 0 or root.get("nivcsw", 0) > max(
                10, 5 * typical_nivcsw):
            why = "the OS (nivcsw %s, majflt %s)" % (
                root.get("nivcsw"), root.get("majflt"))
        elif omost >= 0.5 * excess:
            why = "another thread (%s, %.1f ms over its median)" % (
                oname, omost)
        elif name in WAITS and cpu is not None and cpu < 0.5 * ms:
            why = ("a wait in the runtime with the thread asleep (nvcsw %s, "
                   "cpu_ms %.1f of %.1f)" % (root.get("nvcsw"), cpu, ms))
        else:
            why = "host work (cpu_ms %s)" % (
                "unknown" if cpu is None else "%.1f" % cpu)
        lines.append("  cover: %s, %.1f ms over its median of the %.1f ms "
                     "excess; %s" % (name, most, excess, why))
    return lines


# -- one analysis a run, printed once ---------------------------------------------
def analyse(run):
    """The four metrics of `run` (the harness's dict), computed once and
    kept on it; the tables go to standard error."""
    if "_host_account" in run:
        return run["_host_account"]
    out = run["_host_account"] = dict(EMPTY)
    steps = run["steps"]
    spans = pt.collect_spans()
    if not spans or not steps:
        say("host_account: no span ring or no step: no host account")
        return out
    window = (run["t_open"], steps[-1][3])
    roots = held_roots(spans, window)
    say("host_account: the ring holds %d spans, %d of the window's %d "
        "steps" % (len(spans), len(roots), len(steps)))
    if not roots:
        return out
    out.update(account(spans, roots))
    tid = roots[0]["tid"]
    lo, hi = roots[0]["t0"], roots[-1]["t1"]
    inside = [s for s in spans if lo <= s["t0"] < hi]
    med, slow = stalled(roots)
    say("spans a held step: %.2f on the training thread, %.2f on others, "
        "%.3f gc; median root %.2f ms, %d stalled (over %.1f times)"
        % (sum(1 for s in inside if s["tid"] == tid) / len(roots),
           sum(1 for s in inside if s["tid"] != tid) / len(roots),
           sum(1 for s in inside if s["name"] == "gc") / len(roots),
           1e3 * med, len(slow), STALL))
    say("host account a held step, ms: " + ", ".join(
        "%s %s" % (k, "-" if v is None else "%.4f" % v)
        for k, v in sorted(out.items())))
    attrs = [r for r in roots if "cpu_ms" in r]
    if attrs:
        say("root attrs, median a held step: " + " ".join(
            "%s %.3f" % (k, statistics.median(r.get(k, 0) for r in attrs))
            for k in ROOT_ATTRS))
    _launch_per_leaf(spans, roots)
    for line in stall_table(spans, roots):
        say(line)
    _clock(run, spans, tid)
    return out


def _launch_per_leaf(spans, roots):
    tid = roots[0]["tid"]
    mine = [s for s in spans if s["tid"] == tid]
    own = pt.self_intervals(mine)
    ids = {r["trace_id"] for r in roots}
    launches = [s for s in mine if s["name"] == "step.launch"
                and s["trace_id"] in ids]
    counted = [s for s in launches if s.get("leaves_out")]
    if not counted:
        say("step.launch carries no leaf counts")
        return
    us = 1e6 * sum(tr.total(own.get(s["span_id"], []))
                   for s in launches) / len(launches)
    lin, lout = counted[-1]["leaves_in"], counted[-1]["leaves_out"]
    say("step.launch: %.1f us self a step over %d leaves in, %d out: %.2f "
        "us a leaf out, %.2f a leaf in or out" % (
            us, lin, lout, us / lout, us / (lin + lout)))


def _clock(run, spans, tid):
    trace, traced = run.get("trace"), run.get("traced_steps")
    if trace is None or not trace.devices or not traced:
        return
    win = run["trace_window"]
    clock = causality_clock(spans, trace.devices[0])
    one = pt.clock_shift(spans, trace)
    if clock is None:
        say("causality clock: no step program ran in the trace")
        if one is None:
            return
        shift = one
    else:
        lo, hi, n = clock
        shift = lo
        say("causality clock over %d traced steps: width %.3f ms%s; "
            "clock_shift lies %+.3f ms from its lower bound" % (
                n, 1e3 * (hi - lo),
                " (the bounds cross)" if hi < lo else "",
                1e3 * (one - lo) if one is not None else float("nan")))
    idle_dev = min(trace.devices, key=lambda d: d.busy_seconds(win))
    gaps = idle_dev.idle_gaps(win)
    rows = idle_by_cover(spans, shift, gaps, tid)
    say("idle ms a traced step by the covering span, every thread (%.3f ms "
        "idle a step; rows of different threads may overlap):"
        % (1e3 * tr.total(gaps) / traced))
    for (thread, name), v in sorted(rows.items(), key=lambda kv: -kv[1])[:14]:
        say("  %-14s %-22s %8.3f" % (thread, name, 1e3 * v / traced))
