"""What the program itself says about a run, laid beside what the
benchmark measured from outside: the span ring
(`mxnet_tpu.observability.trace.ring_spans`) for host time, and the
program table (`mxnet_tpu.compile.programs`) for the owner of each
device operation. Read by the metric readers `fwd_device_ms`,
`bwd_device_ms`, `optimizer_device_ms`, `unscoped_device_pct`,
`idle_owned_pct`, `step_prepare_ms`, `step_launch_ms`,
`frontend_host_ms` and `input_stage_ms`; a program that has no such
spans or no such table (the commits before PR 28) gives every one of
them nothing to read, and they return None.

Host spans carry their `perf_counter` stamp (`t0`). They are laid on a
device trace's clock by the harness's own rule
(`trace_reduce.Trace.take_host_steps`): the end of the last `fence` span
(the last fetched loss) meets the end of the last device operation.

A device operation belongs to the run of a compiled program (`XLA
Modules` event) that contains it, and through that program's name and
its own instruction name to the `op_name` the compiler kept for it. A
fusion is owned by its root instruction. Classes: `optimizer` (under
`mx.optimizer`), `other` (`mx.guard`, `mx.cast`), `fwd` / `bwd` (under a
graph node's `mx.<op>.<node>`, without / with `transpose(` around it),
`unscoped` (no `mx.` scope: copies, slices, what XLA adds).

Intervals are (start, end) in seconds; everything below the two
`collect_*` functions works on plain lists, so hand-made ones test it.
"""
import bisect
import re
import sys
from collections import defaultdict

import trace_reduce as tr

ROOT = "step"                    # the iteration's root span
_SPECIAL = re.compile(r"mx\.(optimizer|guard|cast)(?![\w.])")
_NODE = re.compile(r"mx\.([A-Za-z_]\w*)\.([\w.\-]+)")
_RUN_ID = re.compile(r"\(\d+\)$")
CLASSES = ("fwd", "bwd", "optimizer", "other", "unscoped")


def say(*a):
    print(*a, file=sys.stderr, flush=True)


# -- what the program gives ------------------------------------------------
def collect_spans():
    """The ring's spans that keep their perf stamp, as dicts with `t0`
    and `t1` in seconds; [] from a program whose spans keep none."""
    try:
        from mxnet_tpu.observability import trace
        spans = trace.ring_spans()
    except Exception as err:        # noqa: BLE001 — a reader never raises
        say("program_trace: no span ring (%s)" % err)
        return []
    out = []
    for s in spans:
        if "t0" in s and "step_time" in s:
            out.append(dict(s, t1=s["t0"] + s["step_time"]))
    return out


def collect_programs():
    """(`{module name: {instruction: op_name}}`, the table's snapshot),
    or (None, None) from a program that keeps no table."""
    try:
        from mxnet_tpu.compile import programs
    except ImportError:
        return None, None
    snap = programs.snapshot()
    return {name: programs.owners(name) or {} for name in snap}, snap


# -- device time by owner ---------------------------------------------------
def classify(op_name):
    """(class, op kind) of one `op_name` (None: no owner known)."""
    if not op_name or "mx." not in op_name:
        return "unscoped", None
    special = _SPECIAL.search(op_name)
    if special and special.group(1) == "optimizer":
        return "optimizer", "optimizer"
    node = _NODE.search(op_name)
    if node is not None:
        return ("bwd" if "transpose(" in op_name else "fwd"), node.group(1)
    return ("other", special.group(1)) if special else ("unscoped", None)


def module_name(event_name):
    """`jit_sharded_step(1234)` -> `jit_sharded_step`."""
    return _RUN_ID.sub("", event_name)


def device_owners(device, window, owners):
    """Every operation of `device` inside `window`, joined to its owner:
    [(seconds, module, family, class, kind)]. `owners` is
    `{module: {instruction: op_name}}`."""
    starts = [m[1] for m in device.modules]
    out = []
    for name, s, e in device.ops:
        secs = tr.total(tr.clip([(s, e)], window))
        if secs <= 0:
            continue
        i = bisect.bisect_right(starts, s) - 1
        module = None
        if i >= 0 and s < device.modules[i][2]:
            module = module_name(device.modules[i][0])
        op_name = (owners.get(module) or {}).get(tr.short_name(name))
        cls, kind = classify(op_name)
        out.append((secs, module, tr.family(name), cls, kind))
    return out


def by_class(joined):
    out = dict.fromkeys(CLASSES, 0.0)
    for secs, _m, _f, cls, _k in joined:
        out[cls] += secs
    return out


# -- host time by span --------------------------------------------------------
def self_intervals(spans):
    """{span_id: the parts of the span that no child covers}."""
    children = defaultdict(list)
    for s in spans:
        if s.get("parent_id"):
            children[s["parent_id"]].append((s["t0"], s["t1"]))
    return {s["span_id"]: tr.subtract(
        [(s["t0"], s["t1"])],
        tr.union(tr.clip(children.get(s["span_id"], []),
                         (s["t0"], s["t1"]))))
        for s in spans if s["t1"] > s["t0"]}


def consumer_tid(spans):
    roots = [s for s in spans if s["name"] == ROOT and not s.get("parent_id")]
    return roots[-1]["tid"] if roots else None


def host_self_ms(spans, window, steps):
    """{span name: mean self ms a step} of the spans that start inside
    `window` (host clock)."""
    own = self_intervals(spans)
    out = defaultdict(float)
    for s in spans:
        if window[0] <= s["t0"] < window[1] and s["span_id"] in own:
            out[s["name"]] += tr.total(own[s["span_id"]])
    return {k: 1e3 * v / steps for k, v in out.items()} if steps else {}


def clock_shift(spans, trace):
    """Seconds to add to a perf stamp to land on the trace's clock, or
    None: the last `fence` span's end meets the last device op's end."""
    ends = [e for d in trace.devices for _, _, e in d.ops]
    fences = [s["t1"] for s in spans if s["name"] == "fence"]
    if not ends or not fences:
        return None
    return max(ends) - max(fences)


def idle_by_span(spans, shift, gaps):
    """({span name: idle seconds its self time covers}, seconds covered
    by a span other than the root) over the idle `gaps` (trace clock),
    for the consumer thread's spans."""
    tid = consumer_tid(spans)
    mine = [s for s in spans if s["tid"] == tid]
    own = self_intervals(mine)
    gaps = tr.union(gaps)
    out, owned = defaultdict(float), []
    for s in mine:
        parts = [(a + shift, b + shift) for a, b in own.get(s["span_id"], [])]
        hit = tr.subtract(gaps, tr.subtract(gaps, tr.union(parts)))
        if hit:
            out[s["name"]] += tr.total(hit)
            if s["name"] != ROOT:
                owned.extend(hit)
    return dict(out), tr.total(tr.union(owned))


# -- one analysis a run, printed once ---------------------------------------
def analyse(run):
    """Everything the readers need from `run` (the harness's dict),
    computed once and kept on it."""
    if "_program_trace" in run:
        return run["_program_trace"]
    out = run["_program_trace"] = {"device": None, "host": None,
                                   "idle": None, "stage_ms": None}
    spans = collect_spans()
    owners, snap = collect_programs()
    steps = run["steps"]
    window = (run["t_open"], steps[-1][3]) if steps else None

    # host spans of the window
    if spans and window:
        n = sum(1 for s in spans if s["name"] == "step.prepare"
                and window[0] <= s["t0"] < window[1])
        if n:
            out["host"] = host_self_ms(spans, window, n)
            out["host_steps"] = n
            front = [s for s in spans
                     if s["name"] in ("frontend.forward", "frontend.backward")
                     and window[0] <= s["t0"] < window[1]]
            if front:
                out["frontend_ms"] = 1e3 * sum(
                    s["t1"] - s["t0"] for s in front) / n
        stage = [s["t1"] - s["t0"] for s in spans
                 if s["name"] == "input.stage"
                 and window[0] <= s["t0"] < window[1]]
        if stage:
            out["stage_ms"] = 1e3 * sum(stage) / len(stage)
            out["stage_batches"] = len(stage)
    elif not spans:
        say("program_trace: the program recorded no span with a perf "
            "stamp: no host metric")

    trace, traced = run.get("trace"), run.get("traced_steps")
    if trace is not None and trace.devices and traced:
        win = run["trace_window"]
        # device time by owner, busiest chip
        if owners is None:
            say("program_trace: the program keeps no program table: no "
                "device metric by owner")
        else:
            dev = max(trace.devices, key=lambda d: d.busy_seconds(win))
            joined = device_owners(dev, win, owners)
            ran = {m for _s, m, _f, _c, _k in joined if m}
            if not any((snap.get(m) or {}).get("scoped") for m in ran):
                say("program_trace: no program of the traced steps carries "
                    "an mx. scope (%s): built from no graph, or served from "
                    "a cache written before the scopes existed; no device "
                    "metric by owner" % (", ".join(sorted(ran)) or "none ran"))
            else:
                out["device"] = {
                    "ms": {k: 1e3 * v / traced
                           for k, v in by_class(joined).items()},
                    "busy_ms": 1e3 * dev.busy_seconds(win) / traced,
                    "joined": joined}
        # idle time by span, idlest chip
        shift = clock_shift(spans, trace) if spans else None
        if shift is not None:
            idle_dev = min(trace.devices, key=lambda d: d.busy_seconds(win))
            gaps = idle_dev.idle_gaps(win)
            named, owned = idle_by_span(spans, shift, gaps)
            idle = tr.total(gaps)
            out["idle"] = {"by_span": named, "owned_s": owned,
                           "idle_s": idle, "shift": shift}
    report(run, out, spans, snap)
    return out


def _top(rows, n):
    return sorted(rows.items(), key=lambda kv: -kv[1])[:n]


def report(run, out, spans, snap):
    """The tables beside the numbers, on standard error."""
    traced = run.get("traced_steps") or 0
    dev = out["device"]
    if dev:
        kinds, fams, mods = (defaultdict(float), defaultdict(float),
                             defaultdict(float))
        for secs, module, fam, cls, kind in dev["joined"]:
            kinds[(kind or "-", cls)] += secs
            fams[(fam, kind or "-", cls)] += secs
            mods[(module or "-", cls)] += secs
        say("device ms a traced step by class: %s; sum %.3f, busy %.3f"
            % (" ".join("%s %.3f" % (k, dev["ms"][k]) for k in CLASSES),
               sum(dev["ms"].values()), dev["busy_ms"]))
        say("device ms a step by op kind and direction (top 15):")
        for (kind, cls), v in _top(kinds, 15):
            say("  %-28s %-9s %8.3f" % (kind, cls, 1e3 * v / traced))
        say("owners of the trace's op families (top 12 of family x owner):")
        for (fam, kind, cls), v in _top(fams, 12):
            say("  %-32s %-22s %-9s %8.3f" % (fam, kind, cls,
                                              1e3 * v / traced))
        say("device ms a step by program and class (top 10):")
        for (module, cls), v in _top(mods, 10):
            say("  %-44s %-9s %8.3f" % (module, cls, 1e3 * v / traced))
    if out["host"]:
        say("host self ms a step by span, over %d steps of the window:"
            % out["host_steps"])
        for name, v in _top(out["host"], 12):
            say("  %-20s %8.3f" % (name, v))
    if out["idle"] and traced:
        idle = out["idle"]
        say("idle ms a traced step by the span that covers it (idlest "
            "chip; %.3f ms idle a step, %.1f%% under a span of the "
            "program's other than the root):"
            % (1e3 * idle["idle_s"] / traced,
               100.0 * idle["owned_s"] / idle["idle_s"]
               if idle["idle_s"] else 0.0))
        for name, v in _top(idle["by_span"], 10):
            say("  %-20s %8.3f" % (name, 1e3 * v / traced))
    trace = run.get("trace")
    if trace is not None and trace.devices and traced:
        runs = defaultdict(int)
        for m in trace.devices[0].programs(run["trace_window"]):
            runs[module_name(m[0])] += 1
        say("programs a traced step by name (top 10 of %d names):"
            % len(runs))
        for name, n in _top(runs, 10):
            say("  %-52s %8.2f" % (name, n / traced))
    if spans and run["steps"]:
        t_open = run["t_open"]
        late = defaultdict(lambda: [0, 0.0])
        for s in spans:
            if s["name"] == "compile" and s["t0"] >= t_open:
                late[s.get("program", "?")][0] += 1
                late[s.get("program", "?")][1] += s["t1"] - s["t0"]
        say("compile spans since the window opened: %s" % (
            ", ".join("%s x%d %.3f s" % (k, n, secs)
                      for k, (n, secs) in sorted(late.items())) or "none"))
    if snap is not None:
        read = {k: p.get("read_seconds", 0.0) for k, p in snap.items()}
        say("program table: %d names, %d builds, %.3f s spent reading "
            "compiled programs; most on: %s" % (
                len(snap), sum(p["builds"] for p in snap.values()),
                sum(read.values()),
                ", ".join("%s %.3f" % kv for kv in _top(read, 4))))
