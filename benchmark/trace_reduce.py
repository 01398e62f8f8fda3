"""From a `jax.profiler` trace (`.xplane.pb`) to the numbers the
per-layer metrics read. The benchmark's own: nothing here is the
program's.

What a TPU trace holds (looked at by hand, PR 27): one plane
`/device:TPU:<n>` a chip, with the lines `XLA Modules` (one event for
each run of a compiled program), `XLA Ops` (one event for each HLO
operation the core ran, back to back, never overlapping; the name is the
operation's whole HLO text, `%fusion.12 = ...`), `Async XLA Ops` (the
spans of copies and collectives in flight) and `Steps`; and, where the host tracer is on, one plane `/host:CPU` with a
line for each thread, on which a `jax.profiler.TraceAnnotation` is an
event under its own name. All planes share one clock, in nanoseconds. The
harness traces with the host tracer off (its flood of events, 1.2 million
in 23 ResNet steps, stalls the steps for 0.1-0.6 s at a time and made
`stop_trace` take 9-33 s) and hands over its own host-clock records of
each step in their place (`Trace.take_host_steps`).

Intervals are (start, end) in seconds. Everything below works on plain
lists, so hand-made intervals test it without a trace.
"""
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")


def short_name(hlo_text):
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def family(name):
    """`fusion.12` -> `fusion`: operations of one kind under one name."""
    return re.sub(r"[.\d]+$", "", short_name(name)) or short_name(name)


def union(intervals):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(intervals, holes):
    """The parts of merged `intervals` that no interval of merged
    `holes` covers."""
    out, j = [], 0
    for s, e in intervals:
        cur = s
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < e:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Device:
    """One chip's events: ops and modules as (name, start, end)."""

    def __init__(self, index, ops, modules):
        self.index = index
        self.ops = sorted(ops, key=lambda o: o[1])
        self.modules = sorted(modules, key=lambda o: o[1])

    def busy(self, window):
        return union(clip([(s, e) for _, s, e in self.ops], window))

    def busy_seconds(self, window):
        return total(self.busy(window))

    def idle_gaps(self, window):
        return subtract([window], self.busy(window))

    def op_seconds(self, window):
        """{family: seconds} of the operations inside the window."""
        out = defaultdict(float)
        for name, s, e in self.ops:
            for cs, ce in clip([(s, e)], window):
                out[family(name)] += ce - cs
        return dict(out)

    def programs(self, window):
        """The runs of compiled programs that start inside the window."""
        return [m for m in self.modules if window[0] <= m[1] < window[1]]

    def exposed_collective_seconds(self, window):
        """Time inside collective operations during which no other
        operation runs on this chip."""
        coll = union(clip([(s, e) for n, s, e in self.ops
                           if COLLECTIVE.match(short_name(n))], window))
        other = union(clip([(s, e) for n, s, e in self.ops
                            if not COLLECTIVE.match(short_name(n))], window))
        return total(subtract(coll, other))


class Trace:
    def __init__(self, devices, host_spans):
        self.devices = devices          # [Device], by index
        self.host_spans = sorted(host_spans, key=lambda o: o[1])

    def take_host_steps(self, steps, names=("next_batch", "step",
                                            "fetch_loss")):
        """Host spans from the harness's own records, for a trace taken
        with the host tracer off: `steps` holds, a step, the host clock's
        readings at the boundaries of `names` (len(names) + 1 of them).
        The two clocks tick alike; the trace's is anchored where the last
        step's last reading (the loss in hand) meets the end of the last
        device operation, which it follows by well under a millisecond."""
        ends = [e for d in self.devices for _, _, e in d.ops]
        if not ends or not steps:
            return
        shift = max(ends) - steps[-1][len(names)]
        self.host_spans = sorted(
            ((name, st[i] + shift, st[i + 1] + shift)
             for st in steps for i, name in enumerate(names)),
            key=lambda o: o[1])

    def spans(self, name):
        return [(s, e) for n, s, e in self.host_spans if n == name]

    def window(self, first="next_batch", last="fetch_loss", skip=0):
        """From the start of the `skip`-th `first` annotation to the last
        `last` annotation's end (the profiler's own start-up stalls the
        first steps after `start_trace`, so the harness skips them); with
        no annotations, the span of device ops."""
        a, b = self.spans(first), self.spans(last)
        if len(a) > skip and b:
            return (a[skip][0], b[-1][1])
        starts = [d.ops[0][1] for d in self.devices if d.ops]
        ends = [max(e for _, _, e in d.ops) for d in self.devices if d.ops]
        if not starts:
            return None
        return (min(starts), max(ends))

    def name_gaps(self, device, window, top=5):
        """The longest idle gaps of `device`, each named by the host
        annotation that covers most of it (or `none`)."""
        gaps = sorted(device.idle_gaps(window), key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            best, best_cover = "none", 0.0
            for name, hs, he in self.host_spans:
                cover = min(e, he) - max(s, hs)
                if cover > best_cover:
                    best, best_cover = name, cover
            out.append([best, e - s])
        return out


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def load(path, annotations=("next_batch", "step", "fetch_loss")):
    """Read an `.xplane.pb` (or the directory `start_trace` wrote)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices, host = [], []
    wanted = set(annotations)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dest = ops
                elif line.name == "XLA Modules":
                    dest = modules
                else:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dest.append((ev.name, s, s + ev.duration_ns * 1e-9))
            devices.append(Device(int(m.group(1)), ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        s = ev.start_ns * 1e-9
                        host.append((ev.name, s, s + ev.duration_ns * 1e-9))
    devices.sort(key=lambda d: d.index)
    return Trace(devices, host)
