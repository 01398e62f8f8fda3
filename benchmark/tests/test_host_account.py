"""The host's account of the held steps (`host_account.py`) and its four
readers, on hand-made spans (a planted stall, a planted collection on the
staging thread, a ring from a program without `gc0`), and the causality
clock on the small trace recorded on a v5e chip with its spans
(`record_scoped_fixture.py`)."""
import json
import os

import pytest

import harness
import host_account as ha
import program_trace as pt
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ("host_gc_ms", "stall_ms", "feed_wait_ms", "step_host_ms")


def _span(name, t0, t1, sid, parent=None, tid=1, trace="t", **more):
    return dict(name=name, t0=t0, t1=t1, step_time=t1 - t0, span_id=sid,
                parent_id=parent, tid=tid, trace_id=trace, **more)


def _steps(lengths, gc0=True, **attrs):
    """One root a length (s), back to back from 10.0; each with a fence
    of 0.3 s, an input.wait of 0.05 s, then prepare, launch, finish."""
    spans, t = [], 10.0
    for i, length in enumerate(lengths):
        r, tid = "r%d" % i, "t%d" % i
        root = _span("step", t, t + length, r, trace=tid, step=i, **attrs)
        if gc0:
            root.update(gc0=2, gc0_ms=0.1)
        spans += [
            root,
            _span("fence", t, t + length - 0.7, "f%d" % i, r, trace=tid),
            _span("input.wait", t + length - 0.7, t + length - 0.65,
                  "w%d" % i, r, trace=tid),
            _span("step.prepare", t + length - 0.6, t + length - 0.5,
                  "p%d" % i, r, trace=tid),
            _span("step.launch", t + length - 0.5, t + length - 0.3,
                  "l%d" % i, r, trace=tid, leaves_in=10, leaves_out=8),
            _span("step.finish", t + length - 0.3, t + length - 0.2,
                  "n%d" % i, r, trace=tid),
            _span("input.stage", t + 0.1, t + 0.2, "s%d" % i, tid=2,
                  trace="in%d" % i)]
        t += length
    return spans


def _run(spans, n):
    # the window opens at the first root's start, the last loss in hand
    # 0.5 s after the last root closed
    end = max([s["t1"] for s in spans if s["name"] == "step"] or [20.0])
    return {"steps": [(0.0, 0.0, 0.0, end + 0.5, 1.0)] * n,
            "t_open": 10.0, "trace": None, "traced_steps": 0}


def _analyse(monkeypatch, spans, n):
    monkeypatch.setattr(pt, "collect_spans", lambda: spans)
    run = _run(spans, n)
    got = {name: harness.load_file("metrics", name).read(run)
           for name in READERS}
    assert ha.analyse(run) is run["_host_account"]       # once a run
    return got


def test_a_steady_window(monkeypatch):
    got = _analyse(monkeypatch, _steps([1.0] * 5), 5)
    assert got["stall_ms"] == 0.0
    assert got["feed_wait_ms"] == pytest.approx(50.0)
    assert got["host_gc_ms"] == pytest.approx(0.1)
    # 1.0 s a root less the fence (0.3) and the input.wait (0.05)
    assert got["step_host_ms"] == pytest.approx(650.0)


def test_a_planted_stall_under_a_collection_on_the_staging_thread(
        monkeypatch, capsys):
    spans = _steps([1.0, 1.0, 3.0, 1.0, 1.0])
    # the third root runs 12.0-15.0; its fence 12.0-14.3; a collection
    # of generation 2 on the staging thread, under its input.stage
    spans += [_span("input.stage", 12.2, 14.0, "sx", tid=2, trace="inx"),
              _span("gc", 12.3, 13.9, "gx", "sx", tid=2, trace="inx",
                    generation=2, collected=12)]
    got = _analyse(monkeypatch, spans, 5)
    assert got["stall_ms"] == pytest.approx(1e3 * 2.0 / 5)
    assert got["host_gc_ms"] == pytest.approx((1600.0 + 5 * 0.1) / 5)
    assert got["feed_wait_ms"] == pytest.approx(50.0)
    err = capsys.readouterr().err
    assert "stall: step 3 of 5 held (root step 2): 3000.0 ms" in err
    assert "gc: generation 2 1600.0 ms (collected 12, thread 2)" in err
    assert "input.stage on thread 2" in err
    assert "cover: fence" in err and "a collection (generation 2" in err
    assert "step.launch: 200000.0 us self a step over 10 leaves in" in err


@pytest.mark.parametrize("attrs,why", [
    ({"cpu_ms": 100.0, "nvcsw": 40, "nivcsw": 0, "majflt": 0},
     "a wait in the runtime with the thread asleep"),
    ({"cpu_ms": 100.0, "nvcsw": 40, "nivcsw": 0, "majflt": 3},
     "the OS (nivcsw 0, majflt 3)"),
    ({"cpu_ms": 2900.0, "nvcsw": 1, "nivcsw": 0, "majflt": 0},
     "host work (cpu_ms 2900.0)")])
def test_the_cover_of_a_stall_without_a_collection(monkeypatch, capsys,
                                                   attrs, why):
    spans = _steps([1.0, 1.0, 3.0, 1.0, 1.0])
    spans[2 * 7].update(attrs)             # the stalled root
    _analyse(monkeypatch, spans, 5)
    err = capsys.readouterr().err
    assert "cover: fence, 2000.0 ms over its median" in err
    assert why in err


def test_a_ring_from_a_program_without_gc0(monkeypatch):
    got = _analyse(monkeypatch, _steps([1.0] * 4, gc0=False), 4)
    assert got["host_gc_ms"] is None
    assert got["stall_ms"] == 0.0 and got["feed_wait_ms"] == \
        pytest.approx(50.0)


def test_no_ring_reads_none(monkeypatch):
    assert _analyse(monkeypatch, [], 3) == dict.fromkeys(READERS)


def test_only_the_steps_the_ring_holds_are_read(monkeypatch, capsys):
    spans = _steps([1.0] * 6)
    held = [s for s in spans if s["t0"] >= 12.0 or s["tid"] == 2]
    got = _analyse(monkeypatch, held, 6)
    assert got["stall_ms"] == 0.0
    assert "4 of the window's 6 steps" in capsys.readouterr().err


def test_idle_by_cover_on_every_thread():
    spans = _steps([1.0, 1.0])
    spans.append(_span("gc", 10.05, 10.15, "g", "f0", generation=1))
    gaps = [(110.0, 110.25), (111.75, 111.95)]      # shift 100
    rows = ha.idle_by_cover(spans, 100.0, gaps, 1)
    assert rows[("train", "fence")] == pytest.approx(0.25 - 0.1)
    assert rows[("any", "gc")] == pytest.approx(0.1)
    assert rows[("thread 2", "input.stage")] == pytest.approx(0.1)
    assert rows[("train", "step.finish")] == pytest.approx(0.05)
    assert rows[("train", "step")] == pytest.approx(0.15)
    assert rows[("none", "no span but a root")] == pytest.approx(0.15)


def test_causality_clock_on_the_recorded_chip_trace():
    with open(os.path.join(DATA, "scoped_v5e.json")) as f:
        fx = json.load(f)
    spans = [dict(s, t1=s["t0"] + s["step_time"]) for s in fx["spans"]]
    trace = tr.load(os.path.join(DATA, "scoped_v5e.xplane.pb"))
    lo, hi, n = ha.causality_clock(spans, trace.devices[0])
    one = pt.clock_shift(spans, trace)
    print("causality clock on the v5e fixture: %d steps, width %.3f ms, "
          "clock_shift %+.3f ms from the lower bound"
          % (n, 1e3 * (hi - lo), 1e3 * (one - lo)))
    assert n == fx["steps"] and 0.0 <= hi - lo < 5e-3
    # the last operation ends a microsecond after its program's run
    assert lo - 5e-6 <= one <= hi
    # on the lower bound every paired run starts after its launch began
    # and ends before the fence that fetched its loss returned
    launches = sorted((s for s in spans if s["name"] == "step.launch"),
                      key=lambda s: s["t0"])[-n:]
    fences = sorted((s for s in spans if s["name"] == "fence"),
                    key=lambda s: s["t0"])
    for launch, (_m, start, end) in zip(launches, trace.devices[0].modules):
        fence = next(f for f in fences if f["t0"] >= launch["t1"])
        assert launch["t0"] + lo <= start + 1e-9
        assert end <= fence["t1"] + lo + 1e-9
