"""The readers of the program's own spans and program table, on hand-made
intervals and on a small trace recorded on a v5e chip with the map that
goes with it (`record_scoped_fixture.py`: four steps of a conv +
batch-norm + dense net through `ShardedTrainer.step`; PR 28)."""
import json
import os

import pytest

import program_trace as pt
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(DATA, "scoped_v5e.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "scoped_v5e.json")) as f:
        fx = json.load(f)
    spans = [dict(s, t1=s["t0"] + s["step_time"]) for s in fx["spans"]]
    return tr.load(SCOPED), fx, spans


@pytest.mark.parametrize("op_name,expected", [
    (None, ("unscoped", None)),
    ("jit(sharded_step)/jvp()/reduce_sum", ("unscoped", None)),
    ("jit(sharded_step)/jvp(mx.Convolution.conv0_fwd)/conv_general_dilated",
     ("fwd", "Convolution")),
    ("jit(sharded_step)/transpose(jvp(mx.Convolution.conv0_fwd))/mul",
     ("bwd", "Convolution")),
    ("jit(cachedop_fwd_net)/mx.BatchNorm.bn0/rsqrt", ("fwd", "BatchNorm")),
    ("jit(sharded_step)/mx.optimizer/sub", ("optimizer", "optimizer")),
    ("jit(sharded_step)/mx.optimizer/mx.guard/jit(_where)/select_n",
     ("optimizer", "optimizer")),
    ("jit(sharded_step)/mx.guard/reduce_and", ("other", "guard")),
    ("jit(sharded_step)/jvp(mx.cast)/convert_element_type",
     ("other", "cast")),
    ("jit(sharded_step)/transpose(jvp(mx.Cast.cast0))/convert_element_type",
     ("bwd", "Cast")),
])
def test_classify(op_name, expected):
    assert pt.classify(op_name) == expected


def test_join_on_hand_made_intervals():
    """An operation belongs to the program run that contains it, and
    through that program's name to its owner; one outside every run, or
    of a program the table does not know, has none."""
    dev = tr.Device(0, [
        ("%fusion.1 = f32[4] fusion(...)", 1.0, 2.0),
        ("%fusion.2 = f32[4] fusion(...)", 2.0, 2.5),
        ("%copy.3 = f32[4] copy(...)", 2.5, 3.0),
        ("%fusion.1 = f32[4] fusion(...)", 4.0, 5.0),     # another program's
        ("%fusion.9 = f32[4] fusion(...)", 6.0, 6.5),     # outside every run
    ], [("jit_sharded_step(7)", 1.0, 3.0), ("jit_other(8)", 4.0, 5.0)])
    owners = {"jit_sharded_step": {
        "fusion.1": "jit(sharded_step)/jvp(mx.Convolution.c0)/conv",
        "fusion.2": "jit(sharded_step)/mx.optimizer/sub",
        "fusion.9": "jit(sharded_step)/mx.guard/and"}}
    joined = pt.device_owners(dev, (0.0, 6.25), owners)
    assert [(round(s, 6), m, f, c, k) for s, m, f, c, k in joined] == [
        (1.0, "jit_sharded_step", "fusion", "fwd", "Convolution"),
        (0.5, "jit_sharded_step", "fusion", "optimizer", "optimizer"),
        (0.5, "jit_sharded_step", "copy", "unscoped", None),
        (1.0, "jit_other", "fusion", "unscoped", None),
        (0.25, None, "fusion", "unscoped", None)]      # clipped by the window
    assert pt.by_class(joined) == {"fwd": 1.0, "bwd": 0.0, "optimizer": 0.5,
                                   "other": 0.0, "unscoped": 1.75}
    assert sum(pt.by_class(joined).values()) == pytest.approx(
        dev.busy_seconds((0.0, 6.25)))


def _span(name, t0, t1, sid, parent=None, tid=1, **more):
    return dict(name=name, t0=t0, t1=t1, step_time=t1 - t0, span_id=sid,
                parent_id=parent, tid=tid, **more)


HAND = [
    _span("step", 10.0, 14.0, "r"),
    _span("fence", 10.0, 10.5, "f", "r"),
    _span("step.prepare", 11.0, 11.5, "p", "r"),
    _span("step.launch", 11.5, 13.0, "l", "r"),
    _span("compile", 12.0, 12.75, "c", "l"),
    _span("step.finish", 13.0, 13.25, "n", "r"),
    _span("input.stage", 10.0, 13.0, "s", tid=2),       # the staging thread
]


def test_self_time_and_the_window_mean():
    own = pt.self_intervals(HAND)
    assert own["l"] == [(11.5, 12.0), (12.75, 13.0)]
    assert tr.total(own["r"]) == pytest.approx(4.0 - 0.5 - 0.5 - 1.5 - 0.25)
    ms = pt.host_self_ms(HAND, (9.0, 20.0), steps=2)
    assert ms["step.launch"] == pytest.approx(1e3 * 0.75 / 2)
    assert ms["compile"] == pytest.approx(1e3 * 0.75 / 2)
    assert ms["step.prepare"] == pytest.approx(250.0)
    assert pt.host_self_ms(HAND, (11.2, 20.0), steps=1).keys() == {
        "step.launch", "compile", "step.finish"}


def test_clock_anchor_and_idle_by_span():
    """The last fence's end meets the last device op's end; idle time
    then falls to the span whose self time covers it, the staging
    thread's spans and the root's own time apart."""
    dev = tr.Device(0, [("%f.1 = x", 100.0, 100.5), ("%f.2 = x", 103.0, 104.0)],
                    [])
    spans = HAND + [_span("fence", 13.5, 14.0, "g", "r")]
    shift = pt.clock_shift(spans, tr.Trace([dev], []))
    assert shift == pytest.approx(90.0)                 # 14.0 -> 104.0
    gaps = dev.idle_gaps((100.0, 104.0))
    assert gaps == [(100.5, 103.0)]
    named, owned = pt.idle_by_span(spans, shift, gaps)
    assert named == pytest.approx({
        "step": 0.5 + 0.0, "step.prepare": 0.5, "step.launch": 0.75,
        "compile": 0.75})
    assert owned == pytest.approx(2.0)                  # all but the root's
    assert pt.clock_shift(HAND[:1], tr.Trace([dev], [])) is None


def test_recorded_trace_joins_to_its_map(recorded):
    trace, fx, _spans = recorded
    dev, window = trace.devices[0], trace.window()
    assert {pt.module_name(m[0]) for m in dev.modules} == {"jit_sharded_step"}
    joined = pt.device_owners(dev, window, fx["owners"])
    kinds = {(k, c) for _s, _m, _f, c, k in joined}
    assert {("Convolution", "fwd"), ("Convolution", "bwd"),
            ("FullyConnected", "bwd"), ("optimizer", "optimizer"),
            ("guard", "other"), (None, "unscoped")} <= kinds
    ms = pt.by_class(joined)
    assert all(ms[c] > 0 for c in pt.CLASSES)
    # fwd + bwd + optimizer + other + unscoped = busy
    assert sum(ms.values()) == pytest.approx(dev.busy_seconds(window),
                                             rel=1e-9)
    assert len(dev.modules) == fx["steps"]


def test_recorded_spans_land_on_the_trace_clock(recorded):
    trace, fx, spans = recorded
    shift = pt.clock_shift(spans, trace)
    ends = [e for _n, _s, e in trace.devices[0].ops]
    fences = [s for s in spans if s["name"] == "fence"]
    assert max(s["t1"] for s in fences) + shift == pytest.approx(max(ends))
    # every traced step's program ran after its launch began and before
    # the fetch of its loss (the next fence on that thread) ended
    launches = sorted((s for s in spans if s["name"] == "step.launch"),
                      key=lambda s: s["t0"])[-fx["steps"]:]
    for launch, module in zip(launches, trace.devices[0].modules):
        fence = min((s for s in fences if s["t0"] > launch["t0"]),
                    key=lambda s: s["t0"])
        assert launch["t0"] + shift < module[1] < fence["t1"] + shift + 1e-4
    named, owned = pt.idle_by_span(
        spans, shift, trace.devices[0].idle_gaps(trace.window()))
    assert owned > 0 and named["fence"] > 0


def _run(trace, traced):
    return {"steps": [(0.0, 0.0, 0.0, 1.0, 1.0)], "t_open": 0.0,
            "trace": trace, "trace_window": trace.window(),
            "traced_steps": traced}


def test_a_map_with_no_mx_name_reads_none(recorded, monkeypatch, capsys):
    trace, fx, spans = recorded
    stale = {k: {i: op.replace("mx.", "") for i, op in m.items()}
             for k, m in fx["owners"].items()}
    snap = {k: dict(v, scoped=False) for k, v in fx["snapshot"].items()}
    monkeypatch.setattr(pt, "collect_programs", lambda: (stale, snap))
    monkeypatch.setattr(pt, "collect_spans", lambda: spans)
    out = pt.analyse(_run(trace, fx["steps"]))
    assert out["device"] is None
    assert "written before the scopes existed" in capsys.readouterr().err


def test_a_program_with_no_table_and_no_spans_reads_none(recorded,
                                                         monkeypatch):
    trace, fx, _spans = recorded
    monkeypatch.setattr(pt, "collect_programs", lambda: (None, None))
    monkeypatch.setattr(pt, "collect_spans", lambda: [])
    out = pt.analyse(_run(trace, fx["steps"]))
    assert out == {"device": None, "host": None, "idle": None,
                   "stage_ms": None}


def test_analyse_on_the_recorded_run(recorded, monkeypatch, capsys):
    trace, fx, spans = recorded
    monkeypatch.setattr(pt, "collect_programs",
                        lambda: (fx["owners"], fx["snapshot"]))
    monkeypatch.setattr(pt, "collect_spans", lambda: spans)
    run = _run(trace, fx["steps"])
    out = pt.analyse(run)
    assert pt.analyse(run) is out                      # once a run
    dev = out["device"]
    assert sum(dev["ms"].values()) == pytest.approx(dev["busy_ms"], rel=1e-9)
    assert 0 < out["idle"]["owned_s"] <= out["idle"]["idle_s"]
    err = capsys.readouterr().err
    for heading in ("device ms a step by op kind and direction",
                    "owners of the trace's op families",
                    "idle ms a traced step by the span that covers it",
                    "programs a traced step by name"):
        assert heading in err
    assert "jit_sharded_step" in err
