"""The trace reduction, on hand-made intervals and on a small trace
recorded on a v5e chip (three runs of a three-matmul program between the
harness's annotations; PR 27)."""
import os

import pytest

import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")


def test_union_subtract_total():
    merged = tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert merged == [(0, 3), (5, 7)]
    assert tr.total(merged) == 5
    assert tr.subtract([(0, 10)], merged) == [(3, 5), (7, 10)]
    assert tr.subtract([(0, 3), (5, 7)], [(1, 2), (2.5, 6)]) == [
        (0, 1), (2, 2.5), (6, 7)]
    assert tr.clip([(0, 4), (6, 9)], (2, 7)) == [(2, 4), (6, 7)]


def test_names():
    text = "%multiply_reduce_fusion.12 = f32[64]{0} fusion(f32[8]{0} %p.1)"
    assert tr.short_name(text) == "multiply_reduce_fusion.12"
    assert tr.family(text) == "multiply_reduce_fusion"
    assert tr.family("%all-reduce-start.3 = ...") == "all-reduce-start"
    assert tr.family("fusion") == "fusion"


def test_busy_idle_programs_on_hand_made_intervals():
    dev = tr.Device(0, [("%a.1 = x", 1.0, 2.0), ("%b.2 = x", 2.0, 2.5),
                        ("%a.3 = x", 4.0, 5.0)],
                    [("jit_step(1)", 1.0, 2.5), ("jit_step(1)", 4.0, 5.0)])
    window = (0.0, 6.0)
    assert dev.busy_seconds(window) == pytest.approx(2.5)
    assert dev.idle_gaps(window) == [(0.0, 1.0), (2.5, 4.0), (5.0, 6.0)]
    assert dev.op_seconds(window) == {"a": 2.0, "b": 0.5}
    assert len(dev.programs(window)) == 2
    assert len(dev.programs((3.0, 6.0))) == 1
    # an operation that straddles the window counts only inside it
    assert dev.busy_seconds((1.5, 4.5)) == pytest.approx(1.5)


def test_exposed_collective_time():
    # all-reduce 1.0-3.0; a fusion covers 1.5-2.5 of it: 1.0 s exposed
    dev = tr.Device(0, [("%all-reduce.1 = x", 1.0, 3.0),
                        ("%fusion.2 = x", 1.5, 2.5),
                        ("%fusion.3 = x", 3.0, 4.0)], [])
    assert dev.exposed_collective_seconds((0.0, 5.0)) == pytest.approx(1.0)
    hidden = tr.Device(0, [("%all-reduce.1 = x", 1.0, 2.0),
                           ("%fusion.2 = x", 0.5, 2.5)], [])
    assert hidden.exposed_collective_seconds((0.0, 5.0)) == 0


def test_gaps_named_by_the_annotation_that_covers_them():
    dev = tr.Device(0, [("%f.1 = x", 1.0, 2.0), ("%f.2 = x", 3.0, 4.0)], [])
    trace = tr.Trace([dev], [("next_batch", 0.0, 1.0), ("step", 1.0, 1.1),
                             ("fetch_loss", 1.1, 2.1),
                             ("next_batch", 2.1, 2.9), ("step", 2.9, 3.0),
                             ("fetch_loss", 3.0, 4.0)])
    assert trace.window() == (0.0, 4.0)
    assert trace.window(skip=1) == (2.1, 4.0)
    gaps = trace.name_gaps(dev, trace.window())
    assert gaps[0][0] == "next_batch" and gaps[0][1] == pytest.approx(1.0)
    assert gaps[1][0] == "next_batch" and gaps[1][1] == pytest.approx(1.0)


def test_recorded_trace():
    trace = tr.load(FIXTURE)
    assert [d.index for d in trace.devices] == [0]
    dev = trace.devices[0]
    assert len(dev.modules) == 3 and len(dev.ops) == 12
    assert all(name.startswith("jit_tiny") for name, _, _ in dev.modules)
    assert len(trace.spans("next_batch")) == 3
    assert len(trace.spans("step")) == 3
    assert len(trace.spans("fetch_loss")) == 3
    window = trace.window()
    assert 0.005 < window[1] - window[0] < 0.05
    busy = dev.busy_seconds(window)
    # three runs of three 512x512 bf16 products and their tanh: microseconds
    assert 5e-6 < busy < 1e-4
    assert len(dev.programs(window)) == 3
    ops = dev.op_seconds(window)
    assert max(ops, key=ops.get) == "convolution_tanh_fusion"
    assert sum(ops.values()) == pytest.approx(busy, rel=1e-6)
    assert dev.exposed_collective_seconds(window) == 0
    gaps = trace.name_gaps(dev, window)
    assert gaps[0][0] == "next_batch"      # the harness slept 2 ms there
    # every op lies inside its program's run
    for _, s, e in dev.ops:
        assert any(ms <= s and e <= me + 1e-9 for _, ms, me in dev.modules)


def test_host_steps_take_the_place_of_annotations():
    # two steps on the host's own clock (which starts elsewhere): the last
    # reading, the loss in hand at 107.0, meets the last op's end at 4.0
    dev = tr.Device(0, [("%f.1 = x", 1.0, 2.0), ("%f.2 = x", 3.0, 4.0)], [])
    trace = tr.Trace([dev], [])
    trace.take_host_steps([(103.0, 104.0, 104.1, 105.1),
                           (105.1, 105.9, 106.0, 107.0)])
    assert trace.window() == pytest.approx((0.0, 4.0))
    assert trace.window(skip=1) == pytest.approx((2.1, 4.0))
    assert [n for n, _, _ in trace.host_spans][:3] == [
        "next_batch", "step", "fetch_loss"]
    gaps = trace.name_gaps(dev, trace.window())
    assert gaps[0][0] == "next_batch" and gaps[0][1] == pytest.approx(1.0)
