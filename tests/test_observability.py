"""Observability subsystem: metrics registry, spans, step telemetry.

Covers the registry's semantics (labels, kinds, concurrency), span
nesting landing in a profiler.dump() chrome trace, a 5-step gluon
training run streaming well-formed JSONL step records that
tools/telemetry_report.py can summarize, the Module.fit wiring, the
resilience.metrics shim, Speedometer metric routing, the profiler
Counter "C"-event fix, and the overhead guard (disabled path records
no events).
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, autograd, profiler
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import (Counter, Gauge, Histogram,
                                     MetricsRegistry, REGISTRY, span,
                                     current_span, StepTimer, telemetry)
from mxnet_tpu.observability import close_stream
from mxnet_tpu.resilience import metrics as res_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_stream(monkeypatch):
    """Every test starts with streaming off and a closed stream file."""
    monkeypatch.delenv("MXTPU_TELEMETRY", raising=False)
    close_stream()
    yield
    close_stream()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_counter_labels_and_total():
    reg = MetricsRegistry()
    c = reg.counter("req.count", "help text")
    c.inc()
    c.inc(2, site="push")
    c.inc(3, site="pull")
    assert c.get() == 1
    assert c.get(site="push") == 2
    assert c.get(site="pull") == 3
    assert c.get(site="absent") == 0
    assert c.total() == 6
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    a = reg.counter("x")
    assert reg.counter("x") is a
    with pytest.raises(ValueError):
        reg.gauge("x")
    assert reg.get("x") is a
    assert reg.get("missing") is None


def test_gauge_moves_both_ways():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.inc(); g.inc(); g.dec()
    assert g.get() == 1
    g.set(7.5, queue="a")
    assert g.get(queue="a") == 7.5


def test_histogram_sum_count_percentile():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    assert h.count() == 4
    assert abs(h.sum() - 6.05) < 1e-9
    assert h.total_count() == 4
    # p50 lands in the (0.1, 1.0] bucket, p99 in (1.0, 10.0]
    assert 0.1 <= h.percentile(0.5) <= 1.0
    assert 1.0 <= h.percentile(0.99) <= 10.0
    assert h.percentile(0.5, other="labels") == 0.0


def test_counter_concurrent_increments():
    reg = MetricsRegistry()
    c = reg.counter("bumps")
    h = reg.histogram("obs")

    def work():
        for _ in range(1000):
            c.inc(thread="yes")
            h.observe(0.01)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.get(thread="yes") == 8000
    assert h.count() == 8000


def test_prometheus_and_jsonl_export():
    reg = MetricsRegistry()
    reg.counter("kv.push.bytes", "bytes pushed").inc(128)
    reg.gauge("queue.depth").set(3)
    reg.histogram("step.seconds", buckets=(1.0,)).observe(0.5)
    text = reg.to_prometheus()
    assert "# TYPE mxtpu_kv_push_bytes_total counter" in text
    assert "mxtpu_kv_push_bytes_total 128" in text
    assert "mxtpu_queue_depth 3" in text
    assert 'mxtpu_step_seconds_bucket{le="1.0"} 1' in text
    assert "mxtpu_step_seconds_count 1" in text
    lines = [json.loads(l) for l in reg.to_jsonl().splitlines()]
    by_name = {l["name"]: l for l in lines}
    assert by_name["kv.push.bytes"]["value"] == 128
    assert by_name["step.seconds"]["count"] == 1
    # reset zeroes samples but keeps registrations
    reg.reset()
    assert reg.counter("kv.push.bytes").get() == 0


# ---------------------------------------------------------------------------
# resilience.metrics shim
# ---------------------------------------------------------------------------
def test_resilience_shim_bump_get_reset():
    res_metrics.reset_counters()
    res_metrics.bump("chaos.injected.test_site")
    res_metrics.bump("chaos.injected.test_site", 2)
    assert res_metrics.get("chaos.injected.test_site") == 3
    assert res_metrics.get("never.bumped") == 0
    # the mapping view keeps the old defaultdict surface
    assert res_metrics.counters["chaos.injected.test_site"] == 3
    assert res_metrics.counters["missing"] == 0
    assert ("chaos.injected.test_site", 3) in res_metrics.counters.items()
    # and the same data exports with everything else
    assert "mxtpu_resilience_events_total" in REGISTRY.to_prometheus()
    res_metrics.reset_counters()
    assert res_metrics.get("chaos.injected.test_site") == 0


# ---------------------------------------------------------------------------
# spans -> chrome trace
# ---------------------------------------------------------------------------
def test_span_nesting_lands_in_profiler_dump(tmp_path):
    profiler.set_config(filename=str(tmp_path / "prof"))
    profiler.start()
    try:
        assert current_span() is None
        with span("outer", epoch=1):
            assert current_span() == "outer"
            with span("inner"):
                assert current_span() == "inner"
        assert current_span() is None
    finally:
        path = profiler.dump()
    events = json.load(open(path))["traceEvents"]
    spans = {e["name"]: e for e in events if e.get("cat") == "span"}
    assert set(spans) >= {"outer", "inner"}
    assert spans["inner"]["args"]["parent"] == "outer"
    assert spans["outer"]["args"]["parent"] is None
    assert spans["outer"]["args"]["epoch"] == 1
    # inner nests temporally inside outer
    assert spans["inner"]["ts"] >= spans["outer"]["ts"]
    assert spans["inner"]["dur"] <= spans["outer"]["dur"]


def test_span_noop_when_profiler_off():
    before = len(profiler._events)
    with span("quiet"):
        assert current_span() is None  # disabled: no stack bookkeeping
    assert len(profiler._events) == before


# ---------------------------------------------------------------------------
# profiler Counter: thread-safe + "C" events
# ---------------------------------------------------------------------------
def test_profiler_counter_thread_safe_and_dumped(tmp_path):
    c = profiler.Counter(name="inflight")
    threads = [threading.Thread(
        target=lambda: [c.increment() for _ in range(500)])
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 2000
    profiler.set_config(filename=str(tmp_path / "prof_c"))
    profiler.start()
    c.increment(5)
    path = profiler.dump()
    events = json.load(open(path))["traceEvents"]
    cevents = [e for e in events
               if e.get("ph") == "C" and e["name"] == "inflight"]
    assert cevents, "no counter-track events in the trace"
    assert cevents[-1]["args"]["value"] == 2005


# ---------------------------------------------------------------------------
# StepTimer + streaming
# ---------------------------------------------------------------------------
def test_steptimer_record_shape_and_phases(tmp_path, monkeypatch):
    out = tmp_path / "steps.jsonl"
    monkeypatch.setenv("MXTPU_TELEMETRY", str(out))
    timer = StepTimer("unit.test")
    for i in range(3):
        timer.begin_step()
        with timer.phase("optimizer"):
            pass
        rec = timer.end_step(batch_size=4, tag="x")
        assert rec["step"] == i
        assert rec["source"] == "unit.test"
        assert rec["tag"] == "x"
    close_stream()
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert [l["step"] for l in lines] == [0, 1, 2]
    for l in lines:
        for field in ("ts", "step_time", "data_wait", "compile_count",
                      "compile_seconds", "kvstore_bytes", "optimizer_time",
                      "batch_size"):
            assert field in l, field
        assert l["step_time"] >= l["optimizer_time"] >= 0


def test_steptimer_no_stream_still_returns_records():
    timer = StepTimer("unit.nostream")
    timer.begin_step()
    rec = timer.end_step()
    assert rec["step"] == 0 and "step_time" in rec


# ---------------------------------------------------------------------------
# 5-step gluon training run end-to-end (the acceptance scenario)
# ---------------------------------------------------------------------------
def _run_gluon_steps(n_steps, batch_size=8, optimizer="sgd"):
    net = nn.Dense(4, in_units=8)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), optimizer,
                            {"learning_rate": 0.1})
    data = mx.io.NDArrayIter(
        np.random.RandomState(0).rand(n_steps * batch_size, 8)
        .astype(np.float32),
        np.random.RandomState(1).rand(n_steps * batch_size, 4)
        .astype(np.float32),
        batch_size=batch_size)
    loss_fn = gluon.loss.L2Loss()
    for batch in data:
        with autograd.record():
            loss = loss_fn(net(batch.data[0]), batch.label[0])
        loss.backward()
        trainer.step(batch_size)


def test_gluon_5step_jsonl_and_report(tmp_path, monkeypatch):
    out = tmp_path / "telemetry.jsonl"
    # this test documents the STAGED trainer record shape (allreduce/
    # optimizer phases, kvstore bytes); the fused one-program step's
    # record (single "step.launch" phase, no kvstore hop) is covered in
    # tests/test_fused_step.py. RMSProp is outside the one-program
    # step's optimizers, so `step()` itself stages
    # consume the once-per-process cold-start marker BEFORE the stream
    # opens: run solo, the first trainer step would otherwise publish
    # its source="compile" record into this strict 5-line assertion
    from mxnet_tpu.compile import coldstart
    coldstart.mark_ready("test-setup")
    # an earlier test's trainer of the same size would leave the ledger
    # cell as this one sets it, and an unchanged cell writes no record
    from mxnet_tpu.observability import memory
    memory.release("trainer")
    monkeypatch.setenv("MXTPU_TELEMETRY", str(out))
    _run_gluon_steps(5, optimizer="rmsprop")
    close_stream()
    raw = [json.loads(l) for l in out.read_text().splitlines()]
    # the HBM ledger publishes ONE source="memory" timeline record when
    # the trainer registers its param bytes (docs/observability.md
    # "Memory ledger") — a resident-set change, not a step record
    mem = [r for r in raw if r.get("source") == "memory"]
    assert len(mem) == 1 and mem[0]["kind"] == "params"
    lines = [r for r in raw if r.get("source") != "memory"]
    assert len(lines) == 5
    for rec in lines:
        assert rec["source"] == "gluon.trainer"
        for field in ("step_time", "data_wait", "compile_count",
                      "compile_seconds", "kvstore_bytes"):
            assert field in rec, field
        assert rec["kvstore_bytes"] > 0      # grads pushed through kvstore
        assert rec["batch_size"] == 8
    assert [r["step"] for r in lines] == list(range(5))
    # warm-up XLA compiles are visible and attributed to early steps —
    # with a warm persistent compilation cache (tests/conftest.py) the
    # backend never compiles, and the cache-hit delta says why
    assert sum(r["compile_count"] + r.get("compile_cache_hits", 0)
               for r in lines) > 0
    # data_wait was measured on the consumer side of NDArrayIter
    assert sum(r["data_wait"] for r in lines) > 0

    # the CLI summarizes it and exits 0
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "telemetry_report.py"),
         str(out)], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "p50" in proc.stdout and "p95" in proc.stdout
    assert "samples/sec" in proc.stdout

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "telemetry_report.py"),
         "--json", str(out)], capture_output=True, text=True)
    summary = json.loads(proc.stdout)
    assert summary["steps"] == 5
    assert summary["step_time_p50_s"] <= summary["step_time_p95_s"] \
        <= summary["step_time_p99_s"]
    assert summary["samples"] == 40


def test_module_fit_emits_step_records(tmp_path, monkeypatch):
    out = tmp_path / "module.jsonl"
    from mxnet_tpu.compile import coldstart
    coldstart.mark_ready("test-setup")   # see 5-step test above
    monkeypatch.setenv("MXTPU_TELEMETRY", str(out))
    rng = np.random.RandomState(7)
    x = rng.randn(40, 8).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.float32)
    train = mx.io.NDArrayIter(x, y, batch_size=8)
    data = mx.sym.var("data")
    h = mx.sym.FullyConnected(data=data, num_hidden=2, name="fc1")
    sym = mx.sym.SoftmaxOutput(data=h, name="softmax")
    mod = mx.Module(sym, context=mx.cpu())
    mod.fit(train, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    close_stream()
    recs = [json.loads(l) for l in out.read_text().splitlines()]
    mod_recs = [r for r in recs if r["source"] == "module.fit"]
    assert len(mod_recs) == 5     # 40 samples / batch 8
    for r in mod_recs:
        assert "forward_backward_time" in r and "optimizer_time" in r
        assert r["step_time"] > 0


# ---------------------------------------------------------------------------
# report CLI failure modes (CI gate contract)
# ---------------------------------------------------------------------------
def _report(path):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "telemetry_report.py"),
         str(path)], capture_output=True, text=True)


def test_report_rejects_empty_and_malformed(tmp_path):
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    proc = _report(empty)
    assert proc.returncode != 0
    assert "no step records" in proc.stderr

    malformed = tmp_path / "bad.jsonl"
    malformed.write_text('{"step_time": 0.1}\n{not json\n')
    proc = _report(malformed)
    assert proc.returncode != 0
    assert "malformed" in proc.stderr

    missing_field = tmp_path / "nofield.jsonl"
    missing_field.write_text('{"step": 1}\n')
    assert _report(missing_field).returncode != 0

    assert _report(tmp_path / "absent.jsonl").returncode != 0


# ---------------------------------------------------------------------------
# Speedometer -> scrapeable metrics
# ---------------------------------------------------------------------------
def test_speedometer_routes_to_registry():
    gauge = REGISTRY.gauge("train.samples_per_sec")
    hist = REGISTRY.histogram("train.batch.seconds")
    before = hist.total_count()

    class P:
        epoch = 0
        eval_metric = None

        def __init__(self, nbatch):
            self.nbatch = nbatch

    sp = mx.callback.Speedometer(batch_size=4, frequent=2)
    sp(P(1))          # arms the window
    sp(P(2))          # crosses it: reports
    assert gauge.get() > 0
    assert hist.total_count() == before + 1


# ---------------------------------------------------------------------------
# overhead guard: disabled path records nothing
# ---------------------------------------------------------------------------
def test_disabled_path_adds_no_events(tmp_path):
    assert os.environ.get("MXTPU_TELEMETRY") is None
    assert not profiler._active()
    events_before = len(profiler._events)
    stray = tmp_path / "should_not_exist.jsonl"
    _run_gluon_steps(3)
    # no chrome-trace events recorded (spans/ops gate on the profiler)...
    assert len(profiler._events) == events_before
    # ...and no JSONL stream was opened anywhere
    assert telemetry._stream["file"] is None
    assert not stray.exists()
