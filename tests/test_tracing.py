"""Distributed tracing + live introspection plane (ISSUE 13,
docs/observability.md "Distributed tracing").

Covers: W3C traceparent parse/format/echo; span parentage across
thread-pool hops (the PR-2 orphaned-span fix); deterministic per-step
trace ids across ranks + StepTimer integration; the gateway E2E chain
(gateway.request → gateway.admission → serving.batch →
engine.dispatch with the same trace id echoed in the response);
rank-shard merging + critical path via tools/trace_report.py; metric
label-cardinality bounding; histogram trace-id exemplars surfacing in
telemetry_report and a forced perf_gate p99 breach; Prometheus
exposition correctness (escaping, HELP/TYPE once per family,
round-trip through a strict parser); docs_drift as a fast gate; and
~zero-cost disablement via MXTPU_TRACE=0.
"""
import importlib.util
import json
import os
import re
import sys
import threading
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.observability import httpz, registry as obs_registry
from mxnet_tpu.observability import trace
from mxnet_tpu.observability.span import capture_context, restored
from mxnet_tpu.observability.telemetry import StepTimer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name + "_t", os.path.join(ROOT, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_trace(monkeypatch):
    monkeypatch.delenv("MXTPU_TRACE", raising=False)
    monkeypatch.delenv("MXTPU_TRACE_DIR", raising=False)
    monkeypatch.delenv("MXTPU_TRACE_SAMPLE", raising=False)
    trace._drain_gc()           # collections of earlier tests: not ours
    trace.reset_ring()
    trace.close_shard()
    yield
    trace.reset_ring()
    trace.close_shard()


# -- TraceContext / traceparent ------------------------------------------
def test_traceparent_roundtrip():
    ctx = trace.TraceContext("ab" * 16, "cd" * 8, True)
    parsed = trace.TraceContext.from_traceparent(ctx.to_traceparent())
    assert parsed.trace_id == "ab" * 16
    assert parsed.span_id == "cd" * 8
    assert parsed.sampled


def test_traceparent_rejects_malformed():
    bad = [None, "", "garbage", "00-short-cdcd-01",
           "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",   # zero trace id
           "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",  # zero span id
           "ff-" + "ab" * 16 + "-" + "cd" * 8 + "-01",  # version ff
           "00-" + "zz" * 16 + "-" + "cd" * 8 + "-01"]  # non-hex
    for header in bad:
        assert trace.TraceContext.from_traceparent(header) is None, header


def test_unsampled_flag_parses_and_reemits():
    ctx = trace.TraceContext.from_traceparent(
        "00-" + "ab" * 16 + "-" + "cd" * 8 + "-00")
    assert not ctx.sampled
    assert ctx.to_traceparent().endswith("-00")


def test_span_parentage_and_nesting():
    with trace.trace_span("root", ctx=trace.TraceContext.new()) as r:
        with trace.trace_span("child") as c:
            with trace.trace_span("grandchild"):
                pass
    # (a collection may land among them as a `gc` span of its own)
    by_name = {s["name"]: s for s in trace.ring_spans() if s["name"] != "gc"}
    assert by_name["root"]["parent_id"] is None
    assert by_name["child"]["parent_id"] == r.span_id
    assert by_name["grandchild"]["parent_id"] == c.span_id
    assert len({s["trace_id"] for s in by_name.values()}) == 1


def test_capture_restore_across_thread_pool():
    """The satellite fix: a span opened on a worker thread parents to
    the submitting request, not to a fresh orphan root."""
    cap = {}
    with trace.trace_span("submit", ctx=trace.TraceContext.new()) as s:
        cap["ctx"] = capture_context()

    def worker():
        with restored(cap["ctx"]):
            with trace.trace_span("exec"):
                pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    execd = [x for x in trace.ring_spans() if x["name"] == "exec"]
    assert execd and execd[0]["parent_id"] == s.span_id
    assert execd[0]["trace_id"] == s.ctx.trace_id


def test_legacy_span_stack_restored_too(tmp_path):
    """capture_context() also carries the PR-2 span() name stack: the
    profiler event for a worker-side span names the submitting span as
    its parent instead of None."""
    from mxnet_tpu import profiler
    profiler.set_config(filename=str(tmp_path / "prof"),
                        aggregate_stats=True)
    profiler.start()
    try:
        with obs.span("submitter"):
            cap = capture_context()

            def worker():
                with restored(cap):
                    with obs.span("worker-side"):
                        pass

            t = threading.Thread(target=worker)
            t.start()
            t.join()
    finally:
        path = profiler.dump()
    events = json.load(open(path))["traceEvents"]
    ws = [e for e in events if e.get("name") == "worker-side"]
    assert ws and ws[0]["args"]["parent"] == "submitter"


def test_trace_disabled_is_noop(monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE", "0")
    assert not trace.enabled()
    with trace.trace_span("root", ctx=trace.TraceContext("a" * 32)):
        with trace.trace_span("child"):
            pass
    assert trace.ring_spans() == []
    assert trace.step_trace_context("t", 0) is None


def test_unsampled_records_nothing_but_keeps_identity():
    ctx = trace.TraceContext("a" * 32, None, sampled=False)
    with trace.trace_span("root", ctx=ctx):
        # identity visible to children (echoed trace ids), no records
        assert trace.current() is ctx
    assert trace.ring_spans() == []


def test_step_trace_context_deterministic_across_ranks(monkeypatch):
    monkeypatch.setenv("MXTPU_GANG_DIR", "/tmp/gang-x")
    a = trace.step_trace_context("gluon.trainer", 7)
    monkeypatch.setenv("JAX_PROCESS_ID", "1")   # another "rank"
    b = trace.step_trace_context("gluon.trainer", 7)
    c = trace.step_trace_context("gluon.trainer", 8)
    assert a.trace_id == b.trace_id
    assert a.trace_id != c.trace_id


def test_steptimer_step_trace_and_exemplar(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_DIR", str(tmp_path))
    timer = StepTimer("trace.test")
    recs = []
    for _ in range(3):
        timer.begin_step()
        with timer.phase("allreduce"):
            pass
        recs.append(timer.end_step(batch_size=4))
    trace.close_shard()
    assert all("trace_id" in r for r in recs)
    shard = tmp_path / ("trace_rank_%d.jsonl" % trace.current_rank())
    spans = [json.loads(l) for l in open(shard)
             if json.loads(l).get("event") == "span"]
    steps = [s for s in spans if s["name"] == "step"]
    phases = [s for s in spans if s["name"] == "allreduce"]
    assert len(steps) == 3 and len(phases) == 3
    roots = {s["trace_id"]: s["span_id"] for s in steps}
    for p in phases:
        assert p["parent_id"] == roots[p["trace_id"]]
    # the step-time histogram kept the worst steps' trace ids
    hist = obs.REGISTRY.get("train.step.seconds")
    ex = hist.exemplars(source="trace.test")
    assert ex and all(tid in roots for _, tid in ex)


# -- registry: cardinality + exemplars + exposition ----------------------
def test_label_cardinality_collapses_to_overflow(monkeypatch):
    monkeypatch.setenv("MXTPU_METRIC_MAX_LABELS", "3")
    c = obs_registry.Counter("t.cardinality")
    for i in range(10):
        c.inc(model="m%d" % i)
    keys = c.labelsets()
    assert len(keys) == 4                     # 3 real + overflow
    assert obs_registry.OVERFLOW_KEY in keys
    assert c.get(overflow="true") == 7
    # established labelsets keep counting exactly
    c.inc(model="m0")
    assert c.get(model="m0") == 2
    dropped = obs.REGISTRY.get("observability.labels.dropped")
    assert dropped.get(metric="t.cardinality") >= 7


def test_cardinality_bound_applies_to_gauge_and_histogram(monkeypatch):
    monkeypatch.setenv("MXTPU_METRIC_MAX_LABELS", "2")
    g = obs_registry.Gauge("t.gauge.cardinality")
    h = obs_registry.Histogram("t.hist.cardinality")
    for i in range(5):
        g.set(i, trace="t%d" % i)
        h.observe(0.1, trace="t%d" % i)
    assert len(g.labelsets()) == 3
    assert len(h.labelsets()) == 3
    assert h.count(overflow="true") == 3


def test_histogram_exemplars_keep_worst_k(monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_EXEMPLARS", "2")
    h = obs.REGISTRY.histogram("t.exemplars")
    h.observe(0.1, exemplar="fast")
    h.observe(0.9, exemplar="slowest")
    h.observe(0.5, exemplar="slow")
    h.observe(0.2)                 # untagged observations still count
    assert h.exemplars() == [(0.9, "slowest"), (0.5, "slow")]
    assert h.count() == 4
    # snapshot/export carries them
    rows = {name: val for name, kind, labels, val
            in obs.REGISTRY.snapshot() if name == "t.exemplars"}
    assert rows and rows["t.exemplars"]["exemplars"][0][1] == "slowest"


_PROM_LINE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(-?[0-9.e+-]+|NaN)$')
_PROM_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_prometheus(text):
    """Strict exposition-format parser: every non-comment line must be
    `name{labels} value`; label values unescape per the format. Returns
    ({(name, frozen labels): value}, {name: [help/type lines]})."""
    samples, meta = {}, {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            parts = line.split(None, 3)
            meta.setdefault(parts[2], []).append(parts[1])
            continue
        assert not line.startswith("#"), "stray comment %r" % line
        m = _PROM_LINE.match(line)
        assert m, "line %d unparseable: %r" % (lineno, line)
        name, labelstr, value = m.groups()
        labels = {}
        if labelstr:
            consumed = 0
            for lm in _PROM_LABEL.finditer(labelstr):
                raw = lm.group(2)
                labels[lm.group(1)] = (
                    raw.replace("\\n", "\n").replace('\\"', '"')
                    .replace("\\\\", "\\"))
                consumed = lm.end()
            rest = labelstr[consumed:].strip(", ")
            assert not rest, "unparsed label text %r" % rest
        samples[(name, tuple(sorted(labels.items())))] = float(value)
    return samples, meta


def test_prometheus_escaping_roundtrips():
    c = obs_registry.Counter("t.escaping")
    nasty = 'quo"te\\back\nslash'
    c.inc(3, op=nasty)
    reg = obs_registry.MetricsRegistry()
    reg._metrics["t.escaping"] = c      # isolated registry
    samples, _ = _parse_prometheus(reg.to_prometheus())
    key = ("mxtpu_t_escaping_total", (("op", nasty),))
    assert samples.get(key) == 3.0, sorted(samples)


def test_prometheus_help_type_once_per_family_and_roundtrip():
    reg = obs_registry.MetricsRegistry()
    c = reg.counter("t.family", help="a help line")
    c.inc(1, shard="a")
    c.inc(2, shard="b")
    h = reg.histogram("t.latency", help="hist help",
                      buckets=(0.1, 1.0))
    h.observe(0.05, route="x")
    h.observe(5.0, route="x")
    text = reg.to_prometheus()
    assert text.count("# TYPE mxtpu_t_family_total counter") == 1
    assert text.count("# HELP mxtpu_t_family_total a help line") == 1
    assert text.count("# TYPE mxtpu_t_latency histogram") == 1
    samples, meta = _parse_prometheus(text)
    assert samples[("mxtpu_t_family_total", (("shard", "a"),))] == 1.0
    assert samples[("mxtpu_t_family_total", (("shard", "b"),))] == 2.0
    # histogram cumulative buckets + sum/count round-trip
    assert samples[("mxtpu_t_latency_bucket",
                    (("le", "0.1"), ("route", "x")))] == 1.0
    assert samples[("mxtpu_t_latency_bucket",
                    (("le", "+Inf"), ("route", "x")))] == 2.0
    assert samples[("mxtpu_t_latency_count", (("route", "x"),))] == 2.0
    assert meta["mxtpu_t_family_total"] == ["HELP", "TYPE"]


def test_full_registry_exposition_parses():
    """The real process registry (every metric the suite touched so
    far) round-trips through the strict parser — /metricsz is always
    scrapeable."""
    _parse_prometheus(obs.REGISTRY.to_prometheus())


# -- live plane -----------------------------------------------------------
def test_observability_server_routes():
    srv = httpz.ObservabilityServer(port=0).start()
    try:
        text = urllib.request.urlopen(
            srv.url + "/metricsz", timeout=10).read().decode()
        _parse_prometheus(text)
        dbg = json.loads(urllib.request.urlopen(
            srv.url + "/debugz", timeout=10).read().decode())
        assert "threads" in dbg and "trace" in dbg and "lease" in dbg
        assert "compile" in dbg
        ok = json.loads(urllib.request.urlopen(
            srv.url + "/healthz", timeout=10).read().decode())
        assert ok["ok"]
        assert urllib.request.urlopen(
            srv.url + "/metricsz?x=1", timeout=10).status == 200
    finally:
        srv.close()


# -- gateway E2E ----------------------------------------------------------
FEATURES, CLASSES = 8, 4


def _mlp_engine(seed, name):
    from mxnet_tpu.serving import InferenceEngine
    rng = np.random.RandomState(seed)
    h = mx.sym.FullyConnected(data=mx.sym.var("data"),
                              num_hidden=CLASSES, name="fc1")
    sym = mx.sym.SoftmaxOutput(data=h, name="softmax")
    args = {"fc1_weight": mx.nd.array(
                (rng.randn(CLASSES, FEATURES) * 0.5).astype(np.float32)),
            "fc1_bias": mx.nd.array(
                rng.randn(CLASSES).astype(np.float32))}
    return InferenceEngine.from_symbol(
        sym, args, {}, {"data": (FEATURES,)}, 2, name=name)


def test_gateway_traceparent_e2e(tmp_path, monkeypatch):
    """ISSUE acceptance: a request with a traceparent header yields the
    same trace id echoed in the response AND a merged trace with
    gateway → admission → batch → dispatch spans correctly parented
    across >= 2 thread hops (handler thread -> dispatcher -> worker)."""
    from mxnet_tpu.serving import Gateway, ModelRegistry
    monkeypatch.setenv("MXTPU_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_TELEMETRY", str(tmp_path / "t.jsonl"))
    reg = ModelRegistry()
    reg.register("m0", lambda: _mlp_engine(0, "m0"), eager=True)
    gw = Gateway(reg).start()
    try:
        tp_in = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        req = urllib.request.Request(
            gw.url + "/v1/models/m0:predict",
            data=json.dumps({"inputs": [[0.1] * FEATURES]}).encode(),
            headers={"Content-Type": "application/json",
                     "traceparent": tp_in})
        resp = urllib.request.urlopen(req, timeout=60)
        body = json.loads(resp.read().decode())
        tp_out = resp.headers.get("traceparent")
        assert tp_out and tp_out.split("-")[1] == "ab" * 16
        assert body["trace_id"] == "ab" * 16
        # a second request WITHOUT a header mints a fresh root
        req2 = urllib.request.Request(
            gw.url + "/v1/models/m0:predict",
            data=json.dumps({"inputs": [[0.2] * FEATURES]}).encode(),
            headers={"Content-Type": "application/json"})
        resp2 = urllib.request.urlopen(req2, timeout=60)
        tid2 = json.loads(resp2.read().decode())["trace_id"]
        assert tid2 != "ab" * 16
        # gateway introspection routes
        _parse_prometheus(urllib.request.urlopen(
            gw.url + "/metricsz", timeout=10).read().decode())
        dbg = json.loads(urllib.request.urlopen(
            gw.url + "/debugz", timeout=10).read().decode())
        assert dbg["gateway"]["queues"].keys() >= {"interactive"}
        assert "m0" in dbg["registry"]["resident"]
        assert "servers" in dbg and "threads" in dbg
    finally:
        gw.close()
        from mxnet_tpu.observability import telemetry
        telemetry.close_stream()
    trace.close_shard()
    shard = tmp_path / ("trace_rank_%d.jsonl" % trace.current_rank())
    spans = [json.loads(l) for l in open(shard)]
    mine = {s["name"]: s for s in spans
            if s.get("trace_id") == "ab" * 16}
    assert {"gateway.request", "gateway.admission", "serving.queue",
            "serving.batch", "engine.dispatch"} <= set(mine)
    root = mine["gateway.request"]
    assert root["parent_id"] == "cd" * 8          # the client's span
    assert mine["gateway.admission"]["parent_id"] == root["span_id"]
    assert mine["serving.queue"]["parent_id"] == root["span_id"]
    assert mine["serving.batch"]["parent_id"] == root["span_id"]
    assert mine["engine.dispatch"]["parent_id"] == \
        mine["serving.batch"]["span_id"]
    # >= 2 thread hops: handler thread vs worker thread
    assert mine["serving.batch"]["tid"] != root["tid"]
    # trace_report merges the shard and reconstructs the chain
    tr = _load_tool("trace_report")
    entries = tr.summarize(tr.load_spans([str(shard)]))
    e = {x["trace_id"]: x for x in entries}["ab" * 16]
    assert e["name"] == "gateway.request"
    names = [c["name"] for c in e["critical"]]
    assert names[0] == "gateway.request"
    # the gateway telemetry record carries the trace id for exemplars
    recs = [json.loads(l) for l in open(tmp_path / "t.jsonl")]
    served = [r for r in recs if r.get("source") == "gateway"
              and r.get("event") == "request"]
    assert any(r.get("trace_id") == "ab" * 16 for r in served)


def test_gateway_trace_off_no_header(monkeypatch):
    from mxnet_tpu.serving import Gateway, ModelRegistry
    monkeypatch.setenv("MXTPU_TRACE", "0")
    reg = ModelRegistry()
    reg.register("m0", lambda: _mlp_engine(0, "m0"), eager=True)
    gw = Gateway(reg).start()
    try:
        req = urllib.request.Request(
            gw.url + "/v1/models/m0:predict",
            data=json.dumps({"inputs": [[0.1] * FEATURES]}).encode(),
            headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req, timeout=60)
        assert resp.headers.get("traceparent") is None
        assert "trace_id" not in json.loads(resp.read().decode())
    finally:
        gw.close()
    assert trace.ring_spans() == []


# -- trace_report ---------------------------------------------------------
def _write_shard(path, rank, spans, clock_wall=1000.0):
    with open(path, "w") as f:
        f.write(json.dumps({"source": "trace", "event": "clock",
                            "step_time": 0.0, "ts": clock_wall,
                            "perf": 0.0, "rank": rank,
                            "pid": 1}) + "\n")
        for s in spans:
            rec = {"source": "trace", "event": "span", "rank": rank,
                   "pid": 1, "tid": 1, "step_time": s.pop("dur"), **s}
            f.write(json.dumps(rec) + "\n")


def test_trace_report_merges_ranks_and_aligns(tmp_path):
    tid = "f" * 32
    _write_shard(tmp_path / "trace_rank_0.jsonl", 0, [
        {"name": "step", "trace_id": tid, "span_id": "r0",
         "parent_id": None, "ts": 100.0, "dur": 1.0, "step": 4,
         "source": "gluon.trainer"},
        {"name": "allreduce", "trace_id": tid, "span_id": "a0",
         "parent_id": "r0", "ts": 100.1, "dur": 0.8},
        {"name": "exchange/bucket", "trace_id": tid, "span_id": "x0",
         "parent_id": "a0", "ts": 100.15, "dur": 0.7},
    ])
    _write_shard(tmp_path / "trace_rank_1.jsonl", 1, [
        {"name": "step", "trace_id": tid, "span_id": "r1",
         "parent_id": None, "ts": 100.0, "dur": 1.2, "step": 4,
         "source": "gluon.trainer"},
        {"name": "exchange/bucket", "trace_id": tid, "span_id": "x1",
         "parent_id": "r1", "ts": 100.2, "dur": 1.0},
    ])
    tr = _load_tool("trace_report")
    spans = tr.load_spans(tr._shard_files([str(tmp_path)]))
    assert len(spans) == 5
    entries = tr.summarize(spans)
    assert len(entries) == 1
    e = entries[0]
    # ONE merged per-step trace carrying BOTH ranks' exchange spans
    assert e["ranks"] == [0, 1] and e["step"] == 4
    assert e["roots"] == 2
    # critical path follows the slowest root (rank 1)
    assert e["dur_s"] == pytest.approx(1.2)
    assert [c["name"] for c in e["critical"]] == ["step",
                                                  "exchange/bucket"]
    assert e["critical"][1]["rank"] == 1
    # chrome trace: one process lane per rank
    chrome = tr.to_chrome_trace(spans)
    pids = {ev["pid"] for ev in chrome["traceEvents"]
            if ev.get("ph") == "X"}
    assert pids == {0, 1}
    report = tr.format_report(entries)
    assert "step 4" in report and "rank(s) 0,1" in report


def test_trace_report_clock_offset_from_heartbeats(tmp_path):
    tid = "e" * 32
    _write_shard(tmp_path / "trace_rank_0.jsonl", 0, [
        {"name": "step", "trace_id": tid, "span_id": "r0",
         "parent_id": None, "ts": 100.0, "dur": 1.0}])
    # rank 0's clock runs 5s behind the shared FS: heartbeat stamp
    # 100, file mtime now — offset shifts its spans forward
    hb = tmp_path / "rank_0.hb"
    hb.write_text(json.dumps({"rank": 0, "heartbeat": 100.0}))
    tr = _load_tool("trace_report")
    offsets = tr.rank_offsets([str(tmp_path)])
    assert 0 in offsets and offsets[0] > 0
    spans = tr.load_spans([str(tmp_path / "trace_rank_0.jsonl")],
                          offsets)
    assert spans[0]["ts"] == pytest.approx(100.0 + offsets[0])


def test_trace_report_strict_on_garbage(tmp_path):
    tr = _load_tool("trace_report")
    with pytest.raises(tr.TraceReportError):
        tr._shard_files([str(tmp_path)])          # no shards
    bad = tmp_path / "trace_rank_0.jsonl"
    bad.write_text("not json\n{}\n")
    with pytest.raises(tr.TraceReportError):
        tr.load_spans([str(bad)])
    # a torn LAST line (writer died mid-span) is tolerated
    tid = "d" * 32
    torn = tmp_path / "trace_rank_1.jsonl"
    _write_shard(torn, 1, [
        {"name": "s", "trace_id": tid, "span_id": "a",
         "parent_id": None, "ts": 1.0, "dur": 0.1}])
    with open(torn, "a") as f:
        f.write('{"source": "trace", "event": "span", "trunc')
    assert len(tr.load_spans([str(torn)])) == 1


# -- exemplars through report + gate --------------------------------------
def test_report_excludes_trace_source_and_surfaces_exemplars(tmp_path):
    stream = tmp_path / "t.jsonl"
    recs = [
        {"source": "train", "step": 0, "step_time": 0.01,
         "trace_id": "t-fast"},
        {"source": "train", "step": 1, "step_time": 5.0,
         "trace_id": "t-slow"},
        # trace spans must be excluded from the headline exactly once
        {"source": "trace", "event": "span", "step_time": 99.0,
         "trace_id": "t-slow", "name": "step", "span_id": "x",
         "ts": 0.0},
        {"source": "gateway", "event": "request", "step_time": 0.002,
         "class": "interactive", "model": "m", "status": 200,
         "trace_id": "g-fast"},
        {"source": "gateway", "event": "request", "step_time": 0.9,
         "class": "interactive", "model": "m", "status": 200,
         "trace_id": "g-slow"},
    ]
    with open(stream, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    rep = _load_tool("telemetry_report")
    assert "trace" in rep.EXCLUDED_HEADLINE_SOURCES
    s = rep.summarize(rep.load_records(str(stream)))
    assert s["steps"] == 2                     # trace span NOT blended
    assert s["step_time_p99_s"] == pytest.approx(5.0)
    assert s["trace_spans"] == 1
    assert s["step_time_exemplars"][0] == "t-slow"
    assert s["gateway_interactive_exemplars"][0] == "g-slow"
    out = rep.format_summary(s)
    assert "t-slow" in out

    # a forced p99 breach prints >= 1 exemplar trace id (acceptance)
    gate = _load_tool("perf_gate")
    import io
    from contextlib import redirect_stderr, redirect_stdout
    err, out_buf = io.StringIO(), io.StringIO()
    with redirect_stdout(out_buf), redirect_stderr(err):
        rc = gate.main([str(stream),
                        "--max-p99-ms-class", "interactive=1",
                        "--max-step-p95-s", "0.1"])
    assert rc == 1
    stderr = err.getvalue()
    assert "BREACH gateway_interactive_p99_ms" in stderr
    assert "g-slow" in stderr and "t-slow" in stderr
    verdict = json.loads(out_buf.getvalue().splitlines()[0])
    assert verdict["exemplars"]["gateway_interactive_p99_ms"][0] == \
        "g-slow"


# -- docs drift -----------------------------------------------------------
def test_docs_drift_gate_passes():
    drift = _load_tool("docs_drift")
    assert drift.main([]) == 0


def test_docs_drift_detects_both_directions(tmp_path, monkeypatch):
    drift = _load_tool("docs_drift")
    code = drift.code_metrics()
    docs = drift.doc_metrics()
    assert code == docs
    # the expansion shorthand: `a.b.c` / `.d` and `.d.e`
    doc = tmp_path / "obs.md"
    doc.write_text("| `a.b.c` / `.d` / `.d.e` | counter | x |\n")
    assert drift.doc_metrics(str(doc)) == {"a.b.c", "a.b.d", "a.d.e"}
    src = tmp_path / "src"
    src.mkdir()
    (src / "m.py").write_text(
        'from x import counter\n'
        'C = counter("emitted.not.documented")\n'
        'import time\n'
        't = time.perf_counter()\n')
    assert drift.code_metrics(str(src)) == {"emitted.not.documented"}


@pytest.mark.slow
def test_two_rank_step_traces_merge_for_real(tmp_path):
    """The real path, not synthetic shards: two processes tagged as
    ranks 0/1 of one gang train a few steps through the actual
    Trainer/StepTimer pipeline; their shards merge into one per-step
    trace carrying both ranks (the deterministic step-id contract)."""
    import subprocess
    code = (
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import gluon, autograd\n"
        "net = gluon.nn.Dense(4)\n"
        "net.initialize(mx.init.Xavier())\n"
        "tr = gluon.Trainer(net.collect_params(), 'sgd',\n"
        "                   {'learning_rate': 0.1})\n"
        "x = mx.nd.array(np.ones((4, 8), np.float32))\n"
        "y = mx.nd.array(np.ones((4, 4), np.float32))\n"
        "lf = gluon.loss.L2Loss()\n"
        "for _ in range(2):\n"
        "    with autograd.record():\n"
        "        loss = lf(net(x), y)\n"
        "    loss.backward()\n"
        "    tr.step(4)\n")
    for rank in ("0", "1"):
        env = dict(os.environ, MXTPU_GANG_DIR=str(tmp_path),
                   JAX_PROCESS_ID=rank, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
    tr_tool = _load_tool("trace_report")
    spans = tr_tool.load_spans(tr_tool._shard_files([str(tmp_path)]))
    entries = tr_tool.summarize(spans)
    steps = [e for e in entries if e["name"] == "step"]
    assert steps and all(e["ranks"] == [0, 1] for e in steps), entries
    # each merged step trace has one root per rank, phases under each
    assert all(e["roots"] == 2 for e in steps)


def test_metrics_port_singleton(monkeypatch):
    httpz.stop_singleton()
    monkeypatch.delenv("MXTPU_METRICS_PORT", raising=False)
    assert httpz.maybe_start() is None
    monkeypatch.setenv("MXTPU_METRICS_PORT", "0")   # 0 = disabled
    assert httpz.maybe_start() is None
