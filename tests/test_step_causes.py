"""What a stalled step was doing, in the trace ring (docs/observability.md
"Step spans"): garbage collections as `gc` spans under the span they
interrupted, generation 0 folded into the step root, the OS's account of
the root's thread, and the numerics guard's blocking read as a `fence`."""
import gc
import threading

import jax.numpy as jnp
import pytest

from mxnet_tpu.observability import REGISTRY, trace
from mxnet_tpu.resilience import numerics


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("MXTPU_TRACE", raising=False)
    monkeypatch.delenv("MXTPU_TRACE_DIR", raising=False)
    monkeypatch.delenv("MXTPU_TRACE_SAMPLE", raising=False)
    trace.enabled()                       # the switches as the env says
    trace._drain_gc()
    trace._gc0_totals()
    trace.reset_ring()
    numerics.reset_flags()
    yield
    trace.detach()
    trace.reset_ring()
    numerics.reset_flags()


def _iteration(body, source="causes.test"):
    """One recorded iteration of a step root around `body()`; returns
    the ring's spans and the root."""
    root = trace.StepRoot(source)
    root.begin(0)
    body()
    root.end(1)
    trace.detach()
    spans = trace.ring_spans()
    return spans, next(s for s in spans if s["name"] == "step")


def test_a_collection_inside_a_span_is_its_child():
    def body():
        with trace.trace_span("fence") as fence:
            gc.collect(1)
        ids["fence"] = fence.span_id

    ids = {}
    before = REGISTRY.get("host.gc.collections").get(generation="1")
    spans, root = _iteration(body)
    found = [s for s in spans if s["name"] == "gc"]
    assert [s["generation"] for s in found] == [1]
    assert found[0]["parent_id"] == ids["fence"]
    assert found[0]["trace_id"] == root["trace_id"]
    assert found[0]["tid"] == root["tid"] and found[0]["step_time"] > 0
    assert "collected" in found[0]
    assert REGISTRY.get("host.gc.collections").get(generation="1") \
        == before + 1


def test_a_collection_on_a_thread_with_no_context_is_a_root():
    seen = {}

    def other():
        trace.detach()
        seen["tid"] = threading.get_ident() & 0xffff
        gc.collect(1)

    def body():
        t = threading.Thread(target=other)
        t.start()
        t.join()

    spans, root = _iteration(body)
    found = [s for s in spans if s["name"] == "gc"]
    assert len(found) == 1 and found[0]["parent_id"] is None
    assert found[0]["tid"] == seen["tid"] != root["tid"]
    assert found[0]["trace_id"] != root["trace_id"]


def test_generation_zero_folds_into_the_step_root():
    spans, root = _iteration(lambda: gc.collect(0))
    assert not [s for s in spans if s["name"] == "gc"]
    assert root["gc0"] >= 1 and root["gc0_ms"] > 0
    assert not trace._gc0_pending          # the next root starts afresh


def test_a_collection_under_the_planes_locks_finishes():
    """The callback runs on whatever thread allocated, holding whatever
    that thread holds: it must take no lock."""
    done = threading.Event()

    def body():
        with trace._shard_lock, trace._ring_lock:
            gc.collect(1)
        done.set()

    root = trace.StepRoot("causes.test")
    root.begin(0)
    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(5.0)
    assert done.is_set(), "a collection under the ring's lock hung"
    root.end(1)
    trace.detach()
    assert [s["generation"] for s in trace.ring_spans()
            if s["name"] == "gc"] == [1]


def test_the_step_root_carries_the_os_account():
    def body():
        sum(i * i for i in range(20000))     # some CPU time

    _spans, root = _iteration(body)
    for key in ("nvcsw", "nivcsw", "majflt", "minflt"):
        assert isinstance(root[key], int) and root[key] >= 0, key
    assert root["cpu_ms"] >= 0.0
    assert root["cpu_ms"] <= 1e3 * root["step_time"] * 1.5 + 5.0


def test_the_guards_read_is_a_fence_and_its_overflow_is_not(monkeypatch):
    flag = jnp.asarray(True)

    def drain():
        numerics.record_flag(flag, where="step")
        assert numerics.drain_flags()["total"] == 1

    spans, root = _iteration(drain)
    fences = [s for s in spans if s["name"] == "fence"]
    assert len(fences) == 1 and fences[0]["parent_id"] == root["span_id"]

    # the overflow resolves a flag many steps old: no span, no wait
    monkeypatch.setattr(numerics, "_FLAG_CAP", 2)
    trace.reset_ring()

    def overflow():
        for _ in range(5):
            numerics.record_flag(flag, where="step")

    spans, _root = _iteration(overflow)
    assert not [s for s in spans if s["name"] == "fence"]
    assert numerics.pending_flags() == 2


def test_trace_off_records_and_queues_nothing(monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE", "0")
    try:
        root = trace.StepRoot("causes.test")
        root.begin(0)
        with trace.trace_span("fence"):
            gc.collect(1)
            gc.collect(0)
        assert not trace._gc_pending and not trace._gc0_pending
        root.end(1)
        trace.detach()
        assert trace.ring_spans() == []
    finally:
        monkeypatch.delenv("MXTPU_TRACE")
        trace.enabled()
