"""Serving resilience plane tests (ISSUE-14, docs/fault_tolerance.md
"Serving resilience").

Covers: watchdog-bounded dispatch (typed `DeviceUnreachable` trips,
bit-identical off-path), the replica health state machine (wedge →
quarantine → canary re-admission; worker death → reroute; typed
failure only when NO replica survives), the scheduler loop-crash fix
(every stranded request resolves, `drain()` returns — previously those
handles hung forever), the per-model gateway circuit breaker,
Retry-After backpressure, hedged requests, client-disconnect slot
reclamation, and the CI surface (`perf_gate --min-success-rate`,
`telemetry_report` resilience section, `chaos_run --wedge-replica`).
"""
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.observability import registry as obs
from mxnet_tpu.resilience import Deadline, chaos
from mxnet_tpu.resilience.watchdog import HealthWatchdog
from mxnet_tpu.serving import (BreakerOpen, ContinuousBatchScheduler,
                               DecodeEngine, DeviceUnreachable, Gateway,
                               InferenceEngine, ModelRegistry,
                               ModelServer, NoHealthyReplica,
                               SchedulerCrashed, ServerClosed)
from mxnet_tpu.serving import health
from mxnet_tpu.serving.batcher import InferenceRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FEATURES, CLASSES = 6, 3


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    chaos.configure("")
    monkeypatch.delenv("MXTPU_SERVE_DISPATCH_TIMEOUT_S", raising=False)
    monkeypatch.delenv("MXTPU_GATEWAY_HEDGE_MS", raising=False)
    yield
    chaos.reset()


def _arm(monkeypatch, timeout="0.2", trips="2", canary="0.05"):
    monkeypatch.setenv("MXTPU_SERVE_DISPATCH_TIMEOUT_S", timeout)
    monkeypatch.setenv("MXTPU_SERVE_TRIP_LIMIT", trips)
    monkeypatch.setenv("MXTPU_SERVE_CANARY_S", canary)


def _mlp_engine(seed=0, name=None, max_batch=4):
    rng = np.random.RandomState(seed)
    h = mx.sym.FullyConnected(data=mx.sym.var("data"),
                              num_hidden=CLASSES, name="fc1")
    sym = mx.sym.SoftmaxOutput(data=h, name="softmax")
    args = {"fc1_weight": mx.nd.array(
                (rng.randn(CLASSES, FEATURES) * 0.5).astype(np.float32)),
            "fc1_bias": mx.nd.array(
                rng.randn(CLASSES).astype(np.float32))}
    return InferenceEngine.from_symbol(
        sym, args, {}, {"data": (FEATURES,)}, max_batch,
        name=name or ("res%d" % seed))


def _gpt_block(seed=3, vocab=32, max_seq_len=32):
    from mxnet_tpu.gluon.model_zoo.gpt import GPTDecoder
    np.random.seed(seed)
    blk = GPTDecoder(vocab, max_seq_len=max_seq_len, num_layers=1,
                     num_heads=2, embed_dim=16)
    blk.initialize(mx.init.Xavier(magnitude=2.5))
    return blk


def _x(n=1, seed=7):
    return np.random.RandomState(seed).randn(
        n, FEATURES).astype(np.float32)


def _counter_total(name):
    m = obs.REGISTRY.get(name)
    return 0.0 if m is None else float(m.total())


def _teardown(server, timeout=30):
    """drain + wait out the canary thread: a lingering canary probe
    from THIS test could steal seeded chaos draws from the shared
    `serving.replica0.dispatch` site armed by the NEXT test."""
    chaos.reset()
    server.drain(timeout=timeout)
    th = getattr(server, "_canary_thread", None)
    if th is not None:
        th.join(timeout=15)


# -- watchdog-bounded dispatch -------------------------------------------

def test_guard_off_is_direct_call():
    # default (no env): no watchdog thread, plain call
    assert health.dispatch_timeout() == 0.0
    wd = HealthWatchdog()
    assert health.guard(wd, lambda: 41, "x") == 41


def test_guard_trip_is_typed_device_unreachable(monkeypatch):
    monkeypatch.setenv("MXTPU_SERVE_DISPATCH_TIMEOUT_S", "0.1")
    wd = HealthWatchdog()
    before = _counter_total("resilience.watchdog.trips")
    with pytest.raises(DeviceUnreachable) as err:
        health.guard(wd, lambda: time.sleep(5), "wedged thing")
    assert "wedged thing" in str(err.value)
    assert _counter_total("resilience.watchdog.trips") > before


def test_guard_errors_propagate(monkeypatch):
    monkeypatch.setenv("MXTPU_SERVE_DISPATCH_TIMEOUT_S", "5")
    wd = HealthWatchdog()
    with pytest.raises(ValueError):
        health.guard(wd, lambda: (_ for _ in ()).throw(ValueError("e")),
                     "x")


def test_chaos_hang_kind():
    spec = chaos.parse_spec("engine.dispatch:kind=hang,n=1")
    assert spec["engine.dispatch"]["kind"] == "hang"
    # a hang without secs defaults far past any deadline in the system
    chaos.configure("s.x:kind=hang,n=1")
    site = chaos._lookup("s.x")
    assert site.secs == 3600.0


def test_watchdog_off_and_armed_are_bit_identical(monkeypatch):
    server = ModelServer(_mlp_engine(1, name="parity"), num_workers=1,
                         max_wait_ms=1.0, warmup=True).start()
    try:
        x = _x()
        off = np.asarray(server.infer(x, timeout=30)[0])
        monkeypatch.setenv("MXTPU_SERVE_DISPATCH_TIMEOUT_S", "5")
        armed = np.asarray(server.infer(x, timeout=30)[0])
        assert np.array_equal(off, armed)
    finally:
        monkeypatch.delenv("MXTPU_SERVE_DISPATCH_TIMEOUT_S")
        assert server.drain(timeout=30)


# -- replica health state machine ----------------------------------------

def test_wedged_replica_quarantined_then_canary_readmitted(monkeypatch):
    """The tentpole sequence: replica 0 wedges (injected hangs), its
    batches re-dispatch to replica 1 (every request still succeeds),
    the replica quarantines at the trip limit, and once the fault
    clears the canary probe re-admits it."""
    _arm(monkeypatch)
    server = ModelServer(_mlp_engine(2, name="wedge"), num_workers=2,
                         max_wait_ms=1.0, warmup=True).start()
    try:
        # 2 trips to quarantine + 1 canary trip, then the fault clears
        chaos.configure(
            "serving.replica0.dispatch:kind=hang,secs=2,n=3")
        deadline_ok = []
        t_start = time.perf_counter()
        for i in range(6):
            t0 = time.perf_counter()
            out = server.infer(_x(seed=i), timeout=30)
            deadline_ok.append(time.perf_counter() - t0 <= 0.2 + 1.0)
            assert out[0].shape == (1, CLASSES)
        assert all(deadline_ok), "a request outlived budget + grace"
        # quarantined at the trip limit...
        t_stop = time.monotonic() + 30
        quarantined = False
        while time.monotonic() < t_stop and not quarantined:
            st = {w["index"]: w for w in server.stats()["workers"]}
            quarantined = st[0]["state"] == "quarantined"
            if not quarantined:
                server.infer(_x(), timeout=30)   # keep pressure on
        assert quarantined
        # ...then canary-re-admitted once the injected hangs exhaust
        readmitted = False
        t_stop = time.monotonic() + 30
        while time.monotonic() < t_stop and not readmitted:
            st = {w["index"]: w for w in server.stats()["workers"]}
            readmitted = st[0]["state"] == "healthy"
            time.sleep(0.02)
        assert readmitted
        assert _counter_total("serving.replica.quarantines") >= 1
        assert _counter_total("serving.replica.readmits") >= 1
        assert _counter_total("serving.replica.trips") >= 2
        assert obs.REGISTRY.get("serving.replica.state") is not None
    finally:
        _teardown(server)


def test_single_replica_wedge_fails_typed_not_hanging(monkeypatch):
    """With NO surviving replica the request fails typed
    (`NoHealthyReplica`) in bounded time — never a hang."""
    _arm(monkeypatch, timeout="0.15")
    server = ModelServer(_mlp_engine(3, name="solo"), num_workers=1,
                         max_wait_ms=1.0, warmup=True).start()
    try:
        chaos.configure(
            "serving.replica0.dispatch:kind=hang,secs=2,n=50")
        t0 = time.perf_counter()
        with pytest.raises(NoHealthyReplica) as err:
            server.infer(_x(), timeout=10)
        assert time.perf_counter() - t0 < 5.0
        assert err.value.server == "solo"
    finally:
        _teardown(server)


def test_worker_death_detected_and_rerouted():
    """ISSUE-14 satellite: a dead worker thread must stop receiving
    traffic; its in-hand batch re-dispatches and every request still
    resolves. Previously the dispatcher kept feeding the corpse and
    the queue stranded silently."""
    server = ModelServer(_mlp_engine(4, name="death"), num_workers=2,
                         max_wait_ms=1.0, warmup=True).start()
    orig = server._run_batch

    def boom(worker, batch):
        if worker.index == 0:
            raise RuntimeError("synthetic worker crash")
        return orig(worker, batch)

    server._run_batch = boom
    try:
        before = _counter_total("serving.worker.deaths")
        outs = [server.infer(_x(seed=i), timeout=30) for i in range(4)]
        assert all(o[0].shape == (1, CLASSES) for o in outs)
        st = {w["index"]: w for w in server.stats()["workers"]}
        assert st[0]["state"] == "dead" and st[0]["alive"] is False
        assert st[1]["state"] == "healthy" and st[1]["alive"] is True
        assert server.stats()["healthy_workers"] == 1
        assert _counter_total("serving.worker.deaths") == before + 1
    finally:
        assert server.drain(timeout=30)


def test_all_workers_dead_fails_typed_and_drain_returns():
    server = ModelServer(_mlp_engine(5, name="grave"), num_workers=1,
                         max_wait_ms=1.0, warmup=True).start()
    server._run_batch = lambda worker, batch: (_ for _ in ()).throw(
        RuntimeError("synthetic crash"))
    try:
        with pytest.raises(NoHealthyReplica):
            server.infer(_x(), timeout=10)
        # later requests are refused typed at dispatch, not stranded
        with pytest.raises(NoHealthyReplica):
            server.infer(_x(), timeout=10)
    finally:
        assert server.drain(timeout=10)


# -- scheduler loop crash (the drain()-hangs fix) ------------------------

def test_scheduler_crash_rejects_all_and_drain_returns():
    """The satellite bug: a crashed `_loop` left `_closed` False —
    later submits queued into a dead loop and their `result()` hung
    forever. Now: every stranded request resolves with a typed
    `SchedulerCrashed` naming the scheduler, `drain(timeout)` returns,
    and new submits are refused typed."""
    engine = DecodeEngine(_gpt_block(), max_slots=2, name="crashd")
    sched = ContinuousBatchScheduler(engine, max_new_tokens=4,
                                     name="crashd/0")
    before = _counter_total("serving.decode.loop_crash")

    def boom():
        raise RuntimeError("synthetic scheduler crash")

    sched._admit = boom
    sched.start()
    h = sched.submit([1, 2, 3])
    with pytest.raises(SchedulerCrashed) as err:
        h.result(timeout=10)
    assert "crashd/0" in str(err.value)
    assert err.value.server == "crashd/0"
    assert sched.drain(timeout=10)          # returns — used to hang
    assert not sched.alive()
    assert sched.state == "dead"
    with pytest.raises(SchedulerCrashed):
        sched.submit([1, 2, 3])
    assert _counter_total("serving.decode.loop_crash") == before + 1
    st = sched.stats()
    assert st["alive"] is False and st["crashed"] is not None


def test_decode_server_routes_around_crashed_scheduler():
    engine = DecodeEngine(_gpt_block(), max_slots=2, name="route")
    server = ModelServer(engine, num_workers=2, max_new_tokens=4)
    server.start()
    try:
        s0 = server._schedulers[0]
        s0._admit = lambda: (_ for _ in ()).throw(
            RuntimeError("synthetic"))
        # first submit lands on s0 (tie-break) and is rejected typed
        with pytest.raises(SchedulerCrashed):
            server.generate([1, 2, 3], timeout=10)
        # the dead replica stops receiving traffic; s1 serves
        toks = server.generate([1, 2, 3], timeout=30)
        assert len(toks) >= 1
        assert server.stats()["healthy_workers"] == 1
    finally:
        server.drain(timeout=30)


def test_wedged_prefill_requeues_prompt_until_recovery(monkeypatch):
    """A tripped decode PREFILL must not fail the (uncomputed) prompt:
    it requeues at the head and rides the replica once the canary
    re-admits it — only mid-decode sequences fail typed."""
    _arm(monkeypatch, timeout="0.2", trips="2", canary="0.05")
    engine = DecodeEngine(_gpt_block(), max_slots=2, name="requeue")
    # the programs compile before the 0.2 s watchdog is in the way: a
    # cold compile is not the wedge this test plants
    engine.warmup()
    sched = ContinuousBatchScheduler(engine, max_new_tokens=3,
                                     name="requeue/0").start()
    try:
        chaos.configure(
            "serving.replica0.dispatch:kind=hang,secs=2,n=3")
        h = sched.submit([1, 2, 3])
        toks = h.result(timeout=60)      # survives the whole wedge
        assert len(toks) >= 1
        assert sched.trips >= 2
        assert sched.state == "healthy"  # canary re-admitted it
    finally:
        chaos.reset()
        sched.drain(timeout=30)


def test_no_live_decode_replica_is_typed():
    engine = DecodeEngine(_gpt_block(), max_slots=2, name="alldead")
    server = ModelServer(engine, num_workers=1, max_new_tokens=4)
    server.start()
    try:
        s0 = server._schedulers[0]
        s0._admit = lambda: (_ for _ in ()).throw(
            RuntimeError("synthetic"))
        with pytest.raises(SchedulerCrashed):
            server.generate([1, 2], timeout=10)
        t_stop = time.monotonic() + 10
        while time.monotonic() < t_stop and s0.alive():
            time.sleep(0.01)        # let the crashed loop finish dying
        with pytest.raises(NoHealthyReplica):
            server.generate([1, 2], timeout=10)
    finally:
        server.drain(timeout=10)


# -- client cancel / disconnect ------------------------------------------

def test_cancel_evicts_sequence_and_frees_slot():
    engine = DecodeEngine(_gpt_block(max_seq_len=128), max_slots=2,
                          name="cancel")
    sched = ContinuousBatchScheduler(engine, max_new_tokens=100).start()
    try:
        h = sched.submit([1, 2, 3])
        while not h.generated and not h.done():
            time.sleep(0.005)
        h.cancel()
        t0 = time.monotonic()
        with pytest.raises(Exception):
            h.result(timeout=10)
        assert time.monotonic() - t0 < 5.0
        # the KV slot is freed at the step boundary, not leaked until
        # max_new_tokens
        assert len(h.generated) < 100
        t_stop = time.monotonic() + 5
        while time.monotonic() < t_stop and \
                sched.stats()["active_slots"]:
            time.sleep(0.01)
        assert sched.stats()["active_slots"] == 0
        assert sched.evicted >= 1
        # the scheduler still serves
        toks = sched.generate([4, 5], max_new_tokens=3, timeout=30)
        assert len(toks) >= 1
    finally:
        sched.drain(timeout=30)


def test_stream_disconnect_frees_slot_and_keeps_serving():
    """ISSUE-14 satellite: a broken pipe mid-:generate-stream must
    retire the sequence (KV slot freed long before max_new_tokens)
    and must not kill the handler thread."""
    reg = ModelRegistry()
    reg.register("gen", lambda: ModelServer(
        DecodeEngine(_gpt_block(max_seq_len=256), max_slots=2,
                     name="genstream"),
        num_workers=1, max_new_tokens=200), warmup=False)
    gw = Gateway(reg, port=0, concurrency=2).start()
    try:
        server = reg.get("gen")
        # throttle decode steps so the disconnect lands MID-generation
        # (the tiny model would otherwise finish all 200 tokens before
        # the broken pipe is detectable)
        chaos.configure("serving.decode:kind=sleep,secs=0.05")
        body = json.dumps({"tokens": [1, 2, 3], "stream": True,
                           "max_new_tokens": 200}).encode()
        s = socket.create_connection(("127.0.0.1", gw.port), timeout=10)
        s.sendall(b"POST /v1/models/gen:generate HTTP/1.1\r\n"
                  b"Host: x\r\nContent-Type: application/json\r\n" +
                  ("Content-Length: %d\r\n\r\n" % len(body)).encode() +
                  body)
        # read a little of the stream, wait until the sequence is
        # actually decoding, then vanish mid-generation
        s.recv(512)
        sched = server._schedulers[0]
        t_stop = time.monotonic() + 20
        while time.monotonic() < t_stop and \
                not sched.stats()["active_slots"]:
            time.sleep(0.01)
        assert sched.stats()["active_slots"] == 1
        s.close()
        t_stop = time.monotonic() + 20
        while time.monotonic() < t_stop and \
                sched.stats()["active_slots"]:
            time.sleep(0.02)
        st = sched.stats()
        assert st["active_slots"] == 0, \
            "disconnected stream leaked its KV slot"
        assert st["evicted"] >= 1, \
            "sequence ran to completion instead of being cancelled"
        chaos.reset()
        # the handler thread survived: a fresh request still serves
        import urllib.request
        req = urllib.request.Request(
            gw.url + "/v1/models/gen:generate",
            data=json.dumps({"tokens": [1, 2],
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
    finally:
        gw.close(timeout=30)


# -- circuit breaker ------------------------------------------------------

def test_breaker_opens_half_opens_and_recovers(monkeypatch):
    monkeypatch.setenv("MXTPU_BREAKER_FAILS", "2")
    monkeypatch.setenv("MXTPU_BREAKER_COOLDOWN_S", "0.2")
    calls = [0]
    healthy = [False]

    def builder():
        calls[0] += 1
        if not healthy[0]:
            raise RuntimeError("builder down")
        return ModelServer(_mlp_engine(6, name="brk"), num_workers=1,
                           max_wait_ms=1.0)

    reg = ModelRegistry()
    reg.register("brk", builder, warmup=False)
    for _ in range(2):
        with pytest.raises(RuntimeError):
            reg.get("brk")
    assert calls[0] == 2
    assert reg.breaker_state("brk") == "open"
    # open: instant typed refusal, the builder is NOT hammered
    with pytest.raises(BreakerOpen) as err:
        reg.get("brk")
    assert calls[0] == 2
    assert err.value.retry_after_s is not None
    assert err.value.model == "brk"
    # half-open after the cooldown: ONE canary; its success closes
    healthy[0] = True
    time.sleep(0.25)
    server = reg.get("brk")
    assert server is not None and calls[0] == 3
    assert reg.breaker_state("brk") == "closed"
    st = reg.stats()["models"]["brk"]
    assert st["breaker"] == "closed" and st["breaker_opens"] == 1
    reg.drain_all(timeout=30)


def test_breaker_half_open_failure_reopens(monkeypatch):
    monkeypatch.setenv("MXTPU_BREAKER_FAILS", "1")
    monkeypatch.setenv("MXTPU_BREAKER_COOLDOWN_S", "0.15")
    reg = ModelRegistry()
    reg.register("flaky", lambda: (_ for _ in ()).throw(
        RuntimeError("still down")), warmup=False)
    with pytest.raises(RuntimeError):
        reg.get("flaky")
    assert reg.breaker_state("flaky") == "open"
    time.sleep(0.2)
    with pytest.raises(RuntimeError):    # the half-open canary fails
        reg.get("flaky")
    assert reg.breaker_state("flaky") == "open"
    assert _counter_total("serving.breaker.opens") >= 2


def test_breaker_open_ignores_straggler_success(monkeypatch):
    """A success landing mid-cooldown (admitted before the failures)
    must NOT close an OPEN breaker — recovery goes through the
    half-open canary, never around it."""
    monkeypatch.setenv("MXTPU_BREAKER_FAILS", "1")
    monkeypatch.setenv("MXTPU_BREAKER_COOLDOWN_S", "30")
    reg = ModelRegistry()
    reg.register("strag", lambda: (_ for _ in ()).throw(
        RuntimeError("down")), warmup=False)
    with pytest.raises(RuntimeError):
        reg.get("strag")
    assert reg.breaker_state("strag") == "open"
    reg.record_success("strag")
    assert reg.breaker_state("strag") == "open"


def test_breaker_over_http_503_with_retry_after(monkeypatch):
    import urllib.error
    import urllib.request
    monkeypatch.setenv("MXTPU_BREAKER_FAILS", "1")
    monkeypatch.setenv("MXTPU_BREAKER_COOLDOWN_S", "30")
    reg = ModelRegistry()
    reg.register("down", lambda: (_ for _ in ()).throw(
        RuntimeError("dead builder")), warmup=False)
    gw = Gateway(reg, port=0).start()
    try:
        def post():
            req = urllib.request.Request(
                gw.url + "/v1/models/down:predict",
                data=json.dumps(
                    {"inputs": [[0.0] * FEATURES]}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, dict(r.headers), json.loads(
                        r.read())
            except urllib.error.HTTPError as err:
                return err.code, dict(err.headers), json.loads(
                    err.read())

        status, _, _ = post()
        assert status == 500          # the builder failure itself
        status, headers, body = post()
        assert status == 503
        assert "down" in body["error"] and "breaker" in body["error"] \
            or "circuit" in body["error"]
        assert int(headers.get("Retry-After")) >= 1
    finally:
        gw.close(timeout=30)


# -- Retry-After backpressure --------------------------------------------

def test_retry_after_derivation():
    reg = ModelRegistry()
    gw = Gateway(reg, port=0, concurrency=2)
    assert gw._retry_after("interactive") == 1      # no data yet
    gw._svc_ewma["interactive"] = 0.5
    ra = gw._retry_after("interactive")
    assert 1 <= ra <= 30
    gw._svc_ewma["interactive"] = 1e9               # absurd backlog
    assert gw._retry_after("interactive") == 30     # clamped


def test_shed_response_carries_retry_after(monkeypatch):
    import urllib.error
    import urllib.request
    reg = ModelRegistry()
    reg.register("m", lambda: ModelServer(
        _mlp_engine(7, name="shedder"), num_workers=1,
        max_wait_ms=1.0), warmup=True)
    gw = Gateway(reg, port=0, concurrency=1, queue_depth=1).start()
    try:
        # deadline 0 → shed before compute with the backpressure hint
        req = urllib.request.Request(
            gw.url + "/v1/models/m:predict",
            data=json.dumps({"inputs": [[0.0] * FEATURES],
                             "deadline_ms": 0.001}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                status, headers = r.status, dict(r.headers)
        except urllib.error.HTTPError as err:
            status, headers = err.code, dict(err.headers)
        assert status == 504
        assert int(headers.get("Retry-After")) >= 1
    finally:
        gw.close(timeout=30)


# -- hedged requests ------------------------------------------------------

def _handle(resolve_after=None, value=None):
    req = InferenceRequest({"data": np.zeros((1, FEATURES),
                                             np.float32)}, 1)
    if resolve_after is not None:
        import threading

        def later():
            time.sleep(resolve_after)
            req.resolve(value)
        threading.Thread(target=later, daemon=True).start()
    return req


def test_hedge_fires_and_duplicate_wins(monkeypatch):
    monkeypatch.setenv("MXTPU_GATEWAY_HEDGE_MS", "30")
    gw = Gateway(ModelRegistry(), port=0)
    h1 = _handle()                                  # never resolves
    h2 = _handle(resolve_after=0.05, value=["dup"])
    monkeypatch.setattr(gw, "_submit_with_retry",
                        lambda model, submit, count=True: h2)
    before_f = _counter_total("serving.hedge.fired")
    before_w = _counter_total("serving.hedge.won")
    out = gw._hedged_result("m", None, h1, 0.03, 10.0)
    assert out == ["dup"]
    assert gw.hedges == {"fired": 1, "won": 1}
    assert _counter_total("serving.hedge.fired") == before_f + 1
    assert _counter_total("serving.hedge.won") == before_w + 1


def test_hedge_primary_wins_no_fire(monkeypatch):
    monkeypatch.setenv("MXTPU_GATEWAY_HEDGE_MS", "200")
    gw = Gateway(ModelRegistry(), port=0)
    h1 = _handle(resolve_after=0.01, value=["fast"])
    out = gw._hedged_result("m", None, h1, 0.2, 10.0)
    assert out == ["fast"]
    assert gw.hedges == {"fired": 0, "won": 0}


def test_hedge_cancels_losing_decode_handle(monkeypatch):
    """The hedge loser is discarded, not abandoned: a cancellable
    (decode) handle is cancelled so its KV slot frees at the next
    step boundary instead of generating to max_new_tokens."""
    import threading
    monkeypatch.setenv("MXTPU_GATEWAY_HEDGE_MS", "10")
    gw = Gateway(ModelRegistry(), port=0)

    class H:
        def __init__(self):
            self._event = threading.Event()
            self.was_cancelled = False

        def done(self):
            return self._event.is_set()

        def result(self, timeout=None):
            return ["winner"]

        def cancel(self):
            self.was_cancelled = True

    h1, h2 = H(), H()
    h2._event.set()                          # the duplicate wins
    monkeypatch.setattr(gw, "_submit_with_retry",
                        lambda model, submit, count=True: h2)
    out = gw._hedged_result("m", None, h1, 0.01, 5.0)
    assert out == ["winner"]
    assert h1.was_cancelled


def test_hedge_not_fired_when_budget_gone(monkeypatch):
    """A request whose deadline lands exactly at the hedge delay must
    not burn a duplicate it could never use."""
    monkeypatch.setenv("MXTPU_GATEWAY_HEDGE_MS", "50")
    gw = Gateway(ModelRegistry(), port=0)
    h1 = _handle()                                  # never resolves
    with pytest.raises(Exception):
        gw._hedged_result("m", None, h1, 0.05, 0.05)
    assert gw.hedges["fired"] == 0


def test_hedge_off_by_default():
    gw = Gateway(ModelRegistry(), port=0)
    assert gw._hedge_delay_s("interactive") is None
    assert gw._hedge_delay_s("batch") is None


def test_hedge_only_interactive(monkeypatch):
    monkeypatch.setenv("MXTPU_GATEWAY_HEDGE_MS", "10")
    gw = Gateway(ModelRegistry(), port=0)
    assert gw._hedge_delay_s("interactive") == pytest.approx(0.010)
    assert gw._hedge_delay_s("batch") is None
    assert gw._hedge_delay_s("best_effort") is None


# -- CI surface -----------------------------------------------------------

def _write_stream(tmp_path, records):
    p = tmp_path / "t.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    return str(p)


def test_telemetry_report_resilience_section(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from telemetry_report import load_records, summarize
    path = _write_stream(tmp_path, [
        {"ts": 1, "source": "serving", "event": "replica_state",
         "step_time": 0.0, "server": "e", "replica": 0,
         "state": "quarantined", "reason": "watchdog"},
        {"ts": 1, "source": "serving", "event": "replica_state",
         "step_time": 0.0, "server": "e", "replica": 0,
         "state": "healthy", "reason": "canary"},
        {"ts": 1, "source": "serving", "event": "loop_crash",
         "step_time": 0.0, "scheduler": "d/0"},
        {"ts": 1, "source": "serving", "event": "worker_death",
         "step_time": 0.0, "server": "e", "replica": 1},
        {"ts": 1, "source": "serving", "event": "breaker",
         "step_time": 0.0, "model": "m", "state": "open"},
        {"ts": 1, "source": "serving", "event": "hedge",
         "step_time": 0.0, "model": "m", "won": True},
        {"ts": 1, "source": "serving", "step_time": 0.004, "step": 0,
         "batch_size": 2, "requests": 2, "fill_ratio": 0.5,
         "queue_depth": 0, "shed_total": 0, "worker": 0},
        {"ts": 1, "source": "gateway", "event": "request",
         "step_time": 0.01, "model": "m", "class": "interactive",
         "status": 200},
        {"ts": 1, "source": "gateway", "event": "error",
         "step_time": 0.01, "model": "m", "class": "interactive",
         "status": 500},
    ])
    s = summarize(load_records(path))
    assert s["serving_quarantines"] == 1
    assert s["serving_readmits"] == 1
    assert s["serving_loop_crashes"] == 1
    assert s["serving_worker_deaths"] == 1
    assert s["breaker_opens"] == 1 and s["breaker_models"] == ["m"]
    assert s["hedges_fired"] == 1 and s["hedges_won"] == 1
    assert s["gateway_success_rate"] == pytest.approx(0.5)
    # the zero-step_time events must not dilute the batch percentiles
    assert s["serving_batches"] == 1
    assert s["serving_batch_p50_s"] == pytest.approx(0.004)


def test_perf_gate_min_success_rate(tmp_path):
    path = _write_stream(tmp_path, [
        {"ts": 1, "source": "gateway", "event": "request",
         "step_time": 0.01, "model": "m", "class": "interactive",
         "status": 200},
        {"ts": 1, "source": "gateway", "event": "error",
         "step_time": 0.01, "model": "m", "class": "interactive",
         "status": 500},
        {"ts": 1, "source": "gateway", "event": "shed",
         "step_time": 0.0, "model": "m", "class": "best_effort",
         "reason": "queue_full"},
    ])
    gate = os.path.join(ROOT, "tools", "perf_gate.py")
    r = subprocess.run([sys.executable, gate, path,
                        "--min-success-rate", "0.4"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run([sys.executable, gate, path,
                        "--min-success-rate", "0.9"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "gateway_success_rate" in r.stderr
    # absent metric = breach, same contract as every other budget
    path2 = _write_stream(tmp_path / "..", [
        {"ts": 1, "source": "train", "step_time": 0.01}])
    r = subprocess.run([sys.executable, gate, path2,
                        "--min-success-rate", "0.5"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1


def test_chaos_run_wedge_replica_unproven_guard():
    """A run that never touches serving must FAIL the --wedge-replica
    drill (no MXTPU_SERVE marker = no proof the injection fired)."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "chaos_run.py"),
         "--wedge-replica", "0", "--timeout", "60", "--expect",
         "complete", "--", sys.executable, "-c", "print('idle')"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 2, r.stdout + r.stderr
    summary = json.loads(r.stdout.splitlines()[-1])
    assert summary["ok"] is False
    assert "unproven" in summary["note"]
    assert summary["serve_markers"] == 0


@pytest.mark.slow
def test_chaos_run_wedge_replica_end_to_end():
    """The drill against a real serving process: chaos_run arms the
    replica-0 hang via env, the child serves through it (watchdog
    armed), and the MXTPU_SERVE markers prove trips were observed."""
    child = (
        "import numpy as np, os\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.serving import InferenceEngine, ModelServer\n"
        "h = mx.sym.FullyConnected(data=mx.sym.var('data'),"
        " num_hidden=3, name='fc1')\n"
        "sym = mx.sym.SoftmaxOutput(data=h, name='softmax')\n"
        "rng = np.random.RandomState(0)\n"
        "args = {'fc1_weight': mx.nd.array(rng.randn(3, 6)"
        ".astype(np.float32)), 'fc1_bias':"
        " mx.nd.array(rng.randn(3).astype(np.float32))}\n"
        "eng = InferenceEngine.from_symbol(sym, args, {},"
        " {'data': (6,)}, 4, name='drill')\n"
        "srv = ModelServer(eng, num_workers=2, max_wait_ms=1.0,"
        " warmup=True).start()\n"
        "for i in range(6):\n"
        "    srv.infer(np.zeros((1, 6), np.float32), timeout=30)\n"
        "srv.drain(timeout=30)\n"
        "print('served')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXTPU_SERVE_DISPATCH_TIMEOUT_S="0.3",
               MXTPU_SERVE_TRIP_LIMIT="2", MXTPU_SERVE_CANARY_S="0.1")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "chaos_run.py"),
         "--wedge-replica", "0", "--wedge-trips", "2", "--timeout",
         "300", "--expect", "complete", "--", sys.executable, "-c",
         child],
        capture_output=True, text=True, timeout=400, env=env)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    summary = json.loads(r.stdout.splitlines()[-1])
    assert summary["ok"] is True
    assert summary["serve_markers"] >= 1


@pytest.mark.slow
def test_gateway_wedge_acceptance_over_http(monkeypatch):
    """ISSUE-14 acceptance (real HTTP): one of two replicas wedged —
    every interactive request still answers within deadline + grace,
    the replica quarantines then canary-re-admits, and the sequence is
    visible in /debugz replica health."""
    import urllib.request
    _arm(monkeypatch, timeout="0.3", trips="2", canary="0.1")
    reg = ModelRegistry()
    reg.register("acc", lambda: ModelServer(
        _mlp_engine(9, name="acc"), num_workers=2, max_wait_ms=1.0),
        eager=True, warmup=True)
    gw = Gateway(reg, port=0, concurrency=4).start()
    try:
        chaos.configure(
            "serving.replica0.dispatch:kind=hang,secs=3,n=3")
        server = reg.get("acc")
        ok = 0
        for i in range(10):
            req = urllib.request.Request(
                gw.url + "/v1/models/acc:predict",
                data=json.dumps({"inputs": [[0.1] * FEATURES],
                                 "deadline_ms": 5000}).encode(),
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.status == 200
                ok += 1
            assert time.perf_counter() - t0 <= 5.0 + 0.3 + 1.0
        assert ok == 10            # >= (N-1)/N floor, trivially
        t_stop = time.monotonic() + 30
        seen_quarantine = readmitted = False
        while time.monotonic() < t_stop and not readmitted:
            st = {w["index"]: w["state"]
                  for w in server.stats()["workers"]}
            seen_quarantine = seen_quarantine or \
                st[0] == "quarantined"
            readmitted = seen_quarantine and st[0] == "healthy"
            time.sleep(0.05)
        assert seen_quarantine and readmitted
        # visible in /debugz replica health
        with urllib.request.urlopen(gw.url + "/debugz",
                                    timeout=30) as r:
            debug = json.loads(r.read())
        workers = debug["servers"]["acc"]["workers"]
        assert all("state" in w and "alive" in w for w in workers)
    finally:
        chaos.reset()
        gw.close(timeout=30)
