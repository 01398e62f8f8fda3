"""Serving subsystem tests (docs/serving.md).

Covers the ISSUE-5 acceptance surface: bit-parity of the frozen engine
against executor.forward for all three load paths (symbol+params,
Module, Gluon block), the padding-bucket compile-count bound, batcher
coalescing/timeout/deadline-rejection/shedding (including under a
chaos-injected slow `serving.infer`), graceful SIGTERM drain, and the
rebased `c_predict.Predictor` / `Module.predict` shims.
"""
import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.observability import registry as obs
from mxnet_tpu.observability import telemetry
from mxnet_tpu.resilience import (Deadline, DeadlineExceeded,
                                  InjectedFault, chaos)
from mxnet_tpu.serving import (DynamicBatcher, InferenceEngine,
                               ModelServer, RequestRejected,
                               ServerClosed, bucket_sizes)

NF, NCLASS = 8, 4


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.configure("")
    yield
    chaos.reset()


def mlp_symbol():
    data = mx.sym.var("data")
    h = mx.sym.FullyConnected(data=data, num_hidden=16, name="fc1")
    h = mx.sym.Activation(data=h, act_type="relu")
    h = mx.sym.FullyConnected(data=h, num_hidden=NCLASS, name="fc2")
    return mx.sym.SoftmaxOutput(data=h, name="softmax")


def mlp_params(seed=3):
    rng = np.random.RandomState(seed)

    def p(*shape):
        return mx.nd.array(rng.randn(*shape).astype(np.float32) * 0.3)

    return {"fc1_weight": p(16, NF), "fc1_bias": p(16),
            "fc2_weight": p(NCLASS, 16), "fc2_bias": p(NCLASS)}


def make_engine(max_batch=8, **kwargs):
    return InferenceEngine.from_symbol(
        mlp_symbol(), mlp_params(), {}, {"data": (NF,)},
        max_batch_size=max_batch, **kwargs)


def executor_reference(x):
    """The legacy path: full executor bind + forward(is_train=False)."""
    sym = mlp_symbol()
    args = dict(mlp_params(), data=mx.nd.array(x),
                softmax_label=mx.nd.zeros((x.shape[0],)))
    exe = sym.bind(mx.cpu(), args, grad_req="null")
    return [o.asnumpy() for o in exe.forward(is_train=False)]


def compiles_total():
    return obs.REGISTRY.get("serving.engine.compiles").total()


# -- engine ---------------------------------------------------------------
def test_bucket_sizes():
    assert bucket_sizes(1) == (1,)
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(6) == (1, 2, 4, 6)
    assert bucket_sizes(33) == (1, 2, 4, 8, 16, 32, 33)
    with pytest.raises(mx.MXNetError):
        bucket_sizes(0)


def test_engine_symbol_bit_parity():
    rng = np.random.RandomState(0)
    x = rng.randn(8, NF).astype(np.float32)
    eng = make_engine(8)
    out = eng.infer(x)
    ref = executor_reference(x)
    assert len(out) == len(ref)
    # exact bucket (no padding): byte-for-byte with the executor path
    np.testing.assert_array_equal(out[0].asnumpy(), ref[0])


def test_engine_padding_parity():
    rng = np.random.RandomState(1)
    eng = make_engine(8)
    for n in (1, 3, 5, 7):
        x = rng.randn(n, NF).astype(np.float32)
        out = eng.infer(x)[0].asnumpy()
        ref = executor_reference(x)[0]
        assert out.shape == (n, NCLASS)
        np.testing.assert_allclose(out, ref, rtol=0, atol=1e-6)


def test_engine_compile_count_bounded_by_buckets():
    rng = np.random.RandomState(2)
    eng = make_engine(8)
    before = compiles_total()
    # 8 distinct request sizes -> at most log2(8)+1 = 4 programs
    for n in range(1, 9):
        eng.infer(rng.randn(n, NF).astype(np.float32))
    assert compiles_total() - before == len(eng.buckets) == 4
    assert eng.compiled_buckets == [1, 2, 4, 8]
    # steady state: no new compiles, whatever sizes arrive
    for n in (3, 5, 8, 1, 6):
        eng.infer(rng.randn(n, NF).astype(np.float32))
    assert compiles_total() - before == 4


def test_engine_warmup_precompiles():
    eng = make_engine(4)
    before = compiles_total()
    warmed = eng.warmup()
    assert warmed == [1, 2, 4]
    assert compiles_total() - before == 3
    eng.infer(np.zeros((3, NF), np.float32))
    assert compiles_total() - before == 3   # warm: nothing new
    assert eng.warmup() == []               # idempotent


def test_engine_input_validation():
    eng = make_engine(4)
    with pytest.raises(mx.MXNetError):
        eng.infer(np.zeros((5, NF), np.float32))      # > max_batch
    with pytest.raises(mx.MXNetError):
        eng.infer(np.zeros((2, NF + 1), np.float32))  # wrong example dim
    with pytest.raises(mx.MXNetError):
        eng.infer({"bogus": np.zeros((2, NF), np.float32)})


def test_engine_donation_safe_for_device_inputs():
    # an exact-bucket jax-array input must survive the donated dispatch
    eng = make_engine(4)
    x = mx.nd.array(np.random.RandomState(3).randn(4, NF)
                    .astype(np.float32))
    first = eng.infer(x)[0].asnumpy()
    second = eng.infer(x)[0].asnumpy()     # x must still be readable
    np.testing.assert_array_equal(first, second)


def test_engine_from_module_parity():
    x = np.random.RandomState(4).randn(8, NF).astype(np.float32)
    mod = mx.mod.Module(mlp_symbol())
    mod.bind([("data", (8, NF))], for_training=False)
    mod.init_params(mx.init.Xavier())
    eng = InferenceEngine.from_module(mod)
    out = eng.infer(x)[0].asnumpy()
    os.environ["MXTPU_SERVING_ENGINE"] = "0"
    try:
        ref = mod.predict(mx.nd.array(x)).asnumpy()
    finally:
        del os.environ["MXTPU_SERVING_ENGINE"]
    np.testing.assert_array_equal(out, ref)


def test_engine_from_block_parity():
    net = mx.gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(16, activation="relu"))
        net.add(mx.gluon.nn.Dense(NCLASS))
    net.initialize()
    x = mx.nd.array(np.random.RandomState(5).randn(8, NF)
                    .astype(np.float32))
    ref = net(x).asnumpy()
    eng = InferenceEngine.from_block(net, x)
    np.testing.assert_array_equal(eng.infer(x)[0].asnumpy(), ref)
    # padded sizes agree too
    np.testing.assert_allclose(
        eng.infer(x[:3])[0].asnumpy(), ref[:3], rtol=0, atol=1e-6)


# -- batcher --------------------------------------------------------------
def test_batcher_coalesces_to_one_batch():
    b = DynamicBatcher(["data"], max_batch_size=8, max_wait_ms=50,
                       queue_depth=16)
    for i in range(4):
        b.submit(np.full((1, NF), i, np.float32))
    batch = b.next_batch(timeout=1.0)
    assert [r.n for r in batch] == [1, 1, 1, 1]
    assert len(b) == 0


def test_batcher_splits_at_max_batch():
    b = DynamicBatcher(["data"], max_batch_size=4, max_wait_ms=1,
                       queue_depth=16)
    for _ in range(3):
        b.submit(np.zeros((3, NF), np.float32))
    first = b.next_batch(timeout=1.0)
    assert sum(r.n for r in first) == 3     # 3 + 3 > 4: next one waits
    second = b.next_batch(timeout=1.0)
    assert sum(r.n for r in second) == 3


def test_batcher_wait_window_releases_partial_batch():
    b = DynamicBatcher(["data"], max_batch_size=64, max_wait_ms=30,
                       queue_depth=16)
    t0 = time.perf_counter()
    b.submit(np.zeros((1, NF), np.float32))
    batch = b.next_batch(timeout=5.0)
    waited = time.perf_counter() - t0
    assert len(batch) == 1
    assert waited < 2.0        # released by the window, not the timeout


def test_batcher_rejects_expired_deadlines_without_computing():
    b = DynamicBatcher(["data"], max_batch_size=8, max_wait_ms=1,
                       queue_depth=16)
    doomed = b.submit(np.zeros((1, NF), np.float32),
                      deadline=Deadline(0.0, what="req"))
    live = b.submit(np.zeros((1, NF), np.float32))
    time.sleep(0.01)
    batch = b.next_batch(timeout=1.0)
    assert batch == [live] or [r is live for r in batch] == [True]
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=1.0)
    assert b.shed == 1


def test_batcher_sheds_when_full_reject_policy():
    b = DynamicBatcher(["data"], max_batch_size=4, max_wait_ms=1,
                       queue_depth=2, shed_policy="reject")
    before = obs.REGISTRY.get("serving.shed.count").total()
    b.submit(np.zeros((1, NF), np.float32))
    b.submit(np.zeros((1, NF), np.float32))
    with pytest.raises(RequestRejected):
        b.submit(np.zeros((1, NF), np.float32))
    assert b.shed == 1
    assert obs.REGISTRY.get("serving.shed.count").total() == before + 1


def test_batcher_drop_oldest_policy():
    b = DynamicBatcher(["data"], max_batch_size=4, max_wait_ms=1,
                       queue_depth=2, shed_policy="drop_oldest")
    oldest = b.submit(np.zeros((1, NF), np.float32))
    b.submit(np.zeros((1, NF), np.float32))
    newest = b.submit(np.zeros((1, NF), np.float32))  # evicts `oldest`
    with pytest.raises(RequestRejected):
        oldest.result(timeout=1.0)
    batch = b.next_batch(timeout=1.0)
    assert newest in batch and oldest not in batch


def test_batcher_closed_rejects_submits_but_drains_queue():
    b = DynamicBatcher(["data"], max_batch_size=4, max_wait_ms=1,
                       queue_depth=8)
    queued = b.submit(np.zeros((1, NF), np.float32))
    b.close()
    with pytest.raises(ServerClosed):
        b.submit(np.zeros((1, NF), np.float32))
    batch = b.next_batch(timeout=1.0)
    assert batch == [queued]
    assert b.next_batch(timeout=0.05) is None   # closed and empty


def test_batcher_oversized_request_refused():
    b = DynamicBatcher(["data"], max_batch_size=4, max_wait_ms=1,
                       queue_depth=8)
    with pytest.raises(mx.MXNetError):
        b.submit(np.zeros((5, NF), np.float32))


# -- server ---------------------------------------------------------------
def test_server_end_to_end_parity():
    eng = make_engine(16)
    rng = np.random.RandomState(6)
    x = rng.randn(16, NF).astype(np.float32)
    ref = executor_reference(x)[0]
    with ModelServer(eng, num_workers=2, max_wait_ms=5,
                     warmup=True) as server:
        handles = [server.submit(x[i:i + 1]) for i in range(16)]
        got = np.concatenate(
            [h.result(timeout=30)[0] for h in handles], axis=0)
        stats = server.stats()
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)
    assert stats["served"] == 16
    assert stats["batches"] <= 16          # coalescing happened at all
    assert stats["shed"] == 0
    assert stats["compiled_buckets"] == [1, 2, 4, 8, 16]


def test_server_compiles_stay_bounded_under_mixed_sizes():
    eng = make_engine(8)
    before = compiles_total()
    rng = np.random.RandomState(7)
    with ModelServer(eng, num_workers=1, max_wait_ms=2) as server:
        handles = [server.submit(
            rng.randn(1 + (i % 5), NF).astype(np.float32))
            for i in range(20)]
        for h in handles:
            h.result(timeout=30)
    assert compiles_total() - before <= len(eng.buckets)


def test_server_under_chaos_slow_infer():
    """A chaos-slowed serving.infer backs the queue up; everything
    still completes and the site trips are visible."""
    chaos.configure("serving.infer:kind=sleep,secs=0.03")
    eng = make_engine(8)
    with ModelServer(eng, num_workers=1, max_wait_ms=2,
                     warmup=True) as server:
        handles = [server.submit(np.zeros((1, NF), np.float32))
                   for _ in range(12)]
        outs = [h.result(timeout=30) for h in handles]
    assert all(o[0].shape == (1, NCLASS) for o in outs)
    assert chaos.trip_count("serving.infer") >= 1


def test_server_chaos_fault_propagates_to_requests():
    chaos.configure("serving.infer:kind=raise,n=1")
    eng = make_engine(4)
    with ModelServer(eng, num_workers=1, max_wait_ms=1,
                     warmup=True) as server:
        h = server.submit(np.zeros((1, NF), np.float32))
        with pytest.raises(InjectedFault):
            h.result(timeout=30)
        # the injector's budget (n=1) is spent: service recovers
        h2 = server.submit(np.zeros((1, NF), np.float32))
        assert h2.result(timeout=30)[0].shape == (1, NCLASS)


def test_server_graceful_drain_on_sigterm():
    chaos.configure("serving.infer:kind=sleep,secs=0.05")
    eng = make_engine(8)
    server = ModelServer(eng, num_workers=1, max_wait_ms=1,
                         warmup=True).start()
    with server.handle_signals(signals=(signal.SIGTERM,)):
        inflight = [server.submit(np.zeros((1, NF), np.float32))
                    for _ in range(6)]
        signal.raise_signal(signal.SIGTERM)
        # accepted work FINISHES...
        outs = [h.result(timeout=30) for h in inflight]
        assert all(o[0].shape == (1, NCLASS) for o in outs)
        # ...new work is refused (drain flag set by the handler, the
        # batcher closed by the dispatcher thread)
        with pytest.raises(RequestRejected):
            for _ in range(50):
                server.submit(np.zeros((1, NF), np.float32))
                time.sleep(0.01)
    assert server.drain(timeout=30)
    assert server.stats()["draining"]


def test_server_sheds_under_sustained_overload():
    """The bounded batcher queue must stay authoritative: workers hold
    at most one backlog batch each, so overload reaches queue_depth and
    SHEDS instead of piling up in unbounded worker lists."""
    chaos.configure("serving.infer:kind=sleep,secs=0.05")
    eng = make_engine(2)
    shed_before = obs.REGISTRY.get("serving.shed.count").total()
    with ModelServer(eng, num_workers=1, max_wait_ms=1, queue_depth=2,
                     warmup=True) as server:
        rejected, handles = 0, []
        for _ in range(20):
            try:
                handles.append(
                    server.submit(np.zeros((1, NF), np.float32)))
            except RequestRejected:
                rejected += 1
        for h in handles:
            h.result(timeout=30)
    assert rejected > 0
    assert obs.REGISTRY.get("serving.shed.count").total() > shed_before


def test_server_rejects_deadline_expired_in_worker_backlog():
    """A deadline that runs out AFTER batcher dequeue (while the batch
    waits behind a slow one in the worker backlog) still rejects with
    DeadlineExceeded — never computed, never resolved late."""
    chaos.configure("serving.infer:kind=sleep,secs=0.15")
    eng = make_engine(2)
    with ModelServer(eng, num_workers=1, max_wait_ms=1,
                     warmup=True) as server:
        slow = server.submit(np.zeros((1, NF), np.float32))
        time.sleep(0.03)      # let the first batch reach the worker
        doomed = server.submit(np.zeros((1, NF), np.float32),
                               deadline=Deadline(0.05, what="req"))
        slow.result(timeout=30)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=30)


def test_server_stats_and_least_loaded_dispatch():
    eng = make_engine(8)
    with ModelServer(eng, num_workers=3, max_wait_ms=1) as server:
        handles = [server.submit(np.zeros((2, NF), np.float32))
                   for _ in range(9)]
        for h in handles:
            h.result(timeout=30)
        stats = server.stats()
    assert len(stats["workers"]) == 3
    assert sum(w["served_requests"] for w in stats["workers"]) == 9
    assert stats["request_latency_p50_s"] >= 0.0


def test_server_telemetry_records(tmp_path):
    path = str(tmp_path / "serving.jsonl")
    eng = make_engine(8)
    os.environ["MXTPU_TELEMETRY"] = path
    try:
        with ModelServer(eng, num_workers=1, max_wait_ms=1,
                         warmup=True) as server:
            for _ in range(5):
                server.infer(np.zeros((2, NF), np.float32), timeout=30)
    finally:
        del os.environ["MXTPU_TELEMETRY"]
        telemetry.close_stream()
    allrecs = [json.loads(l) for l in open(path) if l.strip()]
    # the stream is shared: the process's one-off cold-start record
    # (source="compile", docs/compilation.md) and the HBM ledger's
    # timeline record of the frozen engine (source="memory",
    # docs/observability.md "Memory ledger") may ride along with the
    # per-batch serving records under test
    recs = [r for r in allrecs if r["source"] == "serving"]
    assert recs
    assert all(r["source"] in ("serving", "compile", "memory")
               for r in allrecs)
    assert all("step_time" in r and "fill_ratio" in r for r in recs)
    assert sum(r["requests"] for r in recs) == 5

    # the CI-gate report renders a serving section from the same file
    import importlib
    report = importlib.import_module("tools.telemetry_report")
    summary = report.summarize(report.load_records(path))
    assert summary["serving_requests"] == 5
    assert summary["serving_batches"] == len(recs)
    assert "serving_batch_p95_s" in summary
    assert "serving" in report.format_summary(summary)


# -- c_predict shim -------------------------------------------------------
def _export_checkpoint(tmp_path):
    sym = mlp_symbol()
    params = mlp_params()
    payload = {"arg:%s" % k: v for k, v in params.items()}
    sym_path = str(tmp_path / "model-symbol.json")
    params_path = str(tmp_path / "model-0000.params")
    sym.save(sym_path)
    mx.nd.save(params_path, payload)
    return sym_path, params_path


def test_predictor_bit_parity_with_executor(tmp_path):
    from mxnet_tpu.c_predict import create_predictor
    sym_path, params_path = _export_checkpoint(tmp_path)
    pred = create_predictor(sym_path, params_path,
                            {"data": (4, NF), "softmax_label": (4,)})
    x = np.random.RandomState(8).randn(4, NF).astype(np.float32)
    assert pred.set_input("data", x.tobytes())
    out = pred.forward()
    ref = executor_reference(x)
    np.testing.assert_array_equal(out[0].asnumpy(), ref[0])


def test_predictor_no_gradient_executor_and_no_aliasing(tmp_path):
    from mxnet_tpu.c_predict import create_predictor
    sym_path, params_path = _export_checkpoint(tmp_path)
    pred = create_predictor(sym_path, params_path,
                            {"data": (2, NF), "softmax_label": (2,)})
    assert not hasattr(pred, "_executor")     # engine shim, not a bind
    x = np.random.RandomState(9).randn(2, NF).astype(np.float32)
    buf = x.tobytes()
    pred.set_input("data", buf)
    first = pred.forward()[0].asnumpy()
    # forward again without set_input: same staged buffer, same answer
    # (the donated dispatch must not have consumed the staging array)
    second = pred.forward()[0].asnumpy()
    np.testing.assert_array_equal(first, second)


def test_predictor_set_input_snapshots_buffer(tmp_path):
    # MXPredSetInput copy semantics: the caller may refill one scratch
    # buffer between set_input calls; earlier inputs must not change
    from mxnet_tpu.c_predict import create_predictor
    sym_path, params_path = _export_checkpoint(tmp_path)
    pred = create_predictor(sym_path, params_path,
                            {"data": (2, NF), "softmax_label": (2,)})
    x = np.random.RandomState(20).randn(2, NF).astype(np.float32)
    scratch = bytearray(x.tobytes())
    pred.set_input("data", scratch)
    ref = pred.forward()[0].asnumpy()
    scratch[:] = b"\x00" * len(scratch)      # caller reuses the buffer
    np.testing.assert_array_equal(pred.forward()[0].asnumpy(), ref)


def test_telemetry_report_headline_excludes_serving(tmp_path):
    # a mixed train+serve stream: serving ~ms batch records must not
    # blend into the training step-time percentiles or samples/sec
    import importlib
    report = importlib.import_module("tools.telemetry_report")
    path = tmp_path / "mixed.jsonl"
    rows = [{"source": "module.fit", "step_time": 1.0, "batch_size": 64}
            for _ in range(4)]
    rows += [{"source": "serving", "step_time": 0.001, "batch_size": 8,
              "requests": 8, "fill_ratio": 1.0, "queue_depth": 0,
              "shed_total": 0} for _ in range(100)]
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    s = report.summarize(report.load_records(str(path)))
    assert s["steps"] == 4
    assert s["step_time_p50_s"] == 1.0          # not diluted to ~1ms
    assert s["samples"] == 4 * 64               # serving rows excluded
    assert s["serving_batches"] == 100          # but fully reported


def test_predictor_dtype_from_bound_array(tmp_path):
    # a float16 parameter sharing the input's name binds the input as
    # float16 — set_input no longer assumes float32
    sym = mlp_symbol()
    params = mlp_params()
    from mxnet_tpu.c_predict import Predictor
    fp16_params = dict(params)
    fp16_params["data"] = mx.nd.array(
        np.zeros((2, NF), np.float16), dtype=np.float16)
    pred = Predictor(sym, fp16_params, {},
                     {"data": (2, NF), "softmax_label": (2,)})
    x16 = np.random.RandomState(10).randn(2, NF).astype(np.float16)
    assert pred.set_input("data", x16.tobytes())
    out = pred.forward()[0]
    assert out.shape == (2, NCLASS)
    with pytest.raises(mx.MXNetError):        # wrong byte count
        pred.set_input("data", x16.astype(np.float32).tobytes())


def test_predictor_independent_leading_dims_and_scalars():
    # the legacy c_predict contract: each declared input is its own
    # fixed-shape buffer — leading dims need not agree and scalar
    # shapes are legal (engine static inputs, no padding)
    from mxnet_tpu.c_predict import Predictor
    data = mx.sym.var("data")
    scale = mx.sym.var("scale")
    out = mx.sym.broadcast_mul(
        mx.sym.FullyConnected(data=data, num_hidden=NCLASS, name="fc"),
        mx.sym.reshape(scale, shape=(1, 1)))
    params = {"fc_weight": mx.nd.array(
        np.random.RandomState(16).randn(NCLASS, NF)
        .astype(np.float32)), "fc_bias": mx.nd.zeros((NCLASS,))}
    pred = Predictor(out, params, {},
                     {"data": (3, NF), "scale": (1,)})
    x = np.random.RandomState(17).randn(3, NF).astype(np.float32)
    pred.set_input("data", x.tobytes())
    pred.set_input("scale", np.float32(2.0).tobytes())
    got = pred.forward()[0].asnumpy()
    exe = out.bind(mx.cpu(), dict(params, data=mx.nd.array(x),
                                  scale=mx.nd.array([2.0])),
                   grad_req="null")
    ref = exe.forward(is_train=False)[0].asnumpy()
    np.testing.assert_array_equal(got, ref)


def test_server_per_device_replica_dispatch():
    # workers place batches + a param copy on their own device — the
    # multi-replica story the docs promise (8 virtual CPU devices here)
    import jax
    eng = make_engine(4)
    with ModelServer(eng, num_workers=2, max_wait_ms=1,
                     warmup=True) as server:
        outs = [server.submit(np.zeros((1, NF), np.float32))
                for _ in range(8)]
        for h in outs:
            h.result(timeout=30)
        stats = server.stats()
    devs = {w["device"] for w in stats["workers"]}
    assert len(devs) == min(2, len(jax.local_devices()))
    # params were replicated onto every worker device
    placed = set(eng._placed)
    worker_ids = {jax.local_devices()[i].id for i in range(2)}
    assert worker_ids <= placed or len(jax.local_devices()) == 1


def test_predictor_errors_match_api():
    from mxnet_tpu.c_predict import Predictor
    with pytest.raises(mx.MXNetError):
        # undeclared argument, no loaded param
        Predictor(mlp_symbol(), {}, {}, {"data": (2, NF)})
    pred = Predictor(mlp_symbol(), mlp_params(), {},
                     {"data": (2, NF), "softmax_label": (2,)})
    with pytest.raises(mx.MXNetError):
        pred.set_input("nope", b"\x00" * 8)


# -- Module routing -------------------------------------------------------
def test_module_predict_parity_engine_vs_legacy():
    x = np.random.RandomState(11).randn(22, NF).astype(np.float32)
    it = mx.io.NDArrayIter(x, None, batch_size=8,
                           last_batch_handle="pad")
    mod = mx.mod.Module(mlp_symbol())
    mod.bind([("data", (8, NF))], for_training=False)
    mod.init_params(mx.init.Xavier())
    out_engine = mod.predict(it).asnumpy()
    assert mod._serving_engine_obj is not None, "engine path not taken"
    os.environ["MXTPU_SERVING_ENGINE"] = "0"
    try:
        it.reset()
        out_legacy = mod.predict(it).asnumpy()
    finally:
        del os.environ["MXTPU_SERVING_ENGINE"]
    assert out_engine.shape == (22, NCLASS)
    np.testing.assert_array_equal(out_engine, out_legacy)


def test_module_env_flag_disables_engine():
    x = np.random.RandomState(12).randn(8, NF).astype(np.float32)
    mod = mx.mod.Module(mlp_symbol())
    mod.bind([("data", (8, NF))], for_training=False)
    mod.init_params(mx.init.Xavier())
    os.environ["MXTPU_SERVING_ENGINE"] = "0"
    try:
        mod.predict(mx.nd.array(x))
        assert mod._serving_engine_obj is None
    finally:
        del os.environ["MXTPU_SERVING_ENGINE"]


def test_module_training_path_untouched():
    # a for_training module never routes through the engine, even for
    # is_train=False eval forwards inside fit/score
    x, y = (np.random.RandomState(13).randn(16, NF).astype(np.float32),
            np.zeros(16, np.float32))
    it = mx.io.NDArrayIter(x, y, batch_size=8,
                           label_name="softmax_label")
    mod = mx.mod.Module(mlp_symbol())
    mod.bind([("data", (8, NF))], [("softmax_label", (8,))],
             for_training=True)
    mod.init_params(mx.init.Xavier())
    mod.score(it, "acc")
    assert mod._serving_engine_obj is None


def test_module_engine_invalidated_on_set_params():
    x = np.random.RandomState(14).randn(8, NF).astype(np.float32)
    mod = mx.mod.Module(mlp_symbol())
    mod.bind([("data", (8, NF))], for_training=False)
    mod.init_params(mx.init.Xavier())
    out1 = mod.predict(mx.nd.array(x)).asnumpy()
    assert mod._serving_engine_obj is not None
    mod.set_params(mlp_params(), {})
    assert mod._serving_engine_obj is None   # stale engine dropped
    out2 = mod.predict(mx.nd.array(x)).asnumpy()
    assert not np.array_equal(out1, out2)    # new params took effect
    np.testing.assert_array_equal(out2, executor_reference(x)[0])


def test_module_iter_predict_depads_via_engine():
    x = np.random.RandomState(15).randn(10, NF).astype(np.float32)
    it = mx.io.NDArrayIter(x, None, batch_size=8,
                           last_batch_handle="pad")
    mod = mx.mod.Module(mlp_symbol())
    mod.bind([("data", (8, NF))], for_training=False)
    mod.init_params(mx.init.Xavier())
    chunks = [outs[0].shape[0] for outs, _, _ in mod.iter_predict(it)]
    assert chunks == [8, 2]                  # tail pad sliced away
