"""Unit tests for the gradient fusion-bucket layer (ISSUE 3):
GradBucketer planning/packing, priority-ordered batched push/pull, the
fused multi-addend merge, the bucketed DistKVStore exchange (with a
stubbed collective — the 4-process bit-identity parity runs in
test_dist_kvstore.py), and the telemetry/report plumbing."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.kvstore import _sum_arrays, _sum_jnp, _priority_order
from mxnet_tpu.observability import registry as obs
from mxnet_tpu.parallel.bucketing import GradBucketer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# GradBucketer planning
# ---------------------------------------------------------------------------
def test_plan_fills_buckets_to_target():
    b = GradBucketer(target_bytes=1024)
    # 8 fp32 keys of 64 elems = 256 B each -> 4 keys per bucket
    items = [("k%d" % i, (64,), "float32", -i, False) for i in range(8)]
    plan = b.plan(items)
    assert len(plan) == 2
    assert plan[0].keys == ["k0", "k1", "k2", "k3"]
    assert plan[1].keys == ["k4", "k5", "k6", "k7"]
    assert plan[0].offsets == [0, 64, 128, 192]
    assert plan[0].total == 256 and plan[0].nbytes == 1024


def test_plan_separates_dtypes_and_lanes():
    b = GradBucketer(target_bytes=1 << 20)
    items = [("a", (8,), "float32", 0, False),
             ("b", (8,), "float16", 0, False),
             ("c", (8,), "float32", 0, False),
             ("d", (8,), "float32", 0, True)]  # different lane
    plan = b.plan(items)
    assert len(plan) == 3
    by_keys = {tuple(p.keys) for p in plan}
    assert ("a", "c") in by_keys
    assert ("b",) in by_keys
    assert ("d",) in by_keys


def test_plan_big_key_rides_alone():
    b = GradBucketer(target_bytes=1024)
    items = [("small1", (8,), "float32", 0, False),
             ("big", (1024,), "float32", -1, False),
             ("small2", (8,), "float32", -2, False)]
    plan = b.plan(items)
    assert len(plan) == 2
    solo = [p for p in plan if p.keys == ["big"]]
    assert solo and solo[0].total == 1024
    small = [p for p in plan if "small1" in p.keys][0]
    assert small.keys == ["small1", "small2"]


def test_plan_orders_buckets_by_priority():
    b = GradBucketer(target_bytes=32)  # each 32 B key rides alone
    items = [("low", (8,), "float32", -5, False),
             ("high", (8,), "float32", 0, False),
             ("mid", (8,), "float32", -2, False)]
    plan = b.plan(items)
    assert [p.keys[0] for p in plan] == ["high", "mid", "low"]


def test_plan_is_cached_per_signature():
    b = GradBucketer(target_bytes=1024)
    items = tuple(("k%d" % i, (4,), "float32", -i, False)
                  for i in range(4))
    assert b.plan(items) is b.plan(items)
    b.clear()
    assert b.plan(items) is b.plan(items)


def test_pack_unpack_roundtrip_bit_identical():
    b = GradBucketer(target_bytes=1 << 20)
    shapes = [(5,), (3, 4), (2, 2, 2)]
    items = [("k%d" % i, s, "float32", -i, False)
             for i, s in enumerate(shapes)]
    (bucket,) = b.plan(items)
    rng = np.random.RandomState(0)
    grads = [jnp.asarray(rng.randn(*s).astype(np.float32))
             for s in shapes]
    outs = bucket.unpack(bucket.pack(grads))
    for g, o in zip(grads, outs):
        assert o.shape == g.shape
        assert np.asarray(o).tobytes() == np.asarray(g).tobytes()


# ---------------------------------------------------------------------------
# fused multi-addend merge (satellite: no O(n) serial add chain)
# ---------------------------------------------------------------------------
def test_sum_jnp_same_shape_fast_path():
    arrs = [jnp.full((3, 2), float(i + 1)) for i in range(4)]
    out = _sum_jnp(arrs)
    assert np.array_equal(np.asarray(out), np.full((3, 2), 10.0))
    assert out.dtype == arrs[0].dtype


def test_sum_jnp_mismatched_shapes_fall_back_to_chain():
    out = _sum_jnp([jnp.ones((2, 2)), jnp.ones((2,))])
    assert np.array_equal(np.asarray(out), np.full((2, 2), 2.0))


def test_sum_arrays_matches_manual_sum():
    vals = [mx.nd.full((4,), float(i)) for i in range(3)]
    assert np.array_equal(np.asarray(_sum_arrays(vals)),
                          np.full((4,), 3.0))


# ---------------------------------------------------------------------------
# priority plumbing (satellite: push/pull no longer drop priority)
# ---------------------------------------------------------------------------
def test_priority_order_stable_descending():
    assert _priority_order(3, None) == [0, 1, 2]
    assert _priority_order(3, [0, 5, 1]) == [1, 2, 0]
    assert _priority_order(3, [0, 0, 0]) == [0, 1, 2]  # stable ties
    with pytest.raises(mx.MXNetError):
        _priority_order(3, [1, 2])


def test_push_all_issues_in_priority_order(monkeypatch):
    kv = mx.kv.create("local")
    for i in range(3):
        kv.init(i, mx.nd.zeros((2,)))
    seen = []
    orig = kv._push_one

    def spy(k, v):
        seen.append(k)
        return orig(k, v)

    monkeypatch.setattr(kv, "_push_one", spy)
    kv.push_all([0, 1, 2], [mx.nd.ones((2,))] * 3, priorities=[-0, -1, -2])
    assert seen == [0, 1, 2]
    seen.clear()
    kv.push_all([0, 1, 2], [mx.nd.ones((2,))] * 3, priorities=[-2, 0, -1])
    assert seen == [1, 2, 0]


def test_pull_all_priority_and_values():
    kv = mx.kv.create("local")
    for i in range(3):
        kv.init(i, mx.nd.full((2,), float(i)))
    outs = [mx.nd.zeros((2,)) for _ in range(3)]
    kv.pull_all([0, 1, 2], outs, priorities=[-0, -1, -2])
    for i, o in enumerate(outs):
        assert np.array_equal(o.asnumpy(), np.full((2,), float(i)))


def test_local_push_all_matches_sequential_push():
    kv_seq = mx.kv.create("local")
    kv_all = mx.kv.create("local")
    shapes = [(3,), (2, 4), (5,)]
    rng = np.random.RandomState(3)
    grads = [mx.nd.array(rng.randn(*s).astype(np.float32))
             for s in shapes]
    for i, s in enumerate(shapes):
        kv_seq.init(i, mx.nd.zeros(s))
        kv_all.init(i, mx.nd.zeros(s))
        kv_seq.push(i, grads[i], priority=-i)
    kv_all.push_all(list(range(3)), grads,
                    priorities=[-i for i in range(3)])
    for i, s in enumerate(shapes):
        a, b = mx.nd.zeros(s), mx.nd.zeros(s)
        kv_seq.pull(i, out=a)
        kv_all.pull(i, out=b)
        assert a.asnumpy().tobytes() == b.asnumpy().tobytes()


# ---------------------------------------------------------------------------
# bucketed DistKVStore exchange with a stubbed collective
# ---------------------------------------------------------------------------
def _fake_dist_store(monkeypatch, calls):
    """DistKVStore forced onto the bucketed path with the cross-process
    collective replaced by a recording doubler (nproc=2 stand-in)."""
    from mxnet_tpu.parallel.kvstore_dist import DistKVStore
    kv = DistKVStore("dist_sync")  # single process: init is a no-op
    kv._nproc = 2

    def fake_sum(x):
        calls.append(int(x.size))
        return x * 2

    monkeypatch.setattr(kv, "_cross_process_sum", fake_sum)
    return kv


def test_dist_push_all_one_collective_per_bucket(monkeypatch):
    calls = []
    kv = _fake_dist_store(monkeypatch, calls)
    shapes = [((8,), "float32"), ((16,), "float32"), ((4, 4), "float32"),
              ((6,), "float16")]
    keys = ["p%d" % i for i in range(len(shapes))]
    grads = []
    for k, (s, dt) in zip(keys, shapes):
        kv.init(k, mx.nd.zeros(s, dtype=dt))
        grads.append(mx.nd.full(s, 3.0, dtype=dt))
    b0 = obs.REGISTRY.get("kvstore.bucket.count").total()
    k0 = obs.REGISTRY.get("kvstore.bucket.keys").total()
    kv.push_all(keys, grads, priorities=[-i for i in range(len(keys))])
    # 3 fp32 keys fuse into one bucket, the fp16 key gets its own:
    # 2 collectives for 4 parameters
    assert calls == [8 + 16 + 16, 6]
    assert obs.REGISTRY.get("kvstore.bucket.count").total() - b0 == 2
    assert obs.REGISTRY.get("kvstore.bucket.keys").total() - k0 == 4
    for k, (s, dt) in zip(keys, shapes):
        out = mx.nd.zeros(s, dtype=dt)
        kv.pull(k, out=out)
        assert np.array_equal(out.asnumpy(),
                              np.full(s, 6.0, dtype=dt))  # doubled


def test_dist_push_all_bucket_size_zero_falls_back(monkeypatch):
    calls = []
    kv = _fake_dist_store(monkeypatch, calls)
    kv.set_bucket_size_mb(0)
    for i in range(3):
        kv.init("q%d" % i, mx.nd.zeros((4,)))
    kv.push_all(["q0", "q1", "q2"], [mx.nd.ones((4,))] * 3,
                priorities=[0, -1, -2])
    assert calls == [4, 4, 4]  # per-key path: one collective per key


def test_dist_push_all_uninitialized_key_raises(monkeypatch):
    kv = _fake_dist_store(monkeypatch, [])
    with pytest.raises(mx.MXNetError):
        kv.push_all(["nope"], [mx.nd.ones((2,))])


def test_trainer_step_uses_batched_exchange(monkeypatch):
    """gluon Trainer's STAGED reduce routes through push_all/pull_all
    (the fused one-program step subsumes the kvstore hop entirely;
    tests/test_fused_step.py covers that path). RMSProp is outside the
    one-program step's optimizers, so `step()` itself stages."""
    from mxnet_tpu import gluon, autograd
    from mxnet_tpu.gluon import nn
    net = nn.Dense(3, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "rmsprop",
                            {"learning_rate": 0.1}, kvstore="device")
    trainer._ensure_ready()
    pushed = {}
    orig_push_all = trainer._kvstore.push_all

    def spy(keys, values, priorities=None):
        pushed["keys"] = list(keys)
        pushed["priorities"] = list(priorities)
        return orig_push_all(keys, values, priorities=priorities)

    monkeypatch.setattr(trainer._kvstore, "push_all", spy)
    x = mx.nd.ones((2, 4))
    with autograd.record():
        y = net(x)
        loss = (y * y).sum()
    loss.backward()
    trainer.step(2)
    assert len(pushed["keys"]) == 2  # weight + bias in ONE batched push
    assert pushed["priorities"] == [-k for k in pushed["keys"]]


# ---------------------------------------------------------------------------
# telemetry record + report section
# ---------------------------------------------------------------------------
def test_steptimer_records_allreduce_and_bucket_deltas():
    from mxnet_tpu.observability.telemetry import StepTimer
    timer = StepTimer("unit.bucket")
    timer.begin_step()
    obs.counter("kvstore.allreduce.calls").inc(3)
    obs.counter("kvstore.allreduce.bytes").inc(4096)
    obs.REGISTRY.get("kvstore.allreduce.seconds").observe(0.25)
    obs.counter("kvstore.bucket.count").inc(2)
    obs.REGISTRY.get("kvstore.bucket.fill_ratio").observe(0.5)
    rec = timer.end_step()
    assert rec["allreduce_calls"] == 3
    assert rec["allreduce_bytes"] == 4096
    assert rec["allreduce_seconds"] == pytest.approx(0.25)
    assert rec["bucket_count"] == 2
    assert rec["bucket_fill_sum"] == pytest.approx(0.5)
    # a quiet step omits the section (single-process records stay small)
    timer.begin_step()
    rec2 = timer.end_step()
    assert "allreduce_calls" not in rec2


def _report(path, *flags):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "telemetry_report.py"),
         *flags, str(path)], capture_output=True, text=True)


def test_report_allreduce_section(tmp_path):
    recs = [{"step_time": 0.1, "allreduce_calls": 4,
             "allreduce_bytes": 1 << 20, "allreduce_seconds": 0.02,
             "bucket_count": 4, "bucket_fill_sum": 3.2,
             "bucket_pack_seconds": 0.001, "bucket_unpack_seconds": 0.002}
            for _ in range(3)]
    # quiet steps (no allreduce fields) must not dilute the p95 to zero
    recs += [{"step_time": 0.05} for _ in range(5)]
    path = tmp_path / "dist.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    proc = _report(path)
    assert proc.returncode == 0, proc.stderr
    assert "allreduce" in proc.stdout and "buckets" in proc.stdout
    proc = _report(path, "--json")
    summary = json.loads(proc.stdout)
    assert summary["allreduce_calls"] == 12
    assert summary["bucket_count"] == 12
    assert summary["bucket_fill_mean"] == pytest.approx(0.8)
    assert summary["allreduce_p95_s"] == pytest.approx(0.02)


def test_report_without_allreduce_omits_section(tmp_path):
    path = tmp_path / "plain.jsonl"
    path.write_text('{"step_time": 0.1}\n')
    proc = _report(path)
    assert proc.returncode == 0
    assert "allreduce" not in proc.stdout


def test_report_still_rejects_malformed_with_bucket_fields(tmp_path):
    path = tmp_path / "torn.jsonl"
    path.write_text('{"step_time": 0.1, "allreduce_calls": 2}\n{"allre')
    proc = _report(path)
    assert proc.returncode != 0  # CI gate still bites


# ---------------------------------------------------------------------------
# bandwidth tool sweep plumbing
# ---------------------------------------------------------------------------
def test_bandwidth_synthetic_shapes_total():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        from bandwidth import _synthetic_shapes
    finally:
        sys.path.pop(0)
    shapes = _synthetic_shapes(16, 1.0)
    assert len(shapes) == 16
    total = sum(s[0] for s in shapes)
    target = 1.0 * (1 << 20) / 4
    assert 0.9 * target <= total <= 1.1 * target
    assert shapes[0][0] > shapes[-1][0]  # few big, many small


@pytest.mark.slow
def test_bandwidth_sweep_two_processes():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "bandwidth.py"),
         "--cpu", "--nproc", "2", "--sweep-bucket-mb", "0,1",
         "--params", "8", "--total-mb", "0.5", "--iters", "2"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "per-key" in proc.stdout
    assert "effective" in proc.stdout
