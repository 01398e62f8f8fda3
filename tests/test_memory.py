"""HBM memory ledger + goodput/MFU accounting plane (ISSUE 17,
docs/observability.md "Memory ledger" / "Goodput & MFU").

The acceptance surface:

1. attribution: the ledger's per-model total matches the engine's own
   `device_bytes()` EXACTLY (the 5% acceptance bound is trivially met
   because device_bytes reconciles the ledger cells it reports) — for
   a frozen InferenceEngine, a DecodeEngine with its KV cache, and the
   fused step's ZeRO-1 carried-state accounting;
2. OOM forensics: a chaos-injected `memory.oom` fault becomes a
   simulated RESOURCE_EXHAUSTED whose `HBMExhausted` report + stderr
   dump name the top-3 consumers, without exhausting anything real;
3. surfaces: `memory.hbm.*` / `goodput.*` Prometheus exposition
   (HELP/TYPE once per family, label cardinality bounded) and the
   `/debugz` memory+goodput sections over real HTTP;
4. goodput: per-step MFU lands non-zero on StepTimer records once a
   program charged the FLOP counter, and `perf_gate --max-hbm-mb` /
   `--min-mfu` turn the stream into a CI exit code (absent metric =
   breach, like every other budget).
"""
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt
from mxnet_tpu.gluon.model_zoo.gpt import GPTDecoder
from mxnet_tpu.observability import goodput, httpz, memory
from mxnet_tpu.observability import registry as obs
from mxnet_tpu.resilience import chaos
from mxnet_tpu.serving import DecodeEngine, InferenceEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NF, NCLASS = 6, 4


@pytest.fixture(autouse=True)
def _clean_plane():
    memory._reset_for_tests()
    goodput._reset_for_tests()
    chaos.configure("")
    yield
    chaos.reset()
    memory._reset_for_tests()
    goodput._reset_for_tests()


def mlp_engine(max_batch=4, name="memtest"):
    data = mx.sym.var("data")
    h = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc1")
    out = mx.sym.SoftmaxOutput(data=h, name="softmax")
    rng = np.random.RandomState(5)
    params = {"fc1_weight": mx.nd.array(
                  rng.randn(8, NF).astype(np.float32)),
              "fc1_bias": mx.nd.array(np.zeros(8, np.float32))}
    return InferenceEngine.from_symbol(
        out, params, {}, {"data": (NF,)}, max_batch_size=max_batch,
        name=name)


# -- ledger core ----------------------------------------------------------

def test_ledger_set_release_totals_peak():
    memory.set_bytes("m1", "engine", "params", 4000)
    memory.set_bytes("m1", "engine", "aux", 1000)
    memory.set_bytes("m2", "decode", "kv_cache", 9000)
    assert memory.total_bytes() == 14000
    assert memory.model_bytes("m1") == 5000
    top = memory.top_consumers(2)
    assert top[0] == ("m2", "decode", "kv_cache", 9000)
    # absolute set is idempotent, not a delta
    memory.set_bytes("m1", "engine", "params", 4000)
    assert memory.total_bytes() == 14000
    memory.release("m2")
    assert memory.total_bytes() == 5000
    assert memory.model_bytes("m2") == 0
    # peak holds the high-water mark across the release
    assert memory.peak_bytes() == 14000
    snap = memory.snapshot()
    assert snap["models"]["m1"]["total_bytes"] == 5000
    assert snap["peak_bytes"] == 14000


def test_disabled_env_is_noop(monkeypatch):
    monkeypatch.setenv("MXTPU_MEMLEDGER", "0")
    memory.set_bytes("m", "s", "k", 1234)
    assert memory.total_bytes() == 0
    assert memory.snapshot()["models"] == {}


def test_headroom_from_env_override(monkeypatch):
    monkeypatch.setenv("MXTPU_HBM_BYTES", "1000000")
    memory.set_bytes("m", "engine", "params", 250000)
    # CPU has no device memory_stats, so the env override is the limit
    assert memory.headroom_bytes() == 750000


def test_record_program_working_set():
    class FakeMA:
        temp_size_in_bytes = 1 << 20
        argument_size_in_bytes = 2 << 20
        output_size_in_bytes = 3 << 20
        generated_code_size_in_bytes = 4096

    class FakeCompiled:
        def memory_analysis(self):
            return FakeMA()

    sizes = memory.record_program("prog/x", FakeCompiled())
    assert sizes == {"temp": 1 << 20, "argument": 2 << 20,
                     "output": 3 << 20, "code": 4096}
    assert memory.snapshot()["programs"]["prog/x"]["temp"] == 1 << 20
    # a backend whose executables can't answer records nothing
    class Dead:
        def memory_analysis(self):
            raise RuntimeError("unimplemented")
    assert memory.record_program("prog/dead", Dead()) is None


# -- engine / decode / trainer attribution -------------------------------

def test_engine_ledger_matches_device_bytes():
    eng = mlp_engine(name="led_eng")
    db = eng.device_bytes()
    assert db > 0
    # device_bytes reconciles the ledger cells: the acceptance's <=5%
    # bound is exact equality by construction
    assert memory.model_bytes("led_eng") == db
    by = memory.snapshot()["models"]["led_eng"]["by"]
    assert "engine/params" in by


def test_decode_ledger_matches_device_bytes():
    np.random.seed(3)
    blk = GPTDecoder(64, max_seq_len=16, num_layers=1, num_heads=2,
                     embed_dim=8)
    blk.initialize(mx.init.Xavier())
    eng = DecodeEngine(blk, max_slots=2, name="led_dec")
    db = eng.device_bytes()
    assert db > 0
    assert memory.model_bytes("led_dec") == db
    by = memory.snapshot()["models"]["led_dec"]["by"]
    # the KV cache is a first-class cell — allocated for max_slots
    # whether or not a sequence is active
    assert by["decode/kv_cache"] > 0


def test_trainer_params_registered():
    from mxnet_tpu import autograd, gluon
    mx.random.seed(0)
    net = gluon.nn.Dense(3)
    net.initialize()
    x = mx.nd.array(np.random.RandomState(1).randn(4, 5).astype("f"))
    y = mx.nd.array(np.random.RandomState(2).randn(4, 3).astype("f"))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    with autograd.record():
        loss = gluon.loss.L2Loss()(net(x), y)
    loss.backward()
    tr.step(4)
    want = sum(int(p.data()._data.nbytes)
               for p in net.collect_params().values())
    by = memory.snapshot()["models"]["trainer"]["by"]
    assert by["trainer/params"] == want


def test_zero1_state_cell_accounting():
    """The carried-state accounting the fused step registers under
    trainer/optimizer/zero1_state: addressable-shard bytes only (the
    1/N per-replica share), released at the flush/drop boundaries."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import fused_step as fs
    upd = opt.get_updater(opt.create("sgd", learning_rate=0.1,
                                     momentum=0.9))
    ws = [mx.nd.array(np.zeros((4, 4), "f"))]
    gs = [mx.nd.array(np.ones((4, 4), "f"))]
    assert fs.step(upd, [0], gs, ws)
    owner = upd._fused_step_owner
    # single-process runs carry no sharded flats; inject the shape the
    # multi-process zero1 path stores and check the byte accounting
    flats = [[jnp.zeros(128, "float32")], [jnp.zeros(64, "float32")]]
    owner._state_flats["fake_sig"] = (None, flats)
    assert owner._carried_state_bytes() == (128 + 64) * 4
    memory.set_bytes("trainer", "optimizer", "zero1_state",
                     owner._carried_state_bytes())
    assert memory.model_bytes("trainer") >= (128 + 64) * 4
    owner.drop_state()           # set_states boundary: cell must drop
    by = memory.snapshot()["models"].get("trainer", {}).get("by", {})
    assert "optimizer/zero1_state" not in by


def test_gateway_eviction_releases_ledger():
    from mxnet_tpu.serving.gateway.registry import ModelRegistry
    reg = ModelRegistry(hbm_budget_mb=1024, max_models=4)
    reg.register("evict_me", lambda: mlp_engine(name="evict_me"),
                 num_workers=1, max_wait_ms=1.0)
    x = np.ones((1, NF), np.float32)
    reg.get("evict_me").infer(x, timeout=30)
    assert memory.model_bytes("evict_me") > 0
    assert reg.evict("evict_me", timeout=30)
    # an evicted model's residency must read zero, not stale
    assert memory.model_bytes("evict_me") == 0


# -- OOM forensics --------------------------------------------------------

def test_chaos_oom_forensics_names_top_consumers(capsys):
    memory.set_bytes("big", "decode", "kv_cache", 8 << 20)
    memory.set_bytes("mid", "engine", "params", 4 << 20)
    memory.set_bytes("small", "engine", "aux", 1 << 20)
    memory.set_bytes("tiny", "engine", "aux", 1 << 10)
    chaos.configure("memory.oom:p=1,kind=raise")
    before = obs.REGISTRY.get("memory.oom.events").total()
    with pytest.raises(memory.HBMExhausted) as ei:
        with memory.oom_guard("engine.infer", "big"):
            pytest.fail("guard must trip on entry")
    rep = ei.value.report
    assert rep["site"] == "engine.infer" and rep["model"] == "big"
    named = [(c["model"], c["subsystem"], c["kind"])
             for c in rep["top_consumers"]]
    assert named == [("big", "decode", "kv_cache"),
                     ("mid", "engine", "params"),
                     ("small", "engine", "aux")]
    assert obs.REGISTRY.get("memory.oom.events").total() == before + 1
    err = capsys.readouterr().err
    assert "[memory]" in err and "#1 big decode/kv_cache" in err


def test_oom_guard_converts_real_resource_exhausted():
    memory.set_bytes("m", "engine", "params", 1 << 20)
    with pytest.raises(memory.HBMExhausted) as ei:
        with memory.oom_guard("decode.step", "m"):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating "
                "1234 bytes")
    assert ei.value.report["total_bytes"] == 1 << 20
    # everything else passes through untouched
    with pytest.raises(ValueError):
        with memory.oom_guard("decode.step", "m"):
            raise ValueError("not an allocator failure")


def test_engine_infer_dispatch_is_guarded():
    eng = mlp_engine(name="oomed")
    x = np.zeros((2, NF), np.float32)
    assert eng.infer(x)            # clean path works
    chaos.configure("memory.oom:p=1,kind=raise,n=1")
    with pytest.raises(memory.HBMExhausted):
        eng.infer(x)
    chaos.reset()
    assert eng.infer(x)            # engine survives the drill


# -- goodput --------------------------------------------------------------

def test_goodput_cost_table_and_charges():
    goodput.record_cost("p1", flops=2.0e9)
    assert goodput.cost("p1")["flops"] == 2.0e9
    f0 = obs.REGISTRY.get("goodput.flops").total()
    assert goodput.note_dispatch("p1") == 2.0e9
    assert obs.REGISTRY.get("goodput.flops").total() - f0 == 2.0e9
    # unregistered programs charge nothing — the gauge stays honest
    assert goodput.note_dispatch("unknown") == 0.0
    # measured beats analytic, and never downgrades back
    class FakeCost:
        def cost_analysis(self):
            return {"flops": 5.0e9, "bytes accessed": 1.0e6}
    goodput.record_cost("p1", compiled=FakeCost())
    assert goodput.cost("p1")["flops"] == 5.0e9
    goodput.record_cost("p1", flops=1.0)
    assert goodput.cost("p1")["flops"] == 5.0e9


def test_mfu_value_unclamped_and_gauged(monkeypatch):
    monkeypatch.setenv("MXTPU_PEAK_FLOPS", "1e10")
    assert goodput.mfu_value(1e9, 1.0, source="t") == \
        pytest.approx(0.1)
    # an impossible reading is shown, not clamped to 1.0
    assert goodput.mfu_value(1e12, 0.001, source="t") == \
        pytest.approx(1e5)
    g = obs.REGISTRY.get("goodput.mfu")
    assert g is not None


def test_peak_flops_keyed_by_device_kind(monkeypatch):
    import jax
    monkeypatch.delenv("MXTPU_PEAK_FLOPS", raising=False)
    goodput._reset_for_tests()
    assert jax.devices()[0].device_kind == "cpu"
    assert goodput.peak_flops() == goodput._KIND_PEAK["cpu"]
    assert goodput._KIND_PEAK["TPU v5 lite"] == 1.97e14


def test_peak_flops_none_for_unknown_device_kind(monkeypatch):
    monkeypatch.delenv("MXTPU_PEAK_FLOPS", raising=False)
    monkeypatch.setattr(goodput, "_KIND_PEAK", {"TPU v99": 1.0e15})
    goodput._reset_for_tests()
    try:
        assert goodput.peak_flops() is None
        # "not measured", and no gauge is set from a guess
        assert goodput.mfu_value(1e9, 1.0, source="t") is None
        # the operator's figure still wins
        monkeypatch.setenv("MXTPU_PEAK_FLOPS", "2e10")
        assert goodput.peak_flops() == 2e10
    finally:
        goodput._reset_for_tests()


def test_step_record_carries_nonzero_mfu(tmp_path, monkeypatch):
    from mxnet_tpu.observability.telemetry import (StepTimer,
                                                   close_stream)
    out = tmp_path / "t.jsonl"
    monkeypatch.setenv("MXTPU_TELEMETRY", str(out))
    timer = StepTimer("goodput.test")
    timer.begin_step()
    goodput.record_cost("step_prog", flops=5.0e8)
    goodput.note_dispatch("step_prog")
    rec = timer.end_step(batch_size=2)
    close_stream()
    assert rec["step_flops"] == 5.0e8
    assert rec["mfu"] > 0.0     # a ratio, no longer clamped to 1
    streamed = [json.loads(l) for l in
                out.read_text().splitlines()][-1]
    assert streamed["mfu"] == rec["mfu"]


# -- exposition + /debugz -------------------------------------------------

def test_prometheus_exposition_of_new_families():
    memory.set_bytes("m", "engine", "params", 1024)
    goodput.record_cost("p", flops=1e6)
    goodput.note_dispatch("p")
    goodput.mfu_value(1e6, 0.5, source="train")
    text = obs.REGISTRY.to_prometheus()
    for fam, kind in (("mxtpu_memory_hbm_bytes", "gauge"),
                      ("mxtpu_memory_hbm_total_bytes", "gauge"),
                      ("mxtpu_goodput_flops_total", "counter"),
                      ("mxtpu_goodput_dispatches_total", "counter"),
                      ("mxtpu_goodput_mfu", "gauge")):
        # HELP/TYPE exactly once per family
        assert text.count("# HELP %s " % fam) == 1, fam
        assert text.count("# TYPE %s %s" % (fam, kind)) == 1, fam
    assert 'mxtpu_memory_hbm_bytes{kind="params",model="m",' \
        'subsystem="engine"} 1024' in text


def test_ledger_label_cardinality_bounded(monkeypatch):
    monkeypatch.setenv("MXTPU_METRIC_MAX_LABELS", "32")
    for i in range(64):
        memory.set_bytes("model%d" % i, "engine", "params", 100)
    # past the cap new labelsets collapse into the overflow bucket
    # instead of growing without bound
    assert len(memory.HBM_BYTES._values) <= 33
    assert obs.OVERFLOW_KEY in memory.HBM_BYTES._values
    # the ledger itself stays exact — only the gauge's labels saturate
    assert memory.total_bytes() == 64 * 100


def test_debugz_memory_section_over_http():
    memory.set_bytes("served", "engine", "params", 2048)
    goodput.record_cost("prog", flops=1e6)
    srv = httpz.ObservabilityServer(port=0).start()
    try:
        dbg = json.loads(urllib.request.urlopen(
            srv.url + "/debugz", timeout=10).read().decode())
        mem = dbg["memory"]
        assert mem["enabled"] and mem["total_bytes"] >= 2048
        assert mem["models"]["served"]["by"]["engine/params"] == 2048
        assert mem["top"][0]["model"] == "served"
        gp = dbg["goodput"]
        assert gp["peak_flops"] > 0
        assert gp["costs"]["prog"]["flops"] == 1e6
    finally:
        srv.close()


# -- report + gate + drift ------------------------------------------------

def _write_stream(path, hbm_mb=100.0, mfu=0.25):
    recs = [{"ts": 1.0, "source": "train", "step": 0,
             "step_time": 0.1, "step_flops": 1e9, "mfu": mfu},
            {"ts": 2.0, "source": "train", "step": 1,
             "step_time": 0.1, "step_flops": 1e9, "mfu": mfu},
            {"ts": 3.0, "source": "memory", "event": "update",
             "model": "m", "subsystem": "engine", "kind": "params",
             "bytes": int(hbm_mb * 2**20),
             "total_bytes": int(hbm_mb * 2**20), "step_time": 0.0}]
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))


def test_telemetry_report_memory_goodput_sections(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        from telemetry_report import (format_summary, load_records,
                                      summarize)
    finally:
        sys.path.pop(0)
    stream = tmp_path / "t.jsonl"
    _write_stream(stream, hbm_mb=64.0, mfu=0.5)
    s = summarize(load_records(str(stream)))
    assert s["hbm_peak_mb"] == pytest.approx(64.0)
    assert s["mfu_p50"] == pytest.approx(0.5)
    assert s["oom_events"] == 0
    text = format_summary(s)
    assert "memory" in text and "goodput" in text
    # memory records are excluded from headline step percentiles
    assert s["steps"] == 2


def test_perf_gate_hbm_and_mfu_budgets(tmp_path):
    gate = os.path.join(ROOT, "tools", "perf_gate.py")
    stream = tmp_path / "t.jsonl"
    _write_stream(stream, hbm_mb=100.0, mfu=0.25)

    def run(path, *budget):
        return subprocess.run(
            [sys.executable, gate, str(path)] + list(budget),
            capture_output=True, text=True)

    r = run(stream, "--max-hbm-mb", "128", "--min-mfu", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    r = run(stream, "--max-hbm-mb", "64")
    assert r.returncode == 1 and "hbm_peak_mb" in r.stdout
    r = run(stream, "--min-mfu", "0.5")
    assert r.returncode == 1 and "mfu_p50" in r.stdout
    # a stream without the budgeted metric breaches, never passes
    bare = tmp_path / "bare.jsonl"
    bare.write_text(json.dumps(
        {"ts": 0, "source": "train", "step": 0, "step_time": 0.1})
        + "\n")
    assert run(bare, "--max-hbm-mb", "1024").returncode == 1
    assert run(bare, "--min-mfu", "0.01").returncode == 1


def test_docs_drift_clean():
    """The three code/docs contracts (metrics, perf_gate flags, chaos
    sites) hold with the new families wired in."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "docs_drift.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_memledger_disabled_overhead_path(monkeypatch):
    """MXTPU_MEMLEDGER=0 short-circuits to one env read — the bench
    A/B knob. Not a timing assertion (CI noise); just that the
    disabled path really skips ledger + goodput work."""
    monkeypatch.setenv("MXTPU_MEMLEDGER", "0")
    assert not memory.enabled() and not goodput.enabled()
    memory.set_bytes("m", "s", "k", 1)
    goodput.record_cost("p", flops=1e9)
    assert memory.total_bytes() == 0
    assert goodput.cost("p") is None
