"""Continuous-batching decode tests (ISSUE-6, docs/serving.md).

The acceptance surface: greedy decode through the KV-cached
continuous-batching path is TOKEN-IDENTICAL to a full-context
re-forward reference at every step — including for sequences that
joined mid-batch — and each DecodeEngine compiles exactly two
decode-path programs (prefill buckets aside). Plus the scheduler edge
cases: join into a freed slot, deadline eviction at a step boundary,
drain with sequences in flight, cache-slot exhaustion reaching the
shed policy, and the bf16 serving dtype.
"""
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo.gpt import GPTDecoder
from mxnet_tpu.observability import registry as obs
from mxnet_tpu.resilience import (Deadline, DeadlineExceeded,
                                  InjectedFault, chaos)
from mxnet_tpu.serving import (ContinuousBatchScheduler, DecodeEngine,
                               InferenceEngine, ModelServer,
                               RequestRejected, ServerClosed)

VOCAB, MAXLEN = 96, 32


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.configure("")
    yield
    chaos.reset()


def make_block(seed=7, max_seq_len=MAXLEN, eos_token=None, layers=2):
    np.random.seed(seed)
    blk = GPTDecoder(VOCAB, max_seq_len=max_seq_len, num_layers=layers,
                     num_heads=2, embed_dim=16, eos_token=eos_token)
    blk.initialize(mx.init.Xavier(magnitude=2.5))
    return blk


def prompts_for(n, seed=11, lo=2, hi=10):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, VOCAB, size=rng.randint(lo, hi + 1))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# the model: hybridizable full forward + single-token step path
# ---------------------------------------------------------------------------

def test_gpt_hybridize_matches_eager():
    blk = make_block()
    toks = mx.nd.array(np.random.RandomState(0).randint(
        0, VOCAB, size=(2, 9)).astype(np.int32))
    eager = blk(toks).asnumpy()
    assert eager.shape == (2, 9, VOCAB)
    blk.hybridize()
    hybrid = blk(toks).asnumpy()
    assert np.array_equal(eager, hybrid)


def test_gpt_jax_forward_matches_block():
    blk = make_block()
    toks = np.random.RandomState(1).randint(0, VOCAB, size=(2, 7))
    want = blk(mx.nd.array(toks.astype(np.int32))).asnumpy()
    # jitted, as the engines run it: op by op it compiles every call
    got = np.asarray(jax.jit(blk.forward_fn())(
        blk.decode_params(), toks.astype(np.int32)))
    assert np.allclose(want, got, atol=1e-5)


def test_gpt_eager_step_api():
    """step(token, kv_cache, position): the single-token path is usable
    without any engine, and matches the reference from a prompt of 1."""
    blk = make_block()
    kv = blk.init_cache(2)
    pos = np.zeros(2, np.int32)
    tok = np.array([5, 9], np.int32)
    out = []
    for _ in range(4):
        nxt, kv, pos = blk.step(tok, kv, pos)
        tok = nxt.asnumpy()
        out.append(tok.copy())
    seq = np.stack(out)     # (steps, 2)
    ref0 = blk.generate_reference([5], 4)
    ref1 = blk.generate_reference([9], 4)
    assert np.array_equal(seq[:, 0], ref0)
    assert np.array_equal(seq[:, 1], ref1)


# ---------------------------------------------------------------------------
# DecodeEngine: token identity + the exactly-two-programs invariant
# ---------------------------------------------------------------------------

def test_engine_prefill_step_token_identity():
    blk = make_block()
    eng = DecodeEngine(blk, max_slots=1, name="ti")
    for prompt in prompts_for(4, seed=3):
        out = [eng.prefill(prompt, 0)]
        for _ in range(7):
            out.append(int(eng.step()[0]))
        eng.retire(0)
        ref = blk.generate_reference(prompt, 8)
        assert np.array_equal(np.asarray(out), ref), prompt


def test_exactly_two_decode_programs():
    """Prefill buckets aside, a DecodeEngine compiles exactly TWO
    decode-path programs (admit + step) — however many prompts, slots,
    lengths, or join/leave cycles it serves. Checked against both the
    engine's own counter and jax's jit cache sizes (the latter catches
    silent retraces the logical counter can't)."""
    blk = make_block()
    eng = DecodeEngine(blk, max_slots=3, name="two")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=5).start()
    handles = [sched.submit(p) for p in prompts_for(9, seed=5, hi=17)]
    for h in handles:
        h.result(timeout=60)
    assert sched.drain(timeout=30)
    progs = eng.compiled_programs
    non_prefill = {k: v for k, v in progs.items() if k != "prefill"}
    assert non_prefill == {"admit": 1, "step": 1}, progs
    assert 1 <= progs["prefill"] <= 6     # <= log2(max_seq_len)+1
    sizes = eng.xla_cache_sizes()
    if sizes:                              # newer jax exposes the cache
        assert sizes["admit"] + sizes["step"] == 2, sizes
        assert sizes["prefill"] == progs["prefill"], sizes
    # the compile counter metric agrees
    counter = obs.REGISTRY.get("serving.decode.compiles")
    assert counter.get(engine="two", kind="admit") == 1
    assert counter.get(engine="two", kind="step") == 1
    assert counter.get(engine="two", kind="prefill") == progs["prefill"]


def test_continuous_batching_token_identity_with_joins():
    """More sequences than slots, random lengths: late sequences join
    mid-batch into freed slots, and every one of them still decodes
    token-identically to the full re-forward reference."""
    blk = make_block(seed=19)
    eng = DecodeEngine(blk, max_slots=3, name="joins")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=9).start()
    prompts = prompts_for(10, seed=23, hi=12)
    handles = [sched.submit(p) for p in prompts]
    outs = [h.result(timeout=60) for h in handles]
    stats = sched.stats()
    assert stats["served"] == len(prompts)
    for prompt, out in zip(prompts, outs):
        ref = blk.generate_reference(prompt, 9)
        assert np.array_equal(out, ref), (prompt, out, ref)
    assert sched.drain(timeout=30)


def test_staggered_joins_stay_token_identical():
    """Sequences submitted while others are mid-decode (true mid-flight
    joins, not a starting burst) produce identical tokens."""
    blk = make_block(seed=29)
    eng = DecodeEngine(blk, max_slots=2, name="stagger")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=12).start()
    prompts = prompts_for(6, seed=31)
    handles = []
    for i, p in enumerate(prompts):
        handles.append(sched.submit(p))
        time.sleep(0.004)      # land between decode steps
    for prompt, h in zip(prompts, handles):
        assert np.array_equal(h.result(timeout=60),
                              blk.generate_reference(prompt, 12))
    sched.drain(timeout=30)


def test_eos_token_stops_generation():
    blk = make_block(seed=37)
    prompt = prompts_for(1, seed=41)[0]
    ref = blk.generate_reference(prompt, 8)
    eos = int(ref[3])
    eng = DecodeEngine(blk, max_slots=1, name="eos")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=8).start()
    out = sched.generate(prompt, eos_token=eos, timeout=60)
    stop = int(np.argmax(ref == eos)) + 1
    assert np.array_equal(out, ref[:stop])
    sched.drain(timeout=30)


def test_cache_full_retires_sequence():
    """A sequence that fills its cache slot resolves with what it has
    instead of stepping past max_seq_len."""
    blk = make_block(max_seq_len=8)
    eng = DecodeEngine(blk, max_slots=1, name="full")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=50).start()
    out = sched.generate(np.arange(1, 5), timeout=60)   # 4 prompt toks
    # prefill leaves position 4; steps write at 4..7 -> 1 prefill token
    # + tokens until the slot is full
    assert 1 <= len(out) <= 5
    assert np.array_equal(out, blk.generate_reference(np.arange(1, 5),
                                                      len(out)))
    sched.drain(timeout=30)


def test_prompt_validation():
    blk = make_block()
    eng = DecodeEngine(blk, max_slots=1)
    sched = ContinuousBatchScheduler(eng)
    with pytest.raises(mx.MXNetError):
        sched.submit([])
    with pytest.raises(mx.MXNetError):
        sched.submit(np.arange(MAXLEN + 1))
    with pytest.raises(mx.MXNetError):
        sched.submit([1, 2], max_new_tokens=0)


# ---------------------------------------------------------------------------
# scheduler edge cases
# ---------------------------------------------------------------------------

def test_join_into_freed_slot_single_slot():
    """slots=1 serializes sequences through one cache slot: every later
    request joins only when the slot frees, and all still finish."""
    blk = make_block()
    eng = DecodeEngine(blk, max_slots=1, name="one")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=4).start()
    prompts = prompts_for(4, seed=43)
    outs = [sched.submit(p) for p in prompts]
    for prompt, h in zip(prompts, outs):
        assert np.array_equal(h.result(timeout=60),
                              blk.generate_reference(prompt, 4))
    assert sched.stats()["served"] == 4
    sched.drain(timeout=30)


def test_deadline_eviction_at_step_boundary():
    """An in-flight sequence whose Deadline runs out is EVICTED between
    steps: rejected with DeadlineExceeded, slot freed, eviction
    counted — and a co-resident sequence without a deadline finishes
    normally."""
    blk = make_block(max_seq_len=128)
    eng = DecodeEngine(blk, max_slots=2, name="evict")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=120).start()
    # chaos stretches each decode step so the 60ms budget dies mid-
    # generation, deterministically
    chaos.configure("serving.decode:kind=sleep,secs=0.01")
    doomed = sched.submit(np.arange(1, 4), deadline=Deadline(0.06))
    safe = sched.submit(np.arange(4, 9), max_new_tokens=3)
    assert np.array_equal(safe.result(timeout=60),
                          blk.generate_reference(np.arange(4, 9), 3))
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=60)
    assert doomed.generated, "evicted mid-flight, not at admission"
    stats = sched.stats()
    assert stats["evicted"] == 1
    # the freed slot is reusable: a follow-up request still decodes
    chaos.configure("")
    again = sched.generate(np.arange(1, 4), max_new_tokens=2,
                           timeout=60)
    assert np.array_equal(again,
                          blk.generate_reference(np.arange(1, 4), 2))
    sched.drain(timeout=30)


def test_deadline_rejected_at_admission():
    """A request already expired when its turn comes is rejected
    without ever being prefilled."""
    blk = make_block()
    eng = DecodeEngine(blk, max_slots=1, name="adm")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=4)
    h = sched.submit([1, 2, 3], deadline=Deadline(0.0))
    steps_before = eng.steps
    sched.start()
    with pytest.raises(DeadlineExceeded):
        h.result(timeout=30)
    assert not h.generated              # never produced a token
    assert eng.steps == steps_before    # never computed
    sched.drain(timeout=30)


def test_drain_finishes_sequences_in_flight():
    blk = make_block()
    eng = DecodeEngine(blk, max_slots=2, name="drain")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=6).start()
    prompts = prompts_for(5, seed=47)
    handles = [sched.submit(p) for p in prompts]
    assert sched.drain(timeout=60)
    # every admitted AND queued sequence finished with full output
    for prompt, h in zip(prompts, handles):
        assert np.array_equal(h.result(timeout=0.1),
                              blk.generate_reference(prompt, 6))
    with pytest.raises(ServerClosed):
        sched.submit([1, 2])


def test_slot_exhaustion_reaches_shed_policy():
    """With every slot busy the queue backs up; past queue_depth the
    shed policy applies — reject refuses the newcomer, drop_oldest
    evicts the stalest queued request in its favor."""
    chaos.configure("serving.decode:kind=sleep,secs=0.02")
    blk = make_block()
    eng = DecodeEngine(blk, max_slots=1, name="shed")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=20,
                                     queue_depth=2).start()
    running = sched.submit([1, 2, 3])       # occupies the slot
    time.sleep(0.03)                        # let it admit
    q1, q2 = sched.submit([4, 5]), sched.submit([5, 6])
    with pytest.raises(RequestRejected):
        sched.submit([6, 7])                # queue full -> shed
    assert sched.stats()["shed"] == 1
    chaos.configure("")
    for h in (running, q1, q2):
        h.result(timeout=60)
    sched.drain(timeout=30)

    # drop_oldest: the newcomer displaces the stalest queued request
    chaos.configure("serving.decode:kind=sleep,secs=0.02")
    eng2 = DecodeEngine(blk, max_slots=1, name="shed2")
    sched2 = ContinuousBatchScheduler(eng2, max_new_tokens=20,
                                      queue_depth=1,
                                      shed_policy="drop_oldest").start()
    sched2.submit([1, 2, 3])
    time.sleep(0.03)
    victim = sched2.submit([4, 5])
    newcomer = sched2.submit([5, 6])        # evicts `victim`
    with pytest.raises(RequestRejected):
        victim.result(timeout=30)
    chaos.configure("")
    newcomer.result(timeout=60)
    sched2.drain(timeout=30)


def test_chaos_step_fault_fails_inflight_and_recovers():
    """An injected fault at the serving.decode site is delivered to
    every in-flight sequence; the scheduler clears the slots and keeps
    serving later traffic."""
    blk = make_block()
    eng = DecodeEngine(blk, max_slots=2, name="chaos")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=4).start()
    chaos.configure("serving.decode:kind=raise,n=1")
    h = sched.submit([1, 2, 3, 4])
    with pytest.raises(InjectedFault):
        h.result(timeout=30)
    # next request decodes normally (n=1: the fault tripped once)
    out = sched.generate([1, 2, 3, 4], timeout=60)
    assert np.array_equal(out, blk.generate_reference([1, 2, 3, 4], 4))
    sched.drain(timeout=30)


# ---------------------------------------------------------------------------
# bf16 serving dtype (MXTPU_SERVE_DTYPE)
# ---------------------------------------------------------------------------

def _mlp(nf=16, nh=24, nc=6, seed=5):
    data = mx.sym.var("data")
    h = mx.sym.FullyConnected(data=data, num_hidden=nh, name="fc1")
    h = mx.sym.Activation(data=h, act_type="relu")
    out = mx.sym.SoftmaxOutput(
        data=mx.sym.FullyConnected(data=h, num_hidden=nc, name="fc2"),
        name="softmax")
    rng = np.random.RandomState(seed)
    args = {
        "fc1_weight": mx.nd.array(rng.randn(nh, nf).astype("f") * 0.2),
        "fc1_bias": mx.nd.array(rng.randn(nh).astype("f") * 0.1),
        "fc2_weight": mx.nd.array(rng.randn(nc, nh).astype("f") * 0.2),
        "fc2_bias": mx.nd.array(rng.randn(nc).astype("f") * 0.1)}
    return out, args, nf


def test_bf16_inference_engine_parity_within_tolerance():
    sym, args, nf = _mlp()
    e32 = InferenceEngine.from_symbol(sym, args, {}, {"data": (nf,)}, 8)
    e16 = InferenceEngine.from_symbol(sym, args, {}, {"data": (nf,)}, 8,
                                      dtype="bf16")
    assert e32.dtype == "fp32" and e16.dtype == "bf16"
    x = np.random.RandomState(9).randn(5, nf).astype(np.float32)
    o32 = e32.infer(x)[0].asnumpy()
    o16 = e16.infer(x)[0].asnumpy()
    # responses stay fp32 regardless of the compute dtype
    assert o16.dtype == np.float32
    assert not np.array_equal(o32, o16)      # genuinely bf16 inside
    assert np.allclose(o32, o16, rtol=0.05, atol=0.02)
    # same compile-cache bound as fp32
    assert e16.buckets == e32.buckets


def test_bf16_env_var_selects_dtype():
    sym, args, nf = _mlp()
    os.environ["MXTPU_SERVE_DTYPE"] = "bf16"
    try:
        eng = InferenceEngine.from_symbol(sym, args, {},
                                          {"data": (nf,)}, 4)
        assert eng.dtype == "bf16"
    finally:
        del os.environ["MXTPU_SERVE_DTYPE"]
    with pytest.raises(mx.MXNetError):
        InferenceEngine.from_symbol(sym, args, {}, {"data": (nf,)}, 4,
                                    dtype="int7")


def test_bf16_decode_engine_generates():
    """bf16 decode: params and KV cache in bfloat16, greedy tokens out;
    still exactly two decode-path programs, and the tokens track the
    fp32 reference for a short horizon (argmax over well-separated
    logits survives the precision drop)."""
    blk = make_block(seed=53)
    eng = DecodeEngine(blk, max_slots=2, dtype="bf16", name="bf16")
    assert eng._cache_k.dtype == np.dtype("bfloat16")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=3).start()
    prompt = prompts_for(1, seed=59)[0]
    out = sched.generate(prompt, timeout=60)
    assert out.dtype == np.int32 and len(out) == 3
    assert np.array_equal(out, blk.generate_reference(prompt, 3))
    non_prefill = {k: v for k, v in eng.compiled_programs.items()
                   if k != "prefill"}
    assert non_prefill == {"admit": 1, "step": 1}
    sched.drain(timeout=30)


# ---------------------------------------------------------------------------
# ModelServer: the second engine kind
# ---------------------------------------------------------------------------

def test_model_server_decode_kind():
    blk = make_block(seed=61)
    eng = DecodeEngine(blk, max_slots=2, name="srv")
    prompts = prompts_for(5, seed=67)
    with ModelServer(eng, num_workers=1, max_new_tokens=5,
                     warmup=True) as server:
        assert server.kind == "decode"
        handles = [server.submit(p) for p in prompts]
        for prompt, h in zip(prompts, handles):
            assert np.array_equal(h.result(timeout=60),
                                  blk.generate_reference(prompt, 5))
        out = server.generate(prompts[0], max_new_tokens=2, timeout=60)
        assert np.array_equal(out, blk.generate_reference(prompts[0], 2))
        stats = server.stats()
        assert stats["kind"] == "decode"
        assert stats["served"] == len(prompts) + 1
        assert stats["max_slots"] == 2
    # context exit drained: new submits refused
    with pytest.raises(ServerClosed):
        server.submit(prompts[0])


def test_model_server_decode_drain_finishes_inflight():
    blk = make_block(seed=71)
    eng = DecodeEngine(blk, max_slots=1, name="srvdrain")
    server = ModelServer(eng, num_workers=1, max_new_tokens=6).start()
    handles = [server.submit(p) for p in prompts_for(3, seed=73)]
    assert server.drain(timeout=60)
    for h in handles:
        assert len(h.result(timeout=0.1)) == 6


def test_model_server_decode_sigterm_drains():
    """SIGTERM under handle_signals() must actually drain the decode
    schedulers (the handler only sets a flag; the watcher thread does
    the close), finishing in-flight sequences and refusing new ones."""
    import signal as _signal
    blk = make_block(seed=97)
    eng = DecodeEngine(blk, max_slots=1, name="sig")
    server = ModelServer(eng, num_workers=1, max_new_tokens=6).start()
    with server.handle_signals():
        handles = [server.submit(p) for p in prompts_for(3, seed=101)]
        _signal.raise_signal(_signal.SIGTERM)
        deadline = time.perf_counter() + 10
        while not all(s.closed for s in server._schedulers):
            assert time.perf_counter() < deadline, "watcher never closed"
            time.sleep(0.01)
        with pytest.raises(ServerClosed):
            server.submit([1, 2])
        for h in handles:               # accepted work still finishes
            assert len(h.result(timeout=60)) == 6
    assert server.drain(timeout=30)


def test_decode_server_rejects_forward_kwargs():
    blk = make_block(seed=103)
    eng = DecodeEngine(blk, max_slots=1)
    with pytest.raises(mx.MXNetError):
        ModelServer(eng, max_batch_size=8)
    with pytest.raises(mx.MXNetError):
        ModelServer(eng, max_wait_ms=5.0)


def test_bf16_engine_set_params_keeps_dtype():
    """Swapping fp32 weights into a bf16 engine must stage them in
    bf16 (no silent fp32 retrace of the warm buckets)."""
    sym, args, nf = _mlp()
    eng = InferenceEngine.from_symbol(sym, args, {}, {"data": (nf,)}, 4,
                                      dtype="bf16")
    eng.warmup()
    compiled = eng.compiled_buckets
    eng.set_params({"fc1_weight":
                    mx.nd.array(np.ones((24, nf), np.float32))})
    assert all(v.dtype == np.dtype("bfloat16")
               for v in eng._params.values())
    eng.infer(np.zeros((3, nf), np.float32))
    assert eng.compiled_buckets == compiled


def test_forward_server_rejects_decode_kwargs():
    sym, args, nf = _mlp()
    eng = InferenceEngine.from_symbol(sym, args, {}, {"data": (nf,)}, 4)
    with ModelServer(eng) as server:
        with pytest.raises(mx.MXNetError):
            server.submit(np.zeros((1, nf), np.float32),
                          max_new_tokens=4)


# ---------------------------------------------------------------------------
# observability wiring
# ---------------------------------------------------------------------------

def test_decode_telemetry_records(tmp_path, monkeypatch):
    path = tmp_path / "decode.jsonl"
    monkeypatch.setenv("MXTPU_TELEMETRY", str(path))
    blk = make_block(seed=79)
    eng = DecodeEngine(blk, max_slots=2, name="tel")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=4).start()
    for h in [sched.submit(p) for p in prompts_for(3, seed=83)]:
        h.result(timeout=60)
    sched.drain(timeout=30)
    records = [json.loads(l) for l in
               path.read_text().splitlines() if l.strip()]
    steps = [r for r in records if r["source"] == "decode"
             and r.get("event") != "request"]
    reqs = [r for r in records if r.get("event") == "request"]
    assert steps and len(reqs) == 3
    for r in steps:
        assert {"step_time", "tokens", "fill_ratio",
                "queue_depth"} <= set(r)
    for r in reqs:
        assert r["tokens"] == 4
        assert r["ttft_s"] > 0
        assert "intertoken_s" in r

    # the report renders a decode section and stays strict
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, os.path.join(root, "tools",
                                      "telemetry_report.py"),
         "--json", str(path)],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    summary = json.loads(res.stdout)
    assert summary["decode_requests"] == 3
    assert summary["decode_tokens"] >= 9   # step tokens (3 via prefill)
    assert "decode_ttft_p95_s" in summary
    assert "decode_intertoken_p50_s" in summary


def test_decode_metrics_registered():
    blk = make_block(seed=89)
    eng = DecodeEngine(blk, max_slots=1, name="met")
    sched = ContinuousBatchScheduler(eng, max_new_tokens=3).start()
    sched.generate([2, 3, 4], timeout=60)
    sched.drain(timeout=30)
    ttft = obs.REGISTRY.get("serving.decode.ttft")
    assert ttft.percentile(0.5, engine="met") > 0
    tokens = obs.REGISTRY.get("serving.decode.tokens")
    assert tokens.get(engine="met") == 3
    fill = obs.REGISTRY.get("serving.decode.slot.fill_ratio")
    # one slot, always full — p50 lands in the top histogram bucket
    assert fill.percentile(0.5, engine="met") >= 0.9
