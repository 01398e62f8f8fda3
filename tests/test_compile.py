"""Compilation artifact subsystem (ISSUE 11, docs/compilation.md):
persistent-cache wiring, AOT executable store + fingerprint fallback,
cold-start telemetry, gang downtime split, GC/holder refusal."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 — framework wiring under test
from mxnet_tpu.compile import aot as aot_mod
from mxnet_tpu.compile import cache as cache_mod
from mxnet_tpu.compile import coldstart as coldstart_mod
from mxnet_tpu.compile import (ArtifactStore, StoreHeld, fingerprint,
                               gc_cache_dir)
from mxnet_tpu.observability import registry as obs
from mxnet_tpu.resilience import chaos

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _total(name):
    m = obs.REGISTRY.get(name)
    return m.total() if m is not None else 0


def _build_engine(name="m", dtype=None, hidden=16):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from serve_bench import _build_model
    from mxnet_tpu.serving import InferenceEngine
    sym, params = _build_model(8, hidden)
    return InferenceEngine.from_symbol(
        sym, params, {}, {"data": (8,)}, 4, name=name, dtype=dtype)


# ---------------------------------------------------------------------------
# cache dir resolution + raw-dir GC
# ---------------------------------------------------------------------------
class TestCacheDir:
    def test_explicit_path_wins(self):
        env = {"MXTPU_COMPILE_CACHE": "/x/y"}
        assert cache_mod.resolve_cache_dir(env) == "/x/y"

    def test_zero_disables(self):
        assert cache_mod.resolve_cache_dir(
            {"MXTPU_COMPILE_CACHE": "0"}) is None

    def test_bench_legacy_spelling(self):
        # bench.py's MXTPU_XLA_CACHE is honored when the canonical
        # knob is absent, and loses to it when both are set
        assert cache_mod.resolve_cache_dir(
            {"MXTPU_XLA_CACHE": "/legacy"}) == "/legacy"
        assert cache_mod.resolve_cache_dir(
            {"MXTPU_XLA_CACHE": "/legacy",
             "MXTPU_COMPILE_CACHE": "/canon"}) == "/canon"

    def test_jax_env_respected(self):
        assert cache_mod.resolve_cache_dir(
            {"JAX_COMPILATION_CACHE_DIR": "/operator",
             "MXTPU_COMPILE_CACHE": "0"}) == "/operator"

    def test_default_is_fixed_dir_in_checkout(self):
        # one fixed directory beside the package: the path is part of
        # jax's cache key, so it never comes from tempfile/pid/uid/time
        assert cache_mod.resolve_cache_dir({}) \
            == cache_mod.default_cache_dir() \
            == os.path.join(ROOT, ".jax_cache")

    def test_default_is_same_in_two_processes(self):
        # run from another directory with no cache variable set: a
        # second process resolves the very same directory
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_COMPILATION_CACHE_DIR",
                            "MXTPU_COMPILE_CACHE", "MXTPU_XLA_CACHE")}
        env["PYTHONPATH"] = ROOT
        code = ("from mxnet_tpu.compile.cache import resolve_cache_dir;"
                "print(resolve_cache_dir())")
        outs = [subprocess.run([sys.executable, "-c", code], env=env,
                               cwd=cwd, capture_output=True, text=True,
                               timeout=120, check=True).stdout.split()[-1]
                for cwd in ("/", ROOT)]
        assert outs[0] == outs[1] == os.path.join(ROOT, ".jax_cache")

    def test_jax_env_dir_is_left_alone(self, tmp_path):
        # with JAX_COMPILATION_CACHE_DIR set, jax has read it into its
        # own config; enable_cache sets no other directory in code
        env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "ops"),
                   MXTPU_COMPILE_CACHE=str(tmp_path / "other"))
        code = ("import jax, mxnet_tpu;"
                "from mxnet_tpu.compile import cache;"
                "print(cache.enable_cache());"
                "print(jax.config.jax_compilation_cache_dir)")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120,
                             check=True).stdout.split()
        assert out[-2:] == [str(tmp_path / "ops")] * 2
        assert not (tmp_path / "other").exists()

    def test_gc_scrubs_empty_and_evicts_lru(self, tmp_path):
        old = tmp_path / "old.bin"
        new = tmp_path / "new.bin"
        husk = tmp_path / "husk.bin"
        old.write_bytes(b"x" * 100)
        new.write_bytes(b"y" * 100)
        husk.write_bytes(b"")
        past = time.time() - 3600
        os.utime(old, (past, past))
        report = gc_cache_dir(str(tmp_path), max_bytes=150)
        assert report["scrubbed"] == 1
        assert not husk.exists()
        # LRU: the old entry goes, the fresh one stays
        assert not old.exists() and new.exists()
        assert report["bytes_after"] <= 150

    def test_multidevice_read_guard_installed(self):
        """enable_cache must wrap jax's cache read so multi-device CPU
        entries never deserialize (jaxlib segfault — the
        test_trainer_checkpoint reproducer); single-device reads pass
        through."""
        cache_mod.enable_cache()
        if not cache_mod.cache_enabled():
            pytest.skip("cache disabled in this session")
        from jax._src import compiler as jc
        assert jc._cache_read.__name__ == "guarded_read"

        class EBO:
            def __init__(self, n):
                self.num_replicas = n
                self.num_partitions = 1

        class Opts:
            def __init__(self, n):
                self.executable_build_options = EBO(n)

        class Backend:
            platform = "cpu"

        # spanning: forced miss, underlying cache never touched
        assert jc._cache_read("m", "key-that-does-not-exist",
                              Opts(8), Backend(), None) == (None, None)

    def test_guarded_read_forwards_all_five_arguments(self, monkeypatch):
        """jax 0.9.0 calls `_cache_read(module_name, cache_key,
        compile_options, backend, executable_devices)`; a single-device
        read reaches jax's own function with all five, in order."""
        from jax._src import compiler as jc
        seen = []

        def orig(*args):
            seen.append(args)
            return "executable", 1.5

        monkeypatch.setattr(jc, "_cache_read", orig)
        cache_mod._install_multidevice_guard()   # wraps `orig`
        assert jc._cache_read.__name__ == "guarded_read"

        class EBO:
            num_replicas = 1
            num_partitions = 1

        class Opts:
            executable_build_options = EBO

        class Backend:
            platform = "cpu"

        opts, backend, devs = Opts(), Backend(), ["d0"]
        assert jc._cache_read("mod", "key", opts, backend, devs) \
            == ("executable", 1.5)
        assert seen == [("mod", "key", opts, backend, devs)]

    def test_gc_dry_run_touches_nothing(self, tmp_path):
        f = tmp_path / "a.bin"
        f.write_bytes(b"z" * 100)
        report = gc_cache_dir(str(tmp_path), max_bytes=1, dry_run=True)
        assert report["evicted"] == 1 and f.exists()


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------
class TestFingerprint:
    def test_deterministic(self):
        assert fingerprint({"a": 1}) == fingerprint({"a": 1})

    def test_sensitive_to_extra(self):
        assert fingerprint({"a": 1}) != fingerprint({"a": 2})

    def test_sensitive_to_keyed_env_flag(self, monkeypatch):
        base = fingerprint({})
        monkeypatch.setenv("MXTPU_SERVE_DTYPE", "bf16")
        assert fingerprint({}) != base

    def test_aval_signature_orders_shapes_and_dtypes(self):
        import jax
        sig = aot_mod.aval_signature(
            {"w": jax.ShapeDtypeStruct((2, 3), np.float32), "k": None})
        assert sig == {"k": None, "w": [[2, 3], "float32"]}


# ---------------------------------------------------------------------------
# ArtifactStore
# ---------------------------------------------------------------------------
class TestArtifactStore:
    def _compiled(self):
        # compile_fresh, not a bare lower().compile(): an executable
        # that came out of the persistent cache serializes into a blob
        # a loader cannot resolve ("Symbols not found") — the exact
        # invariant export_jit enforces (see test below)
        import jax
        import jax.numpy as jnp
        jitted = jax.jit(lambda a: jnp.tanh(a) * 2.0)
        aval = (jax.ShapeDtypeStruct((4,), np.float32),)
        return aot_mod.compile_fresh(jitted, aval)

    def test_verify_and_prune_drops_unloadable_blob(self, tmp_path):
        """Regression guard for the export-verification invariant: a
        blob a fresh interpreter cannot load (here: torn payload) is
        pruned from the manifest and counted; a good blob survives."""
        store = ArtifactStore(tmp_path, create=True)
        good_fp = fingerprint({"k": "good"})
        store.put("good", good_fp, self._compiled())
        bad_fp = fingerprint({"k": "bad"})
        store.put("bad", bad_fp, self._compiled())
        blob = tmp_path / store.entries()["bad"]["file"]
        blob.write_bytes(b"\x80\x04not an executable")
        before = _total("compile.aot.fallbacks")
        result = store.verify_and_prune()
        assert result == {"good": True, "bad": False}
        assert set(store.entries()) == {"good"}
        assert not blob.exists()
        assert _total("compile.aot.fallbacks") == before + 1

    def test_export_after_warm_cache_hit_is_caught(self, tmp_path):
        """The flaky-export mode end to end: warm the persistent cache
        for a program in a subprocess, hit it in THIS process via
        lower().compile(), serialize that executable. Whether the blob
        comes out poisoned (symbol-referencing) depends on the
        process's accumulated dedup state — the invariant under test
        is HONESTY: after verify_and_prune, the surviving entries are
        exactly the ones a fresh interpreter proved loadable."""
        import jax
        import jax.numpy as jnp
        cache_dir = str(tmp_path / "cache")
        # the warming program must match the in-process one exactly —
        # the cache key covers the HLO module name, so `f` by `def`
        prog = ("import jax, jax.numpy as jnp, numpy as np\n"
                "jax.config.update('jax_platforms', 'cpu')\n"
                "jax.config.update('jax_compilation_cache_dir', %r)\n"
                "jax.config.update("
                "'jax_persistent_cache_min_compile_time_secs', 0.0)\n"
                "jax.config.update("
                "'jax_persistent_cache_min_entry_size_bytes', -1)\n"
                "def f(a):\n"
                "    return jnp.sinh(a) * 5.0\n"
                "jax.jit(f).lower(\n"
                "    jax.ShapeDtypeStruct((4,), jnp.float32)"
                ").compile()\n" % cache_dir)
        r = subprocess.run([sys.executable, "-c", prog],
                           capture_output=True, text=True, timeout=300,
                           env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 0, r.stdout + r.stderr

        def f(a):
            return jnp.sinh(a) * 5.0

        aval = (jax.ShapeDtypeStruct((4,), np.float32),)
        prev = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        try:
            via_cache = jax.jit(f).lower(*aval).compile()
            fresh = aot_mod.compile_fresh(jax.jit(f), aval)
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
        store = ArtifactStore(tmp_path / "store", create=True)
        store.put("via_cache", fingerprint({"k": 1}), via_cache)
        store.put("fresh", fingerprint({"k": 2}), fresh)
        result = store.verify_and_prune()
        assert set(result) == {"via_cache", "fresh"}
        # survivors are exactly the provably-loadable blobs, and a
        # pruned blob is gone from disk as well as the manifest
        assert set(store.entries()) == {n for n, ok in result.items()
                                        if ok}
        for name, ok in result.items():
            if not ok:
                blobs = [f for f in os.listdir(tmp_path / "store")
                         if f.endswith(".aot")]
                assert len(blobs) == sum(result.values())

    def test_put_get_roundtrip(self, tmp_path):
        store = ArtifactStore(tmp_path, create=True)
        fp = fingerprint({"k": "v"})
        nbytes = store.put("p", fp, self._compiled())
        assert nbytes > 0
        assert store.entries()["p"]["fingerprint"] == fp
        fn = store.get("p", fp)
        assert fn is not None
        out = np.asarray(fn(np.ones(4, np.float32))[0]
                         if isinstance(fn(np.ones(4, np.float32)),
                                       tuple)
                         else fn(np.ones(4, np.float32)))
        assert np.allclose(out, np.tanh(1.0) * 2.0)

    def test_fingerprint_mismatch_falls_back(self, tmp_path):
        store = ArtifactStore(tmp_path, create=True)
        store.put("p", fingerprint({"k": 1}), self._compiled())
        before = _total("compile.aot.fallbacks")
        assert store.get("p", fingerprint({"k": 2})) is None
        assert _total("compile.aot.fallbacks") == before + 1

    def test_missing_and_corrupt_fall_back(self, tmp_path):
        store = ArtifactStore(tmp_path, create=True)
        assert store.get("absent", fingerprint({})) is None
        fp = fingerprint({"k": 3})
        store.put("p", fp, self._compiled())
        blob = tmp_path / store.entries()["p"]["file"]
        blob.write_bytes(b"not a pickle")
        assert store.get("p", fp) is None

    def test_torn_manifest_degrades_to_empty(self, tmp_path):
        store = ArtifactStore(tmp_path, create=True)
        (tmp_path / "manifest.json").write_text("{torn")
        assert store.entries() == {}
        assert store.get("p", fingerprint({})) is None

    def test_chaos_compile_load_falls_back_clean(self, tmp_path):
        store = ArtifactStore(tmp_path, create=True)
        fp = fingerprint({"k": 4})
        store.put("p", fp, self._compiled())
        chaos.configure("compile.load:kind=fatal")
        try:
            before = _total("compile.aot.fallbacks")
            assert store.get("p", fp) is None    # fault, not a raise
            assert _total("compile.aot.fallbacks") == before + 1
        finally:
            chaos.reset()
        assert store.get("p", fp) is not None    # disarmed: loads again

    # -- holders + gc --------------------------------------------------
    def test_gc_refuses_live_holder_then_runs_after_release(
            self, tmp_path):
        store = ArtifactStore(tmp_path, create=True)
        store.put("p", fingerprint({"k": 5}), self._compiled())
        store.hold(what="test")
        assert len(store.live_holders()) == 1
        with pytest.raises(StoreHeld):
            store.gc(max_bytes=0)
        store.release()
        report = store.gc(max_bytes=0)
        assert report["evicted"] == 1
        assert store.entries() == {}

    def test_dead_holder_cleared_in_passing(self, tmp_path):
        store = ArtifactStore(tmp_path, create=True)
        hd = tmp_path / "holders"
        hd.mkdir()
        (hd / "999999.json").write_text(json.dumps(
            {"pid": 999999, "host": "", "boot_id": "x",
             "starttime": 1, "heartbeat": 0}))
        assert store.live_holders() == []
        assert not (hd / "999999.json").exists()

    def test_gc_evicts_version_mismatch(self, tmp_path):
        store = ArtifactStore(tmp_path, create=True)
        fp = fingerprint({"k": 6})
        store.put("stale", fp, self._compiled())
        manifest = store.manifest()
        manifest["entries"]["stale"]["jax"] = "0.0.1"
        store._write_manifest(manifest)
        report = store.gc()
        assert report["evicted"] == 1
        assert "stale" not in store.entries()

    def test_gc_lru_respects_budget(self, tmp_path):
        store = ArtifactStore(tmp_path, create=True)
        store.put("a", fingerprint({"k": "a"}), self._compiled())
        store.put("b", fingerprint({"k": "b"}), self._compiled())
        blob_a = tmp_path / store.entries()["a"]["file"]
        past = time.time() - 3600
        os.utime(blob_a, (past, past))
        budget = int(store.entries()["b"]["bytes"]) + 10
        report = store.gc(max_bytes=budget)
        assert report["evicted"] == 1
        assert set(store.entries()) == {"b"}


# ---------------------------------------------------------------------------
# InferenceEngine AOT path
# ---------------------------------------------------------------------------
class TestEngineAOT:
    def test_export_load_bit_identical_no_compile(self, tmp_path):
        e1 = _build_engine("aot_m")
        # export BEFORE any dispatch: a warm-persistent-cache infer
        # first would dedupe the export's object code in-process (the
        # verification invariant; see TestArtifactStore)
        store = ArtifactStore(tmp_path, create=True)
        exported = e1.aot_export(store)
        assert [b for b, _ in exported] == [1, 2, 4]
        x = np.random.RandomState(0).randn(3, 8).astype(np.float32)
        ref = np.asarray(e1.infer(x)[0])

        e2 = _build_engine("aot_m")
        assert e2.aot_load(store) == [1, 2, 4]
        compiles_before = _total("serving.engine.compiles")
        out = np.asarray(e2.infer(x)[0])
        assert np.array_equal(ref, out)
        # the AOT dispatch marked the bucket warm without compiling
        assert _total("serving.engine.compiles") == compiles_before
        assert 4 in e2.compiled_buckets
        assert e2.aot_buckets == [1, 2, 4]

    def test_dtype_flip_refuses_load(self, tmp_path):
        e1 = _build_engine("aot_d")
        store = ArtifactStore(tmp_path, create=True)
        e1.aot_export(store)
        e2 = _build_engine("aot_d", dtype="bf16")
        before = _total("compile.aot.fallbacks")
        assert e2.aot_load(store) == []
        assert _total("compile.aot.fallbacks") > before
        # and the JIT path still serves
        out = e2.infer(np.zeros((2, 8), np.float32))[0]
        assert np.asarray(out).shape == (2, 16)

    def test_server_loads_artifacts_before_first_dispatch(
            self, tmp_path):
        from mxnet_tpu.serving import ModelServer
        e1 = _build_engine("aot_srv")
        store = ArtifactStore(tmp_path, create=True)
        e1.aot_export(store)
        e2 = _build_engine("aot_srv")
        with ModelServer(e2, num_workers=1, warmup=True,
                         artifacts=store) as server:
            stats = server.stats()
            assert stats["aot_buckets"] == [1, 2, 4]
            out = server.infer(np.zeros((1, 8), np.float32),
                               timeout=30)
            assert np.asarray(out[0]).shape == (1, 16)

    def test_decode_engine_aot_token_identical(self, tmp_path):
        from mxnet_tpu.gluon.model_zoo.gpt import GPTDecoder
        from mxnet_tpu.serving import DecodeEngine
        np.random.seed(3)
        block = GPTDecoder(32, max_seq_len=8, num_layers=1,
                           num_heads=2, embed_dim=8)
        block.initialize(mx.init.Xavier(magnitude=2.5))
        prompts = [np.array([3, 1, 4]), np.array([1, 5])]

        def run(engine):
            outs = []
            for p in prompts:
                slot = engine.free_slots[0]
                toks = [engine.prefill(p, slot)]
                while len(toks) < 3 and not engine.slot_full(slot):
                    toks.append(int(engine.step()[slot]))
                engine.retire(slot)
                outs.append(toks)
            return outs

        e1 = DecodeEngine(block, max_slots=2, name="aot_gpt")
        store = ArtifactStore(tmp_path, create=True)
        exported = e1.aot_export(store)      # before any dispatch
        assert len(exported) == 6            # admit+step+4 buckets
        ref = run(e1)
        e2 = DecodeEngine(block, max_slots=2, name="aot_gpt")
        loaded = e2.aot_load(store)
        assert "admit" in loaded and "step" in loaded
        assert run(e2) == ref
        # the whole run rode AOT executables — the program census
        # still holds its exactly-two invariant, while the compile
        # metric counted nothing (nothing compiled)
        census = e2.compiled_programs
        assert census["admit"] == 1 and census["step"] == 1

    def test_fresh_process_load_bit_identical(self, tmp_path):
        """ISSUE 11 acceptance: an AOT-serialized executable loaded in
        a FRESH process produces outputs bit-identical to the JIT
        path."""
        script = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, os.path.join(%(root)r, "tools"))
sys.path.insert(0, %(root)r)
from serve_bench import _build_model
from mxnet_tpu.serving import InferenceEngine
from mxnet_tpu.compile import ArtifactStore
sym, params = _build_model(8, 16)
engine = InferenceEngine.from_symbol(
    sym, params, {}, {"data": (8,)}, 4, name="xproc")
x = np.random.RandomState(7).randn(3, 8).astype(np.float32)
mode = sys.argv[1]
store = ArtifactStore(%(store)r, create=True)
if mode == "export":
    exported = engine.aot_export(store)         # before any dispatch
    assert [b for b, _ in exported] == [1, 2, 4], exported
    out = engine.infer(x)[0].asnumpy()          # JIT path
    np.save(os.path.join(%(store)r, "ref.npy"), out)
else:
    loaded = engine.aot_load(store)
    assert loaded == [1, 2, 4], loaded
    out = engine.infer(x)[0].asnumpy()          # AOT path
    ref = np.load(os.path.join(%(store)r, "ref.npy"))
    print(json.dumps({"identical": bool(np.array_equal(out, ref))}))
""" % {"root": ROOT, "store": str(tmp_path)}

        def run(mode):
            return subprocess.run(
                [sys.executable, "-c", script, mode],
                capture_output=True, text=True, timeout=300,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))

        r = run("export")
        assert r.returncode == 0, r.stdout + r.stderr
        r = run("load")
        assert r.returncode == 0, r.stdout + r.stderr
        verdict = json.loads(r.stdout.strip().splitlines()[-1])
        assert verdict["identical"] is True


# ---------------------------------------------------------------------------
# fused-update AOT capture/replay
# ---------------------------------------------------------------------------
class TestFusedUpdateAOT:
    def _train(self, seed=0, steps=3, staged=False):
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        np.random.seed(seed)
        mx.random.seed(seed)    # identical init across runs
        net = nn.Dense(4, in_units=8)
        net.initialize(mx.init.Xavier())
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.01})
        loss_fn = gluon.loss.L2Loss()
        rng = np.random.RandomState(9)
        X = rng.rand(steps * 8, 8).astype(np.float32)
        Y = rng.rand(steps * 8, 4).astype(np.float32)
        for i in range(steps):
            x = mx.nd.array(X[i * 8:(i + 1) * 8])
            y = mx.nd.array(Y[i * 8:(i + 1) * 8])
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            if staged:      # the halves that always stage
                tr.allreduce_grads()
                tr.update(8)
            else:
                tr.step(8)
        return {k: p.data().asnumpy()
                for k, p in net.collect_params().items()}

    def test_capture_then_replay_bit_identical(self, tmp_path,
                                               monkeypatch):
        from mxnet_tpu.parallel import fused_update
        ref = self._train()                       # plain JIT
        monkeypatch.setenv("MXTPU_AOT_STORE", str(tmp_path))
        monkeypatch.setenv("MXTPU_AOT_EXPORT", "1")
        fused_update._AOT.clear()
        try:
            # fused-step era (ISSUE 15): the Trainer loop dispatches
            # ONE exchange+update program per step, so the capture
            # harvests a fused_step/ executable; the staged kernels
            # are captured where the staged halves run
            captured = self._train()              # capture pass
            store = ArtifactStore(tmp_path)
            assert any(n.startswith("fused_step/")
                       for n in store.entries())
            staged = self._train(staged=True)     # staged capture pass
            assert any(n.startswith("fused/adam/")
                       for n in store.entries())
            fused_update._AOT.clear()             # force a re-load
            monkeypatch.setenv("MXTPU_AOT_EXPORT", "0")
            loads_before = _total("compile.aot.loads")
            replayed = self._train()              # AOT replay pass
            assert _total("compile.aot.loads") > loads_before
        finally:
            fused_update._AOT.clear()

        # gluon name manager gives each run a fresh dense<N> prefix:
        # compare by (sorted) suffix — weight/bias
        def by_suffix(d):
            return {k.rsplit("_", 1)[1]: v for k, v in d.items()}

        ref, captured, staged, replayed = (
            by_suffix(ref), by_suffix(captured), by_suffix(staged),
            by_suffix(replayed))
        for k in ref:
            assert np.array_equal(ref[k], captured[k]), k
            assert np.array_equal(ref[k], staged[k]), k
            assert np.array_equal(ref[k], replayed[k]), k


# ---------------------------------------------------------------------------
# cold-start telemetry
# ---------------------------------------------------------------------------
class TestColdStart:
    def test_process_start_predates_now(self):
        t = coldstart_mod.process_start_time()
        assert 0 < t <= time.time()

    def test_mark_ready_once_and_record_fields(self, tmp_path,
                                               monkeypatch):
        from mxnet_tpu.observability.telemetry import close_stream
        stream = tmp_path / "t.jsonl"
        monkeypatch.setenv("MXTPU_TELEMETRY", str(stream))
        coldstart_mod._reset_for_tests()
        rec = coldstart_mod.mark_ready("serving", engine="e")
        assert rec is not None and rec["what"] == "serving"
        assert rec["step_time"] > 0
        for field in ("compile_seconds", "cache_hits", "cache_misses",
                      "aot_loads", "aot_fallbacks"):
            assert field in rec, field
        # once per process: the second marker is refused
        assert coldstart_mod.mark_ready("train") is None
        assert coldstart_mod.cold_record()["what"] == "serving"
        close_stream()
        lines = [json.loads(l)
                 for l in stream.read_text().splitlines()]
        assert len(lines) == 1
        assert lines[0]["source"] == "compile"
        assert lines[0]["event"] == "cold_start"

    def test_gang_record_appended_with_generation(self, tmp_path,
                                                  monkeypatch):
        monkeypatch.setenv("MXTPU_GANG_DIR", str(tmp_path))
        monkeypatch.setenv("MXTPU_GANG_GENERATION", "2")
        monkeypatch.setenv("JAX_PROCESS_ID", "1")
        coldstart_mod._reset_for_tests()
        coldstart_mod.mark_ready("train")
        lines = (tmp_path / "coldstart.jsonl").read_text().splitlines()
        rec = json.loads(lines[-1])
        assert rec["generation"] == 2 and rec["rank"] == 1
        coldstart_mod._reset_for_tests()


# ---------------------------------------------------------------------------
# supervisor downtime split
# ---------------------------------------------------------------------------
class TestGangReportSplit:
    def test_restart_incident_gains_downtime_split(self, tmp_path):
        from mxnet_tpu.resilience.supervisor import GangSupervisor
        sup = GangSupervisor(["true"], nranks=2,
                             gang_dir=str(tmp_path))
        os.makedirs(str(tmp_path), exist_ok=True)
        sup.incidents = [
            {"generation": 0, "rank": 1, "exit_code": -9,
             "action": "restart", "downtime_s": 0.4},
            {"generation": 1, "rank": 0, "exit_code": 75,
             "action": "stop (preempted)", "downtime_s": 0.0},
        ]
        with open(os.path.join(str(tmp_path), "coldstart.jsonl"),
                  "w") as f:
            for rank, gen, cold, comp in ((0, 0, 4.0, 3.0),
                                          (1, 0, 4.5, 3.2),
                                          (0, 1, 1.2, 0.1),
                                          (1, 1, 1.4, 0.2)):
                f.write(json.dumps({
                    "rank": rank, "generation": gen,
                    "step_time": cold, "compile_seconds": comp,
                    "cache_hits": 5, "cache_misses": 1,
                    "aot_loads": 0, "aot_fallbacks": 0,
                    "compile_count": 3}) + "\n")
            f.write("torn {\n")          # tolerated, skipped
        report = sup.report()
        restart = report["incidents"][0]
        assert restart["downtime_split"] == {
            "relaunch_s": 0.4, "recompile_s": 0.2,
            "rank_ready_max_s": 1.4}
        # the preempt-stop incident has no relaunched generation
        assert "downtime_split" not in report["incidents"][1]
        assert report["cold_starts"]["0"]["ranks"] == 2
        assert report["cold_starts"]["1"]["compile_s_max"] == 0.2

    def test_generation_zero_spawn_clears_stale_records(
            self, tmp_path):
        from mxnet_tpu.resilience.supervisor import GangSupervisor
        stale = tmp_path / "coldstart.jsonl"
        stale.write_text('{"generation": 0, "step_time": 9}\n')
        sup = GangSupervisor([sys.executable, "-c", "pass"], nranks=1,
                             gang_dir=str(tmp_path))
        procs = sup.spawn()
        for p in procs:
            p.wait()
        assert not stale.exists()


# ---------------------------------------------------------------------------
# telemetry_report + perf_gate integration
# ---------------------------------------------------------------------------
def _write_stream(path, cold_start_s=1.5):
    records = [
        {"ts": 1.0, "source": "train", "step": 0, "step_time": 0.1,
         "compile_cache_hits": 4, "compile_cache_misses": 2,
         "batch_size": 8},
        {"ts": 2.0, "source": "train", "step": 1, "step_time": 0.1,
         "batch_size": 8},
        {"ts": 3.0, "source": "compile", "event": "cold_start",
         "what": "serving", "step_time": cold_start_s,
         "compile_seconds": 1.0, "cache_hits": 1, "cache_misses": 9,
         "aot_loads": 3, "aot_fallbacks": 1, "rank": 0},
    ]
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


class TestReporting:
    def test_compile_section_and_headline_exclusion(self, tmp_path):
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        from telemetry_report import load_records, summarize
        p = str(tmp_path / "t.jsonl")
        _write_stream(p)
        s = summarize(load_records(p))
        assert s["steps"] == 2            # cold_start excluded
        assert s["cold_starts"] == 1
        assert s["cold_start_max_s"] == 1.5
        # step deltas win (the cold record's CUMULATIVE totals cover
        # the same warm-up hits — summing both would double-count)
        assert s["compile_cache_hits"] == 4
        assert s["compile_cache_misses"] == 2
        assert s["aot_loads"] == 3 and s["aot_fallbacks"] == 1
        # a serving-only stream has no step deltas: cold totals used
        with open(p, "w") as f:
            f.write(json.dumps({
                "ts": 3.0, "source": "compile", "event": "cold_start",
                "what": "serving", "step_time": 1.0,
                "cache_hits": 7, "cache_misses": 2}) + "\n")
        s2 = summarize(load_records(p))
        assert s2["compile_cache_hits"] == 7
        assert s2["compile_cache_misses"] == 2

    def test_perf_gate_cold_start_budget(self, tmp_path):
        p = str(tmp_path / "t.jsonl")
        _write_stream(p, cold_start_s=1.5)
        gate = os.path.join(ROOT, "tools", "perf_gate.py")

        def run(*args):
            return subprocess.run(
                [sys.executable, gate, p, *args],
                capture_output=True, text=True)

        ok = run("--max-cold-start-s", "2.0")
        assert ok.returncode == 0, ok.stdout + ok.stderr
        breach = run("--max-cold-start-s", "1.0")
        assert breach.returncode == 1
        assert "cold_start_s" in breach.stderr
        # a stream with no cold-start records can't satisfy the budget
        with open(p, "w") as f:
            f.write(json.dumps({"ts": 1, "source": "train",
                                "step_time": 0.1}) + "\n")
        absent = run("--max-cold-start-s", "2.0")
        assert absent.returncode == 1

    @pytest.mark.slow
    def test_chaos_run_compile_load_falls_back_to_jit(self, tmp_path):
        """The docs/fault_tolerance.md chaos-row proof, end to end via
        tools/chaos_run.py: with the compile.load site armed fatal, a
        serving process's artifact loads all fault — and it must still
        COMPLETE by serving through the JIT path."""
        script = r"""
import os, sys
import numpy as np
sys.path.insert(0, os.path.join(%(root)r, "tools"))
sys.path.insert(0, %(root)r)
from serve_bench import _build_model
from mxnet_tpu.serving import InferenceEngine, ModelServer
from mxnet_tpu.compile import ArtifactStore
from mxnet_tpu.observability import registry as obs
sym, params = _build_model(8, 16)
store = ArtifactStore(%(store)r)
engine = InferenceEngine.from_symbol(
    sym, params, {}, {"data": (8,)}, 4, name="chaosload")
with ModelServer(engine, num_workers=1, warmup=True,
                 artifacts=store) as server:
    assert server.stats()["aot_buckets"] == []   # every load faulted
    out = server.infer(np.zeros((1, 8), np.float32), timeout=60)
    assert np.asarray(out[0]).shape == (1, 16)
fb = obs.REGISTRY.get("compile.aot.fallbacks")
assert fb is not None and fb.total() >= 3, fb
print("served through JIT fallback")
""" % {"root": ROOT, "store": str(tmp_path)}
        # export the store from a clean process first
        exp = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools",
                                          "aot_build.py"),
             "--out", str(tmp_path), "--mlp", "--features", "8",
             "--hidden", "16", "--depth", "3", "--max-batch", "4"],
            capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert exp.returncode == 0, exp.stdout + exp.stderr
        r = subprocess.run(
            [sys.executable,
             os.path.join(ROOT, "tools", "chaos_run.py"),
             "--chaos", "compile.load:kind=fatal",
             "--expect", "complete", "--timeout", "300",
             "--", sys.executable, "-c", script],
            capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 0, r.stdout + r.stderr
        verdict = json.loads(r.stdout.strip().splitlines()[-1])
        assert verdict["outcome"] == "COMPLETED"

    def test_aot_build_tool_roundtrip(self, tmp_path):
        build = os.path.join(ROOT, "tools", "aot_build.py")
        out = str(tmp_path / "store")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, build, "--out", out, "--mlp",
             "--features", "8", "--hidden", "16", "--depth", "3",
             "--max-batch", "4"],
            capture_output=True, text=True, timeout=600, env=env)
        assert r.returncode == 0, r.stdout + r.stderr
        built = json.loads(r.stdout.strip().splitlines()[-1])
        assert built["entries"] == 3      # buckets 1, 2, 4
        listed = subprocess.run(
            [sys.executable, build, "--list", out],
            capture_output=True, text=True, timeout=300, env=env)
        assert listed.returncode == 0
        assert len(json.loads(
            listed.stdout.strip().splitlines()[-1])["entries"]) == 3
        # GC with a live holder refuses with exit 2
        store = ArtifactStore(out)
        store.hold(what="test")
        try:
            refused = subprocess.run(
                [sys.executable, build, "--gc", out,
                 "--max-bytes", "0"],
                capture_output=True, text=True, timeout=300, env=env)
            assert refused.returncode == 2
            assert json.loads(refused.stdout.strip().splitlines()[-1]
                              )["refused"] is True
        finally:
            store.release()
        done = subprocess.run(
            [sys.executable, build, "--gc", out, "--max-bytes", "0"],
            capture_output=True, text=True, timeout=300, env=env)
        assert done.returncode == 0
        assert ArtifactStore(out).entries() == {}
