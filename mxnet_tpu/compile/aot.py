"""Ahead-of-time compiled executables as durable artifacts (tier 2 of
docs/compilation.md).

The persistent cache (compile/cache.py) makes a *recompile* cheap; this
module removes it entirely for the program sets that are knowable ahead
of time — the deployment stance of the Julia-to-TPU compiler (PAPERS.md
arXiv:1810.09868) and TVM (arXiv:1802.04799): compile the whole program
at build time, ship the executable. The serving engines are exactly
that shape (InferenceEngine's ≤ log2(max_batch)+1 padding buckets,
DecodeEngine's two-program contract) and the fused-update kernels are
one program per optimizer group.

`jit(...).lower().compile()` produces the executable;
`jax.experimental.serialize_executable` turns it into bytes; an
`ArtifactStore` directory holds the blobs plus a ``manifest.json``.

**Never a wrong-program load.** Every artifact is keyed by a content
fingerprint — sha256 over the jax/jaxlib versions, backend platform and
device kind, local device count, ``XLA_FLAGS``, the program-relevant
``MXTPU_*`` flags, and the caller's own key material (abstract avals,
dtypes, donation layout, hyperparameters). A load whose stored
fingerprint does not match the one recomputed *now* is refused and the
caller falls back to JIT; so is a missing entry, an unreadable blob, a
deserialization error, or an injected ``compile.load`` chaos fault.
Fallbacks are counted per reason in ``compile.aot.fallbacks``; they are
never errors.

**Trust model.** Deserialization runs `pickle` on the blob (jax's
serialization format carries pytree defs): an artifact store is trusted
input, like the model checkpoint it sits next to. Point
``MXTPU_AOT_STORE`` only at directories you own; the store never loads
from world-writable paths it created itself (same 0700 guard as the
cache tier).

GC (`tools/aot_build.py --gc`): version-mismatched entries (stale
jax/platform) and LRU overflow beyond a byte budget are evicted —
but never while a *live holder* (a process that registered via
`ArtifactStore.hold()`, liveness proven by the device-lease identity
record: pid + starttime + boot_id) has the store open.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import socket
import subprocess
import sys
import threading
import time

import numpy as np

from ..base import MXNetError, getenv
from ..observability import registry as _obs
from ..resilience.atomic import atomic_write
from ..resilience.chaos import (InjectedFailure, InjectedFault,
                                chaos_point)

__all__ = ["ArtifactStore", "StoreHeld", "fingerprint",
           "global_key_material", "aval_signature", "export_jit",
           "LOADS", "FALLBACKS"]

LOADS = _obs.counter(
    "compile.aot.loads",
    "AOT executables deserialized from an ArtifactStore")
FALLBACKS = _obs.counter(
    "compile.aot.fallbacks",
    "AOT loads refused -> JIT fallback (label reason: missing / "
    "fingerprint / corrupt / chaos / dispatch / device)")
EXPORTS = _obs.counter(
    "compile.aot.exports",
    "executables compiled ahead of time and serialized into a store")

_MANIFEST = "manifest.json"
_HOLDERS = "holders"

# the env knobs that change generated programs: part of every
# fingerprint, so flipping one can never replay a stale executable
_KEYED_FLAGS = ("MXTPU_SERVE_DTYPE", "MXTPU_SERVE_DONATE",
                "MXTPU_NUMERICS", "MXTPU_BUCKET_MB")


class StoreHeld(MXNetError):
    """GC refused: a live process holds the artifact store open."""


def global_key_material():
    """The environment half of every fingerprint: anything that changes
    what XLA would generate for the same trace."""
    import jax
    import jaxlib
    devs = jax.local_devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "",
        "local_devices": len(devs),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "flags": {k: os.environ.get(k, "") for k in _KEYED_FLAGS},
    }


def _canon(obj):
    """Canonicalize arbitrary key material into JSON-stable primitives
    (tuples -> lists, dtypes -> str, sets sorted)."""
    if isinstance(obj, dict):
        return {str(k): _canon(v) for k, v in sorted(obj.items())}
    if isinstance(obj, (list, tuple)):
        return [_canon(v) for v in obj]
    if isinstance(obj, (set, frozenset)):
        return sorted(str(v) for v in obj)
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    if isinstance(obj, np.dtype):
        return str(obj)
    return repr(obj)


def fingerprint(extra):
    """sha256 hex over the canonical global + caller key material."""
    material = {"global": global_key_material(), "extra": _canon(extra)}
    blob = json.dumps(material, sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()


def aval_signature(tree):
    """A fingerprint-able signature of a pytree of arrays / ShapeDtype
    structs / scalars: nested (shape, dtype) pairs in structure
    order. None stays None (absent rng key)."""
    import jax
    def one(x):
        if x is None:
            return None
        shape = tuple(getattr(x, "shape", ()))
        dtype = getattr(x, "dtype", None)
        return [list(shape), str(np.dtype(dtype)) if dtype is not None
                else type(x).__name__]
    return _canon(jax.tree_util.tree_map(
        one, tree, is_leaf=lambda x: x is None))


def abstract(tree):
    """Concrete arrays -> ShapeDtypeStructs (lowering inputs), other
    leaves (None) untouched."""
    import jax

    def one(x):
        if x is None:
            return None
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(tuple(x.shape),
                                        np.dtype(x.dtype))
        return x
    return jax.tree_util.tree_map(one, tree,
                                  is_leaf=lambda x: x is None)


_fresh_lock = threading.Lock()


def compile_fresh(jitted, abstract_args):
    """`jitted.lower(*abstract_args).compile()` with the persistent
    compilation cache bypassed for the call. An executable that came
    OUT of the persistent cache references jit symbols registered in
    the process that loaded it — serializing one produces a blob a
    fresh process cannot resolve ("Symbols not found"). Export must
    always serialize a from-scratch compile, whatever the cache state
    (regression-tested in tests/test_compile.py).

    jax latches cache usage at first compile and ignores the
    `jax_enable_compilation_cache` flag afterwards, so the latched
    state is stashed and restored around the compile (under a lock:
    a concurrent compile on another thread would otherwise miss its
    cache reads — harmless but wasteful)."""
    with _fresh_lock:
        try:
            from jax._src import compilation_cache as _jcc
            saved = (_jcc._cache, _jcc._cache_used, _jcc._cache_checked)
            _jcc._cache, _jcc._cache_used, _jcc._cache_checked = \
                None, False, True
        except (ImportError, AttributeError):
            saved = None
            _jcc = None
        try:
            return jitted.lower(*abstract_args).compile()
        finally:
            if _jcc is not None and saved is not None:
                (_jcc._cache, _jcc._cache_used,
                 _jcc._cache_checked) = saved


def export_jit(store, name, jitted, abstract_args, extra_key):
    """Lower + compile `jitted` for `abstract_args` ahead of time and
    persist the executable under `name`. Returns (fingerprint, bytes
    written). Registration doubles as the observability capture point:
    the fresh Compiled's memory_analysis()/cost_analysis() feed the
    HBM ledger's per-program working sets and the goodput FLOP table
    (docs/observability.md "Memory ledger" / "Goodput & MFU")."""
    fp = fingerprint(extra_key)
    compiled = compile_fresh(jitted, abstract_args)
    record_analyses(name, compiled)
    nbytes = store.put(name, fp, compiled)
    return fp, nbytes


def record_analyses(name, compiled):
    """Best-effort memory/cost capture for a freshly compiled
    executable (shared by export_jit and the fused-step registration)."""
    try:
        from ..observability import goodput as _goodput
        from ..observability import memory as _memory
        _memory.record_program(name, compiled)
        _goodput.record_cost(name, compiled)
    except Exception:   # noqa: BLE001 — analysis must never break export
        pass


class ArtifactStore:
    """A directory of serialized XLA executables plus their manifest.

    Layout::

        <root>/manifest.json        {"version": 1, "entries": {name:
                                     {fingerprint, file, bytes, created,
                                      jax, platform}}}
        <root>/<fingerprint>.aot    pickled (serialized, in_tree,
                                    out_tree) from
                                    jax.experimental.serialize_executable
        <root>/holders/<pid>.json   live-holder records (GC refusal)

    Writers are release-time tools (`tools/aot_build.py`, an engine's
    `aot_export`); concurrent writers last-write-win on the manifest,
    which is fine for a build artifact. Readers (`get`) are lock-free.
    """

    def __init__(self, root, create=False):
        self.root = os.path.abspath(os.fspath(root))
        if create:
            os.makedirs(self.root, exist_ok=True)
        self._held = None

    def __repr__(self):
        return "ArtifactStore(%r)" % self.root

    # -- manifest ------------------------------------------------------
    def _manifest_path(self):
        return os.path.join(self.root, _MANIFEST)

    def manifest(self):
        """The parsed manifest, or an empty one when absent/corrupt
        (a torn manifest must degrade to JIT, not crash the loader)."""
        try:
            with open(self._manifest_path()) as f:
                m = json.load(f)
        except (OSError, ValueError):
            return {"version": 1, "entries": {}}
        if not isinstance(m, dict) or not isinstance(
                m.get("entries"), dict):
            return {"version": 1, "entries": {}}
        return m

    def entries(self):
        return self.manifest()["entries"]

    def _write_manifest(self, manifest):
        with atomic_write(self._manifest_path(), "w") as f:
            f.write(json.dumps(manifest, sort_keys=True, indent=1))

    # -- write side ----------------------------------------------------
    def put(self, name, fp, compiled):
        """Serialize `compiled` (a jax.stages.Compiled) under `name`
        with fingerprint `fp`. Returns bytes written."""
        from jax.experimental import serialize_executable as _se
        serialized, in_tree, out_tree = _se.serialize(compiled)
        # jax 0.9 loads onto ALL of the backend's devices unless told
        # which ones the program was compiled for
        device_ids = [d.id for d in
                      compiled.runtime_executable().local_devices()]
        payload = pickle.dumps(
            {"fingerprint": fp, "name": str(name), "devices": device_ids,
             "payload": (serialized, in_tree, out_tree)},
            protocol=pickle.HIGHEST_PROTOCOL)
        os.makedirs(self.root, exist_ok=True)
        blob = "%s.aot" % fp
        with atomic_write(os.path.join(self.root, blob), "wb") as f:
            f.write(payload)
        manifest = self.manifest()
        manifest["entries"][str(name)] = {
            "fingerprint": fp, "file": blob, "bytes": len(payload),
            "created": time.time(),
            "jax": global_key_material()["jax"],
            "platform": global_key_material()["platform"],
        }
        self._write_manifest(manifest)
        EXPORTS.inc()
        return len(payload)

    # -- read side -----------------------------------------------------
    def _fallback(self, name, reason):
        # fallbacks are silent by design (the JIT path covers them);
        # MXTPU_AOT_DEBUG=1 surfaces the swallowed cause when
        # diagnosing why a store refuses to load
        if os.environ.get("MXTPU_AOT_DEBUG"):
            import traceback
            traceback.print_exc()
        FALLBACKS.inc(reason=reason)
        return None

    def get(self, name, fp):
        """Load the executable stored under `name` iff its fingerprint
        matches `fp` exactly. Returns the loaded callable or None —
        every failure mode (absent, mismatched, torn, injected chaos)
        is a counted JIT fallback, never an error."""
        try:
            chaos_point("compile.load")
            entry = self.entries().get(str(name))
            if entry is None:
                return self._fallback(name, "missing")
            if entry.get("fingerprint") != fp:
                return self._fallback(name, "fingerprint")
            blob = os.path.join(self.root, entry.get("file", ""))
            with open(blob, "rb") as f:
                payload = pickle.load(f)
            if payload.get("fingerprint") != fp:
                return self._fallback(name, "fingerprint")
            serialized, in_tree, out_tree = payload["payload"]
            import jax
            from jax.experimental import serialize_executable as _se
            by_id = {d.id: d for d in jax.devices()}
            loaded = _se.deserialize_and_load(
                serialized, in_tree, out_tree,
                execution_devices=[by_id[i] for i in payload["devices"]])
            # LRU recency for gc: reads bump the blob's mtime
            try:
                os.utime(blob, None)
            except OSError:
                pass
            LOADS.inc()
            return loaded
        except (InjectedFault, InjectedFailure):
            # the compile.load chaos site (docs/fault_tolerance.md):
            # an injected artifact-read fault degrades to JIT exactly
            # like a real one — proven by tools/chaos_run.py
            return self._fallback(name, "chaos")
        except Exception:   # noqa: BLE001 — any failure = JIT fallback
            return self._fallback(name, "corrupt")

    def load_jit(self, name, extra_key):
        """`get` with the fingerprint computed from `extra_key` — the
        one-call loader engines use."""
        return self.get(name, fingerprint(extra_key))

    # -- export verification -------------------------------------------
    # XLA:CPU dedups jit object code in-process: when the same program
    # was previously obtained THROUGH the persistent cache, a later
    # compile's serialization references process-registered symbols
    # instead of embedding code — a blob only THIS process can load.
    # In-process deserialization masks that (the symbols resolve
    # locally), so the only honest check is a fresh interpreter.
    _VERIFY_SCRIPT = (
        "import json, pickle, sys\n"
        "from jax.experimental import serialize_executable as se\n"
        "out = {}\n"
        "for path in sys.argv[1:]:\n"
        "    try:\n"
        "        with open(path, 'rb') as f:\n"
        "            payload = pickle.load(f)\n"
        "        se.deserialize_and_load(*payload['payload'])\n"
        "        out[path] = True\n"
        "    except Exception:\n"
        "        out[path] = False\n"
        "print(json.dumps(out))\n")

    def verify_and_prune(self, names=None, timeout=600):
        """Prove each blob loads in a FRESH interpreter; drop the ones
        that don't (counted as fallback reason="unverified"). Returns
        {name: ok}. When verification itself is unavailable (no
        subprocess, timeout), blobs are kept and {} returned — the
        loader's own fallback still guards consumers."""
        entries = self.entries()
        names = [n for n in (entries if names is None else names)
                 if n in entries]
        paths = {}
        for n in names:
            paths.setdefault(
                os.path.join(self.root, entries[n]["file"]),
                []).append(n)
        if not paths:
            return {}
        try:
            r = subprocess.run(
                [sys.executable, "-c", self._VERIFY_SCRIPT,
                 *paths.keys()],
                capture_output=True, text=True, timeout=timeout)
            verdicts = json.loads(r.stdout.strip().splitlines()[-1])
        except Exception:  # noqa: BLE001 — verification unavailable
            return {}
        result = {}
        manifest = self.manifest()
        pruned = False
        for path, ns in paths.items():
            ok = bool(verdicts.get(path))
            for n in ns:
                result[n] = ok
            if not ok:
                for n in ns:
                    manifest["entries"].pop(n, None)
                FALLBACKS.inc(reason="unverified")
                try:
                    os.unlink(path)
                except OSError:
                    pass
                pruned = True
        if pruned:
            self._write_manifest(manifest)
        return result

    # -- holders (GC refusal) ------------------------------------------
    def _holders_dir(self):
        return os.path.join(self.root, _HOLDERS)

    def hold(self, what="aot"):
        """Register this process as a live reader: GC refuses to evict
        while the record's pid (verified by starttime + boot_id, the
        device-lease pid-reuse defense) is alive."""
        from ..resilience.lease import _boot_id, _proc_starttime
        pid = os.getpid()
        rec = {"pid": pid, "host": socket.gethostname(),
               "boot_id": _boot_id(),
               "starttime": _proc_starttime(pid),
               "what": str(what), "created": time.time(),
               "heartbeat": time.time()}
        os.makedirs(self._holders_dir(), exist_ok=True)
        try:
            with atomic_write(os.path.join(self._holders_dir(),
                                           "%d.json" % pid), "w") as f:
                f.write(json.dumps(rec, sort_keys=True))
            self._held = pid
        except OSError:
            pass
        return self

    def release(self):
        if self._held is None:
            return
        try:
            os.unlink(os.path.join(self._holders_dir(),
                                   "%d.json" % self._held))
        except OSError:
            pass
        self._held = None

    def live_holders(self):
        """Holder records whose process is provably or possibly alive
        (foreign-host records count as alive — same conservatism as
        kill_stale); dead records are reaped in passing."""
        from ..resilience.lease import _holder_alive
        out = []
        hd = self._holders_dir()
        try:
            names = os.listdir(hd)
        except OSError:
            return out
        for nm in names:
            path = os.path.join(hd, nm)
            try:
                with open(path) as f:
                    rec = json.load(f)
            except (OSError, ValueError):
                rec = None
            if isinstance(rec, dict) and _holder_alive(rec):
                out.append(rec)
            else:
                try:
                    os.unlink(path)     # dead holder: clear in passing
                except OSError:
                    pass
        return out

    # -- gc ------------------------------------------------------------
    def gc(self, max_bytes=None, dry_run=False):
        """Evict version-mismatched entries (stale jax/platform can
        never load — their fingerprint check would refuse them) and,
        past `max_bytes`, the least-recently-used blobs. Raises
        `StoreHeld` when a live holder has the store open (the
        kill_stale refusal contract: recovery blocked is an explicit
        outcome, not a silent skip)."""
        holders = self.live_holders()
        if holders and not dry_run:
            raise StoreHeld(
                "artifact store %s is held by %d live process(es) "
                "(e.g. pid %s on %s) — refusing GC; stop the holders "
                "or wait for release" %
                (self.root, len(holders), holders[0].get("pid"),
                 holders[0].get("host")))
        gkm = global_key_material()
        manifest = self.manifest()
        entries = manifest["entries"]
        report = {"dir": self.root, "entries": len(entries),
                  "evicted": 0, "evicted_bytes": 0,
                  "dry_run": bool(dry_run), "holders": len(holders)}

        def _drop(name, entry, reason):
            if not dry_run:
                try:
                    os.unlink(os.path.join(self.root,
                                           entry.get("file", "")))
                except OSError:
                    pass
                entries.pop(name, None)
                _obs.counter("compile.cache.evictions").inc(
                    reason=reason)
            report["evicted"] += 1
            report["evicted_bytes"] += int(entry.get("bytes", 0))

        for name, entry in list(entries.items()):
            if entry.get("jax") != gkm["jax"] or \
                    entry.get("platform") != gkm["platform"]:
                _drop(name, entry, "mismatch")
                continue
            blob = os.path.join(self.root, entry.get("file", ""))
            if not os.path.isfile(blob):
                _drop(name, entry, "corrupt")
        if max_bytes is not None:
            def mtime(entry):
                try:
                    return os.lstat(os.path.join(
                        self.root, entry.get("file", ""))).st_mtime
                except OSError:
                    return 0.0
            total = sum(int(e.get("bytes", 0))
                        for e in entries.values())
            for name, entry in sorted(entries.items(),
                                      key=lambda kv: mtime(kv[1])):
                if total <= max_bytes:
                    break
                total -= int(entry.get("bytes", 0))
                _drop(name, entry, "lru")
        if not dry_run:
            self._write_manifest(manifest)
        report["entries_after"] = len(entries)
        report["bytes_after"] = sum(int(e.get("bytes", 0))
                                    for e in entries.values())
        return report


_store_lock = threading.Lock()
_store_cache = {"path": None, "store": None}


def default_store():
    """The process-wide store named by ``MXTPU_AOT_STORE``, or None.
    Re-resolved when the env var changes (tests); one dict read on the
    steady path."""
    path = os.environ.get("MXTPU_AOT_STORE") or None
    with _store_lock:
        if path != _store_cache["path"]:
            _store_cache["path"] = path
            _store_cache["store"] = ArtifactStore(path) if path else None
        return _store_cache["store"]


def export_enabled():
    """True when ``MXTPU_AOT_EXPORT=1``: a JIT path that misses its
    artifact compiles ahead of time and captures the executable into
    the default store — how `tools/aot_build.py` harvests program sets
    that only exist once real shapes flow (fused-update groups)."""
    return getenv("MXTPU_AOT_EXPORT", False)
