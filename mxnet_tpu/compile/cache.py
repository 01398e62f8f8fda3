"""Persistent XLA compilation cache (tier 1 of docs/compilation.md).

Every process used to pay full XLA compilation on boot — and PR 8/PR 9
made restarts *routine* (gang relaunches, divergence rollbacks), so
compile time became the dominant term in measured downtime. This module
wires JAX's persistent compilation cache through the framework's own
init paths (Context first device query, CachedOp jit builds, serving
engine freezes, fused-update kernels), so a compiled program outlives
the process that compiled it: the next boot pays a disk read, not a
compile.

Default ON. Resolution order for the cache directory:

1. ``JAX_COMPILATION_CACHE_DIR`` (jax's own env knob) — respected
   verbatim when the operator set it;
2. ``MXTPU_COMPILE_CACHE`` — a path, or ``0`` to disable;
3. ``MXTPU_XLA_CACHE`` — bench.py's pre-existing spelling, same
   semantics (the two tools share one artifact universe);
4. the default ``<repo>/.jax_cache`` — one fixed directory beside the
   package (computed from this file's own path, never from a temporary
   name, pid, uid or time: the path is part of jax's cache key, so a
   directory that moves never hits). bench.py and tests/conftest.py
   ask `resolve_cache_dir` instead of spelling it again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it into
its own config; `enable_cache` then sets no directory in code.

Size bound: ``MXTPU_COMPILE_CACHE_MAX_BYTES`` (default 1 GiB) is handed
to jax's own LRU eviction; `gc_cache_dir` is the offline mirror
(`tools/aot_build.py --gc`) that also scrubs unreadable/empty entries —
corrupt-entry tolerance on the write side comes from jax's atomic
tempfile+rename (the `resilience.atomic` idiom), and on the read side
from ``jax_raise_persistent_cache_errors=False``: a torn entry logs a
warning and recompiles, it never takes the process down.

Metrics: ``compile.cache.{hits,misses}`` count jax's cache events,
``compile.cache.bytes`` gauges the directory size at `cache_stats()`
time, ``compile.cache.evictions`` counts `gc_cache_dir` removals.
"""
from __future__ import annotations

import os
import threading

from ..base import getenv
from ..observability import registry as _obs

__all__ = ["resolve_cache_dir", "enable_cache", "cache_enabled",
           "cache_stats", "gc_cache_dir"]

HITS = _obs.counter("compile.cache.hits",
                    "persistent-compilation-cache hits (jax events)")
MISSES = _obs.counter("compile.cache.misses",
                      "persistent-compilation-cache misses (jax events)")
BYTES = _obs.gauge("compile.cache.bytes",
                   "persistent-compilation-cache directory size")
EVICTIONS = _obs.counter("compile.cache.evictions",
                         "cache entries removed by gc_cache_dir "
                         "(label reason: lru / mismatch / corrupt)")

_lock = threading.Lock()
_state = {"enabled": None, "dir": None, "listener": False,
          "guarded": False}

_DISABLED = ("", "0", "false", "False")


def default_cache_dir():
    """The one fixed default: ``.jax_cache`` in the checkout that holds
    this package (bench children, tests and framework processes reuse
    each other's compiles)."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def resolve_cache_dir(environ=None):
    """The persistent-cache directory this process should use, or None
    when disabled (module docstring has the resolution order)."""
    env = os.environ if environ is None else environ
    explicit = env.get("JAX_COMPILATION_CACHE_DIR")
    if explicit:
        return explicit
    for var in ("MXTPU_COMPILE_CACHE", "MXTPU_XLA_CACHE"):
        val = env.get(var)
        if val is not None:
            return None if val in _DISABLED else val
    return default_cache_dir()


def _on_cache_event(name, **kwargs):
    if name == "/jax/compilation_cache/cache_hits":
        HITS.inc()
    elif name == "/jax/compilation_cache/cache_misses":
        MISSES.inc()


def _install_multidevice_guard():
    """Exclude MULTI-DEVICE programs from the CPU persistent cache.

    jaxlib's CPU client can segfault (observed: pxla __call__ SIGSEGV /
    `Check failed: buffer_info.buffer.IsAvailable()`) when it executes
    a cache-DESERIALIZED executable that spans devices — e.g. a
    donated 8-way pjit train step dispatched right after an orbax
    restore (tests/test_trainer_checkpoint.py is the reproducer).
    Single-device programs deserialize reliably and dominate both
    serving and the test suite, so the guard turns cache READS into
    misses when `num_replicas * num_partitions > 1` on the cpu
    platform (writes stay: the risk is executing a deserialized
    executable, not writing one; jax's LRU bounds the space). The hook
    is jax 0.9's private `compiler._cache_read`; if it moves, the
    AttributeError is the error to see."""
    from jax._src import compiler as _jc

    def _spans_devices(compile_options, backend):
        if backend.platform != "cpu":
            return False
        ebo = compile_options.executable_build_options
        return (ebo.num_replicas * ebo.num_partitions) > 1

    orig_read = _jc._cache_read

    def guarded_read(module_name, cache_key, compile_options, backend,
                     executable_devices):
        if _spans_devices(compile_options, backend):
            return None, None
        return orig_read(module_name, cache_key, compile_options,
                         backend, executable_devices)

    _jc._cache_read = guarded_read


def enable_cache(path=None):
    """Idempotently point jax's persistent compilation cache at the
    resolved directory (or `path`). Called from every compile entry
    point (Context backend init, CachedOp jit builds, serving engine
    freezes, fused-update kernel builds) — one flag check after the
    first call. Returns the active directory or None when disabled."""
    with _lock:
        if _state["enabled"] is not None and path is None:
            return _state["dir"]
        # the program table rides the same private compile path and is
        # wanted with or without a cache directory (compile/programs.py)
        from . import programs
        programs.install()
        target = path if path is not None else resolve_cache_dir()
        if target is None:
            _state["enabled"], _state["dir"] = False, None
            return None
        try:
            # jax skips (with a swallowed warning) writes into a missing
            # directory — create it up front so "enabled" means enabled
            os.makedirs(target, exist_ok=True)
        except OSError:
            _state["enabled"], _state["dir"] = False, None
            return None
        import jax
        if not _state["guarded"]:
            _install_multidevice_guard()
            _state["guarded"] = True
        try:
            if jax.config.jax_compilation_cache_dir:
                # JAX_COMPILATION_CACHE_DIR (or an earlier config) already
                # placed the cache: use, report and meter THAT directory
                # and set no other in code
                target = jax.config.jax_compilation_cache_dir
            else:
                jax.config.update("jax_compilation_cache_dir", target)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              getenv("MXTPU_COMPILE_CACHE_MIN_S", 0.0))
            # cache even one-liner programs: entry-size floors exist for
            # shared network filesystems, not a local artifact dir
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              -1)
            jax.config.update("jax_compilation_cache_max_size",
                              getenv("MXTPU_COMPILE_CACHE_MAX_BYTES",
                                     1 << 30))
            # a torn/corrupt entry must recompile, never raise
            jax.config.update("jax_raise_persistent_cache_errors", False)
        except Exception:   # ancient jax without the knobs: stay JIT
            _state["enabled"], _state["dir"] = False, None
            return None
        # jax latches cache initialization at the FIRST compile of the
        # process; anything that compiled during import (op registry
        # probes) latched it with no directory. Reset so the next
        # compile re-initializes against the configured dir.
        try:
            from jax._src import compilation_cache as _jcc
            if _jcc._cache is None:
                _jcc.reset_cache()
        except Exception:
            pass
        if not _state["listener"]:
            try:
                from jax import monitoring
                monitoring.register_event_listener(_on_cache_event)
                _state["listener"] = True
            except Exception:
                pass
        _state["enabled"], _state["dir"] = True, target
        return target


def cache_enabled():
    """True once `enable_cache` activated a directory this process."""
    return bool(_state["enabled"])


def _reset_for_tests():
    with _lock:
        _state["enabled"], _state["dir"] = None, None


def _dir_entries(path):
    """[(file_path, bytes, mtime)] for regular files under `path`
    (one level — jax's file cache is flat)."""
    out = []
    try:
        names = os.listdir(path)
    except OSError:
        return out
    for name in names:
        fp = os.path.join(path, name)
        try:
            st = os.lstat(fp)
        except OSError:
            continue
        if os.path.isfile(fp) and not os.path.islink(fp):
            out.append((fp, st.st_size, st.st_mtime))
    return out


def cache_stats(path=None):
    """Point-in-time snapshot: directory, entry count, bytes on disk,
    and the process-local hit/miss counters. Also refreshes the
    `compile.cache.bytes` gauge."""
    path = path or _state["dir"] or resolve_cache_dir()
    entries = _dir_entries(path) if path else []
    total = sum(b for _, b, _ in entries)
    if path:
        BYTES.set(total, dir=path)
    return {"dir": path, "entries": len(entries), "bytes": total,
            "hits": HITS.total(), "misses": MISSES.total()}


def gc_cache_dir(path, max_bytes=None, dry_run=False):
    """kill_stale-style offline GC for a raw persistent-cache
    directory: unlink empty/unreadable entries (corrupt husks from a
    torn writer), then evict least-recently-used entries until the
    directory fits `max_bytes` (None: scrub only). Returns a report
    dict; never raises on an unlinkable file (best effort, like the
    cache itself)."""
    entries = _dir_entries(path)
    report = {"dir": path, "entries": len(entries),
              "bytes": sum(b for _, b, _ in entries),
              "evicted": 0, "evicted_bytes": 0, "scrubbed": 0,
              "dry_run": bool(dry_run)}

    def _drop(fp, nbytes, reason):
        if not dry_run:
            try:
                os.unlink(fp)
            except OSError:
                return False
            EVICTIONS.inc(reason=reason)
        report["evicted"] += 1
        report["evicted_bytes"] += nbytes
        if reason == "corrupt":
            report["scrubbed"] += 1
        return True

    live = []
    for fp, nbytes, mtime in entries:
        if nbytes == 0:
            _drop(fp, nbytes, "corrupt")
        else:
            live.append((fp, nbytes, mtime))
    if max_bytes is not None:
        total = sum(b for _, b, _ in live)
        # oldest-mtime first: jax touches entries on read, so mtime
        # order IS recency order
        for fp, nbytes, _ in sorted(live, key=lambda e: e[2]):
            if total <= max_bytes:
                break
            if _drop(fp, nbytes, "lru"):
                total -= nbytes
    report["bytes_after"] = report["bytes"] - report["evicted_bytes"]
    return report
