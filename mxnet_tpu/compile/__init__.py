"""Compilation artifact subsystem: compiled programs as durable
artifacts instead of per-process ephemera (docs/compilation.md).

Four pieces:

- `cache` — JAX's persistent compilation cache wired through every
  framework compile entry point (Context backend init, CachedOp jit
  builds, serving engine freezes, fused-update kernels). Default on;
  a recompile after restart becomes a disk read.
- `programs` — the table of what compiled or loaded, by module name,
  with the map from each HLO instruction to the scope that owns it:
  what lets a device trace's `fusion.12` be laid to a layer.
- `aot` — ahead-of-time `jit(...).lower().compile()` executables,
  serialized into an `ArtifactStore` and loaded in a fresh process
  before first dispatch, keyed by a content fingerprint that falls
  back to JIT on any mismatch — never a wrong-program load.
- `coldstart` — process boot → first useful dispatch as a first-class
  metric: telemetry records for `tools/telemetry_report.py`, a budget
  for `tools/perf_gate.py --max-cold-start-s`, and per-rank gang
  records that let `GangSupervisor.report()` split restart downtime
  into relaunch vs recompile.
"""
from . import cache
from . import programs
from . import aot
from . import coldstart
from .cache import (enable_cache, cache_enabled, cache_stats,
                    resolve_cache_dir, gc_cache_dir)
from .aot import (ArtifactStore, StoreHeld, fingerprint,
                  aval_signature, export_jit, default_store)
from .coldstart import mark_ready, process_start_time

__all__ = ["cache", "programs", "aot", "coldstart", "enable_cache", "cache_enabled",
           "cache_stats", "resolve_cache_dir", "gc_cache_dir",
           "ArtifactStore", "StoreHeld", "fingerprint",
           "aval_signature", "export_jit", "default_store",
           "mark_ready", "process_start_time"]
