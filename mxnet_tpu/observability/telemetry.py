"""Per-step training telemetry: StepTimer + JSONL streaming.

Two layers, both fed from the hot paths but with different defaults:

1. Counters (always on): XLA compile stalls (count + seconds, via
   `jax.monitoring` duration events), kvstore wire bytes, input batch
   waits, and step-time histograms accumulate in the process-wide
   registry regardless of any env var — one lock + dict add per
   step/batch.
2. Step records (off by default): when ``MXTPU_TELEMETRY=<path>`` is
   set, every training step appends ONE JSON line to <path> with wall
   time, data-wait, optimizer/allreduce time, compile events, and
   kvstore bytes — the deltas of the counters above between step
   boundaries. `tools/telemetry_report.py` summarizes the file
   (p50/p95/p99 step time, samples/sec, compile stall, bytes moved).

The env var is re-read per step (a dict lookup), so tests and
long-running jobs can toggle streaming without reimporting.
"""
from __future__ import annotations

import json
import os
import threading
import time
import warnings

from . import trace as _trace
from .registry import REGISTRY, counter, gauge, histogram

__all__ = ["StepTimer", "stream_path", "stream_enabled", "emit",
           "close_stream", "COMPILE_COUNT", "COMPILE_SECONDS",
           "mark_producer_thread", "is_producer_thread"]

# -- registry wiring (shared with the instrumented call sites) ----------
COMPILE_COUNT = counter("xla.compile.count",
                        "XLA backend compiles observed via jax.monitoring")
COMPILE_SECONDS = counter("xla.compile.seconds",
                          "Seconds spent in XLA backend compilation")
STEP_SECONDS = histogram("train.step.seconds",
                         "Training step wall time (end-to-end)")
_KV_BYTE_COUNTERS = (counter("kvstore.push.bytes"),
                     counter("kvstore.pull.bytes"),
                     counter("kvstore.allreduce.bytes"))
_BATCH_WAIT = histogram("io.batch_wait.seconds",
                        "Time the consumer blocked waiting for a batch")


def _install_compile_listener():
    """Count XLA compiles + seconds process-wide. `jax.monitoring`
    invokes duration listeners for `/jax/core/compile/
    backend_compile_duration` on every real backend compile (cache hits
    don't fire it), which is exactly the recompile signal cached_op/jit
    can't see from the Python side."""
    try:
        from jax import monitoring as _jmon
    except Exception:  # ancient jax: counters just stay at zero
        return

    def _on_duration(name, secs, **kwargs):
        if name.endswith("backend_compile_duration"):
            COMPILE_COUNT.inc()
            COMPILE_SECONDS.inc(secs)

    try:
        _jmon.register_event_duration_secs_listener(_on_duration)
    except Exception:
        pass


_install_compile_listener()


# -- producer/consumer attribution --------------------------------------
_thread_role = threading.local()


def mark_producer_thread():
    """Tag the calling thread as an input-pipeline *producer* (prefetch
    workers). Batch pulls on producer threads are background assembly
    overlapped with compute, not a consumer stall, so instrumented
    iterators route them to `io.batch_assemble.seconds` instead of the
    data-wait histogram StepTimer charges to the training step."""
    _thread_role.producer = True


def is_producer_thread():
    return getattr(_thread_role, "producer", False)


# -- JSONL stream -------------------------------------------------------
_stream_lock = threading.Lock()
_stream = {"path": None, "file": None, "warned": False}


def stream_path():
    """The MXTPU_TELEMETRY destination, or None (the one flag check the
    instrumented sites pay when streaming is off)."""
    return os.environ.get("MXTPU_TELEMETRY") or None


def stream_enabled():
    return stream_path() is not None


def _stream_file():
    path = stream_path()
    if path is None:
        return None
    with _stream_lock:
        if _stream["path"] != path or _stream["file"] is None:
            if _stream["file"] is not None:
                try:
                    _stream["file"].close()
                except OSError:
                    pass
                # drop the stale handle NOW: if the open below fails, a
                # later revert to the old path must reopen, not write
                # into a closed file
                _stream["path"], _stream["file"] = None, None
            try:
                f = open(path, "a", buffering=1)
            except OSError as err:
                if not _stream["warned"]:
                    _stream["warned"] = True
                    warnings.warn("MXTPU_TELEMETRY=%s not writable (%s); "
                                  "step records disabled" % (path, err),
                                  RuntimeWarning)
                return None
            _stream["path"], _stream["file"] = path, f
        return _stream["file"]


def emit(record):
    """Append one JSON object to the MXTPU_TELEMETRY stream (no-op when
    unset). Never raises: telemetry must not take down training."""
    f = _stream_file()
    if f is None:
        return False
    line = json.dumps(record, sort_keys=True)
    try:
        with _stream_lock:
            f.write(line + "\n")
    except (OSError, ValueError):
        return False
    return True


def close_stream():
    """Close the JSONL stream (tests; also safe mid-run — the next emit
    reopens in append mode)."""
    with _stream_lock:
        if _stream["file"] is not None:
            try:
                _stream["file"].close()
            except OSError:
                pass
        _stream["path"], _stream["file"] = None, None
        _stream["warned"] = False


# -- StepTimer ----------------------------------------------------------
def _counter_total(name):
    """Total of a registry counter that may not be registered yet (the
    kvstore.bucket.* family registers on first dist-kvstore import, with
    its own bucket bounds — looked up by name so this module never
    races that registration)."""
    m = REGISTRY.get(name)
    return m.total() if m is not None and hasattr(m, "total") else 0


def _hist_totals(name):
    """(sum, count) of a maybe-unregistered registry histogram."""
    m = REGISTRY.get(name)
    if m is None or not hasattr(m, "total_sum"):
        return 0.0, 0
    return m.total_sum(), m.total_count()


def _counters_snapshot():
    fill_sum, _ = _hist_totals("kvstore.bucket.fill_ratio")
    pack_s, _ = _hist_totals("kvstore.bucket.pack.seconds")
    unpack_s, _ = _hist_totals("kvstore.bucket.unpack.seconds")
    ar_s, _ = _hist_totals("kvstore.allreduce.seconds")
    fused_pack_s, _ = _hist_totals("optimizer.fused.pack.seconds")
    fused_update_s, _ = _hist_totals("optimizer.fused.update.seconds")
    return {
        "compile_count": COMPILE_COUNT.total(),
        "compile_seconds": COMPILE_SECONDS.total(),
        # persistent-compilation-cache hits/misses (compile/cache.py):
        # on a warm cache, compile_count reads 0 and the hits say why
        "compile_cache_hits": _counter_total("compile.cache.hits"),
        "compile_cache_misses": _counter_total("compile.cache.misses"),
        "kvstore_bytes": sum(c.total() for c in _KV_BYTE_COUNTERS),
        "data_wait": _BATCH_WAIT.total_sum(),
        "allreduce_calls": _counter_total("kvstore.allreduce.calls"),
        "allreduce_bytes": _counter_total("kvstore.allreduce.bytes"),
        "allreduce_seconds": ar_s,
        "bucket_count": _counter_total("kvstore.bucket.count"),
        "bucket_fill_sum": fill_sum,
        "bucket_pack_seconds": pack_s,
        "bucket_unpack_seconds": unpack_s,
        # optimizer-update family (optimizer.py / parallel/fused_update):
        # dispatches/step drops to the fused group count when fusion is
        # on — tools/telemetry_report.py's optimizer section
        "update_dispatches": _counter_total("optimizer.update.dispatches"),
        "fused_groups": _counter_total("optimizer.fused.groups"),
        "fused_pack_seconds": fused_pack_s,
        "fused_update_seconds": fused_update_s,
        # numerics guard (resilience/numerics.py): per-step deltas let
        # tools/perf_gate.py fail a silently-skipping run
        "skipped_steps": _counter_total("numerics.skipped_steps"),
        "anomalies": _counter_total("numerics.anomalies"),
        # fused train step (parallel/fused_step.py): device programs
        # dispatched for exchange+update — 1/step on the fused path,
        # O(buckets)+O(groups) staged; perf_gate budgets it via
        # --max-dispatches-per-step
        "step_dispatches": _counter_total("train.step.dispatches"),
        # goodput plane (observability/goodput.py): model FLOPs charged
        # by dispatches this window — the per-step MFU numerator
        "step_flops": _counter_total("goodput.flops"),
    }


class _Phase:
    """Accumulates one named phase's wall time into its StepTimer and
    records it once, as a trace span child of the step's trace root
    (the merged per-step timeline in tools/trace_report.py; the ring's
    sink mirrors it into the chrome trace while the profiler runs)."""

    __slots__ = ("_timer", "_name", "_t0", "_tspan")

    def __init__(self, timer, name):
        self._timer = timer
        self._name = name
        self._tspan = _trace.trace_span(name)

    def __enter__(self):
        self._tspan.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._tspan.__exit__(*exc)
        phases = self._timer._phases
        phases[self._name] = phases.get(self._name, 0.0) + dt
        return False


class StepTimer:
    """Per-step telemetry for a training loop.

    Usage (gluon Trainer.step / module fit loop)::

        timer = StepTimer("gluon.trainer")
        ...
        timer.begin_step()
        with timer.phase("allreduce"):  ...
        with timer.phase("optimizer"):  ...
        timer.end_step(batch_size=bs)

    `end_step` emits one JSONL record (when MXTPU_TELEMETRY is set)
    whose step_time spans end-of-previous-step -> now — i.e. the FULL
    iteration including forward/backward and data wait that happened
    outside begin/end — and whose compile/kvstore/data-wait fields are
    the deltas of the process-wide counters across that window. The
    first step's step_time starts at its begin_step() (there is no
    earlier boundary), so warm-up compile time is attributed to step 0's
    compile_seconds, not to a bogus interval.

    Not thread-safe per instance (one training loop = one timer);
    distinct loops get distinct timers and tag records via `source`.
    """

    def __init__(self, source="train"):
        self.source = source
        self.step = 0
        self._phases = {}
        self._last_end = None
        self._snap = None
        self._root = _trace.StepRoot(source)

    def begin_step(self):
        # a failed step never reached end_step: drop its phase times so
        # the aborted attempt doesn't inflate the next record (its
        # trace root stays open: this attempt goes on under it)
        self._phases = {}
        if self._last_end is None:
            self._last_end = time.perf_counter()
            self._snap = _counters_snapshot()
        # live introspection plane: training ranks bind /metricsz +
        # /debugz when MXTPU_METRICS_PORT is set (one env read here)
        from . import httpz as _httpz
        _httpz.maybe_start()
        # per-step trace root (docs/observability.md "Distributed
        # tracing"): trace id hashed from (gang dir, source, step) so
        # all ranks share it; opened where the previous end_step
        # returned, so the root covers the FULL iteration and the
        # spans of forward, backward and input record under it
        self._root.begin(self.step)

    def phase(self, name):
        return _Phase(self, name)

    def end_step(self, batch_size=None, close_root=True, **extra):
        """Close the current step: observe the step-time histogram and
        (streaming on) emit the JSONL record. Returns the record dict
        (also when streaming is off — callers/tests can inspect it).
        A caller that holds a trace span open around this call passes
        `close_root=False` and calls `close_root()` once its span has
        closed: the iteration's root closes last."""
        now = time.perf_counter()
        if self._last_end is None:  # end without begin: degenerate step
            self._last_end = now
            self._snap = _counters_snapshot()
        step_time = now - self._last_end
        self._last_end = now
        snap = _counters_snapshot()
        prev, self._snap = self._snap, snap
        record = {
            "ts": time.time(),
            "source": self.source,
            "step": self.step,
            "step_time": step_time,
            "data_wait": max(0.0, snap["data_wait"] - prev["data_wait"]),
            "compile_count": snap["compile_count"] - prev["compile_count"],
            "compile_seconds": max(
                0.0, snap["compile_seconds"] - prev["compile_seconds"]),
            "kvstore_bytes": snap["kvstore_bytes"] - prev["kvstore_bytes"],
        }
        # allreduce/bucket deltas (tools/telemetry_report.py's
        # allreduce section); zero-valued fields are omitted so
        # single-process step records stay the size they were
        for field in ("compile_cache_hits", "compile_cache_misses",
                      "allreduce_calls", "allreduce_bytes",
                      "allreduce_seconds", "bucket_count",
                      "bucket_fill_sum", "bucket_pack_seconds",
                      "bucket_unpack_seconds", "update_dispatches",
                      "fused_groups", "fused_pack_seconds",
                      "fused_update_seconds", "skipped_steps",
                      "anomalies", "step_dispatches", "step_flops"):
            delta = snap[field] - prev.get(field, 0)
            if delta:
                record[field] = delta
        # per-step MFU (observability/goodput.py): derived from the
        # FLOP delta over this step's peak-FLOP envelope; absent when
        # no program charged the goodput counter (pre-goodput streams
        # keep their shape)
        if record.get("step_flops") and step_time > 0:
            from . import goodput as _goodput
            mfu = _goodput.mfu_value(record["step_flops"], step_time,
                                     source=self.source)
            if mfu is not None:
                record["mfu"] = mfu
        # current loss scale rides along once a GradScaler armed it —
        # a gauge, not a delta (absent on unscaled runs)
        scale_gauge = REGISTRY.get("numerics.loss_scale")
        if scale_gauge is not None and scale_gauge.labelsets():
            record["loss_scale"] = scale_gauge.get()
        for name, secs in self._phases.items():
            record[name + "_time"] = secs
        self._phases = {}
        if batch_size:
            record["batch_size"] = batch_size
            if step_time > 0:
                record["samples_per_sec"] = batch_size / step_time
        record.update(extra)
        self.step += 1
        trace_id = self._root.trace_id
        if trace_id is not None:
            record["trace_id"] = trace_id
        # worst-K step times retain their trace ids as exemplars: a
        # step-time p99 breach names a concrete traceable step
        STEP_SECONDS.observe(step_time, exemplar=trace_id,
                             source=self.source)
        if stream_path() is not None:
            emit(record)
        if close_root:
            self.close_root()
        return record

    def close_root(self):
        """Close this iteration's trace root and open the next one's."""
        self._root.end(self.step)
