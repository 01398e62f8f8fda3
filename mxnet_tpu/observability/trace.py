"""Distributed tracing: W3C trace contexts, cross-thread propagation,
rank-tagged span shards (docs/observability.md "Distributed tracing").

The PR-2 `span()` API records host spans into the profiler's chrome
trace — but only while the profiler runs, only with thread-local
parentage, and only inside one process. This module is the *request-
and step-scoped* tracing plane on top:

- `TraceContext` is a W3C ``traceparent`` identity (trace id, parent
  span id, sampled flag). The gateway accepts/emits the header; every
  serving request and every training step carries a context;
- spans survive **thread-pool hops**: the submitting thread captures
  its context (`capture()` / the request object's `trace` slot), the
  executing thread restores it (`attached(ctx)`), so a span opened on
  a batcher/gateway worker thread parents to the submitting request
  instead of becoming an orphaned root;
- every finished span lands in a bounded in-memory ring (``/debugz``)
  and — when a shard directory is configured — as one JSONL line in a
  **rank-tagged shard** (``trace_rank_<r>.jsonl``), which
  `tools/trace_report.py` merges into one Perfetto/chrome trace with
  per-rank clock alignment;
- **step traces are deterministic across ranks**: the trace id is a
  hash of (gang dir, source, step), so rank 0's allreduce span and
  rank 1's land in the SAME merged trace without any wire protocol;
- `device_annotation()` wraps device dispatch in a
  ``jax.profiler.TraceAnnotation`` named by the trace id, so host
  spans line up with the XLA profiler timeline;
- a **garbage collection** of generation 1 or 2 is a `gc` span under
  whatever span was open on the thread it stopped (generation 0 is
  summed into the next step root's `gc0` / `gc0_ms`), and every step
  root carries the OS's account of its thread (`cpu_ms`, `nvcsw`,
  `nivcsw`, `majflt`, `minflt`): what a stalled step was doing is in
  the ring (docs/observability.md "Step spans").

Env knobs (resolved once a step or request, where a root context is
made — `step_trace_context`, `TraceContext.new`/`from_traceparent`, a
`trace_span` given its `ctx=`, `enabled()` — so tests/long jobs can
toggle live and a recorded span reads no environment at all; except
MXTPU_TRACE_BUFFER, which sizes the ring once at import):

  MXTPU_TRACE          0 disables the whole plane (contexts, spans,
                       shards all become no-ops)                  (1)
  MXTPU_TRACE_SAMPLE   fraction of new roots that record spans
                       (step traces hash-sample deterministically
                       so all ranks agree)                      (1.0)
  MXTPU_TRACE_DIR      span shard directory; falls back to
                       MXTPU_GANG_DIR (supervised ranks), else
                       spans stay in-memory only             (unset)
  MXTPU_TRACE_BUFFER   in-memory ring size, in spans           (4096)

Sampling gates *recording*, not identity: an unsampled request still
carries (and echoes) its trace id — it just writes no spans.
"""
from __future__ import annotations

import contextlib
import gc
import hashlib
import itertools
import json
import os
import random
import threading
import time
from collections import deque

try:
    import resource as _resource
except ImportError:             # not a Unix: steps carry no OS account
    _resource = None

from .. import profiler as _prof
from ..base import getenv
from .registry import counter as _counter

__all__ = ["TraceContext", "trace_span", "record_span", "current",
           "capture", "attached", "detach", "device_annotation",
           "enabled", "sample_rate", "shard_dir", "shard_path",
           "ring_spans", "reset_ring", "trace_stats",
           "step_trace_context", "StepRoot", "current_rank"]

# wall/perf clock pair captured at import: every span's `ts` is wall
# time derived from perf_counter stamps (monotonic within the process),
# so one process's spans never interleave wrongly even if NTP steps
# the wall clock mid-run
_CLOCK_WALL = time.time()
_CLOCK_PERF = time.perf_counter()


# A step's context reads eight names, most of them unset, and
# `os.environ.get` pays an encode and a raised KeyError for each
# (1.5 us apiece where this was measured): look them up in the mapping
# `os.environ` itself keeps, under keys encoded once. An interpreter
# without that mapping takes the public way.
_ENV_DATA = getattr(os.environ, "_data", None)
_ENV_KEYS = {}


def _env(name):
    if _ENV_DATA is None:
        return os.environ.get(name)
    key = _ENV_KEYS.get(name)
    if key is None:
        key = _ENV_KEYS[name] = os.environ.encodekey(name)
    val = _ENV_DATA.get(key)
    return None if val is None else os.environ.decodevalue(val)


def _env2(name):
    """MXTPU_<x>, else the reference's spelling MXNET_<x> (`getenv`)."""
    val = _env("MXTPU_" + name)
    return val if val is not None else _env("MXNET_" + name)


class _Config:
    """The plane's switches as the environment last gave them."""

    __slots__ = ("on", "rate", "shard", "rank", "token")

    def __init__(self):
        on, rate = _env2("TRACE"), _env2("TRACE_SAMPLE")
        self.on = on not in ("0", "false", "False", "")
        self.rate = 1.0 if rate is None else float(rate)
        r = _env("JAX_PROCESS_ID") or _env("DMLC_WORKER_ID")
        try:
            self.rank = int(r)
        except (TypeError, ValueError):
            self.rank = 0
        gang = _env("MXTPU_GANG_DIR")
        d = _env("MXTPU_TRACE_DIR") or gang or None
        self.shard = (os.path.join(d, "trace_rank_%d.jsonl" % self.rank)
                      if d else None)
        self.token = gang or ("pid:%d" % _PID)


_PID = os.getpid()
# span and trace ids: a per-process random prefix and a counter (unique
# across ranks without a system call a span)
_ID_PREFIX = random.getrandbits(32)
_ID_COUNTER = itertools.count(1)


def _after_fork():
    global _PID, _ID_PREFIX, _cfg
    _PID = os.getpid()
    _ID_PREFIX = random.getrandbits(32)
    _cfg = _Config()


os.register_at_fork(after_in_child=_after_fork)
_cfg = _Config()


def _refresh():
    """Re-read the environment: once a step or request, never a span."""
    global _cfg
    _cfg = _Config()
    return _cfg


def enabled():
    return _refresh().on


def sample_rate():
    return _refresh().rate


def current_rank():
    """This process's gang/dist rank (0 outside a gang) — the shard
    tag and the `rank` attr on every span."""
    return _refresh().rank


def _new_id(nbytes):
    """8 (a span id) or 16 (a trace id) bytes of hex: the process
    prefix, then the counter."""
    n = next(_ID_COUNTER)
    if nbytes == 8:                                  # a span id
        return "%08x%08x" % (_ID_PREFIX, n & 0xffffffff)
    return "%08x%08x%016x" % (_ID_PREFIX, _PID & 0xffffffff, n)


def _sampled(rate):
    return rate >= 1.0 or (rate > 0.0 and random.random() < rate)


class TraceContext:
    """One W3C trace identity: ``trace_id`` (32 hex), ``span_id`` (16
    hex — the *current parent*: the remote caller's span for an
    incoming ``traceparent``, the innermost local span while a
    `trace_span` is active, or None for a fresh root), ``sampled``."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id, span_id=None, sampled=True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = bool(sampled)

    @classmethod
    def new(cls, sampled=None):
        """Fresh root context. `sampled` defaults to a coin flip at
        MXTPU_TRACE_SAMPLE (identity is always created — an unsampled
        request still echoes its trace id, it just records nothing)."""
        if sampled is None:
            sampled = _sampled(_refresh().rate)
        return cls(_new_id(16), None, sampled)

    @classmethod
    def from_traceparent(cls, header):
        """Parse a ``traceparent`` header (version 00). Returns None on
        anything malformed — a bad header means a fresh root, never an
        error surfaced to the client."""
        _refresh()
        if not header or not isinstance(header, str):
            return None
        parts = header.strip().lower().split("-")
        if len(parts) < 4:
            return None
        version, trace_id, span_id, flags = parts[:4]
        if len(version) != 2 or version == "ff":
            return None
        if len(trace_id) != 32 or trace_id == "0" * 32:
            return None
        if len(span_id) != 16 or span_id == "0" * 16:
            return None
        try:
            int(trace_id, 16), int(span_id, 16)
            sampled = bool(int(flags, 16) & 0x01)
        except ValueError:
            return None
        return cls(trace_id, span_id, sampled)

    def to_traceparent(self):
        # a root context has no span id yet; the spec forbids the
        # all-zero parent id, so an unsampled root (which never opens
        # a span) echoes a synthetic one — the trace id is the part
        # the caller correlates on
        return "00-%s-%s-%02x" % (self.trace_id,
                                  self.span_id or _new_id(8),
                                  0x01 if self.sampled else 0x00)

    def __repr__(self):
        return ("TraceContext(%s, span=%s, sampled=%s)"
                % (self.trace_id, self.span_id, self.sampled))


def step_trace_context(source, step):
    """Deterministic per-step context: the trace id hashes (gang dir |
    pid, source, step), so every rank of a supervised gang lands its
    step-S spans in the SAME trace id, and `tools/trace_report.py` can
    merge shards into one per-step timeline with zero coordination.
    The sampling verdict hashes too — ranks always agree."""
    cfg = _refresh()
    if not cfg.on:
        return None
    digest = hashlib.sha256(
        ("mxtpu-step:%s:%s:%d" % (cfg.token, source, int(step)))
        .encode()).hexdigest()
    rate = cfg.rate
    sampled = rate >= 1.0 or (
        rate > 0.0 and int(digest[32:40], 16) / float(0xffffffff) < rate)
    return TraceContext(digest[:32], None, sampled)


# -- thread-local context -----------------------------------------------
_tls = threading.local()


def current():
    """The calling thread's active `TraceContext`, or None."""
    return getattr(_tls, "ctx", None)


def _set_current(ctx):
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    return prev


def capture():
    """Snapshot the calling thread's trace context for a thread-pool
    handoff: stash the return value at submit time, `attached()` it on
    the executing thread. (The request objects in `serving/` carry
    this in their `trace` slot automatically.)"""
    return current()


@contextlib.contextmanager
def attached(ctx):
    """Restore a captured context on the executing thread: spans opened
    inside parent to the *submitting* request instead of orphaning."""
    prev = _set_current(ctx)
    try:
        yield ctx
    finally:
        _tls.ctx = prev


def detach():
    """Drop the calling thread's context: a training loop's root stays
    current on its thread between steps (`StepRoot`), so a thread that
    goes on to other work after its last step calls this."""
    _tls.ctx = None


# -- span sink: in-memory ring + rank-tagged shard file -----------------
_ring_lock = threading.Lock()
_ring = deque(maxlen=int(getenv("MXTPU_TRACE_BUFFER", 4096)))
_shard_lock = threading.Lock()
_shard = {"path": None, "file": None, "warned": False}


def shard_dir():
    """Where span shards go: MXTPU_TRACE_DIR, else the gang directory
    (supervised training ranks shard next to their heartbeats), else
    None (ring buffer only)."""
    return (os.environ.get("MXTPU_TRACE_DIR")
            or os.environ.get("MXTPU_GANG_DIR") or None)


def shard_path():
    return _refresh().shard


def _shard_file(path):
    """Open (or re-resolve) this process's shard at `path`, writing one
    `clock` record at open so the merger can map this rank's
    perf-derived timestamps and estimate cross-rank offsets."""
    with _shard_lock:
        if _shard["path"] != path or _shard["file"] is None:
            if _shard["file"] is not None:
                try:
                    _shard["file"].close()
                except OSError:
                    pass
                _shard["path"], _shard["file"] = None, None
            try:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                f = open(path, "a", buffering=1)
            except OSError as err:
                if not _shard["warned"]:
                    _shard["warned"] = True
                    import warnings
                    warnings.warn(
                        "trace shard %s not writable (%s); spans stay "
                        "in-memory" % (path, err), RuntimeWarning)
                return None
            _shard["path"], _shard["file"] = path, f
            clock = {"source": "trace", "event": "clock",
                     "step_time": 0.0, "ts": time.time(),
                     "perf": time.perf_counter(),
                     "rank": _cfg.rank, "pid": _PID}
            try:
                f.write(json.dumps(clock, sort_keys=True) + "\n")
            except (OSError, ValueError):
                pass
        return _shard["file"]


def close_shard():
    """Close the shard file (tests; the next span reopens in append)."""
    with _shard_lock:
        if _shard["file"] is not None:
            try:
                _shard["file"].close()
            except OSError:
                pass
        _shard["path"], _shard["file"] = None, None
        _shard["warned"] = False


def ring_spans(trace_id=None, limit=None):
    """Recent finished spans from the in-memory ring (newest last),
    optionally filtered to one trace id — the `/debugz` surface."""
    with _ring_lock:
        spans = list(_ring)
    if trace_id is not None:
        spans = [s for s in spans if s.get("trace_id") == trace_id]
    return spans[-limit:] if limit else spans


def reset_ring():
    with _ring_lock:
        _ring.clear()


def trace_stats():
    """Point-in-time plane state for `/debugz`."""
    with _ring_lock:
        spans = list(_ring)
    traces = {}
    for s in spans:
        traces.setdefault(s.get("trace_id"), 0)
        traces[s["trace_id"]] += 1
    cfg = _refresh()
    return {
        "enabled": cfg.on,
        "sample_rate": cfg.rate,
        "shard": cfg.shard,
        "ring_spans": len(spans),
        "ring_traces": len(traces),
        "recent_trace_ids": list(traces)[-8:],
    }


#: record_span default: inherit the parent from ctx.span_id (pass
#: None explicitly to force a root span)
_INHERIT = object()


# -- garbage collections as spans ----------------------------------------
# The collector calls `_on_gc` at the start and the stop of every
# collection, on the thread whose allocation set it off, while that
# thread may hold any lock (the ring's, the shard's, a registry's:
# `record_span`'s json.dumps allocates). So the callback takes no lock
# and does one thing, queue a tuple; `_drain_gc` makes the spans at the
# next `record_span` or `StepRoot.end`. Collections never overlap, so
# one start stamp serves every thread.
_gc_t0 = [0.0]
_gc_pending = deque(maxlen=4096)  # (gen, t0, t1, collected, tid, ctx)
_gc0_pending = deque(maxlen=4096)  # seconds of each generation-0 one
_stepping = [False]     # a step root has opened: the loop has begun
GC_COLLECTIONS = _counter(
    "host.gc.collections",
    "Garbage collections while the trace plane is on (label generation)")
GC_SECONDS = _counter(
    "host.gc.seconds",
    "Seconds in garbage collection while the trace plane is on (label "
    "generation)")


def _on_gc(phase, info):
    if not _cfg.on:
        return
    if phase == "start":
        _gc_t0[0] = time.perf_counter()
    elif _gc_t0[0]:
        _gc_pending.append((info["generation"], _gc_t0[0],
                            time.perf_counter(), info["collected"],
                            threading.get_ident(),
                            getattr(_tls, "ctx", None)))
        _gc_t0[0] = 0.0


gc.callbacks.append(_on_gc)


def _drain_gc():
    """The queued collections: generations 1 and 2 each a `gc` span
    under the context open on its thread when it ran (so self time and
    idle-by-span credit it to the span it interrupted), or a root of
    its own where none was (once a step root has opened: before, a
    collection with no context is the imports' or the set-up's, counted
    and not kept); generation 0 summed for the next step root. Counted
    in `host.gc.collections` / `.seconds`."""
    counts = {}
    while _gc_pending:
        try:
            gen, t0, t1, collected, tid, ctx = _gc_pending.popleft()
        except IndexError:          # another thread drained the last
            break
        n = counts.setdefault(gen, [0, 0.0])
        n[0] += 1
        n[1] += t1 - t0
        if gen == 0:
            _gc0_pending.append(t1 - t0)
            continue
        parent = None if ctx is None else ctx.span_id
        if ctx is None:
            if not _stepping[0]:
                continue            # no loop yet: imports, set-up
            ctx = TraceContext(_new_id(16), None, True)
        _record("gc", ctx, t0, t1, parent, None, tid,
                {"generation": gen, "collected": collected})
    for gen, (n, secs) in counts.items():
        GC_COLLECTIONS.inc(n, generation=str(gen))
        GC_SECONDS.inc(secs, generation=str(gen))


def _gc0_totals():
    """(count, ms) of the generation-0 collections drained so far."""
    n, secs = 0, 0.0
    while _gc0_pending:
        try:
            secs += _gc0_pending.popleft()
        except IndexError:
            break
        n += 1
    return n, 1e3 * secs


# -- the OS's account of a step ------------------------------------------
_RUSAGE_WHO = (getattr(_resource, "RUSAGE_THREAD", None)
               or getattr(_resource, "RUSAGE_SELF", None))


def _rusage():
    """This thread's resource usage (the process's where the OS keeps
    none a thread), or None: one system call."""
    return None if _resource is None else _resource.getrusage(_RUSAGE_WHO)


def _os_account(a, b):
    """The step root's attrs from two readings: CPU ms of the thread,
    voluntary switches (it blocked: the runtime, a lock, the GIL),
    involuntary ones (the OS took its CPU), major and minor faults."""
    if a is None or b is None:
        return {}
    return {"cpu_ms": 1e3 * (b.ru_utime - a.ru_utime
                             + b.ru_stime - a.ru_stime),
            "nvcsw": b.ru_nvcsw - a.ru_nvcsw,
            "nivcsw": b.ru_nivcsw - a.ru_nivcsw,
            "majflt": b.ru_majflt - a.ru_majflt,
            "minflt": b.ru_minflt - a.ru_minflt}


def record_span(name, ctx, t0, t1, parent_id=_INHERIT, span_id=None,
                **attrs):
    """Record one finished span (perf_counter stamps) into the ring +
    shard under `ctx`'s trace. `parent_id` defaults to ``ctx.span_id``
    (the submitting/enclosing span); pass None for an explicit root.
    Returns the span id (chain it as another record's `parent_id` for
    retroactive sub-spans — batch consumers reconstruct per-request
    queue/compute spans this way), or None when the context is
    absent/unsampled/disabled — recording is best-effort and never
    raises into the traced path. The record keeps the perf stamp
    (`t0`) beside the wall time derived from it (`ts`), so a reader
    can lay the span on any clock that ticks with `perf_counter`.
    Reads no environment: the switches are those of the last root.
    First turns the collections queued since the last record into
    spans (`_drain_gc`)."""
    if _gc_pending:
        _drain_gc()
    return _record(name, ctx, t0, t1, parent_id, span_id, None, attrs)


def _record(name, ctx, t0, t1, parent_id, span_id, tid, attrs):
    """`record_span` without the drain; `tid` None is the caller's
    thread."""
    cfg = _cfg
    if ctx is None or not ctx.sampled or not cfg.on:
        return None
    span_id = span_id or _new_id(8)
    rec = {"source": "trace", "event": "span", "name": name,
           "trace_id": ctx.trace_id, "span_id": span_id,
           "parent_id": ctx.span_id if parent_id is _INHERIT
           else parent_id,
           "t0": t0, "ts": _CLOCK_WALL + (t0 - _CLOCK_PERF),
           "step_time": t1 - t0 if t1 > t0 else 0.0,
           "rank": cfg.rank, "pid": _PID,
           "tid": (threading.get_ident() if tid is None else tid) & 0xffff}
    if attrs:
        rec.update({k: v for k, v in attrs.items() if v is not None})
    with _ring_lock:
        _ring.append(rec)
    if cfg.shard is not None:
        f = _shard_file(cfg.shard)
        if f is not None:
            try:
                with _shard_lock:
                    f.write(json.dumps(rec, sort_keys=True) + "\n")
            except (OSError, ValueError, TypeError):
                pass
    # mirror into the profiler's chrome-trace stream when it is
    # running, so host trace spans and eager-op rows share a timeline
    if _prof._running["on"]:
        _prof._record_event(name, t0, t1, cat="trace",
                            args={"trace_id": ctx.trace_id,
                                  "span_id": span_id})
    return span_id


class trace_span:
    """Context manager recording one span under the thread's (or an
    explicitly `ctx=`-passed) trace context. While active, the thread's
    current context points at this span, so nested `trace_span`s and
    queue submits parent correctly. A no-op (one attr read, no
    allocation beyond the object) when tracing is off, the context is
    absent, or the trace is unsampled."""

    __slots__ = ("name", "attrs", "ctx", "span_id", "_t0", "_prev",
                 "_parent", "_on", "_t0_override")

    def __init__(self, name, ctx=None, t0=None, **attrs):
        self.name = name
        self.attrs = attrs
        self.ctx = ctx
        self.span_id = None
        self._t0_override = t0

    def __enter__(self):
        if self.ctx is not None:
            parent, cfg = self.ctx, _refresh()    # a root: once a request
        else:
            parent, cfg = getattr(_tls, "ctx", None), _cfg
        self._on = (parent is not None and parent.sampled and cfg.on)
        if not self._on:
            # still make an explicitly-passed root context current, so
            # children opened inside inherit identity (for the echoed
            # trace id) even when unsampled
            if self.ctx is not None:
                self._prev = _set_current(self.ctx)
                self._parent = None
            else:
                self._prev, self._parent = False, None
            return self
        self.span_id = _new_id(8)
        self._parent = parent.span_id
        self.ctx = parent
        self._prev = _set_current(
            TraceContext(parent.trace_id, self.span_id, True))
        self._t0 = self._t0_override if self._t0_override is not None \
            else time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if not self._on:
            if self._prev is not False:
                _tls.ctx = self._prev
            return False
        t1 = time.perf_counter()
        _tls.ctx = self._prev
        attrs = self.attrs
        if exc_type is not None:
            attrs = dict(attrs, error=exc_type.__name__)
        # record with OUR span id (not a fresh one) so children that
        # captured the context while we were active resolve to a real
        # recorded span; self._parent is None for roots, which
        # record_span keeps as an explicit root (no inherit)
        record_span(self.name, self.ctx, self._t0, t1,
                    parent_id=self._parent, span_id=self.span_id,
                    **attrs)
        return False


class StepRoot:
    """The root spans of one training loop, one an iteration, under one
    rule for every trainer: the root of iteration n opens where the
    previous `step()` returned (the first at its own entry) and closes
    where this one returns, and its context stays current on the
    training thread in between. So what a script runs between two
    steps (the fence of the previous loss, the wait for a batch, the
    forward and backward of a Gluon loop) records under the iteration
    it belongs to, with that step's trace id. `begin` at the entry of
    `step()`, `end` at its return; a step that raised never reached
    `end`, and the next `begin` goes on under the same root.

    A recorded root carries what the thread's OS account says of the
    iteration (`_os_account`: one `getrusage` an iteration, the reading
    at `end` the next root's baseline) and the generation-0 collections
    drained since the last root (`gc0`, `gc0_ms`)."""

    __slots__ = ("source", "_ctx", "_child", "_t0", "_step", "_ru")

    def __init__(self, source):
        self.source = source
        self._ctx = self._child = None
        self._t0 = self._step = self._ru = None

    def _open(self, step, t0, ru=None):
        ctx = step_trace_context(self.source, step)
        self._ctx, self._t0, self._step = ctx, t0, step
        if ctx is not None and ctx.sampled:
            self._child = TraceContext(ctx.trace_id, _new_id(8), True)
            self._ru = ru if ru is not None else _rusage()
            _stepping[0] = True
        else:
            self._child = ctx       # identity without records, or None
            self._ru = None
        _tls.ctx = self._child

    @property
    def trace_id(self):
        """The open root's trace id, or None where it records nothing."""
        ctx = self._ctx
        return ctx.trace_id if ctx is not None and ctx.sampled else None

    def begin(self, step):
        """Make iteration `step`'s root current on this thread, opening
        it here if no earlier `end` did."""
        if self._ctx is None or self._step != step:
            self._open(step, time.perf_counter())
        else:
            _tls.ctx = self._child

    def end(self, next_step):
        """Close the open root and open iteration `next_step`'s where
        this one ends."""
        now = time.perf_counter()
        ctx, ru = self._ctx, None
        if ctx is not None and ctx.sampled:
            ru = _rusage()
            if _gc_pending:
                _drain_gc()
            gc0, gc0_ms = _gc0_totals()
            record_span("step", ctx, self._t0, now, parent_id=None,
                        span_id=self._child.span_id, step=self._step,
                        source=self.source, gc0=gc0, gc0_ms=gc0_ms,
                        **_os_account(self._ru, ru))
        self._open(next_step, now, ru)


def device_annotation(ctx=None, name=None):
    """A ``jax.profiler.TraceAnnotation`` naming the trace id, wrapped
    around device dispatch so the XLA profiler's device rows correlate
    with host spans (`name` defaults to ``trace:<id>``). Returns a
    null context when there is nothing to annotate."""
    ctx = ctx if ctx is not None else current()
    if ctx is None or not ctx.sampled or not _cfg.on:
        return contextlib.nullcontext()
    try:
        import jax
        return jax.profiler.TraceAnnotation(
            name or ("trace:%s" % ctx.trace_id))
    except Exception:   # noqa: BLE001 — tracing must never break dispatch
        return contextlib.nullcontext()
