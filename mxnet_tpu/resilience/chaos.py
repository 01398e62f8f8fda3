"""Seeded, env-driven fault injector (docs/fault_tolerance.md).

Spec grammar (MXTPU_CHAOS)::

    site:field=value,field=value[;site2:...]

    MXTPU_CHAOS="kvstore.push:p=0.1,kind=raise;io.read:p=0.05"

Fields per site:
  p      probability a draw trips the fault            (default 1.0)
  kind   raise  -> InjectedFault (a TransientError: retry-safe)
         fatal  -> InjectedFailure (never retried)
         sleep  -> time.sleep(secs) (exercises deadlines)
         hang   -> time.sleep(secs, default 3600) — the wedged-device
                   simulation: a dispatch that never returns on its
                   own. Only a watchdog deadline (or the chaos_run
                   reaper) bounds it; the serving resilience plane's
                   `engine.dispatch` / `serving.replica<k>.dispatch`
                   sites are its home
         kill   -> SIGKILL this process (the rank-death chaos mode —
                   no cleanup, no atexit: exactly what a preempted VM
                   or an OOM kill looks like to the gang)
         nan    -> poison one seeded element of the array at a
                   corrupt_point (numerics-guard skip proof)
         bitflip-> flip one seeded bit at a corrupt_point (the silent
                   data corruption simulation)       (default raise)
  secs   sleep duration for kind=sleep                 (default 0.1)
  n      stop tripping after n faults                  (default unlimited)
  after  skip the first `after` draws                  (default 0)

A site name ending in ``*`` prefix-matches (``kvstore.*``). Draws are
deterministic: each site gets its own `random.Random` seeded from
MXTPU_CHAOS_SEED (default 0) and the site name, so a chaos run replays
bit-identically across processes and reruns.

Per-rank arming: a distributed worker merges
``MXTPU_CHAOS_RANK_<rank>`` (rank from JAX_PROCESS_ID /
DMLC_WORKER_ID) into the global spec, per-rank entries winning on a
site collision — the tools/chaos_run.py ``--kill-rank`` plumbing: one
env block reaches the whole gang but only the targeted rank arms the
extra sites. A GangSupervisor strips these variables from relaunched
generations (an injected incident happens once;
docs/fault_tolerance.md).

Injection sites wired through the runtime: `kvstore.push`, `dist.init`,
`checkpoint.save`, `io.read`, `worker.kill` (fires at every training
step boundary — `resilience.preempt.at_step_boundary` — so `kind=kill`
kills a rank mid-run), `engine.host_push`, `serving.infer`,
`serving.decode` (fires before every continuous-batching decode step;
kind=sleep stretches steps so deadline eviction can be exercised,
kind=raise fails every in-flight sequence), `engine.dispatch` (inside
every watchdog-guarded serving dispatch — forward batches, decode
prefill/step; kind=hang is the wedged-device drill the dispatch
watchdog bounds) plus its replica-addressed twins
`serving.replica<k>.dispatch` (fired by ModelServer worker `k` and its
canary probe, so a chaos run can wedge ONE replica of N —
tools/chaos_run.py ``--wedge-replica``), `gateway.admit` (on every
gateway admission attempt, before the priority queues — a tripped
fault is one 500 response, the gateway keeps serving), `lease.acquire`
(before a
`DeviceLease.acquire` touches the lease file), `device.init`
(before `HealthWatchdog.init_devices` probes the backend — kind=sleep
exercises the init deadline), `memory.oom` (inside every
`memory.oom_guard`-wrapped device dispatch — engine infer, decode
prefill/step, the fused train step; a tripped fault is converted to a
simulated RESOURCE_EXHAUSTED so the HBM-ledger forensics dump and the
typed `HBMExhausted` re-raise can be drilled without exhausting a real
chip — docs/observability.md "Memory ledger"), and the
array-corruption sites
`grad.post` / `weight.post` (`corrupt_point` in the fused update:
kind=nan / kind=bitflip mutate the packed flats — the numerics-guard
proof sites, docs/fault_tolerance.md "Training numerics guard"). A
`chaos_point(site)` call is free when no spec is configured (one dict
lookup).
"""
from __future__ import annotations

import os
import random
import signal
import threading
import time

from ..base import MXNetError, getenv
from .retry import TransientError
from . import metrics

__all__ = ["InjectedFault", "InjectedFailure", "parse_spec", "configure",
           "reset", "armed", "chaos_point", "corrupt_point", "trip_count"]


class InjectedFault(TransientError):
    """A chaos-injected *transient* fault (kind=raise): the retry layer
    is expected to absorb it."""


class InjectedFailure(MXNetError):
    """A chaos-injected *fatal* fault (kind=fatal): retry policies must
    give up immediately and surface it."""


_FIELDS = {"p": float, "secs": float, "n": int, "after": int, "kind": str}
_KINDS = ("raise", "fatal", "sleep", "hang", "kill", "nan", "bitflip")
# kinds that mutate an ARRAY at a corrupt_point instead of raising at a
# chaos_point: kind=nan poisons one element (caught by the numerics
# guard's in-graph isfinite check -> the skip path), kind=bitflip flips
# one seeded bit (the silent-data-corruption simulation: usually a
# finite-but-wrong value the isfinite check can NOT see, so only the
# divergence watchdog / SDC replay catch it)
_CORRUPT_KINDS = ("nan", "bitflip")

_KILL = object()      # decide() verdict sentinel for kind=kill
_CORRUPT = object()   # decide() verdict sentinel for corrupt kinds


def parse_spec(spec):
    """Parse a MXTPU_CHAOS string into {site: field-dict}. Unknown
    fields or kinds raise MXNetError naming the offender — a chaos run
    with a typo'd spec silently injecting nothing is itself a failure
    mode."""
    out = {}
    for part in filter(None, (p.strip() for p in (spec or "").split(";"))):
        site, _, rest = part.partition(":")
        site = site.strip()
        if not site:
            raise MXNetError("MXTPU_CHAOS entry %r lacks a site name"
                             % part)
        fields = {}
        for field in filter(None, (f.strip() for f in rest.split(","))):
            key, eq, val = field.partition("=")
            key = key.strip()
            if key not in _FIELDS or not eq:
                raise MXNetError(
                    "MXTPU_CHAOS site %r: unknown field %r (valid: %s)"
                    % (site, field, ", ".join(sorted(_FIELDS))))
            fields[key] = _FIELDS[key](val.strip())
        kind = fields.get("kind", "raise")
        if kind not in _KINDS:
            raise MXNetError("MXTPU_CHAOS site %r: unknown kind %r "
                             "(valid: %s)" % (site, kind,
                                              ", ".join(_KINDS)))
        out[site] = fields
    return out


class _Site:
    """One armed injection site: seeded RNG, trip accounting."""

    def __init__(self, name, fields, seed):
        self.name = name
        self.p = float(fields.get("p", 1.0))
        self.kind = fields.get("kind", "raise")
        # a hang is a sleep that never ends on its own: the default
        # dwarfs every deadline in the system, so only a watchdog (or
        # the chaos_run reaper) unwedges the caller
        self.secs = float(fields.get(
            "secs", 3600.0 if self.kind == "hang" else 0.1))
        self.n = fields.get("n")
        self.after = int(fields.get("after", 0))
        self.rng = random.Random("%s:%s" % (seed, name))
        self.draws = 0
        self.trips = 0

    def decide(self, at_site):
        """Advance the draw/trip accounting and return the verdict:
        None (no fault), a float (sleep that many seconds), or an
        exception instance to raise. Runs under the injector lock; the
        CALLER acts after releasing it, so a sleep fault never stalls
        other threads' chaos points on the lock."""
        self.draws += 1
        if self.draws <= self.after:
            return None
        if self.n is not None and self.trips >= self.n:
            return None
        if self.rng.random() >= self.p:
            return None
        self.trips += 1
        metrics.bump("chaos.injected.%s" % at_site)
        if self.kind in ("sleep", "hang"):
            return self.secs
        if self.kind == "kill":
            return _KILL
        if self.kind in _CORRUPT_KINDS:
            return _CORRUPT
        cls = InjectedFailure if self.kind == "fatal" else InjectedFault
        return cls("[chaos] injected %s fault at %r (trip %d, draw %d, "
                   "spec site %r)" % (self.kind, at_site, self.trips,
                                      self.draws, self.name))


_lock = threading.Lock()
# None => lazily (re)load from MXTPU_CHAOS at the next chaos_point
_state = {"exact": None, "prefix": []}


def _rank_spec():
    """The per-rank spec for this process, or "". A distributed worker
    arms MXTPU_CHAOS_RANK_<its rank> (rank from the standard
    rendezvous env) IN ADDITION to any global MXTPU_CHAOS, so a single
    env block can target one rank of a gang; same-site entries in the
    rank spec override the global ones (later entries win)."""
    rank = os.environ.get("JAX_PROCESS_ID") or \
        os.environ.get("DMLC_WORKER_ID")
    if rank is None:
        return ""
    try:
        rank = int(rank)
    except ValueError:
        return ""
    return os.environ.get("MXTPU_CHAOS_RANK_%d" % rank, "")


def configure(spec=None, seed=None):
    """Arm the injector programmatically (tests) or from the env
    (spec=None reads MXTPU_CHAOS merged with this rank's
    MXTPU_CHAOS_RANK_<r> — the per-rank entries win on a site
    collision, so a global spec can never silently mask a targeted
    rank kill). An empty spec disarms."""
    if spec is None:
        spec = ";".join(filter(None, [os.environ.get("MXTPU_CHAOS", ""),
                                      _rank_spec()]))
    if seed is None:
        seed = getenv("MXTPU_CHAOS_SEED", 0)
    parsed = parse_spec(spec)
    with _lock:
        _state["exact"] = {}
        _state["prefix"] = []
        for name, fields in parsed.items():
            site = _Site(name, fields, seed)
            if name.endswith("*"):
                _state["prefix"].append((name[:-1], site))
            else:
                _state["exact"][name] = site


def reset():
    """Disarm and forget; the next chaos_point re-reads the env."""
    with _lock:
        _state["exact"] = None
        _state["prefix"] = []


def _lookup(site):
    exact = _state["exact"]
    if exact is None:
        configure()
        exact = _state["exact"]
    sp = exact.get(site)
    if sp is not None:
        return sp
    for prefix, psite in _state["prefix"]:
        if site.startswith(prefix):
            return psite
    return None


def armed(site):
    """Whether a chaos spec arms `site` (one dict lookup). A caller that
    has moved a corruption site's array inside a compiled program asks
    this to decide whether the array must be brought out for the site
    to fire on."""
    return _lookup(site) is not None


def chaos_point(site):
    """Declare a named injection site. No-op (one dict lookup) unless a
    chaos spec arms this site; then a seeded draw may raise
    InjectedFault/InjectedFailure or sleep, per the spec."""
    sp = _lookup(site)
    if sp is None:
        return
    if sp.kind in _CORRUPT_KINDS:
        # corrupt kinds only fire at corrupt_point (they need an array
        # to mutate); a plain chaos_point must not burn their draws
        return
    with _lock:
        verdict = sp.decide(site)
    if verdict is None:
        return
    if verdict is _KILL:
        # the rank-death mode: no unwinding, no atexit, no flushing —
        # what a preempted VM or the OOM killer looks like to the gang
        os.kill(os.getpid(), signal.SIGKILL)
        return  # pragma: no cover — unreachable
    if isinstance(verdict, float):
        time.sleep(verdict)
        return
    raise verdict


def corrupt_point(site, array):
    """Declare a named ARRAY-corruption site (`grad.post` fires on each
    packed gradient flat entering the fused update, `weight.post` on
    each updated weight flat leaving it). Returns `array` unchanged —
    one dict lookup — unless the site is armed with a corrupt kind and
    the seeded draw trips; then a corrupted copy is returned:

    - ``kind=nan``: one seeded element set to NaN (the in-graph
      isfinite guard catches it -> skip-and-preserve);
    - ``kind=bitflip``: one seeded bit of one seeded element flipped
      (the SDC simulation: typically finite-but-wrong, invisible to
      isfinite — only divergence/replay machinery can catch it).

    The corruption is deterministic (element and bit come from the
    site's seeded RNG), so a chaos run replays bit-identically.
    Non-corrupt kinds armed on a corrupt site behave like chaos_point
    (raise/sleep/kill), so e.g. `grad.post:kind=fatal` still works."""
    sp = _lookup(site)
    if sp is None:
        return array
    if sp.kind not in _CORRUPT_KINDS:
        chaos_point(site)
        return array
    with _lock:
        verdict = sp.decide(site)
        if verdict is None:
            return array
        # draws under the lock so concurrent corrupt points stay
        # deterministic: element/bit picks are part of the site stream
        pick = sp.rng.random()
        bitpick = sp.rng.random()
    import numpy as _np
    host = _np.array(array, copy=True)
    flat = host.reshape(-1)
    idx = min(int(pick * flat.size), flat.size - 1) if flat.size else 0
    if flat.size == 0:
        return array
    if sp.kind == "nan":
        if _np.issubdtype(flat.dtype, _np.floating):
            flat[idx] = _np.nan
        else:   # integer buffers have no NaN: max value is the poison
            flat[idx] = _np.iinfo(flat.dtype).max
    else:   # bitflip
        view = flat.view(_np.uint8)
        nbits = 8 * flat.dtype.itemsize
        bit = min(int(bitpick * nbits), nbits - 1)
        byte = idx * flat.dtype.itemsize + bit // 8
        view[byte] ^= _np.uint8(1 << (bit % 8))
    try:
        import jax.numpy as _jnp
        return _jnp.asarray(host)
    except ImportError:       # host-array caller (tests)
        return host


def trip_count(site):
    """How many times `site` has actually tripped (for assertions and
    monitoring; also mirrored in metrics.counters)."""
    sp = _lookup(site)
    return 0 if sp is None else sp.trips
