"""The program table: what compiled, when, under which name, and which
instruction of it belongs to which scope (docs/observability.md
"Program table").

A device trace names an operation by its HLO instruction (`fusion.12`)
and a program by its module (`jit_sharded_step`); it carries no scope.
The owner of an instruction — the `op_name` XLA keeps in its metadata,
`jit(sharded_step)/transpose(jvp(mx.Convolution.conv0))/...` — is known
only to the process that compiled or loaded the program. This module
keeps it: one hook around `jax._src.compiler.compile_or_get_cached`, the
one function both the cold compile and the persistent-cache load return
through (`compile/cache.py` rides the same private module), parses the
optimized HLO's text once into `{instruction: op_name}` and drops the
text. Only a program traced under one of the framework's scopes
(`scope()`) is read at all; nothing runs for a program that JAX's
in-memory cache already holds, and nothing is compiled twice.

Per program, keyed by module name: builds, persistent-cache hits and
misses, seconds (and, of them, the seconds the table itself took to
read the program), and one map for each distinct build (programs that
share a name keep a map each; an instruction they disagree on has no
owner). Each build is one `compile` span in the trace ring, under the
step that caused it if a step's context is current, and one count of
``compile.programs{name, outcome}``. A program whose map holds no `mx.`
scope is flagged `scoped: false`: it was built from no graph, or it was
served from a cache written before the scopes existed.

The hook is fail-safe as the cache's guard is: if JAX's private path
has moved, the table stays empty, one line on standard error says so,
and no compile fails.
"""
from __future__ import annotations

import sys
import threading
import time

import jax

from ..observability import registry as _obs
from ..observability.trace import trace_span

__all__ = ["scope", "note_scoped", "install", "snapshot", "owners",
           "parse_owners", "reset"]

BUILDS = _obs.counter("compile.programs",
                      "program builds by module name and outcome "
                      "(hit / miss of the persistent cache, or uncached)")

_SCOPE = "mx."

_lock = threading.Lock()
_table = {}                      # module name -> _Program
_state = {"installed": False, "warned": False}
_tls = threading.local()


class _Program:
    __slots__ = ("builds", "hits", "misses", "seconds", "read_seconds",
                 "maps")

    def __init__(self):
        self.builds = self.hits = self.misses = 0
        self.seconds = self.read_seconds = 0.0
        self.maps = []           # one {instruction: op_name} a distinct build

    def owners(self):
        """The maps merged; None where two builds disagree."""
        if len(self.maps) == 1:
            return self.maps[0]
        merged = {}
        for m in self.maps:
            for instr, op in m.items():
                if merged.setdefault(instr, op) != op:
                    merged[instr] = None
        return merged

    def scoped(self):
        return any(_SCOPE in op for m in self.maps for op in m.values())


def scope(name):
    """`jax.named_scope(name)` for the framework's own `mx.` scopes,
    noted on the tracing thread: the programs built there from now on
    are read until one is found to carry a scope. A program traced with
    no scope (an eager op's, a user's own jit) is counted and timed but
    not read, which is most of what keeping the table would cost."""
    note_scoped()
    return jax.named_scope(name)


def note_scoped():
    """Note on the tracing thread, as `scope` does, a program that
    carries the scopes without entering one: a backward program that
    replays what its forward's trace recorded (cached_op.py)."""
    _tls.scoped = True


def parse_owners(hlo_text):
    """`{instruction name: op_name}` of every instruction of an HLO
    module's text that carries one. A fusion carries its root's, and
    the instructions inside a fused computation, which no trace shows
    on their own, are left out."""
    out, seen, fused = {}, {}, False
    for line in hlo_text.splitlines():
        if not line.startswith(" "):         # a computation opens or ends
            fused = line.lstrip("%").startswith("fused_computation")
            continue
        at = -1 if fused else line.find('op_name="')
        head, eq, _ = line.partition(" = ") if at >= 0 else ("", "", "")
        if not eq:
            continue
        op = line[at + 9:line.index('"', at + 9)]
        name = head.strip()
        if name.startswith("ROOT "):
            name = name[5:]
        out[name.lstrip("%")] = seen.setdefault(op, op)  # one string a scope
    return out


def _on_cache_event(name, **_kwargs):
    if name == "/jax/compilation_cache/cache_hits":
        _tls.outcome = "hit"
    elif name == "/jax/compilation_cache/cache_misses":
        _tls.outcome = "miss"


def _warn(what):
    if not _state["warned"]:
        _state["warned"] = True
        print("[mxnet_tpu] program table: %s; the table stays empty or "
              "partial, compiles are not affected" % what, file=sys.stderr)


def _record(name, executable, seconds, outcome):
    t0 = time.perf_counter()
    owners = {}
    if getattr(_tls, "scoped", False):
        modules = executable.hlo_modules()
        if modules:
            owners = parse_owners(modules[0].to_string())
        if any(_SCOPE in op for op in owners.values()):
            _tls.scoped = False      # the program the scopes were for
    read = time.perf_counter() - t0
    with _lock:
        prog = _table.get(name)
        if prog is None:
            prog = _table[name] = _Program()
        prog.builds += 1
        prog.seconds += seconds
        prog.read_seconds += read
        if outcome == "hit":
            prog.hits += 1
        elif outcome == "miss":
            prog.misses += 1
        if owners and owners not in prog.maps:
            prog.maps.append(owners)
    BUILDS.inc(name=name, outcome=outcome)


def install():
    """Wrap JAX's compile entry once. Returns True when the hook is in."""
    with _lock:
        if _state["installed"]:
            return True
        try:
            from jax import monitoring
            from jax._src import compiler as _jc
            from jax._src.lib.mlir import ir
            orig = _jc.compile_or_get_cached
        except (ImportError, AttributeError) as err:
            _warn("JAX's compile path is not where it was (%s)" % err)
            return False

        def compile_or_get_cached(backend, computation, *args, **kwargs):
            try:
                name = ir.StringAttr(
                    computation.operation.attributes["sym_name"]).value
            except Exception:   # noqa: BLE001 — naming must not fail a compile
                name = "unnamed"
            _tls.outcome = "uncached"
            t0 = time.perf_counter()
            with trace_span("compile", program=name):
                executable = orig(backend, computation, *args, **kwargs)
            try:
                _record(name, executable, time.perf_counter() - t0,
                        _tls.outcome)
            except Exception as err:   # noqa: BLE001 — the table is best-effort
                _warn("could not read a compiled program (%s: %s)"
                      % (type(err).__name__, err))
            return executable

        monitoring.register_event_listener(_on_cache_event)
        _jc.compile_or_get_cached = compile_or_get_cached
        _state["installed"] = True
        return True


def owners(name):
    """`{instruction: op_name}` of the programs built under module
    `name` (None for an instruction its builds disagree on), or None
    when no such program was built here."""
    with _lock:
        prog = _table.get(name)
        return None if prog is None else prog.owners()


def snapshot():
    """The table without its maps: the `programs` section of `/debugz`."""
    with _lock:
        return {name: {"builds": p.builds, "cache_hits": p.hits,
                       "cache_misses": p.misses,
                       "seconds": round(p.seconds, 6),
                       "read_seconds": round(p.read_seconds, 6),
                       "instructions": sum(len(m) for m in p.maps),
                       "scoped": p.scoped()}
                for name, p in sorted(_table.items())}


def reset():
    """Forget every program (tests)."""
    with _lock:
        _table.clear()
