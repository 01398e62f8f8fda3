"""CachedOp: trace-once, replay-many graph execution.

Reference: src/imperative/cached_op.{h,cc} (Forward :834, Backward :1046) —
the backend of Gluon hybridize(). The reference re-plans memory and bulks
engine ops; here the whole graph is ONE jax.jit computation, compiled per
(mode, input-shape signature) and cached — jit *is* CachedOp on TPU.

Autograd integration: under autograd.record() the forward call registers a
tape node whose pullback is a separately jit-compiled backward computation
(rematerialized: it recomputes the forward inside the same XLA program,
trading FLOPs for memory exactly like MXNET_BACKWARD_DO_MIRROR).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from .base import MXNetError
from .graph import build_graph_fn, collect_vars
from .ndarray import NDArray
from .observability import registry as _obs
from .observability.trace import trace_span
from . import autograd
from . import random as _random

__all__ = ["CachedOp"]

# jit-wrapper builds per (op, mode, direction). Each build retraces the
# graph and usually triggers an XLA backend compile — the per-compile
# truth (count + seconds, including per-shape recompiles inside one
# wrapper) is xla.compile.* via the jax.monitoring listener
# (observability/telemetry.py); this counter attributes WHICH CachedOp
# keeps rebuilding.
_JIT_BUILDS = _obs.counter("cachedop.jit.builds",
                           "jit wrapper constructions by CachedOp")


class _GraphOpStub:
    """Minimal op-like object for tape nodes created by CachedOp."""
    needs_rng = False

    def __init__(self, name):
        self.name = name


class CachedOp:
    def __init__(self, sym, flags=()):
        self._symbol = sym
        self._flags = dict(flags) if not isinstance(flags, dict) else flags
        arg_nodes, aux_nodes = collect_vars(sym._entries)
        self._arg_names = [n.name for n in arg_nodes]
        self._aux_names = [n.name for n in aux_nodes]
        # call convention: inputs in list_inputs() order = args then aux
        self._input_names = self._arg_names + self._aux_names
        self._fwd_jits = {}
        self._bwd_jits = {}
        self._stub = _GraphOpStub("cached_op_%s" % (sym.name or "graph"))
        # the compiled programs' names: `jit_cachedop_fwd_<symbol>` in a
        # device trace and in the program table (compile/programs.py)
        self._program = re.sub(r"[^A-Za-z0-9_]", "_", sym.name or "graph")

    @property
    def input_names(self):
        return list(self._input_names)

    @property
    def symbol(self):
        """The traced graph this op replays — the freeze surface
        serving.InferenceEngine.from_block builds its forward-only
        program from (same entries, so engine outputs match the
        hybridized block bit-for-bit)."""
        return self._symbol

    def _fwd(self, mode):
        if mode not in self._fwd_jits:
            _JIT_BUILDS.inc(op=self._stub.name, mode=mode, direction="fwd")
            from .compile.cache import enable_cache
            enable_cache()   # flag check after the first build
            fn, _, _, needs_rng = build_graph_fn(self._symbol._entries, mode)
            fn.__name__ = "cachedop_fwd_" + self._program
            self._fwd_jits[mode] = (jax.jit(fn), needs_rng)
        return self._fwd_jits[mode]

    def _bwd(self, mode):
        if mode not in self._bwd_jits:
            _JIT_BUILDS.inc(op=self._stub.name, mode=mode, direction="bwd")
            fn, _, _, _ = build_graph_fn(self._symbol._entries, mode)

            def bwd(args, aux, key, cots):
                def f(g):
                    outs, _ = fn(g, aux, key)
                    return outs

                _, vjp_fn = jax.vjp(f, args)
                return vjp_fn(list(cots))[0]

            bwd.__name__ = "cachedop_bwd_" + self._program
            # nothing is donated: weights and aux must outlive the
            # call, and a cotangent can alias a user-visible .grad
            # buffer (an intermediate output with attach_grad)
            self._bwd_jits[mode] = jax.jit(bwd)
        return self._bwd_jits[mode]

    def __call__(self, *inputs):
        with trace_span("frontend.forward"):
            return self._call(inputs)

    def _call(self, inputs):
        if len(inputs) != len(self._input_names):
            raise MXNetError(
                "CachedOp: expected %d inputs (%s), got %d"
                % (len(self._input_names), self._input_names, len(inputs)))
        n_args = len(self._arg_names)
        args = {n: x._data for n, x in zip(self._arg_names, inputs[:n_args])}
        aux = {n: x._data for n, x in
               zip(self._aux_names, inputs[n_args:])}
        is_train = autograd.is_training()
        mode = "train" if is_train else "predict"
        fwd, needs_rng = self._fwd(mode)
        key = _random.next_key() if needs_rng else None
        outs, auxup = fwd(args, aux, key)
        # write back mutated aux states (BatchNorm moving stats)
        if auxup:
            for name, val in auxup.items():
                idx = n_args + self._aux_names.index(name)
                inputs[idx]._data = val
        ctx = inputs[0]._ctx if inputs else None
        outputs = [NDArray(o, ctx) for o in outs]

        if autograd.is_recording():
            bwd_jit = self._bwd(mode)
            arg_inputs = list(inputs[:n_args])

            def vjp_fn(cots, _args=args, _aux=aux, _key=key):
                grads = bwd_jit(_args, _aux, _key, cots)
                return tuple(grads[n] for n in self._arg_names)

            autograd._record(self._stub, arg_inputs, outputs,
                             tuple(o._data for o in outputs), vjp_fn)
        return outputs
