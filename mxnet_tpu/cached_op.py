"""CachedOp: trace-once, replay-many graph execution.

Reference: src/imperative/cached_op.{h,cc} (Forward :834, Backward :1046) —
the backend of Gluon hybridize(). The reference re-plans memory and bulks
engine ops; here the whole graph is ONE jax.jit computation, compiled per
(mode, input-shape signature) and cached — jit *is* CachedOp on TPU.

Autograd integration: under autograd.record() the forward is a program of
its own that also returns the residuals of the graph's vjp, and the tape
node's pullback is a second program that reads them. What the recorded
forward keeps is one fixed rule (`save_products_and_sums`): the outputs of
the convolutions and matrix products, which the forward writes to HBM
anyway, and of the sums (a batch norm's statistics); the backward
recomputes the cheap passes between them (normalisation, activations,
residual adds) from those. Inputs that come back as residuals (weights,
gammas, betas, the data) are forwarded from the call's own arrays, and
the small residuals share one buffer, so that the launch returns few new
buffers. Outside record() the forward returns no residuals and is the
program it always was. To trade FLOPs for memory, mark layers with
`HybridBlock.remat_scope`: a marked group keeps only what enters and
leaves it (graph.build_graph_fn).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from .base import MXNetError
from .compile import programs as _programs
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
# what a recorded forward keeps for its backward, set when the forward is
# traced: the vjp's array leaves, the new output buffers among them (the
# rest are the call's own inputs, forwarded) and those buffers' bytes
_RES_LEAVES = _obs.gauge("cachedop.residuals.leaves",
                         "array leaves of a recorded forward's residuals")
_RES_OUTPUTS = _obs.gauge("cachedop.residuals.outputs",
                          "residuals a recorded forward returns as new "
                          "buffers (not forwarded inputs)")
_RES_BYTES = _obs.gauge("cachedop.residuals.bytes",
                        "bytes of a recorded forward's new residual buffers")

# what the recorded forward keeps: the outputs of the MXU's products, and
# of the sums (a batch norm's statistics), which are small and each cost a
# full pass over their input to recompute
_SAVED_PRIMITIVES = (jax.lax.conv_general_dilated_p, jax.lax.dot_general_p,
                     jax.lax.reduce_sum_p)
# residuals of at most this many elements share one buffer a dtype: each
# output buffer costs the launch host time, whatever its size
_PACK_LIMIT = 1 << 18


def save_products_and_sums(prim, *_, **__):
    """`jax.checkpoint` policy of the recorded forward: keep what the MXU
    and the sums computed, recompute everything else in the backward."""
    return prim in _SAVED_PRIMITIVES


class _Slot:
    """Where the backward finds one residual: its argument `arg`, whole,
    or `size` elements at `offset` of it, reshaped to `shape`."""

    __slots__ = ("arg", "offset", "size", "shape")

    def __init__(self, arg, offset=None, size=None, shape=None):
        self.arg, self.offset, self.size, self.shape = arg, offset, size, shape

    def read(self, arrays):
        a = arrays[self.arg]
        if self.offset is None:
            return a
        return a[self.offset:self.offset + self.size].reshape(self.shape)


class _Pullback:
    """What one trace of the recorded forward knows of its residuals: the
    vjp's tree, a `_Slot` for each array leaf (the leaves that are no
    arrays stand as they are), and `take`, which of the call's flat inputs
    followed by the program's new outputs the backward reads. jit's cache
    entry holds one per input signature; the backward takes it as a
    static argument, compared by identity, so a signature's backward
    compiles once."""

    __slots__ = ("tree", "leaves", "take")

    def __init__(self, tree, leaves, take):
        self.tree, self.leaves, self.take = tree, leaves, take

    def unflatten(self, arrays):
        return jax.tree_util.tree_unflatten(self.tree, [
            x.read(arrays) if isinstance(x, _Slot) else x
            for x in self.leaves])


@jax.tree_util.register_pytree_node_class
class _Residuals:
    """The recorded forward's third result: its new buffers as pytree
    children, the `_Pullback` as static data."""

    def __init__(self, new, pullback):
        self.new, self.pullback = new, pullback

    def tree_flatten(self):
        return self.new, self.pullback

    @classmethod
    def tree_unflatten(cls, pullback, new):
        return cls(list(new), pullback)


def _split_residuals(vjp, inputs):
    """Sort the vjp's leaves: an input tracer is forwarded from the host,
    a small one is packed with the others of its dtype, any other leaves
    the program once, a non-array stays static. Returns the program's new
    buffers and the `_Pullback`."""
    leaves, tree = jax.tree_util.tree_flatten(vjp)
    # (index in the pool the host builds: the call's flat inputs, then
    # the new buffers; offset in a pack, or None)
    where = {id(x): (i, None) for i, x in enumerate(inputs)}
    whole, small = [], {}
    for x in leaves:
        if isinstance(x, jax.core.Tracer) and id(x) not in where:
            where[id(x)] = (len(inputs) + len(whole), None)
            if x.size > _PACK_LIMIT:
                whole.append(x)
            else:
                small.setdefault(x.dtype, []).append(x)
    packs = []
    for group in small.values():
        offset = 0
        for x in group:
            where[id(x)] = (len(inputs) + len(whole) + len(packs), offset)
            offset += x.size
        packs.append(jnp.concatenate([x.ravel() for x in group]))
    take, arg, recipe = [], {}, []
    for x in leaves:
        if not isinstance(x, jax.core.Tracer):
            recipe.append(x)
            continue
        pool, offset = where[id(x)]
        if pool not in arg:
            arg[pool] = len(take)
            take.append(pool)
        recipe.append(_Slot(arg[pool]) if offset is None
                      else _Slot(arg[pool], offset, x.size, x.shape))
    return whole + packs, _Pullback(tree, recipe, tuple(take))


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

    def _fwd(self, mode, recording):
        """The forward program: `fn(args, aux, key) -> (outs, aux
        updates)`, and when `recording` a third result, the `_Residuals`
        of the graph's vjp."""
        if (mode, recording) not in self._fwd_jits:
            _JIT_BUILDS.inc(op=self._stub.name, mode=mode, direction="fwd")
            from .compile.cache import enable_cache
            enable_cache()   # flag check after the first build
            fn, _, _, needs_rng = build_graph_fn(
                self._symbol._entries, mode,
                policy=save_products_and_sums if recording else None)
            fn.__name__ = "cachedop_fwd_" + self._program
            if recording:
                fn = self._recorded(fn, mode)
            self._fwd_jits[mode, recording] = (jax.jit(fn), needs_rng)
        return self._fwd_jits[mode, recording]

    def _recorded(self, fn, mode):
        def fwd(args, aux, key):
            def f(g):
                return fn(g, aux, key)

            outs, vjp, auxup = jax.vjp(f, args, has_aux=True)
            new, pullback = _split_residuals(
                vjp, jax.tree_util.tree_leaves((args, aux, key)))
            labels = dict(op=self._stub.name, mode=mode)
            _RES_LEAVES.set(sum(isinstance(x, _Slot) for x in pullback.leaves),
                            **labels)
            _RES_OUTPUTS.set(len(new), **labels)
            _RES_BYTES.set(sum(x.size * x.dtype.itemsize for x in new),
                           **labels)
            return outs, auxup, _Residuals(new, pullback)

        fwd.__name__ = fn.__name__
        return fwd

    def _bwd(self, mode):
        if mode not in self._bwd_jits:
            _JIT_BUILDS.inc(op=self._stub.name, mode=mode, direction="bwd")

            def bwd(pullback, arrays, cots):
                _programs.note_scoped()
                return pullback.unflatten(arrays)(list(cots))[0]

            bwd.__name__ = "cachedop_bwd_" + self._program
            # nothing is donated: the residuals hold weights and aux that
            # must outlive the call, and a cotangent can alias a
            # user-visible .grad buffer (an intermediate output with
            # attach_grad)
            self._bwd_jits[mode] = jax.jit(bwd, static_argnums=0)
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
        recording = autograd.is_recording()
        fwd, needs_rng = self._fwd(mode, recording)
        key = _random.next_key() if needs_rng else None
        if recording:
            outs, auxup, res = fwd(args, aux, key)
        else:
            outs, auxup = fwd(args, aux, key)
        # write back mutated aux states (BatchNorm moving stats)
        if auxup:
            for name, val in auxup.items():
                idx = n_args + self._aux_names.index(name)
                inputs[idx]._data = val
        ctx = inputs[0]._ctx if inputs else None
        outputs = [NDArray(o, ctx) for o in outs]

        if recording:
            bwd_jit = self._bwd(mode)
            pool = jax.tree_util.tree_leaves((args, aux, key)) + res.new
            arrays = [pool[i] for i in res.pullback.take]

            def vjp_fn(cots, _pullback=res.pullback, _arrays=arrays):
                grads = bwd_jit(_pullback, _arrays, cots)
                return tuple(grads[n] for n in self._arg_names)

            autograd._record(self._stub, list(inputs[:n_args]), outputs,
                             tuple(o._data for o in outputs), vjp_fn)
        return outputs
