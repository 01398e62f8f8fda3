"""Gluon Block / HybridBlock / SymbolBlock.

Reference: python/mxnet/gluon/block.py (Block :126, HybridBlock :669,
_build_cache :746-783, SymbolBlock :950).

TPU-native notes: ``hybridize()`` traces ``hybrid_forward`` with Symbol
proxies exactly like the reference, but the resulting CachedOp is one
``jax.jit`` XLA computation (whole-graph compile subsumes the reference's
memory planning / op bulking). Non-hybridized forward runs eagerly on the
NDArray path. The trace-once/replay contract is identical.
"""
from __future__ import annotations

import copy
import re
import warnings

from .. import ndarray
from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray import NDArray
from .. import name as _name
from .. import symbol
from ..symbol import Symbol
from ..cached_op import CachedOp
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockNaming:
    """Name-manager scope for Blocks (reference: block.py:33)."""
    _current = None

    def __init__(self, block):
        self._owner = block
        self._hint_counts = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        """Resolve the (prefix, ParameterDict) pair for a new Block: child
        blocks get auto-numbered names under the enclosing scope; top-level
        blocks draw from the global name manager."""
        scope = _BlockNaming._current
        if scope is not None and prefix is None:
            seq = scope._hint_counts
            seq[hint] = seq.get(hint, 0) + 1
            prefix = "%s%d_" % (hint, seq[hint] - 1)
        elif prefix is None:
            prefix = _name.current().get(None, hint) + "_"
        if params is not None:
            shared = ParameterDict(params.prefix, params)
        elif scope is not None:
            owner = scope._owner.params
            shared = ParameterDict(owner.prefix + prefix, owner._shared)
        else:
            shared = ParameterDict(prefix)
        full = prefix if scope is None else scope._owner.prefix + prefix
        return full, shared

    def __enter__(self):
        if self._owner._empty_prefix:
            return self
        self._old_scope = _BlockNaming._current
        _BlockNaming._current = self
        self._name_scope = _name.Prefix(self._owner.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._owner._empty_prefix:
            return
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockNaming._current = self._old_scope


# ---------------------------------------------------------------------------
# pytree codec for block inputs/outputs. Same role as jax.tree_util, but a
# Symbol leaf may stand for SEVERAL flat values: tracing flattens a grouped
# symbol to one graph node, while the executed CachedOp yields one array per
# output — the spec records that multiplicity so both sides round-trip.
# Spec grammar: 1 = single leaf; n > 1 = multi-output symbol leaf consuming
# n executed values; tuple = nested sequence of specs.
# ---------------------------------------------------------------------------


def _tree_flatten(tree, where):
    leaves = []

    def walk(node):
        if isinstance(node, NDArray):
            leaves.append(node)
            return 1
        if isinstance(node, Symbol):
            leaves.append(node)
            n = len(node.list_outputs())
            return n if n > 1 else 1
        if not isinstance(node, (list, tuple)):
            raise TypeError(
                "HybridBlock %s: expected NDArray, Symbol, or a (nested) "
                "list of them, found %r" % (where, type(node).__name__))
        return tuple(walk(child) for child in node)

    return leaves, walk(tree)


def _tree_unflatten(values, spec):
    """Rebuild the nested structure from flat `values` (arrays or symbols)
    per `spec`. A multi-leaf spec entry consumes that many values and
    yields them as a list."""
    it = iter(values)

    def build(s):
        if isinstance(s, tuple):
            return [build(child) for child in s]
        if s == 1:
            return next(it)
        return [next(it) for _ in range(s)]

    out = build(spec)
    rest = list(it)
    return out, rest


class Block:
    """Base class for all neural network layers and models
    (reference: block.py:126)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockNaming.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._naming = _BlockNaming(self)
        self._children = {}
        self._attr_params = {}
        self._forward_pre_hooks = []
        self._forward_hooks = []

    def __repr__(self):
        import textwrap
        body = []
        for key, child in self.__dict__.items():
            if isinstance(child, Block):
                rendered = textwrap.indent(repr(child), "  ").lstrip()
                body.append("  (%s): %s" % (key, rendered))
        return "%s(\n%s\n)" % (type(self).__name__, "\n".join(body))

    def __setattr__(self, name, value):
        """Registers parameters and child blocks."""
        prev = getattr(self, name, None)
        if isinstance(prev, (Parameter, Block)) and \
                not isinstance(value, type(prev)):
            raise TypeError(
                "attribute %r holds a %s; rebinding it to a %s would "
                "orphan the registered one" % (name, type(prev).__name__,
                                               type(value).__name__))
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            if name in self._attr_params:
                raise MXNetError(
                    "a Parameter named %r is already registered on this "
                    "block" % name)
            self._attr_params[name] = value
        super().__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        """Returns a name-space scope managing child naming
        (reference: block.py:238)."""
        return self._naming

    @property
    def params(self):
        """This block's direct ParameterDict (not including children)."""
        return self._params

    def collect_params(self, select=None):
        """Returns a ParameterDict of this Block's and children's Parameters
        (reference: block.py:252)."""
        keep = re.compile(select).match if select else (lambda _: True)
        out = ParameterDict(self._params.prefix)
        stack = [self]
        while stack:
            blk = stack.pop()
            out.update({k: v for k, v in blk.params.items() if keep(k)})
            stack.extend(reversed(list(blk._children.values())))
        return out

    def _collect_params_with_prefix(self, prefix=""):
        """Parameters keyed by dotted block path (save/load naming)."""
        out = {}
        stack = [(prefix, self)]
        while stack:
            path, blk = stack.pop()
            dot = path + "." if path else ""
            for key, val in blk._attr_params.items():
                out[dot + key] = val
            for name, child in blk._children.items():
                stack.append((dot + name, child))
        return out

    def save_parameters(self, filename):
        """Save parameters to file using block-structured names
        (reference: block.py:313)."""
        payload = {}
        for key, p in self._collect_params_with_prefix().items():
            payload[key] = (p._reduce() if hasattr(p, "_reduce")
                            else p.data())
        ndarray.save(filename, payload)

    def save_params(self, filename):
        warnings.warn("save_params is deprecated. Please use "
                      "save_parameters.")
        try:
            self.collect_params().save(filename, strip_prefix=self.prefix)
        except ValueError as e:
            raise ValueError("%s\nsave_params is deprecated; using "
                             "save_parameters may resolve this error." % e)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False):
        """Load parameters from file (reference: block.py:355)."""
        saved = ndarray.load(filename)
        own = self._collect_params_with_prefix()
        if not (saved or own):
            return
        dotted = any("." in k for k in saved)
        if not dotted:
            # pre-dotted-naming checkpoint: route through the flat
            # ParameterDict loader, which understands name prefixes
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix)
            return
        missing = [k for k in own if k not in saved]
        if missing and not allow_missing:
            raise MXNetError(
                "checkpoint %r lacks parameter(s) %s (pass "
                "allow_missing=True to initialize them separately)"
                % (filename, ", ".join(sorted(missing))))
        stray = [k for k in saved if k not in own]
        if stray and not ignore_extra:
            raise MXNetError(
                "checkpoint %r carries parameter(s) %s unknown to this "
                "block (pass ignore_extra=True to skip them)"
                % (filename, ", ".join(sorted(stray))))
        for key in saved.keys() - set(stray):
            own[key]._load_init(saved[key], ctx)

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        warnings.warn("load_params is deprecated. Please use "
                      "load_parameters.")
        self.load_parameters(filename, ctx, allow_missing, ignore_extra)

    def register_child(self, block, name=None):
        """Registers a child block (reference: block.py:386)."""
        key = str(len(self._children)) if name is None else name
        self._children[key] = block

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return _HookHandle(self._forward_pre_hooks, hook)

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return _HookHandle(self._forward_hooks, hook)

    def apply(self, fn):
        """Applies fn recursively to every child and self
        (reference: block.py:413)."""
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        """Initialize Parameters of this Block and children
        (reference: block.py:426)."""
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        """Activates HybridBlocks recursively (reference: block.py:442)."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        """Cast this Block to another dtype (reference: block.py:454)."""
        for child in self._children.values():
            child.cast(dtype)
        for p in self.params.values():
            p.cast(dtype)

    def __call__(self, *args):
        """Calls forward (reference: block.py:535)."""
        for pre in self._forward_pre_hooks:
            pre(self, args)
        result = self.forward(*args)
        for post in self._forward_hooks:
            post(self, args, result)
        return result

    def forward(self, *args):
        """Override to implement the computation."""
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a summary of the Block (simplified reference
        block.py:555)."""
        lines = []
        stack = [("", self)]
        while stack:
            indent, blk = stack.pop()
            n = sum(int(p.data().size) for p in blk.params.values()
                    if p._data is not None)
            lines.append("%-40s %-20s %10d"
                         % (indent + blk.name, type(blk).__name__, n))
            stack.extend((indent + "  ", c)
                         for c in reversed(list(blk._children.values())))
        print("\n".join(lines))


class _HookHandle:
    def __init__(self, hooks, hook):
        self._hooks = hooks
        self._hook = hook

    def detach(self):
        if self._hook in self._hooks:
            self._hooks.remove(self._hook)


class HybridBlock(Block):
    """A Block that can be traced into a Symbol graph and compiled
    (reference: block.py:669). ``hybridize()`` makes subsequent calls run
    through a CachedOp — on TPU, one jit-compiled XLA computation."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = []
        self._cached_op = None
        self._cached_graph = ()
        self._in_format = self._out_format = None

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, HybridBlock):
            self._clear_cached_op()

    def _get_graph(self, *args):
        """Trace hybrid_forward once with Symbol proxies; cache the
        (input vars, grouped output) pair."""
        if not self._cached_graph:
            leaves, self._in_format = _tree_flatten(args, "input")
            names = (["data"] if len(leaves) == 1
                     else ["data%d" % i for i in range(len(leaves))])
            tracers = [symbol.var(n) for n in names]
            nested, _ = _tree_unflatten(tracers, self._in_format)
            pvars = {k: p.var() for k, p in self._attr_params.items()}
            with self.name_scope():
                out = self.hybrid_forward(symbol, *_as_list(nested), **pvars)
            out_leaves, self._out_format = _tree_flatten(out, "output")
            self._cached_graph = tracers, symbol.Group(out_leaves)
        return self._cached_graph

    def _build_cache(self, *args):
        """Compile the traced graph into a CachedOp and derive the binding
        plan: for each graph input, where its value comes from at call
        time (positional data slot vs Parameter)."""
        tracers, out = self._get_graph(*args)
        slot_of = {t.name: i for i, t in enumerate(tracers)}
        params = self.collect_params()

        graph_inputs = out.list_inputs()
        for name in graph_inputs:
            if name not in slot_of and name not in params:
                raise MXNetError(
                    "HybridBlock graph wants input %r, which is neither a "
                    "forward argument nor a collected Parameter" % name)
        wanted = set(graph_inputs)
        idle_data = sorted(i for n, i in slot_of.items() if n not in wanted)
        if idle_data:
            warnings.warn(
                "forward argument(s) %s of this HybridBlock do not reach "
                "the traced computation" % idle_data, stacklevel=4)
        idle_params = sorted(n for n in params if n not in wanted)
        if idle_params:
            warnings.warn(
                "Parameter(s) %s do not reach the traced computation"
                % ", ".join(idle_params), stacklevel=4)

        # the plan mirrors the CachedOp's positional signature:
        # arguments first, then auxiliary states
        self._binding_plan = [
            ("data", slot_of[name]) if name in slot_of
            else ("param", params[name])
            for name in out.list_arguments() + out.list_auxiliary_states()
        ]
        self._cached_op = CachedOp(out, self._flags)

    def _deferred_infer_shape(self, *args):
        try:
            self.infer_shape(*args)
        except Exception as e:
            raise ValueError(
                "Deferred initialization failed because shape cannot be "
                "inferred. {}".format(e))

    def _bind_plan(self, leaves):
        return [leaves[src] if kind == "data" else src.data()
                for kind, src in self._binding_plan]

    def _call_cached_op(self, *args):
        if self._cached_op is None:
            self._build_cache(*args)
        leaves, fmt = _tree_flatten(args, "input")
        if fmt != self._in_format:
            raise MXNetError(
                "HybridBlock called with input structure %r; traced with %r"
                % (fmt, self._in_format))
        try:
            bound = self._bind_plan(leaves)
        except DeferredInitializationError:
            # first call: shapes only now known — finish param init, retry
            self._deferred_infer_shape(*args)
            for kind, src in self._binding_plan:
                if kind == "param":
                    src._finish_deferred_init()
            bound = self._bind_plan(leaves)
        out = self._cached_op(*bound)
        if isinstance(out, NDArray):
            out = [out]
        return _tree_unflatten(list(out), self._out_format)[0]

    def _clear_cached_op(self):
        self._cached_graph = ()
        self._cached_op = None

    def register_child(self, block, name=None):
        if not isinstance(block, HybridBlock):
            raise ValueError(
                "every child of a HybridBlock must itself be hybridizable; "
                "%r is a %s (use HybridSequential rather than Sequential "
                "for containers)" % (block.name, type(block).__name__))
        super().register_child(block, name)
        self._clear_cached_op()

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = list(kwargs.items())
        self._clear_cached_op()
        if active and (self._forward_hooks or self._forward_pre_hooks):
            warnings.warn("Forward hooks will not be invoked in "
                          "hybridized mode.")
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Infers shapes of all Parameters from inputs
        (reference: block.py:858)."""
        self._infer_attrs("infer_shape", "shape", *args)

    def infer_type(self, *args):
        self._infer_attrs("infer_type", "dtype", *args)

    def _infer_attrs(self, infer_fn, attr, *args):
        """Propagate shapes/dtypes from example inputs through the traced
        graph onto the Parameters (deferred-init completion)."""
        tracers, out = self._get_graph(*args)
        leaves, _ = _tree_flatten(args, "input")
        seed = {t.name: getattr(leaf, attr)
                for t, leaf in zip(tracers, leaves)}
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            if infer_fn == "infer_shape":
                arg_vals, _, aux_vals = out.infer_shape(**seed)
            else:
                arg_vals, _, aux_vals = out.infer_type(
                    **{k: str(v) for k, v in seed.items()})
        inferred = dict(zip(out.list_arguments(), arg_vals))
        inferred.update(zip(out.list_auxiliary_states(), aux_vals))
        for p in self.collect_params().values():
            if p.name in inferred:
                setattr(p, attr, inferred[p.name])

    def export(self, path, epoch=0):
        """Export HybridBlock to symbol-JSON + params files loadable by
        SymbolBlock / the Module API (reference: block.py:884)."""
        if not self._cached_graph:
            raise RuntimeError(
                "Please first call block.hybridize() and then run forward "
                "with this block at least once before calling export.")
        sym = self._cached_graph[1]
        sym.save("%s-symbol.json" % path)
        kind_of = {n: "arg" for n in sym.list_arguments()}
        kind_of.update((n, "aux") for n in sym.list_auxiliary_states())
        payload = {"%s:%s" % (kind_of[name], name): p.data()
                   for name, p in self.collect_params().items()
                   if name in kind_of}
        ndarray.save("%s-%04d.params" % (path, epoch), payload)

    def forward(self, x, *args):
        """Defers to hybrid_forward, with params materialized
        (reference: block.py:899)."""
        if isinstance(x, NDArray):
            if self._active:
                return self._call_cached_op(x, *args)
            try:
                pdata = {k: p.data() for k, p in self._attr_params.items()}
            except DeferredInitializationError:
                self._deferred_infer_shape(x, *args)
                for p in self.params.values():
                    p._finish_deferred_init()
                pdata = {k: p.data() for k, p in self._attr_params.items()}
            return self.hybrid_forward(ndarray, x, *args, **pdata)
        if not isinstance(x, Symbol):
            raise TypeError(
                "forward expects an NDArray (eager) or Symbol (traced) "
                "first argument; got %s" % type(x).__name__)
        pvars = {k: p.var() for k, p in self._attr_params.items()}
        with self.name_scope():
            return self.hybrid_forward(symbol, x, *args, **pvars)

    def hybrid_forward(self, F, x, *args, **kwargs):
        """Override to construct symbolic graph for this Block."""
        raise NotImplementedError

    def remat_scope(self, name):
        """`with self.remat_scope("l3"):` inside `hybrid_forward` marks the
        graph nodes made in the block as one group of rematerialisation:
        a traced training graph runs the group as one `jax.checkpoint`
        (graph.build_graph_fn), which keeps what enters and what leaves the
        group and computes the inside again in the backward pass. The mark
        is a node attribute; the eager path and `predict` ignore it. The
        group has to be closed: no node outside it between two of its own
        (docs/performance.md "Rematerialisation by layer")."""
        from ..attribute import AttrScope
        from ..graph import REMAT_ATTR
        return AttrScope(**{REMAT_ATTR: self.prefix + str(name)})


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]


class SymbolBlock(HybridBlock):
    """Construct a block from a Symbol (reference: block.py:950)."""

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Import a model exported by HybridBlock.export
        (reference: block.py:985)."""
        if isinstance(input_names, str):
            input_names = [input_names]
        blk = SymbolBlock(symbol.load(symbol_file),
                          [symbol.var(n) for n in input_names])
        if param_file is not None:
            saved = ndarray.load(param_file)
            for name, p in blk.collect_params().items():
                # prefer the export format's explicit tags over bare names
                for key in ("arg:" + name, "aux:" + name, name):
                    if key in saved:
                        p._load_init(saved[key], ctx)
                        break
        return blk

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        self._prefix = ""
        self._params = ParameterDict("", params)
        if isinstance(outputs, (list, tuple)):
            if len(outputs) == 1 and isinstance(outputs[0], list):
                outputs = outputs[0]
            outputs = symbol.Group(outputs)
        if isinstance(inputs, Symbol) and len(inputs.list_outputs()) == 1:
            inputs = [inputs]
        in_syms, self._in_format = _tree_flatten(inputs, "input")
        out_leaves, self._out_format = _tree_flatten(outputs, "output")
        graph = symbol.Group(out_leaves)

        feed_names = set()
        for s_ in in_syms:
            ent = s_._entries
            if len(ent) != 1 or not ent[0][0].is_variable:
                raise MXNetError(
                    "SymbolBlock inputs must be plain variables; %r is "
                    "computed by an operator" % str(s_))
            feed_names.add(s_.name)

        # every non-fed graph input becomes a (deferred-init) Parameter;
        # auxiliary states train with grad_req null
        for name in graph.list_arguments():
            if name not in feed_names:
                self.params.get(name, allow_deferred_init=True)
        for name in graph.list_auxiliary_states():
            if name not in feed_names:
                self.params.get(name, grad_req="null",
                                allow_deferred_init=True)

        self._cached_graph = in_syms, graph
        strip = len(_common_prefix(list(self._params.keys())))
        self._attr_params = {k[strip:]: v for k, v in self._params.items()}

    def forward(self, x, *args):
        if isinstance(x, NDArray):
            return self._call_cached_op(x, *args)
        if not isinstance(x, Symbol):
            raise TypeError(
                "forward expects an NDArray (eager) or Symbol (traced) "
                "first argument; got %s" % type(x).__name__)
        _, in_fmt = _tree_flatten([x] + list(args), "input")
        if in_fmt != self._in_format:
            raise MXNetError(
                "SymbolBlock called with input structure %r; built with %r"
                % (in_fmt, self._in_format))
        ret = copy.copy(self._cached_graph[1])
        return _tree_unflatten(list(ret), self._out_format)[0]

    def _clear_cached_op(self):
        keep = self._cached_graph     # the graph IS this block's definition
        super()._clear_cached_op()
        self._cached_graph = keep

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError


def _common_prefix(names):
    import os.path
    return os.path.commonprefix(list(names)) if names else ""
