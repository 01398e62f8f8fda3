"""Executor: whole-graph compiled execution.

Reference: src/executor/graph_executor.cc (GraphExecutor::Init :514,
RunOps :1586) + python/mxnet/executor.py.

TPU-native design: `bind` lowers the ENTIRE symbol graph — forward AND
backward — into ONE jax function and jit-compiles it. XLA buffer assignment
replaces PlanMemory/InitDataEntryMemory; XLA fusion replaces op bulking;
XLA autodiff (jax.vjp) replaces the NNVM Gradient pass. A training step is
a single fused XLA computation: forward, loss-head gradients, and all
parameter gradients in one device launch (the reference needs hundreds of
kernel launches coordinated by the threaded engine for the same batch).

forward(is_train=True) eagerly runs the fused fwd+bwd computation with
default head gradients and caches the results, so the
forward()/backward() API pair costs one device call per batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError, dtype_from_name
from .graph import build_graph_fn, collect_vars, infer_structs
from .ndarray import NDArray
from . import random as _random

__all__ = ["Executor"]


class Executor:
    def __init__(self, symbol, ctx, arg_dict, grad_dict, grad_req_dict,
                 aux_dict):
        self._symbol = symbol
        self._ctx = ctx
        self.arg_dict = arg_dict          # name -> NDArray
        self.grad_dict = grad_dict        # name -> NDArray (grad buffers)
        self.aux_dict = aux_dict          # name -> NDArray
        self._grad_req = grad_req_dict    # name -> 'write'|'add'|'null'
        arg_nodes, aux_nodes = collect_vars(symbol._entries)
        self._arg_names = [n.name for n in arg_nodes]
        self._aux_names = [n.name for n in aux_nodes]
        self._grad_names = [n for n in self._arg_names
                            if grad_req_dict.get(n, "null") != "null"]
        self.arg_arrays = [arg_dict[n] for n in self._arg_names]
        self.grad_arrays = [grad_dict.get(n) for n in self._arg_names]
        self.aux_arrays = [aux_dict[n] for n in self._aux_names]
        self.outputs = []
        self._cached = None     # (outputs_raw, aux_up, grads) from fused call
        self._jits = {}         # (mode, fused) -> jitted fn
        self._needs_rng = None
        self._monitor_callback = None
        # optional SPMD plan: name -> jax Sharding, enforced on every
        # dispatch (the PlaceDevice-pass equivalent; set by the executor
        # group when running over a device mesh)
        self._shardings = None

    def set_shardings(self, shardings):
        self._shardings = dict(shardings) if shardings else None
        self._jits = {}

    # ------------------------------------------------------------------
    # binding constructors (reference: MXExecutorSimpleBind / Bind)
    # ------------------------------------------------------------------
    @staticmethod
    def _normalize_grad_req(grad_req, arg_names):
        if isinstance(grad_req, str):
            return {n: grad_req for n in arg_names}
        if isinstance(grad_req, (list, tuple)):
            return dict(zip(arg_names, grad_req))
        out = {n: "null" for n in arg_names}
        out.update(grad_req or {})
        return out

    @classmethod
    def _simple_bind(cls, symbol, ctx, grad_req="write", type_dict=None,
                     shared_exec=None, shape_kwargs=None):
        shape_kwargs = shape_kwargs or {}
        known = {}
        type_dict = type_dict or {}
        for k, v in shape_kwargs.items():
            dt = dtype_from_name(type_dict.get(k, "float32"))
            known[k] = (tuple(v), dt)
        # honor __shape__ attrs on variables (reference: var(shape=...))
        arg_nodes, aux_nodes = collect_vars(symbol._entries)
        for n in arg_nodes + aux_nodes:
            if n.name not in known and "__shape__" in n.attrs:
                dt = dtype_from_name(
                    n.attrs.get("__dtype__", type_dict.get(n.name, "float32")))
                known[n.name] = (tuple(n.attrs["__shape__"]), dt)
        var_structs, _ = infer_structs(symbol._entries, known, mode="train")
        arg_names = [n.name for n in arg_nodes]
        missing = [n for n in arg_names + [a.name for a in aux_nodes]
                   if var_structs.get(n) is None]
        if missing:
            raise MXNetError(
                "simple_bind: could not infer shapes for %s — provide their "
                "shapes as keyword arguments" % missing)

        def alloc(name):
            s = var_structs[name]
            # reuse shared executor memory where shapes match (reference:
            # shared_exec bucketing path)
            if shared_exec is not None:
                prev = shared_exec.arg_dict.get(name)
                if prev is None:  # `or` would call NDArray.__bool__,
                    prev = shared_exec.aux_dict.get(name)  # which raises
                if prev is not None and prev.shape == tuple(s.shape) \
                        and np.dtype(prev.dtype) == np.dtype(s.dtype):
                    return prev
            return NDArray(jnp.zeros(s.shape, s.dtype), ctx)

        arg_dict = {n: alloc(n) for n in arg_names}
        aux_dict = {n.name: alloc(n.name) for n in aux_nodes}
        req = cls._normalize_grad_req(grad_req, arg_names)
        grad_dict = {}
        for n in arg_names:
            if req.get(n, "null") != "null":
                s = var_structs[n]
                grad_dict[n] = NDArray(jnp.zeros(s.shape, s.dtype), ctx)
        return cls(symbol, ctx, arg_dict, grad_dict, req, aux_dict)

    @classmethod
    def _bind(cls, symbol, ctx, args=None, args_grad=None, grad_req="write",
              aux_states=None, shared_exec=None):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            arg_dict = dict(zip(arg_names, args))
        else:
            arg_dict = dict(args or {})
        if isinstance(aux_states, (list, tuple)):
            aux_dict = dict(zip(aux_names, aux_states))
        else:
            aux_dict = dict(aux_states or {})
        missing = [n for n in arg_names if n not in arg_dict]
        if missing:
            raise MXNetError("bind: missing arguments %s" % missing)
        missing_aux = [n for n in aux_names if n not in aux_dict]
        if missing_aux:
            raise MXNetError("bind: missing aux states %s" % missing_aux)
        req = cls._normalize_grad_req(grad_req, arg_names)
        if isinstance(args_grad, (list, tuple)):
            grad_dict = {n: g for n, g in zip(arg_names, args_grad)
                         if g is not None}
        else:
            grad_dict = dict(args_grad or {})
        for n in arg_names:
            if req.get(n, "null") != "null" and n not in grad_dict:
                a = arg_dict[n]
                grad_dict[n] = NDArray(jnp.zeros(a.shape, a.dtype), ctx)
        for n in list(grad_dict):
            if req.get(n, "null") == "null":
                del grad_dict[n]
        return cls(symbol, ctx, arg_dict, grad_dict, req, aux_dict)

    # ------------------------------------------------------------------
    # compiled graph functions
    # ------------------------------------------------------------------
    def _get_jit(self, mode, fused):
        key = (mode, fused)
        if key in self._jits:
            return self._jits[key]
        fn, arg_names, aux_names, needs_rng = build_graph_fn(
            self._symbol._entries, mode=mode)
        self._needs_rng = needs_rng
        grad_names = tuple(self._grad_names)

        # the programs' names in a device trace and the program table
        if not fused:
            fn.__name__ = "executor_" + mode
            jitted = jax.jit(fn)
        else:
            def fwdbwd(args, aux, key, ograds):
                rest = {n: v for n, v in args.items() if n not in grad_names}

                def f(g):
                    outs, auxup = fn({**rest, **g}, aux, key)
                    return outs, auxup

                garg = {n: args[n] for n in grad_names}
                outs, vjp_fn, auxup = jax.vjp(f, garg, has_aux=True)
                if ograds is None:
                    ograds = [jnp.ones(o.shape, o.dtype) for o in outs]
                grads = vjp_fn(list(ograds))[0]
                return outs, auxup, grads

            fwdbwd.__name__ = "executor_fwdbwd"
            jitted = jax.jit(fwdbwd)
        self._jits[key] = jitted
        return jitted

    def _raw_inputs(self):
        if self._shardings is not None:
            sh = self._shardings
            for n in self._arg_names:
                a = self.arg_dict[n]
                if n in sh:
                    a._data = jax.device_put(a._data, sh[n])
            for n in self._aux_names:
                a = self.aux_dict[n]
                if n in sh:
                    a._data = jax.device_put(a._data, sh[n])
        args = {n: self.arg_dict[n]._data for n in self._arg_names}
        aux = {n: self.aux_dict[n]._data for n in self._aux_names}
        return args, aux

    def _key(self):
        # build_graph_fn may need a key; harmless to pass one always (it is
        # ignored when no random ops exist because jit drops unused inputs)
        return _random.next_key()

    # ------------------------------------------------------------------
    # public API (reference: executor.py forward/backward/outputs)
    # ------------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("forward: unknown argument %r" % k)
            tgt = self.arg_dict[k]
            tgt._data = v._data if isinstance(v, NDArray) else jnp.asarray(v)
        args, aux = self._raw_inputs()
        key = self._key()
        if is_train and self._grad_names:
            fused = self._get_jit("train", True)
            outs, auxup, grads = fused(args, aux, key, None)
            # cache the exact (args, aux, key) this forward used so a later
            # backward(out_grads) replays the SAME computation (same
            # dropout masks / RNG draws), not a fresh one
            self._cached = (args, aux, key, grads)
        else:
            mode = "train" if is_train else "predict"
            fn = self._get_jit(mode, False)
            outs, auxup = fn(args, aux, key)
            self._cached = None
        if is_train:
            for name, val in auxup.items():
                self.aux_dict[name]._data = val
        self.outputs = [NDArray(o, self._ctx) for o in outs]
        if self._monitor_callback is not None:
            for name, out in zip(self._symbol.list_outputs(), self.outputs):
                self._monitor_callback(name, out)
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        if not self._grad_names:
            return
        if out_grads is None and self._cached is not None:
            grads = self._cached[3]
        else:
            if self._cached is not None:
                # reuse the forward's inputs AND its PRNG key so random ops
                # (dropout) use identical masks in this replayed fwd+bwd
                args, aux, key, _ = self._cached
            else:
                args, aux = self._raw_inputs()
                key = self._key()
            if out_grads is not None:
                if isinstance(out_grads, NDArray):
                    out_grads = [out_grads]
                out_grads = [g._data if isinstance(g, NDArray)
                             else jnp.asarray(g) for g in out_grads]
            fused = self._get_jit("train", True)
            _, _, grads = fused(args, aux, key, out_grads)
        for name, g in grads.items():
            buf = self.grad_dict.get(name)
            if buf is None:
                continue
            if self._grad_req.get(name) == "add":
                buf._data = buf._data + g
            else:
                buf._data = g
        self._cached = None

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in (arg_params or {}).items():
            if name in self.arg_dict:
                self.arg_dict[name]._data = jnp.asarray(
                    arr._data if isinstance(arr, NDArray) else arr,
                    self.arg_dict[name].dtype)
            elif not allow_extra_params:
                raise MXNetError("copy_params_from: %r not an argument" % name)
        for name, arr in (aux_params or {}).items():
            if name in self.aux_dict:
                self.aux_dict[name]._data = jnp.asarray(
                    arr._data if isinstance(arr, NDArray) else arr,
                    self.aux_dict[name].dtype)
            elif not allow_extra_params:
                raise MXNetError("copy_params_from: %r not an aux state" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor for new input shapes. XLA recompiles per
        shape signature automatically (the bucketing cost model)."""
        from .base import dtype_name
        known = dict(kwargs)
        # preserve the bound dtypes of the reshaped inputs
        type_dict = {n: dtype_name(self.arg_dict[n].dtype)
                     for n in known if n in self.arg_dict}
        ex = Executor._simple_bind(
            self._symbol, self._ctx, grad_req=self._grad_req,
            type_dict=type_dict, shape_kwargs=known, shared_exec=self)
        ex._shardings = self._shardings
        return ex

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_callback = callback

    @property
    def symbol(self):
        return self._symbol

    def debug_str(self):
        return self._symbol.debug_str()
