"""Sharded training step: the TPU-native data/tensor-parallel path.

Reference mapping: this module replaces the whole reference stack of
DataParallelExecutorGroup (module/executor_group.py:143 — slice batch,
replicate executors), KVStore comm (src/kvstore/comm.h reduce+broadcast)
and the optimizer drive loop (model.py:145 _update_params_on_kvstore):
one pjit-compiled XLA program computes forward, loss, backward, gradient
allreduce (inserted by XLA from the shardings, riding ICI) and the
optimizer update — no per-parameter push/pull round trips.

Usage::

    mesh = make_mesh({"dp": 8})
    st = ShardedTrainer(net, loss_fn, "sgd", {"learning_rate": .1},
                        mesh=mesh)
    for xb, yb in loader:
        loss = st.step(xb, yb)
    st.copy_params_to_net()

Tensor parallelism: pass `param_rules` = [(regex, PartitionSpec)] to
shard weights over the 'tp' axis; everything else is replicated. XLA
inserts the matching all-gathers/reduce-scatters.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..base import MXNetError
from ..ndarray import NDArray
from .. import symbol as _sym
from ..graph import build_graph_fn, collect_vars, counter_vars
from .. import random as _random
from ..compile.programs import scope as _scope
from ..observability import device_counters as _devc
from ..observability.trace import StepRoot, detach, trace_span
from ..resilience import numerics as _num
from ..resilience.preempt import at_step_boundary
from . import fused_step as _fstep
from .mesh import make_mesh, replicated, current_mesh

__all__ = ["ShardedTrainer", "sgd_init", "sgd_update", "adam_init",
           "adam_update"]


# --------------------------------------------------------------------------
# fused in-graph optimizers (pytree-level; the reference's fused update ops
# src/operator/optimizer_op.cc play this role)
# --------------------------------------------------------------------------
def sgd_init(params):
    return {k: jnp.zeros_like(v) for k, v in params.items()}


def sgd_update(params, grads, state, lr=0.01, momentum=0.0, wd=0.0):
    new_p, new_s = {}, {}
    for k, p in params.items():
        g = grads[k] + wd * p
        if momentum:
            m = momentum * state[k] + g
            new_s[k] = m
        else:  # plain SGD: no momentum to update
            m = g
        new_p[k] = p - lr * m
    # at momentum=0 the carried state passes through structurally
    # unchanged (callers may hold a full dict from a schedule that
    # enables momentum later); ShardedTrainer allocates {} in that case
    return new_p, (new_s if momentum else state)


def adam_init(params):
    return {"m": {k: jnp.zeros_like(v) for k, v in params.items()},
            "v": {k: jnp.zeros_like(v) for k, v in params.items()},
            "t": jnp.zeros((), jnp.int32)}


def adam_update(params, grads, state, lr=0.001, beta1=0.9, beta2=0.999,
                eps=1e-8, wd=0.0):
    t = state["t"] + 1
    new_m, new_v, new_p = {}, {}, {}
    for k, p in params.items():
        g = grads[k] + wd * p
        m = beta1 * state["m"][k] + (1 - beta1) * g
        v = beta2 * state["v"][k] + (1 - beta2) * g * g
        mhat = m / (1 - beta1 ** t)
        vhat = v / (1 - beta2 ** t)
        new_m[k] = m
        new_v[k] = v
        new_p[k] = p - lr * mhat / (jnp.sqrt(vhat) + eps)
    return new_p, {"m": new_m, "v": new_v, "t": t}


def _grads_finite(grads):
    """In-graph all-finite verdict over a gradient pytree (numerics
    guard, ISSUE 10): one fused reduction per leaf, stacked into a 0-d
    bool — XLA folds it into the step program, so detection costs no
    extra dispatch and no host round-trip."""
    leaves = jax.tree.leaves(grads)
    if not leaves:
        return jnp.bool_(True)
    return jnp.all(jnp.stack([jnp.isfinite(g).all() for g in leaves]))


# defaults match mx.optimizer's SGD/Adam (optimizer.py): momentum 0
_OPTIMIZERS = {"sgd": (sgd_init, sgd_update, {"lr": 0.01, "momentum": 0.0,
                                              "wd": 0.0}),
               "adam": (adam_init, adam_update,
                        {"lr": 0.001, "beta1": 0.9, "beta2": 0.999,
                         "eps": 1e-8, "wd": 0.0})}

_OPT_PARAM_ALIASES = {"learning_rate": "lr"}


class ShardedTrainer:
    """One-program data/tensor-parallel trainer over a device mesh."""

    def __init__(self, net, loss, optimizer="sgd", optimizer_params=None,
                 mesh=None, param_rules=None, batch_axis=0,
                 data_names=("data",), label_names=("label",),
                 aux_mode="train", compute_dtype=None,
                 gradient_compression=None,
                 shard_optimizer_state=None, remat=False,
                 input_specs=None):
        """compute_dtype: e.g. "bfloat16" for mixed precision — master
        params stay fp32; weights (ndim>=2) and data inputs are cast to
        the compute dtype inside the step, so matmuls/convs hit the MXU
        in bf16 and activation HBM traffic halves. Per-channel params
        (biases, BN gamma/beta), labels, aux stats and the optimizer
        state stay fp32; grads accumulate fp32.

        shard_optimizer_state: weight-update sharding (SURVEY §2.3,
        ZeRO-1, arXiv:2004.13336): optimizer state (momentum / adam
        m,v) shards row-wise over the dp axis instead of replicating,
        cutting its memory to 1/n per device. The partitioner
        reduce-scatters gradients into the sharded update and
        re-gathers weights — same numerics, tested. Defaults to the
        ``MXTPU_ZERO1`` env knob (parallel/fused_step.py) when None;
        an explicit bool wins.

        gradient_compression: e.g. {"type": "2bit", "threshold": 0.5} —
        the data-parallel gradient exchange becomes an explicit
        compressed collective (shard_map over 'dp': per-device 2-bit
        quantize with error feedback, all_gather of the packed words,
        local dequantize+sum), 1/16 the gradient bytes on ICI/DCN.
        Reference: src/kvstore/gradient_compression.h. Requires a pure
        data-parallel mesh (no param_rules).

        remat: wrap the WHOLE traced graph in one jax.checkpoint: the
        forward pass keeps no residual, and the backward pass begins by
        computing the whole forward again, every residual of which is
        then live at once. That costs ~33% more FLOPs and does not
        lower the step's peak memory; what it shortens is the time the
        residuals are held, and with a policy name (a
        jax.checkpoint_policies member, e.g.
        "dots_with_no_batch_dims_saveable") which of them are kept from
        the first pass. To fit a model whose activations do not, mark
        its layers in the block (`HybridBlock.remat_scope`,
        docs/performance.md "Rematerialisation by layer"): each marked
        group is recomputed on its own and the peak falls to one
        group's residuals. (Reference analog:
        MXNET_BACKWARD_DO_MIRROR, docs/faq/env_var.md.)"""
        self._net = net
        self._compute_dtype = (jnp.dtype(compute_dtype)
                               if compute_dtype is not None else None)
        self._grad_compression = None
        if shard_optimizer_state is None:
            # MXTPU_ZERO1 (docs/performance.md "Fused train step &
            # ZeRO-1"): weight-update sharding by environment, the
            # same knob the gluon.Trainer fused step honors — except
            # under gradient compression, whose step keeps replicated
            # state (an env default must not turn into a hard error)
            shard_optimizer_state = (_fstep.zero1_enabled()
                                     and gradient_compression is None)
        if gradient_compression is not None:
            gc = dict(gradient_compression)
            if gc.get("type", "2bit") != "2bit":
                raise MXNetError("unsupported gradient compression type %r"
                                 % gc.get("type"))
            if param_rules:
                raise MXNetError("gradient_compression requires a pure "
                                 "data-parallel mesh (no param_rules)")
            self._grad_compression = {"threshold":
                                      float(gc.get("threshold", 0.5))}
            if shard_optimizer_state:
                raise MXNetError(
                    "shard_optimizer_state is not supported with "
                    "gradient_compression (the compressed step keeps "
                    "replicated optimizer state around its per-device "
                    "residual exchange)")
        if mesh is None:
            mesh = current_mesh()  # use_mesh() scope, if any
        self._mesh = mesh if mesh is not None else make_mesh()
        self._batch_axis = batch_axis
        self._data_names = tuple(data_names)
        self._label_names = tuple(label_names)
        self._param_rules = [(re.compile(p), spec)
                             for p, spec in (param_rules or [])]
        # per-input PartitionSpec overrides (e.g. {"data": ("dp", "sp")}
        # shards long sequences over the sp axis at ingest, so no device
        # ever materializes the full sequence before the compute's own
        # resharding). Unlisted inputs keep the batch-axis default.
        self._input_specs = {
            k: (v if isinstance(v, PartitionSpec) else PartitionSpec(*v))
            for k, v in (input_specs or {}).items()}
        self._shard_opt = bool(shard_optimizer_state)

        # trace net + loss into one symbol graph
        data_syms = [_sym.var(n) for n in self._data_names]
        label_syms = [_sym.var(n) for n in self._label_names]
        out = net(*data_syms)
        loss_sym = loss(out, *label_syms) if loss is not None else out
        if isinstance(loss_sym, (list, tuple)):
            loss_sym = loss_sym[0]
        self._loss_sym = loss_sym

        arg_nodes, aux_nodes = collect_vars(loss_sym._entries)
        input_set = set(self._data_names) | set(self._label_names)
        self._param_names = [n.name for n in arg_nodes
                             if n.name not in input_set]
        self._aux_names = [n.name for n in aux_nodes]
        # aux states that an op declares as device counters: the step
        # returns them a second time, not donated, for
        # observability/device_counters.py (none: nothing is added)
        self._counter_vars = counter_vars(loss_sym._entries)
        self._fn, _, _, self._needs_rng = build_graph_fn(
            loss_sym._entries, aux_mode)
        if remat:
            if isinstance(remat, str):
                policy = getattr(jax.checkpoint_policies, remat)
            elif callable(remat):
                policy = remat  # a jax.checkpoint_policies member
            elif remat is True:
                policy = None  # full rematerialization
            else:
                raise MXNetError("remat must be True, a policy name, "
                                 "or a checkpoint policy callable")
            self._fn = jax.checkpoint(self._fn, policy=policy)

        # pull initial values out of the gluon net
        net_params = {p.name: p for p in net.collect_params().values()}
        missing = [n for n in self._param_names + self._aux_names
                   if n not in net_params]
        if missing:
            raise MXNetError(
                "ShardedTrainer: net has no parameters %s; initialize the "
                "net (and run one forward to materialize deferred shapes) "
                "first" % missing)
        self._params = {n: self._shard_param(n, net_params[n].data()._data)
                        for n in self._param_names}
        self._aux = {n: self._shard_param(n, net_params[n].data()._data)
                     for n in self._aux_names}

        opt_params = dict(optimizer_params or {})
        for old, new in _OPT_PARAM_ALIASES.items():
            if old in opt_params:
                opt_params[new] = opt_params.pop(old)
        if optimizer not in _OPTIMIZERS:
            raise MXNetError("ShardedTrainer: unknown optimizer %r "
                             "(have %s)" % (optimizer,
                                            sorted(_OPTIMIZERS)))
        opt_init, opt_update, defaults = _OPTIMIZERS[optimizer]
        self._opt_hp = {**defaults, **opt_params}
        if optimizer == "sgd" and not self._opt_hp.get("momentum"):
            self._opt_state = {}  # plain SGD: no state to allocate
        else:
            self._opt_state = opt_init(self._params)
        self._opt_update = opt_update
        if self._shard_opt:
            # place optimizer state on its dp-sharded layout up front so
            # the jitted step's in_shardings match committed arrays
            _, _, opt_sh, _, _ = self._shardings()
            self._opt_state = jax.tree.map(jax.device_put,
                                           self._opt_state, opt_sh)
        self._step_fn = None
        self._step_count = 0
        self._root = StepRoot("sharded_trainer")

        if self._grad_compression is not None:
            # per-device error-feedback residuals: leading dp axis, one
            # slice per mesh device (each device's residual never leaves it)
            dp = self._dp_axis_name()
            n_dp = self._mesh.shape[dp]
            sh = NamedSharding(self._mesh, PartitionSpec(dp))
            self._gc_residuals = {
                k: jax.device_put(
                    jnp.zeros((n_dp,) + v.shape, jnp.float32), sh)
                for k, v in self._params.items()}
        self._register_ledger_bytes()

    def _register_ledger_bytes(self):
        """HBM-ledger cells for this trainer's resident device state
        (docs/observability.md "Memory ledger"): params, aux stats and
        optimizer state are all committed at __init__ exit. Sharded
        layouts report LOGICAL bytes (the per-device sum equals this),
        matching how the gluon trainer accounts its ZeRO-1 cell."""
        from ..observability import memory as _memory
        if not _memory.enabled():
            return
        _memory.set_bytes("trainer", "sharded_trainer", "params",
                          _memory.nbytes(self._params))
        if self._aux:
            _memory.set_bytes("trainer", "sharded_trainer", "aux",
                              _memory.nbytes(self._aux))
        state_leaves = jax.tree.leaves(self._opt_state)
        if state_leaves:
            _memory.set_bytes("trainer", "sharded_trainer", "opt_state",
                              _memory.nbytes(state_leaves))

    def _dp_axis_name(self):
        return "dp" if "dp" in self._mesh.axis_names \
            else self._mesh.axis_names[0]

    # -- shardings ------------------------------------------------------
    def _spec_for(self, name):
        for pat, spec in self._param_rules:
            if pat.search(name):
                return spec
        return PartitionSpec()

    def _shard_param(self, name, value):
        # private copy first: device_put aliases when the sharding already
        # matches, and the donated step would then delete the net's (or a
        # sibling trainer's) live buffer
        return jax.device_put(
            jnp.array(value, copy=True),
            NamedSharding(self._mesh, self._spec_for(name)))

    def _batch_axis_for(self, ndim):
        """Effective batch axis for an input of rank `ndim`: arrays of
        lower rank than batch_axis+1 (e.g. (B,) labels alongside
        batch_axis=1 TNC data) batch on dim 0."""
        ax = self._batch_axis
        if ndim is not None and ax >= ndim:
            ax = 0
        return ax

    def _batch_sharding(self, ndim=None):
        """Sharding splitting the (rank-clamped) batch axis over dp."""
        ax = self._batch_axis_for(ndim)
        spec = [None] * (ax + 1)
        spec[ax] = self._dp_axis_name()
        return NamedSharding(self._mesh, PartitionSpec(*spec))

    def _input_sharding(self, name, ndim=None):
        """Sharding for a named input: explicit input_specs override,
        else the batch-axis default."""
        over = self._input_specs.get(name)
        if over is not None:
            return NamedSharding(self._mesh, over)
        return self._batch_sharding(ndim)

    # -- compiled step --------------------------------------------------
    def _make_step_body(self, guarded=None):
        """The pure per-step function (params, aux, opt_state, inputs,
        key) -> (params', aux', opt_state', loss, ok, counters), shared by the
        single-step jit and the scanned multi-step program. `ok` is the
        numerics guard's in-graph verdict: with MXTPU_NUMERICS (read at
        trace time) a step whose gradients are not all finite is
        SKIPPED — params/aux/opt state pass through bit-identical via
        `jnp.where` — and `ok` reports it; with the guard off `ok` is a
        constant True and the jaxpr is exactly the pre-guard one.

        `counters` is {var: array} of the aux states that are device
        counters, as the step left them ({} for a graph with none).

        `guarded=False` forces the unguarded body regardless of the
        env: the scanned multi-step program uses it — a few hundred
        selects inside a `lax.scan` body blow XLA's CPU compile up by
        an order of magnitude (measured on inception-v3), so
        `step_many` guards the WINDOW outside the loop instead."""
        fn = self._fn
        opt_update = self._opt_update
        hp = self._opt_hp
        cd = self._compute_dtype
        data_names = set(self._data_names)
        guard = _num.enabled() if guarded is None else bool(guarded)
        counter_names = sorted(self._counter_vars)

        # the scopes name the owners of device time that no graph node
        # is (compile/programs.py); the function's name is the program's
        def sharded_step(params, aux, opt_state, inputs, key):
            if cd is not None:
                # mixed precision: cast weights + data (not labels — class
                # indices >256 are not exact in bf16) at the step boundary
                with _scope("mx.cast"):
                    inputs = {k: v.astype(cd)
                              if k in data_names and
                              jnp.issubdtype(v.dtype, jnp.floating) else v
                              for k, v in inputs.items()}

            def loss_fn(p):
                if cd is not None:
                    with _scope("mx.cast"):
                        p = {k: v.astype(cd) if v.ndim >= 2 else v
                             for k, v in p.items()}
                outs, auxup = fn({**p, **inputs}, aux, key)
                return jnp.mean(outs[0].astype(jnp.float32)), auxup

            (loss, auxup), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            with _scope("mx.optimizer"):
                new_params, new_state = opt_update(params, grads,
                                                   opt_state, **hp)
            new_aux = dict(aux)
            new_aux.update(auxup or {})
            if guard:
                keep = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
                with _scope("mx.guard"):
                    ok = _grads_finite(grads)
                    # aux (BN stats) updated by a poisoned forward are
                    # suspect too: the skip preserves them with the rest
                    new_aux = jax.tree.map(keep, new_aux, dict(aux))
                # XLA fuses these selects into the update and a fusion
                # is owned by its root: they stay the optimizer's
                with _scope("mx.optimizer/mx.guard"):
                    new_params = jax.tree.map(keep, new_params, params)
                    new_state = jax.tree.map(keep, new_state, opt_state)
            else:
                ok = jnp.bool_(True)
            counters = {k: new_aux[k] for k in counter_names}
            return new_params, new_aux, new_state, loss, ok, counters

        return sharded_step

    def _shardings(self):
        param_sh = {n: NamedSharding(self._mesh, self._spec_for(n))
                    for n in self._params}
        aux_sh = {n: NamedSharding(self._mesh, self._spec_for(n))
                  for n in self._aux}
        rep = replicated(self._mesh)
        if self._shard_opt:
            # weight-update sharding: optimizer state rows over dp —
            # but never fight an explicit param_rules spec (tp etc.)
            dp = self._dp_axis_name()
            n_dp = self._mesh.shape[dp]
            zero_sh = {}
            for n, v in self._params.items():
                if (self._spec_for(n) == PartitionSpec()
                        and v.ndim >= 1 and v.shape[0] % n_dp == 0
                        and v.shape[0] >= n_dp):
                    zero_sh[n] = NamedSharding(self._mesh,
                                               PartitionSpec(dp))
                else:
                    zero_sh[n] = param_sh[n]
            _fstep.ZERO1_SHARD_PARAMS.set(sum(
                1 for n in self._params
                if zero_sh[n].spec != PartitionSpec()
                and self._spec_for(n) == PartitionSpec()))
            opt_sh = _match_param_shardings(self._opt_state, zero_sh,
                                            rep)
        else:
            opt_sh = _match_param_shardings(self._opt_state, param_sh,
                                            rep)
        ndims = getattr(self, "_input_ndims", {})
        in_sh = {n: self._input_sharding(n, ndims.get(n))
                 for n in self._data_names + self._label_names}
        return param_sh, aux_sh, opt_sh, in_sh, rep

    def _build_step(self):
        # the ONE program per training step (ROADMAP open item 1):
        # forward + backward + XLA-inserted gradient collectives +
        # optimizer update in a single donated pjit. Builds run under
        # the persistent compilation cache (PR 11) so gang relaunches
        # and rollback restarts reload instead of re-tracing XLA.
        from ..compile.cache import enable_cache
        enable_cache()
        step = self._make_step_body()
        param_sh, aux_sh, opt_sh, in_sh, rep = self._shardings()
        self._step_fn = jax.jit(
            step,
            in_shardings=(param_sh, aux_sh, opt_sh, in_sh, None),
            out_shardings=(param_sh, aux_sh, opt_sh, rep, rep,
                           {k: rep for k in self._counter_vars}),
            donate_argnums=(0, 1, 2))

    def _build_step_many(self):
        """K steps fused into ONE XLA program: `lax.scan` over the step
        body, reusing the staged batch each iteration (the reference's
        `--benchmark 1` synthetic-data mode). One dispatch per K steps —
        on high-latency links (multi-host controllers) the
        per-call round trip amortizes away; on any TPU it removes K-1
        host dispatches."""
        from ..compile.cache import enable_cache
        enable_cache()   # program build is a compile entry point
        # the scan body is UNGUARDED (see _make_step_body: per-step
        # selects inside the while loop explode XLA compile); the
        # window is guarded once OUTSIDE the loop instead — a NaN step
        # poisons the rest of the window exactly like the pre-guard
        # behavior, but the window's verdict is still recorded, so a
        # poisoned benchmark window can never post a silent number
        body = self._make_step_body(guarded=False)
        needs_rng = self._needs_rng
        guard = _num.enabled()

        def sharded_step_many(params, aux, opt_state, inputs, key,
                              n_steps, unroll):
            def scan_body(carry, _):
                params, aux, opt_state, key = carry
                if needs_rng:
                    key, sub = jax.random.split(key)
                else:
                    sub = None
                params, aux, opt_state, loss, _ok, _counters = body(
                    params, aux, opt_state, inputs, sub)
                return (params, aux, opt_state, key), loss
            (params, aux, opt_state, _), losses = lax.scan(
                scan_body, (params, aux, opt_state, key), None,
                length=n_steps, unroll=unroll)
            if guard:
                # window-level verdict: non-finite anywhere in the
                # losses or the final params means some step of this
                # window went bad (NaN in params persists once it
                # appears, so the post-window check cannot miss it)
                with _scope("mx.guard"):
                    ok = jnp.all(jnp.stack(
                        [jnp.isfinite(losses).all()]
                        + [jnp.isfinite(p).all()
                           for p in jax.tree.leaves(params)]))
            else:
                ok = jnp.bool_(True)
            return params, aux, opt_state, losses, ok

        param_sh, aux_sh, opt_sh, in_sh, rep = self._shardings()
        self._step_many_fn = jax.jit(
            sharded_step_many,
            in_shardings=(param_sh, aux_sh, opt_sh, in_sh, None),
            out_shardings=(param_sh, aux_sh, opt_sh, rep, rep),
            donate_argnums=(0, 1, 2), static_argnums=(5, 6))

    def step_many(self, *batch_and_labels, n_steps, unroll=1):
        """Run `n_steps` fused train steps as one jitted scan over the
        given (single) batch; returns the per-step losses as an (n_steps,)
        NDArray. `unroll` replicates the step body inside the scan —
        measured ~10%% faster at 8-10 on real hardware (XLA schedules
        across step boundaries) at the cost of compile time. Not
        available with gradient compression (whose step carries
        per-device residual state through shard_map)."""
        if self._grad_compression is not None:
            raise MXNetError("step_many: not supported with gradient "
                             "compression; call step() per batch")
        at_step_boundary()  # pending SIGTERM: checkpoint + stop here
        names = self._data_names + self._label_names
        if len(batch_and_labels) != len(names):
            raise MXNetError("step_many expects %s" % (names,))
        inputs = {}
        ndims = {}
        for n, x in zip(names, batch_and_labels):
            arr = x._data if isinstance(x, NDArray) else jnp.asarray(x)
            ndims[n] = arr.ndim
            inputs[n] = jax.device_put(arr,
                                       self._input_sharding(n, arr.ndim))
        if getattr(self, "_step_many_fn", None) is None:
            self._input_ndims = ndims
            self._build_step_many()
        key = _random.next_key() if self._needs_rng else None
        from .mesh import use_mesh
        with use_mesh(self._mesh):
            (self._params, self._aux, self._opt_state, losses,
             ok) = self._step_many_fn(
                self._params, self._aux, self._opt_state,
                inputs, key, int(n_steps), int(unroll))
        _fstep.STEP_DISPATCHES.inc()   # K steps, ONE scanned program
        if _num.enabled():
            # one scalar verdict for the whole fused window — recorded
            # as where="window": DETECTION-only (the scan body is
            # unguarded, a bad window's weights WERE poisoned), so the
            # collector counts it as an anomaly but never as a
            # preserved/skipped step and never as SDC-replay-sound
            _num.record_flag(ok, where="window")
        self._step_count += int(n_steps)
        return NDArray(losses)

    # -- input staging / fit loop ---------------------------------------
    def _stage_inputs(self, parts):
        """device_put a batch's arrays with this trainer's input
        shardings; returns NDArrays so step() reuses the staged buffers
        (device_put on an already-placed array is an alias, not a
        copy)."""
        staged = []
        names = self._data_names + self._label_names
        for n, x in zip(names, parts):
            arr = x._data if isinstance(x, NDArray) else jnp.asarray(x)
            staged.append(NDArray(jax.device_put(
                arr, self._input_sharding(n, arr.ndim))))
        return staged

    def prefetched(self, data_iter, depth=2):
        """Wrap an iterable of batches into a host→device double buffer
        (reference: src/io/iter_prefetcher.h): a background thread
        pulls and stages batch k+1..k+depth while step k runs. Batches
        may be DataBatch objects or (data..., label...) tuples matching
        this trainer's input names."""
        from .prefetch import DevicePrefetcher

        def stage(batch):
            if hasattr(batch, "data") and hasattr(batch, "label"):
                parts = list(batch.data) + list(batch.label or [])
            elif isinstance(batch, (tuple, list)):
                parts = list(batch)
            else:
                parts = [batch]
            return self._stage_inputs(parts)

        return DevicePrefetcher(data_iter, stage, depth)

    def fit(self, data_iter, num_epochs=1, prefetch_depth=2,
            batch_end_callback=None):
        """Epoch loop over a DataIter with device-side double buffering
        (async device_put of batch k+1 overlapping step k). Returns the
        final loss NDArray."""
        loss = None
        if num_epochs > 1 and not hasattr(data_iter, "reset"):
            raise MXNetError(
                "fit(num_epochs=%d) needs a resettable DataIter; a "
                "plain iterator/generator is exhausted after one "
                "epoch" % num_epochs)
        for epoch in range(num_epochs):
            if hasattr(data_iter, "reset"):
                data_iter.reset()
            pf = self.prefetched(data_iter, depth=prefetch_depth)
            try:
                for nbatch, staged in enumerate(pf):
                    loss = self.step(*staged)
                    if batch_end_callback is not None:
                        batch_end_callback(epoch, nbatch, loss)
            finally:
                pf.close()
        detach()        # the loop is over: its last root parents nothing
        return loss

    def _build_step_compressed(self):
        """Compressed-DP step: shard_map over the dp axis with an explicit
        quantize -> all_gather(packed) -> dequantize+sum gradient
        exchange. The optimizer update runs on the (replicated)
        reconstructed gradient outside the shard_map."""
        from .mesh import shard_map_compat
        from ..gradient_compression import quantize_2bit, dequantize_2bit

        fn = self._fn
        opt_update = self._opt_update
        hp = self._opt_hp
        cd = self._compute_dtype
        data_names = set(self._data_names)
        thr = self._grad_compression["threshold"]
        dp = self._dp_axis_name()
        n_dp = self._mesh.shape[dp]
        mesh = self._mesh
        batch_axis = self._batch_axis

        def shard_grads(params, aux, inputs, residuals, key):
            # runs per-device: local batch shard, replicated params.
            # distinct randomness per shard (dropout etc.): the key is
            # replicated, so fold the device's axis index in
            if key is not None:
                key = jax.random.fold_in(key, lax.axis_index(dp))
            if cd is not None:
                with _scope("mx.cast"):
                    inputs = {k: v.astype(cd)
                              if k in data_names and
                              jnp.issubdtype(v.dtype, jnp.floating) else v
                              for k, v in inputs.items()}

            def loss_fn(p):
                if cd is not None:
                    with _scope("mx.cast"):
                        p = {k: v.astype(cd) if v.ndim >= 2 else v
                             for k, v in p.items()}
                outs, auxup = fn({**p, **inputs}, aux, key)
                return jnp.mean(outs[0].astype(jnp.float32)), auxup

            (loss, auxup), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_res, gsum = {}, {}
            for k, g in grads.items():
                packed, r = quantize_2bit(g, residuals[k][0], thr)
                new_res[k] = r[None]
                allq = lax.all_gather(packed, dp)  # wire: packed words only
                parts = [dequantize_2bit(allq[i], g.shape, thr, g.dtype)
                         for i in range(n_dp)]
                tot = parts[0]
                for p_ in parts[1:]:
                    tot = tot + p_
                gsum[k] = tot / n_dp
            loss = lax.pmean(loss, dp)
            # emit a value for EVERY aux var so the out_specs pytree
            # matches even when fn produces no updates (predict mode)
            auxup = dict(auxup or {})
            auxup = {k: (lax.pmean(auxup[k], dp) if k in auxup
                         else aux[k]) for k in aux}
            return loss, gsum, new_res, auxup

        rep_tree = lambda t: jax.tree.map(lambda _: PartitionSpec(), t)
        ndims = getattr(self, "_input_ndims", {})

        def in_spec(name):
            ax = self._batch_axis_for(ndims.get(name))
            return PartitionSpec(*([None] * ax + [dp]))

        in_spec_inputs = {n: in_spec(n)
                          for n in self._data_names + self._label_names}
        smapped = shard_map_compat(
            shard_grads, mesh,
            (rep_tree(self._params), rep_tree(self._aux),
             in_spec_inputs,
             jax.tree.map(lambda _: PartitionSpec(dp),
                          self._gc_residuals),
             PartitionSpec()),
            (PartitionSpec(), rep_tree(self._params),
             jax.tree.map(lambda _: PartitionSpec(dp),
                          self._gc_residuals),
             rep_tree(self._aux)))

        guard = _num.enabled()

        def sharded_step_compressed(params, aux, opt_state, residuals,
                                    inputs, key):
            loss, grads, new_res, auxup = smapped(params, aux, inputs,
                                                  residuals, key)
            with _scope("mx.optimizer"):
                new_params, new_state = opt_update(params, grads,
                                                   opt_state, **hp)
            new_aux = dict(aux)
            new_aux.update(auxup or {})
            if guard:
                # numerics guard over the RECONSTRUCTED (dequantized)
                # gradients: a poisoned step passes params/aux/opt
                # state AND the error-feedback residuals through
                # bit-identical (a NaN residual would otherwise poison
                # every later compressed exchange)
                keep = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
                with _scope("mx.guard"):
                    ok = _grads_finite(grads)
                    new_aux = jax.tree.map(keep, new_aux, dict(aux))
                    new_res = jax.tree.map(keep, new_res, residuals)
                with _scope("mx.optimizer/mx.guard"):
                    new_params = jax.tree.map(keep, new_params, params)
                    new_state = jax.tree.map(keep, new_state, opt_state)
            else:
                ok = jnp.bool_(True)
            return new_params, new_aux, new_state, new_res, loss, ok

        rep = replicated(self._mesh)
        param_sh = {n: rep for n in self._params}
        aux_sh = {n: rep for n in self._aux}
        opt_sh = _match_param_shardings(self._opt_state, param_sh, rep)
        res_sh = {n: NamedSharding(self._mesh, PartitionSpec(dp))
                  for n in self._gc_residuals}
        in_sh = {n: self._input_sharding(n, ndims.get(n))
                 for n in self._data_names + self._label_names}
        self._step_fn = jax.jit(
            sharded_step_compressed,
            in_shardings=(param_sh, aux_sh, opt_sh, res_sh, in_sh, None),
            out_shardings=(param_sh, aux_sh, opt_sh, res_sh, rep, rep),
            donate_argnums=(0, 1, 2, 3))

    def _count_leaves(self, inputs, key):
        """`step.launch`'s attrs: the arrays the step program takes and
        the ones it returns, counted once when it is built (the call's
        host cost grows with the buffers it hands out)."""
        state = [self._params, self._aux, self._opt_state]
        extra = 2                                   # the loss and `ok`
        if self._grad_compression is not None:
            state.append(self._gc_residuals)
        else:
            extra += len(self._counter_vars)
        n = len(jax.tree.leaves(state))
        return {"leaves_in": n + len(jax.tree.leaves((inputs, key))),
                "leaves_out": n + extra}

    def step(self, *batch_and_labels):
        """Run one fused train step; returns the scalar loss NDArray."""
        # the iteration's root and the three spans every trainer's step
        # has (docs/observability.md "Step spans"): host time between
        # the fence and the launch is the chip's idle time
        self._root.begin(self._step_count)
        with trace_span("step.prepare"):
            # step boundary: state is consistent before new work begins,
            # so a pending SIGTERM checkpoints and stops cleanly right
            # here (resilience/preempt.py)
            at_step_boundary()
            names = self._data_names + self._label_names
            if len(batch_and_labels) != len(names):
                raise MXNetError("step expects %s" % (names,))
            inputs = {}
            ndims = {}
            for n, x in zip(names, batch_and_labels):
                arr = x._data if isinstance(x, NDArray) else jnp.asarray(x)
                ndims[n] = arr.ndim
                inputs[n] = jax.device_put(
                    arr, self._input_sharding(n, arr.ndim))
            built = self._step_fn is None
            if built:
                self._input_ndims = ndims
                if self._grad_compression is not None:
                    self._build_step_compressed()
                else:
                    self._build_step()
            key = _random.next_key() if self._needs_rng else None
            if built:
                self._leaves = self._count_leaves(inputs, key)
        # trace (first call) under this trainer's mesh so mesh-aware ops
        # (contrib.RingAttention / contrib.MoEFFN) pick their sp/ep paths
        from .mesh import use_mesh
        with use_mesh(self._mesh), trace_span("step.launch",
                                              **self._leaves):
            counters = None
            if self._grad_compression is not None:
                (self._params, self._aux, self._opt_state,
                 self._gc_residuals, loss, ok) = self._step_fn(
                    self._params, self._aux, self._opt_state,
                    self._gc_residuals, inputs, key)
            else:
                (self._params, self._aux, self._opt_state,
                 loss, ok, counters) = self._step_fn(
                    self._params, self._aux, self._opt_state, inputs, key)
        with trace_span("step.finish"):
            _fstep.STEP_DISPATCHES.inc()   # the whole step was ONE program
            if _num.enabled():
                _num.record_flag(ok, where="step")
            if counters:
                _devc.publish(self._counter_vars, counters)
            self._step_count += 1
            loss = NDArray(loss)
        self._root.end(self._step_count)
        return loss

    # -- param sync back to the frontend --------------------------------
    @property
    def params(self):
        """Copies of the current parameters. Copies, not the live
        arrays: step()/step_many() donate their inputs, so the
        internal buffers are deleted by the next step."""
        return {k: jnp.array(v, copy=True)
                for k, v in self._params.items()}

    def copy_params_to_net(self):
        """Write trained values back into the gluon net's Parameters."""
        net_params = {p.name: p
                      for p in self._net.collect_params().values()}
        for n, v in {**self._params, **self._aux}.items():
            gathered = jax.device_get(v)
            net_params[n].set_data(NDArray(jnp.asarray(gathered)))


def _match_param_shardings(opt_state, param_sh, rep):
    """Optimizer state entries keyed like params shard like their param
    (weight-update sharding); everything else is replicated."""
    if isinstance(opt_state, dict):
        out = {}
        for k, v in opt_state.items():
            if k in param_sh and not isinstance(v, dict):
                out[k] = param_sh[k]
            else:
                out[k] = _match_param_shardings(v, param_sh, rep)
        return out
    return rep
