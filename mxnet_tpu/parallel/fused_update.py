"""Fused, donated optimizer step: one compiled update per group.

PR 3 collapsed the gradient exchange into a few fused collectives; this
module does the same for the weight update. The reference pays one
engine op per parameter per step (src/operator/optimizer_op.cc kernels
driven by kvstore/updater loops), and our per-op jits in optimizer.py
kept that dispatch shape. "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training" (arXiv:2004.13336) identifies the
weight-update phase as the dominant non-overlappable cost in
data-parallel training — dispatch overhead on a ~160-parameter ResNet
is pure loss.

`FusedUpdater` (a drop-in `optimizer.Updater`) groups trainable
parameters by (optimizer class, packed dtype, multi-precision,
`lr_mult`/`wd_mult` lanes, update count), packs each group's weights,
grads, and optimizer-state leaves into flat fusion buffers — **reusing
the `GradBucketer` layout machinery from PR 3** with an unbounded
bucket target, so plans are memoized exactly like exchange buckets and
grads arriving from `push_all`/`pull_all` bucket slices concatenate
back into contiguous flats without a host round-trip — and runs ONE
`jax.jit` update per group with `donate_argnums` on the weight and
state buffers: XLA writes the new values into the donated storage, so
a steady-state step allocates no fresh weight/state buffers.

Parity: every fused kernel repeats the *exact* elementwise
expressions of the per-parameter path in optimizer.py (same `_prep`,
same operand order). Add, multiply, divide and sqrt are
IEEE-deterministic per element, so for SGD, Adam and AdaGrad fused and
per-parameter updates are bit-identical. RMSProp divides by a square
root, which XLA turns into an rsqrt that XLA:CPU only approximates
(optimizer.py, above `_adagrad_math`): there the two agree to one ulp
of the quotient a step, and bit for bit wherever the loops XLA emits
have one shape. Both are asserted in tests/test_fused_update.py.

Grouped or per-key is chosen in `update_all` from the optimizer's
class and the keys it is given, nothing else. Per-key (always
bit-exact) are:
- optimizer classes without a fused kernel (exact-type match: a
  subclass with its own `update` never rides a parent's kernel),
- row-sparse grads/weights, multi-device grad lists, malformed states,
- a call with a single key.
The per-key reference for a whole set is the base class:
`optimizer.Updater(o)` beside `optimizer.get_updater(o)` (how the
tests compare the two).

Donation caveat (docs/performance.md): the update jits always donate
the weight and state buffers, and a donated buffer's old `jax.Array`
handle is invalidated. The framework's own aliases are re-pointed
immediately after the call, but external code that captured a
parameter's raw `.asjax()` array before a step must not read it after
(copy it first: `jnp.array(x)`, `.asnumpy()`).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from ..compile import aot as _aot
from ..compile.programs import scope as _scope
from ..ndarray import NDArray
from ..observability import registry as _obs
from .. import optimizer as opt
from ..optimizer import _prep, _UPDATE_DISPATCHES
from ..resilience import numerics as _num
from ..resilience.chaos import corrupt_point
from .bucketing import GradBucketer

__all__ = ["FusedUpdater", "update_cost"]

# effectively unbounded bucket target: one fusion buffer per group lane
_NO_LIMIT = 1 << 62

FUSED_GROUPS = _obs.counter(
    "optimizer.fused.groups",
    "Fused optimizer groups dispatched (one donated jit call each)")
FUSED_PACK_SECONDS = _obs.histogram(
    "optimizer.fused.pack.seconds",
    "Host time packing one group's weights/grads/states into flats")
FUSED_UPDATE_SECONDS = _obs.histogram(
    "optimizer.fused.update.seconds",
    "Wall time dispatching one fused group update (async dispatch)")


# ---------------------------------------------------------------------------
# fused kernels — each repeats the per-key math of optimizer.py exactly
# ---------------------------------------------------------------------------
# Shared signature: fn(w, g, states, lr, t, wd, hyper) -> (w', states')
#   w, g    flat fusion buffers;  states  tuple of flat state buffers
#   lr, t   traced (lr changes per step via schedulers; t is the
#           per-cohort update count, traced like _adam_kernel's)
#   wd      static per group (the per-key jits treat it static too)
#   hyper   static tuple of the optimizer's global hyperparameters


def _sgd_fused(w, g, states, lr, t, wd, hyper):
    rescale, clip, momentum = hyper
    g = _prep(g, rescale, clip, wd, w)
    if momentum:
        m = momentum * states[0] - lr * g
        return w + m, (m,)
    return w - lr * g, ()


def _adam_fused(w, g, states, lr, t, wd, hyper):
    beta1, beta2, epsilon, rescale, clip = hyper
    mean, var = states
    g = _prep(g, rescale, clip, wd, w)
    mean = beta1 * mean + (1 - beta1) * g
    var = beta2 * var + (1 - beta2) * jnp.square(g)
    coef1 = 1.0 - beta1 ** t
    coef2 = 1.0 - beta2 ** t
    lr_t = lr * (coef2 ** 0.5) / coef1
    w = w - lr_t * mean / (jnp.sqrt(var) + epsilon)
    return w, (mean, var)


# RMSProp/AdaGrad reuse the exact math function the per-key jitted
# kernels wrap (optimizer._rmsprop_math/_adagrad_math): identical
# source function → identical jaxpr (how close that brings the bits:
# the module docstring).
_rmsprop_fused = opt._rmsprop_math
_adagrad_fused = opt._adagrad_math


class _Spec:
    """One optimizer class's fused-kernel contract."""

    __slots__ = ("name", "fn", "n_states", "hyper", "cost")

    def __init__(self, name, fn, n_states, hyper, cost):
        self.name = name
        self.fn = fn
        self.n_states = n_states   # opt -> number of flat state buffers
        self.hyper = hyper         # opt -> static hyperparameter tuple
        self.cost = cost           # opt -> (reads, writes, flops)/elem


_SUPPORTED = {
    opt.SGD: _Spec(
        "sgd", _sgd_fused,
        lambda o: 1 if o.momentum else 0,
        lambda o: (o.rescale_grad, o.clip_gradient, o.momentum),
        lambda o: (3, 2, 5) if o.momentum else (2, 1, 3)),
    opt.Adam: _Spec(
        "adam", _adam_fused,
        lambda o: 2,
        lambda o: (o.beta1, o.beta2, o.epsilon, o.rescale_grad,
                   o.clip_gradient),
        lambda o: (4, 3, 11)),
    opt.RMSProp: _Spec(
        "rmsprop", _rmsprop_fused,
        lambda o: 3 if o.centered else 1,
        lambda o: (o.gamma1, o.gamma2, o.epsilon, o.centered,
                   o.clip_weights, o.rescale_grad, o.clip_gradient),
        lambda o: (5, 4, 14) if o.centered else (3, 2, 8)),
    opt.AdaGrad: _Spec(
        "adagrad", _adagrad_fused,
        lambda o: 1,
        lambda o: (o.float_stable_eps, o.rescale_grad, o.clip_gradient),
        lambda o: (3, 2, 6)),
}

_JITS = {}


def _guard_wrap(fn):
    """Numerics-guarded kernel (ISSUE 10): the packed gradient flat
    gets ONE fused isfinite-all reduce, and the update runs under a
    ``lax.cond`` whose false branch passes the weight AND every state
    flat through untouched — a poisoned group's step is skipped
    in-graph, pre-step bits preserved exactly, no host round-trip in
    the decision. `ok` rides out as a third result for the guard's
    (deferred) host accounting.

    ``lax.cond`` rather than ``jnp.where`` on purpose: the branch
    compiles as its OWN XLA computation, so the update math keeps the
    exact codegen (same fusion/FMA choices) of the standalone per-key
    kernel — `jnp.where` merges the select into the update program and
    XLA's different fusion decisions break the bit-parity contract
    (observed on centered RMSProp)."""
    def guarded(w, g, states, lr, t, wd, hyper):
        ok = jnp.isfinite(g).all()
        new_w, new_states = jax.lax.cond(
            ok,
            lambda: fn(w, g, states, lr, t, wd, hyper),
            lambda: (w, tuple(states)))
        return new_w, new_states, ok
    return guarded


def _jit_for(spec, guarded=None):
    """The jitted fused kernel for one optimizer class. jax.jit's own
    cache handles per-(shape, static-hyper) specialization; donation
    covers the weight flat (0) and every state flat (2). `guarded`
    selects the numerics-guard wrapper (default: MXTPU_NUMERICS,
    re-read per call)."""
    if guarded is None:
        guarded = _num.enabled()
    key = (spec.name, bool(guarded))
    fn = _JITS.get(key)
    if fn is None:
        from ..compile.cache import enable_cache
        enable_cache()    # kernel build is a compile entry point
        body = _guard_wrap(spec.fn) if guarded else spec.fn
        # named for the device trace and the program table; the scope
        # owns the kernel's device time (compile/programs.py)
        def kernel(w, g, states, lr, t, wd, hyper):
            with _scope("mx.optimizer"):
                return body(w, g, states, lr, t, wd, hyper)

        kernel.__name__ = "fused_update_" + spec.name
        fn = _JITS[key] = jax.jit(
            kernel, static_argnums=(5, 6), donate_argnums=(0, 2))
    return fn


# -- ahead-of-time fused kernels (docs/compilation.md) ----------------------
# The fused-update program set is fixed once the model and optimizer
# are: one kernel per (optimizer class, guard, group layout, static
# hypers). With MXTPU_AOT_STORE set, each group signature tries
# its serialized executable first; with MXTPU_AOT_EXPORT=1 a miss is
# compiled ahead of time (`jit.lower().compile()`) and captured into
# the store — how `tools/aot_build.py --train` harvests kernels whose
# layouts only exist once real shapes flow.
_AOT = {}    # signature -> loaded executable, or False (known miss)


def _aot_sig(spec, guarded, w_flat, g_flat, state_flats, wd, hyper,
             layout=None):
    return (spec.name, bool(guarded),
            tuple(w_flat.shape), str(w_flat.dtype), str(g_flat.dtype),
            tuple((tuple(s.shape), str(s.dtype)) for s in state_flats),
            wd, hyper, layout)


def _aot_kernel(spec, guarded, w_flat, g_flat, state_flats, wd, hyper,
                layout=None):
    """The AOT executable for one group signature, or None (JIT path).
    lr/t stay traced inputs (they change per step); wd/hyper are baked
    into the exported closure exactly as static_argnums bakes them into
    the jit program, and both ride the fingerprint — as does `layout`,
    the stable bucket plan signature (GradBucketer.plan_signature):
    flat shapes alone cannot distinguish two orderings of the same
    keys, so a layout change must miss the store (a counted fallback),
    never load a same-shaped program built for another layout."""
    store = _aot.default_store()
    if store is None:
        return None
    sig = _aot_sig(spec, guarded, w_flat, g_flat, state_flats, wd,
                   hyper, layout)
    cached = _AOT.get(sig)
    if cached is not None:
        return cached or None
    avals = (jax.ShapeDtypeStruct(w_flat.shape, w_flat.dtype),
             jax.ShapeDtypeStruct(g_flat.shape, g_flat.dtype),
             tuple(jax.ShapeDtypeStruct(s.shape, s.dtype)
                   for s in state_flats),
             jax.ShapeDtypeStruct((), jnp.float32),
             jax.ShapeDtypeStruct((), jnp.int32))
    extra = {"kind": "fused_update", "spec": spec.name,
             "guarded": bool(guarded),
             "wd": wd, "hyper": hyper, "layout": layout,
             "args": _aot.aval_signature(avals)}
    name = "fused/%s/%s" % (spec.name, _aot.fingerprint(extra)[:16])
    fn = store.load_jit(name, extra)
    if fn is None and _aot.export_enabled():
        body = _guard_wrap(spec.fn) if guarded else spec.fn

        def kernel(w, g, states, lr, t):
            return body(w, g, states, lr, t, wd, hyper)

        try:
            jitted = jax.jit(kernel, donate_argnums=(0, 2))
            fn = _aot.compile_fresh(jitted, avals)
            store.put(name, _aot.fingerprint(extra), fn)
        except Exception:  # noqa: BLE001 — capture is best-effort
            fn = None
    _AOT[sig] = fn or False
    return fn


def update_cost(optimizer, n_elems, itemsize=4):
    """Estimated FLOPs and HBM bytes of the fused update phase for
    `n_elems` parameters under `optimizer` — so MFU/roofline accounting
    (tools/mfu_probe.py) includes the optimizer, not just fwd/bwd.
    Returns None for optimizers without a fused kernel."""
    spec = _SUPPORTED.get(type(optimizer))
    if spec is None:
        return None
    reads, writes, flops = spec.cost(optimizer)
    return {"reads": reads, "writes": writes,
            "bytes": (reads + writes) * int(n_elems) * int(itemsize),
            "flops": flops * int(n_elems)}


_DTYPE_NAMES = {}


def _dtype_name(dtype):
    """`str(dtype)`, remembered: numpy assembles the name anew at every
    call (microseconds), and the planning below asks twice a parameter
    a step."""
    name = _DTYPE_NAMES.get(dtype)
    if name is None:
        name = _DTYPE_NAMES[dtype] = str(dtype)
    return name


class _Entry:
    """One fused-eligible parameter's resolved update inputs."""

    __slots__ = ("index", "weight", "pack_w", "grad", "state_leaves",
                 "master", "lr", "wd", "t", "lane")

    def __init__(self, index, weight, pack_w, grad, state_leaves, master,
                 lr, wd, t, lane):
        self.index = index
        self.weight = weight           # the caller-visible NDArray
        self.pack_w = pack_w           # jax array packed as the weight
        self.grad = grad               # jax array, dtype-matched to pack_w
        self.state_leaves = state_leaves  # list[NDArray], kernel order
        self.master = master           # fp32 master NDArray or None
        self.lr = lr
        self.wd = wd
        self.t = t
        self.lane = lane


class FusedUpdater(opt.Updater):
    """Drop-in `optimizer.Updater` whose `update_all` fuses eligible
    parameters into one donated jit call per group. Per-key `__call__`,
    `get_states`/`set_states`, and the pickled state format are
    inherited unchanged, so save/load round-trips are oblivious to
    fusion."""

    def __init__(self, optimizer):
        super().__init__(optimizer)
        # PR-3 layout machinery with an unbounded target: one fusion
        # buffer per (dtype, lane); plans memoized on the item tuple so
        # steady-state steps pay one dict lookup
        self._layout = GradBucketer(target_bytes=_NO_LIMIT)
        # set by an attached parallel.fused_step.FusedTrainStep: its
        # ZeRO-1-sharded state flats must flush back into self.states
        # before any per-key read/write (get_states, staged fallback)
        self._fused_step_owner = None

    def _flush_fused_step(self):
        if self._fused_step_owner is not None:
            self._fused_step_owner.flush_state()

    # -- eligibility ----------------------------------------------------
    def _collect(self, spec, indices, grads, weights, require_all=False):
        """Resolve counts/lr/wd and split (fused entries, per-key
        leftovers), preserving caller order inside each split. Count
        bookkeeping for fused entries happens in caller order — exactly
        where the per-key path would do it — but only AFTER the whole
        set validated, so `require_all=True` (the fused-step probe) can
        refuse a set with leftovers as `(None, leftovers)` without
        having bumped a single update count."""
        o = self.optimizer
        entries, leftovers = [], []
        for i, g, w in zip(indices, grads, weights):
            if isinstance(g, (list, tuple)):
                if len(g) != 1:
                    leftovers.append((i, g, w))
                    continue
                g = g[0]
            if i not in self.states:
                self.states[i] = o.create_state_multi_precision(i, w)
                self.states_synced[i] = True
            elif not self.states_synced.get(i, True):
                self.states[i] = self.sync_state_context(self.states[i],
                                                         w._ctx)
                self.states_synced[i] = True
            state = self.states[i]
            if getattr(g, "stype", "default") != "default" or \
                    getattr(w, "stype", "default") != "default":
                leftovers.append((i, g, w))
                continue
            # multi-precision detection: THE SAME predicate the per-key
            # path branches on, so fused and fallback always agree
            mp = o._is_multi_precision_state(w, state)
            if mp:
                master, base = state
                pack_w = master._data
            else:
                master, base = None, state
                pack_w = w._data
            # mp grads stay raw here and are cast to fp32 ONCE per
            # packed group (cast commutes with concat elementwise, so
            # parity holds) — a per-param astype would re-introduce
            # O(n_params) host dispatches
            g_arr = g._data
            if g_arr.dtype != w._data.dtype or g_arr.shape != pack_w.shape:
                leftovers.append((i, g, w))
                continue
            n = spec.n_states(o)
            if n == 0:
                leaves = [] if base is None else None
            else:
                raw = base if isinstance(base, (list, tuple)) else (base,)
                leaves = list(raw) if len(raw) == n and all(
                    isinstance(s, NDArray)
                    and s._data.dtype == pack_w.dtype
                    and s._data.shape == pack_w.shape for s in raw) \
                    else None
            if leaves is None:
                leftovers.append((i, g, w))
                continue
            # lane: the stable group identity — raw weight dtype rides
            # along so mp groups never mix fp16 and bf16 grads in one
            # packed buffer (the flat itself is master-fp32 for mp)
            lane = (spec.name, mp, _dtype_name(w._data.dtype),
                    o._resolved_mult(i, "lr_mult"),
                    o._resolved_mult(i, "wd_mult"))
            entries.append(_Entry(i, w, pack_w, g_arr, leaves, master,
                                  None, None, None, lane))
        if require_all and leftovers:
            return None, leftovers
        # phase 2: counts + lr/wd resolution in caller order, each
        # entry reading the scheduler state its predecessors advanced —
        # identical interleaving to the per-key path
        for e in entries:
            o._update_count(e.index)
            e.lr = o._get_lr(e.index)
            e.wd = o._get_wd(e.index)
            e.t = o._index_update_count[e.index]
        return entries, leftovers

    # -- the fused step -------------------------------------------------
    def update_all(self, indices, grads, weights):
        """Apply the optimizer to the whole (index, grad, weight) set:
        a few donated jit calls for the fused groups, the inherited
        per-key path for everything else — bit-identical either way."""
        # a ZeRO-1 fused-step owner may hold the authoritative state
        # as sharded flats: re-materialize before any per-key use
        self._flush_fused_step()
        spec = _SUPPORTED.get(type(self.optimizer))
        if spec is None or len(indices) < 2:
            super().update_all(indices, grads, weights)
            return
        entries, leftovers = self._collect(spec, indices, grads, weights)
        if leftovers and _num.enabled():
            # per-key leftover lanes update WITHOUT the in-graph guard:
            # they veto full_skip so a partially-unguarded step can
            # never claim the SDC replay's pre-step-state soundness
            _num.note_unguarded(len(leftovers))
        # update counts for fused entries already happened in _collect;
        # they must NOT be rerouted through per-key __call__ (update()
        # would bump the count again). A 1-entry group still runs the
        # fused kernel — same math, one dispatch.
        for bucket, group, t, _lr, _wd in self._plan_cohorts(entries):
            self._run_group(spec, bucket, group, t)
        for i, g, w in leftovers:
            self(i, g, w)

    def _plan_cohorts(self, entries):
        """Yield (bucket, group, t, lr, wd) for the whole entry set —
        THE cohort/layout planning both the staged per-group dispatch
        and the fused one-program step (parallel/fused_step.py) share,
        so their flats stay byte-identical by construction.

        Cohort key is (t, lr, wd), not just t: with an lr_scheduler
        and skewed update counts, two same-t entries can resolve
        DIFFERENT lr values mid-collection (the scheduler reads the
        global num_update another entry just bumped) — the per-key
        path would honor each, so the planned groups must too."""
        by_cohort = {}
        for pos, e in enumerate(entries):
            by_cohort.setdefault((e.t, e.lr, e.wd), []).append((pos, e))
        if len(self._layout._plans) > 64:
            # membership churn (a trainable subset that varies per
            # step) would grow the memoized layouts without bound;
            # steady-state training holds exactly one plan (each new
            # membership still costs an XLA retrace)
            self._layout.clear()
        for (t, lr, wd), cohort in sorted(by_cohort.items()):
            items = tuple(
                (e.index, tuple(e.pack_w.shape),
                 _dtype_name(e.pack_w.dtype),
                 -pos, e.lane)
                for pos, e in cohort)
            by_index = {e.index: e for _, e in cohort}
            for bucket in self._layout.plan(items):
                yield (bucket, [by_index[k] for k in bucket.keys],
                       t, lr, wd)

    def __call__(self, index, grad, weight):
        self._flush_fused_step()
        super().__call__(index, grad, weight)

    def get_states(self, dump_optimizer=False):
        self._flush_fused_step()
        return super().get_states(dump_optimizer=dump_optimizer)

    def set_states(self, states):
        if self._fused_step_owner is not None:
            # the pickled states are about to become authoritative:
            # drop (don't flush) any carried sharded flats
            self._fused_step_owner.drop_state()
        super().set_states(states)

    def _run_group(self, spec, bucket, group, t):
        o = self.optimizer
        n_states = spec.n_states(o)
        t0 = time.perf_counter()
        w_flat = bucket.pack([e.pack_w for e in group])
        g_flat = bucket.pack([e.grad for e in group])
        if g_flat.dtype != w_flat.dtype:
            # multi-precision group: ONE fp32 cast of the whole flat
            # (bit-identical to the per-key per-param casts — astype is
            # elementwise, so it commutes with concatenation)
            g_flat = g_flat.astype(w_flat.dtype)
        # chaos corruption site on the packed gradient flat: kind=nan
        # must be visible to the in-jit isfinite guard below, kind=raise
        # behaves like a plain chaos_point (free when disarmed)
        g_flat = corrupt_point("grad.post", g_flat)
        state_flats = tuple(
            bucket.pack([e.state_leaves[s]._data for e in group])
            for s in range(n_states))
        FUSED_PACK_SECONDS.observe(time.perf_counter() - t0)
        lr, wd = group[0].lr, group[0].wd
        t0 = time.perf_counter()
        guarded = _num.enabled()
        out = None
        hyper = spec.hyper(o)
        # layout fingerprint only when a store is configured: the
        # repr+sha256 walk is wasted work on the storeless hot path
        layout = self._layout.plan_signature([bucket]) \
            if _aot.default_store() is not None else None
        aot_fn = _aot_kernel(spec, guarded, w_flat, g_flat, state_flats,
                             wd, hyper, layout)
        if aot_fn is not None:
            try:
                out = aot_fn(w_flat, g_flat, state_flats,
                             jnp.float32(lr), jnp.int32(t))
            except (TypeError, ValueError):
                # signature/aval refusal happens BEFORE execution, so
                # the donated flats are intact: latch this signature
                # to the known-miss sentinel (never reload a broken
                # executable every step) and take the JIT path. The
                # sig is rebuilt HERE, not on the hot path — failure
                # is the rare case
                _AOT[_aot_sig(spec, guarded, w_flat, g_flat, state_flats,
                              wd, hyper, layout)] = False
                _aot.FALLBACKS.inc(reason="dispatch")
            except Exception:
                # a failure DURING execution may have consumed the
                # donated weight/state flats — re-dispatching them
                # would corrupt the update; latch and surface
                _AOT[_aot_sig(spec, guarded, w_flat, g_flat, state_flats,
                              wd, hyper, layout)] = False
                _aot.FALLBACKS.inc(reason="dispatch")
                raise
        if out is None:
            out = _jit_for(spec, guarded)(
                w_flat, g_flat, state_flats, lr, t, wd, hyper)
        if guarded:
            new_w, new_states, ok = out
            # device scalar only — resolved at the guard's next step
            # boundary, so the skip itself costs no host round-trip
            _num.record_flag(ok, keys=bucket.keys, where="update")
        else:
            new_w, new_states = out
        # post-update corruption site: a bitflip HERE lands in the
        # written weights past the guard — the silent-data-corruption
        # scenario only divergence/rollback machinery can catch
        new_w = corrupt_point("weight.post", new_w)
        FUSED_GROUPS.inc()
        _UPDATE_DISPATCHES.inc()
        from .fused_step import STEP_DISPATCHES
        STEP_DISPATCHES.inc()   # staged path: one dispatch per group
        FUSED_UPDATE_SECONDS.observe(time.perf_counter() - t0)
        for e, w_sub in zip(group, bucket.unpack(new_w)):
            if e.master is not None:
                e.master._data = w_sub
                e.weight._data = w_sub.astype(e.weight._data.dtype)
            else:
                e.weight._data = w_sub
        for s in range(n_states):
            for e, s_sub in zip(group, bucket.unpack(new_states[s])):
                e.state_leaves[s]._data = s_sub
