"""One compiled program per training step + ZeRO-1 weight-update
sharding (docs/performance.md "Fused train step & ZeRO-1").

PR 3 collapsed the gradient exchange into a few bucketed collectives
and PR 4 collapsed the weight update into a few donated group jits —
but a `gluon.Trainer.step()` / `Module.update()` remained TWO
host-orchestrated phases with host-visible buffers between them, and
the reference framework's multi-machine story (arXiv:1512.01274) was
still split across a kvstore hop. This module fuses **gradient
exchange + optimizer update into ONE donated jit program**: the
cross-replica sum (the kvstore allreduce) and the fused update kernels
ride the same XLA computation, so XLA schedules the collective behind
the update math and zero Python runs between the phases. Forward and
backward already execute as one compiled program on every path
(executor / CachedOp / ShardedTrainer), so a training step is now a
single device program on the `ShardedTrainer` path and a single
exchange+update program behind the imperative facades.

What "one program a step" covers depends on where the program's
boundary lies, and the step chooses that from what it can observe:

- **leaves** (one process, replicated state: `gluon.Trainer` on a chip,
  `Module.update`): the program takes the per-parameter weight (or
  master), gradient and state arrays and returns per-parameter arrays.
  The concatenation into lane flats, the multi-precision casts and the
  slicing back are traced inside it with the lane's own `Bucket`
  layout, so NO device program runs around it: `run()` gathers
  references, calls once, and assigns the results to the NDArrays.
- **flats** (the multi-process exchange over the `proc` mesh, ZeRO-1's
  carried sharded state, or an armed `grad.post` / `weight.post` chaos
  site, which must fire on the flat itself): the flats are packed and
  unpacked eagerly around the program with `Bucket.pack` / `unpack`,
  O(parameters) small programs a step. Their inputs are global arrays
  built from one flat a process; per-leaf `device_put`s would cost
  them more than the pack does.

Both share `_plan_lanes`, the layout, the kernels and the guard: one
algorithm with the program's boundary moved.
``train.step.fused_path{path}`` counts which one a step took.

On top rides **ZeRO-1 weight-update sharding** ("Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training",
arXiv:2004.13336): with ``MXTPU_ZERO1=1`` the optimizer state and the
update computation are sharded across the data-parallel axis
(reduce-scatter grads -> shard-local fused update -> all-gather
params, expressed as NamedSharding constraints the partitioner lowers
onto the ring), cutting optimizer-state memory to 1/N per replica.
Sharded state is carried as donated program state between steps and
all-gathered only at the get_states/save boundaries
(`zero1.allgather.seconds`). `ShardedTrainer` honors the same knob by
defaulting `shard_optimizer_state` from ``MXTPU_ZERO1``.

Numerics-guard contract (PR 9): the whole fused step body runs under
ONE in-graph ``lax.cond`` — a step whose (post-exchange) gradients are
not all finite is skipped with weights AND optimizer state preserved
bit-identically, and the single verdict lands in the PR-9 flag
collector as ``where="step"`` (a protected provenance: it counts as a
skipped step, feeds the DivergenceWatchdog, and keeps SDC replay
sound). The ``grad.post`` / ``weight.post`` chaos corruption sites of
the staged path fire at the same places around the fused program
(an armed site puts the step on the flats' boundary for that).
The guard is never applied inside a ``lax.scan`` — `step_many`'s
post-scan window verdict stays as-is (see data_parallel.py).

Bit parity: flats are packed (eagerly or inside the trace) with the
SAME `GradBucketer` layout plans the staged `FusedUpdater` uses and
updated by the SAME kernel functions, and the cross-replica sum is the
same stacked `jnp.sum` the bucketed exchange issues — elementwise IEEE
ops commute with concatenation, so the fused step is bit-identical to
the staged path on either boundary (asserted in
tests/test_fused_step.py).

Which path a step takes is decided HERE and nowhere else: `step()` is
what `gluon.Trainer.step` and `Module.update` call, and `_staged_by`
is the whole rule, read from what the step observes (the updater's
type, the optimizer's class, the kvstore, the process count with
`ignore_stale_grad`, a key set that refused before). A refusal sends
the caller to the staged bucketed exchange + grouped update
(parallel/fused_update.py), which stays the parity oracle and is what
`Trainer.allreduce_grads()` + `Trainer.update()` always run: that pair
is how a test reaches it.

Artifact subsystem (PR 11): program builds run under the persistent
compilation cache, and single-device programs register with the
``MXTPU_AOT_STORE`` exactly like the fused-update kernels — keyed by a
fingerprint that includes the bucket-layout **plan signature**
(`GradBucketer.plan_signature`), so a layout change is a counted JIT
fallback, never a wrong-program load. `tools/aot_build.py --train`
captures the step program by driving a tiny Trainer loop under
``MXTPU_AOT_EXPORT=1``. Multi-device / multi-process programs never
touch the store — a deserialized multi-device CPU executable can
segfault jaxlib (the compile/cache.py guard).

Env knob:
  MXTPU_ZERO1        shard optimizer state over the dp axis (default 0)
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import (NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from ..base import getenv
from ..compile import aot as _aot
from ..compile.programs import scope as _scope
from ..observability import goodput as _goodput
from ..observability import memory as _memory
from ..observability import registry as _obs
from .. import optimizer as opt
from ..resilience import numerics as _num
from ..resilience.chaos import armed as _chaos_armed, corrupt_point

__all__ = ["FusedTrainStep", "zero1_enabled", "step",
           "STEP_DISPATCHES", "FUSED_PATH", "ZERO1_SHARD_PARAMS",
           "ZERO1_ALLGATHER_SECONDS"]

# every device program dispatched on behalf of a training step's
# exchange/update work: ONE per fused step; O(buckets)+O(groups) on the
# staged path (each bucket collective and each update jit counts). The
# per-step delta rides StepTimer records and is the
# perf_gate --max-dispatches-per-step budget.
STEP_DISPATCHES = _obs.counter(
    "train.step.dispatches",
    "Device programs dispatched per training step for gradient "
    "exchange + optimizer update (fused path: exactly 1)")
FUSED_PATH = _obs.counter(
    "train.step.fused_path",
    "Fused steps by the boundary of their one program (label path: "
    "leaves = per-parameter arrays in and out, pack and unpack traced "
    "inside; flats = packed eagerly around it: multi-process, ZeRO-1, "
    "an armed chaos corruption site)")
ZERO1_SHARD_PARAMS = _obs.gauge(
    "zero1.shard_params",
    "Parameters whose optimizer state/update is ZeRO-1-sharded over "
    "the data-parallel axis (0 = replicated state)")
ZERO1_ALLGATHER_SECONDS = _obs.histogram(
    "zero1.allgather.seconds",
    "Wall time all-gathering ZeRO-1-sharded optimizer state into a "
    "full copy (get_states / checkpoint / staged-fallback boundaries)")


def zero1_enabled():
    """MXTPU_ZERO1 gate, re-read per call (default off): shard
    optimizer state + the weight update over the data-parallel axis."""
    return getenv("MXTPU_ZERO1", False)


# fused-step-eligible optimizer classes: the parity-contract set whose
# kernels are pure elementwise expressions (bit-identical under any XLA
# fusion context). RMSProp/AdaGrad keep the staged path — their
# centered/eps codegen is fusion-sensitive (fused_update._guard_wrap)
_STEP_OPTS = (opt.SGD, opt.Adam)


class _Lane:
    """One packed fusion buffer's worth of same-(cohort, lane) params
    inside the fused step program."""

    __slots__ = ("bucket", "group", "spec", "wd", "hyper", "lr", "t",
                 "n_states")

    def __init__(self, bucket, group, spec, lr, t, hyper, n_states):
        self.bucket = bucket
        self.group = group          # [_Entry] in bucket key order
        self.spec = spec
        self.wd = group[0].wd
        self.hyper = hyper
        self.lr = lr
        self.t = t
        self.n_states = n_states

    @property
    def key(self):
        """Static program identity: kernel + hyperparameters + the
        full bucket-layout signature (a layout change re-keys the
        program — counted JIT fallback, never a stale load)."""
        return (self.spec.name, self.bucket.signature, float(self.wd),
                self.hyper)


class FusedTrainStep:
    """One donated program per imperative training step.

    Owns nothing but program caches; parameter/optimizer state stays in
    the caller's NDArrays (and the attached `FusedUpdater`'s state
    dict), except ZeRO-1-sharded state flats which are carried as
    donated program state between steps and flushed back on demand.
    """

    def __init__(self, updater):
        self._updater = updater
        updater._fused_step_owner = self     # get_states flush hook
        self._programs = {}       # signature -> callable
        self._aot = {}            # signature -> exe | False
        self._refused = set()     # program signatures latched staged
        # FULL program signature -> (lanes_meta, [per-lane flats]):
        # the ZeRO-1 carried state (authoritative until flushed). The
        # key includes the zero1/guard flags, so a knob toggled
        # mid-run (MXTPU_ZERO1 off) mismatches and flushes instead of
        # feeding sharded padded flats to a program traced for
        # replicated unpadded ones
        self._state_flats = {}
        self._gather_fn = {}      # (shape, dtype, mesh) -> gather jit
        self._gauge_val = None    # last zero1.shard_params value set
        self._cost_name = {}      # signature -> goodput program name

    # -- public ----------------------------------------------------------
    def program_count(self):
        """Compiled step programs alive in this step object — the
        jit-cache census hook (steady-state training holds exactly 1)."""
        return len(self._programs)

    def run(self, indices, grads, weights, kvstore=None,
            ignore_stale_grad=False):
        """Run one fused exchange+update step over the whole trainable
        set. Returns True when the fused program ran (gradient arrays
        are left UNREDUCED and alive — the program never donates them);
        False means the caller must take the staged path (no state was
        mutated, no update counts were bumped)."""
        from .fused_update import _SUPPORTED
        nproc, mesh = _exchange_plan(kvstore)
        probe_key = (type(self._updater.optimizer), tuple(indices))
        if self._staged_by(probe_key, nproc, ignore_stale_grad):
            return False
        spec = _SUPPORTED[probe_key[0]]
        entries, _left = self._updater._collect(
            spec, indices, grads, weights, require_all=True)
        if entries is None:     # ineligible key: nothing was mutated
            if len(self._refused) > 64:   # membership churn bound
                self._refused.clear()
            self._refused.add(probe_key)
            return False
        lanes = self._plan_lanes(spec, entries)
        zero1 = zero1_enabled() and mesh is not None
        guard = _num.enabled()
        # the program's boundary, from what the step can observe: one
        # process with replicated state hands the per-parameter leaves
        # in and gets leaves back (ZeRO-1 needs a mesh, so nproc == 1
        # rules it out); the process mesh's global arrays are built
        # from one flat a process, and an armed corruption site must
        # fire on the flat itself, so both keep the eager pack
        leaves = nproc == 1 and not (_chaos_armed("grad.post")
                                     or _chaos_armed("weight.post"))
        sig = (tuple(l.key for l in lanes), nproc, zero1, guard, leaves)
        if self._state_flats and sig not in self._state_flats:
            # layout/cohort/knob change: re-materialize the carried
            # state before the old flats' lane map goes stale
            self.flush_state()
        args = self._leaf_args(lanes) if leaves else \
            self._pack(lanes, sig, nproc, mesh, zero1)
        fn = self._program_for(sig, lanes, args, nproc, mesh, zero1,
                               guard, leaves)
        with _memory.oom_guard("train.step", "trainer"):
            out = fn(*args)
        STEP_DISPATCHES.inc()
        FUSED_PATH.inc(path="leaves" if leaves else "flats")
        self._charge_goodput(sig, lanes, nproc)
        n_sharded = sum(len(l.group) for l in lanes) if zero1 else 0
        if n_sharded != self._gauge_val:
            self._gauge_val = n_sharded
            ZERO1_SHARD_PARAMS.set(n_sharded)
        if guard:
            keys = [e.index for l in lanes for e in l.group]
            _num.record_flag(out[2], keys=keys, where="step")
        if leaves:
            self._assign_leaves(lanes, out[0], out[1], out[3])
        else:
            self._unpack(lanes, out[0], out[1], sig, nproc, zero1)
        return True

    def _staged_by(self, probe_key, nproc, ignore_stale_grad):
        """THE rule: what sends a step to the staged path before
        anything is collected, each clause something the step observes.
        Cheap enough to run every step of a run that stays staged."""
        opt_class, indices = probe_key
        return (
            # RMSProp/AdaGrad and every per-key optimizer: _STEP_OPTS
            opt_class not in _STEP_OPTS
            or not indices
            # a compressing or updating store, several workers behind
            # a store that is not the process mesh's: _exchange_plan
            or nproc is None
            # freshness is RANK-LOCAL: filtering a collective's members
            # by it would desynchronize the SPMD program across ranks
            # (the staged path exchanges the full trainable set)
            or (ignore_stale_grad and nproc > 1)
            # a set that refused once (row-sparse key, unpackable
            # leaves) refuses every step: no second collection probe
            or probe_key in self._refused)

    def _charge_goodput(self, sig, lanes, nproc):
        """Charge the step program's FLOPs to the goodput ledger.
        XLA-measured cost (cost_analysis via the AOT capture path)
        wins; the JIT-only path falls back to the analytic
        `update_cost` model over the packed element count, plus the
        cross-replica sum on multi-process meshes."""
        if not _goodput.enabled():
            return
        name = self._cost_name.get(sig)
        if name is None:
            name = "fused_step/sig%d" % len(self._cost_name)
            self._cost_name[sig] = name
        if _goodput.cost(name) is None:
            from .fused_update import update_cost
            o = self._updater.optimizer
            flops = 0.0
            for l in lanes:
                n = int(l.bucket.total)
                itemsize = int(l.group[0].pack_w.dtype.itemsize)
                c = update_cost(o, n, itemsize)
                if c is not None:
                    flops += float(c.get("flops", 0))
                if nproc > 1:    # the in-program gradient sum
                    flops += float(n) * (nproc - 1)
            _goodput.record_cost(name, flops=flops)
        _goodput.note_dispatch(name)

    def _carried_state_bytes(self):
        """Live device bytes of the ZeRO-1 carried state flats —
        addressable shards only, so the ledger reflects the 1/N
        per-replica share ZeRO-1 actually holds."""
        total = 0
        for _sig, (_meta, flats) in self._state_flats.items():
            for lane_flats in flats:
                for f in lane_flats:
                    shards = getattr(f, "addressable_shards", None)
                    if shards:
                        total += sum(int(s.data.nbytes)
                                     for s in shards)
                    else:
                        total += int(getattr(f, "nbytes", 0))
        return total

    def flush_state(self):
        """All-gather any ZeRO-1-sharded state flats back into the
        updater's per-key NDArrays (the get_states / save_states /
        staged-fallback boundary). Collective: in a multi-process run
        every rank must call it."""
        if not self._state_flats:
            return
        t0 = time.perf_counter()
        for _sig, (lanes_meta, flats) in \
                list(self._state_flats.items()):
            for (bucket, leaves_list, sizes), lane_flats in zip(
                    lanes_meta, flats):
                for s, flat in enumerate(lane_flats):
                    full = self._replicate(flat)[:bucket.total]
                    for leaves, sub in zip(leaves_list,
                                           bucket.unpack(full)):
                        leaves[s]._data = sub
        self._state_flats.clear()
        _memory.release("trainer", "optimizer", "zero1_state")
        ZERO1_ALLGATHER_SECONDS.observe(time.perf_counter() - t0)

    def drop_state(self):
        """Forget carried state flats WITHOUT syncing (set_states just
        replaced the authoritative per-key states)."""
        self._state_flats.clear()
        _memory.release("trainer", "optimizer", "zero1_state")

    # -- lane planning ---------------------------------------------------
    def _plan_lanes(self, spec, entries):
        """Cohort + layout planning THROUGH the updater's own
        `_plan_cohorts` — the exact generator the staged per-group
        dispatch consumes, so the flats are byte-identical to the
        staged path's by construction."""
        o = self._updater.optimizer
        hyper, n_states = spec.hyper(o), spec.n_states(o)
        return [_Lane(bucket, group, spec, lr, t, hyper, n_states)
                for bucket, group, t, lr, _wd
                in self._updater._plan_cohorts(entries)]

    # -- the program's arguments: leaves, or eagerly packed flats ----------
    @staticmethod
    def _leaf_args(lanes):
        """The per-parameter arrays as they are, a tuple a lane: no
        device program runs here. Masters and user-visible weights ride
        separate arguments because only the first may be donated: a
        weight's buffer can be shared with another live NDArray
        (`detach()` hands out the same array), a master or a state
        leaf lives only inside the updater."""
        masters, weights, grads, states = [], [], [], []
        for lane in lanes:
            group = lane.group
            mp = group[0].master is not None    # the lane key holds mp
            w = tuple(e.pack_w for e in group)
            masters.append(w if mp else ())
            weights.append(() if mp else w)
            grads.append(tuple(e.grad for e in group))
            states.append(tuple(
                tuple(e.state_leaves[s]._data for e in group)
                for s in range(lane.n_states)))
        # host scalars, traced weakly: see _pack
        return (tuple(masters), tuple(weights), tuple(grads),
                tuple(states), tuple(l.lr for l in lanes),
                tuple(l.t for l in lanes))

    @staticmethod
    def _zero1_pad(flat, nproc):
        pad = (-int(flat.shape[0])) % nproc
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)])
        return flat

    def _pack(self, lanes, sig, nproc, mesh, zero1):
        from .bucketing import PACK_SECONDS
        t0 = time.perf_counter()
        carried = self._state_flats.get(sig)
        w_flats, g_flats, state_flats, lrs, ts = [], [], [], [], []
        for i, lane in enumerate(lanes):
            b, group = lane.bucket, lane.group
            w = b.pack([e.pack_w for e in group])
            g = b.pack([e.grad for e in group])
            if g.dtype != w.dtype:
                # multi-precision: ONE fp32 cast of the whole flat
                # (elementwise, commutes with concat — parity holds)
                g = g.astype(w.dtype)
            # chaos corruption site, same as the staged fused update:
            # kind=nan here must be caught by the in-program guard
            g = corrupt_point("grad.post", g)
            if zero1:
                w = self._zero1_pad(w, nproc)
                g = self._zero1_pad(g, nproc)
            if carried is not None:
                states = carried[1][i]      # sharded, donated carry
            else:
                states = tuple(
                    b.pack([e.state_leaves[s]._data for e in group])
                    for s in range(lane.n_states))
                if zero1:
                    states = tuple(self._zero1_pad(s, nproc)
                                   for s in states)
            # host scalars, traced weakly — the exact spelling of the
            # staged per-group jits (fused_update._jit_for passes lr/t
            # as python values), so math AND per-step host cost match
            lr, t = lane.lr, lane.t
            if nproc > 1:
                w = self._to_global(w, mesh, PartitionSpec())
                g = self._to_global(g[None], mesh,
                                    PartitionSpec("proc"))
                if carried is None:
                    states = tuple(
                        self._to_global_sharded(
                            s, mesh, PartitionSpec("proc"))
                        if zero1 else
                        self._to_global(s, mesh, PartitionSpec())
                        for s in states)
                lr = self._to_global(jnp.float32(lr), mesh,
                                     PartitionSpec())
                t = self._to_global(jnp.int32(t), mesh,
                                    PartitionSpec())
            w_flats.append(w)
            g_flats.append(g)
            state_flats.append(states)
            lrs.append(lr)
            ts.append(t)
        PACK_SECONDS.observe(time.perf_counter() - t0)
        return (tuple(w_flats), tuple(g_flats), tuple(state_flats),
                tuple(lrs), tuple(ts))

    def _my_devices(self, mesh):
        return [d for d in mesh.devices.flat
                if d.process_index == jax.process_index()]

    def _to_global(self, x, mesh, pspec):
        """A host-local array -> global jax.Array over the proc mesh
        (each process contributes its device's shard — the
        kvstore_dist._cross_process_sum recipe)."""
        sharding = NamedSharding(mesh, pspec)
        x = jnp.asarray(x)
        if pspec == PartitionSpec("proc"):
            shape = (mesh.shape["proc"],) + tuple(x.shape[1:])
        else:
            shape = tuple(x.shape)
        arrays = [jax.device_put(x, d) for d in self._my_devices(mesh)]
        return jax.make_array_from_single_device_arrays(
            shape, sharding, arrays)

    def _to_global_sharded(self, flat, mesh, pspec):
        """A full host-local state flat -> ZeRO-1 global array; the
        process device_puts ONLY its own 1/N slice."""
        nproc = mesh.shape["proc"]
        rank = jax.process_index()
        shard = int(flat.shape[0]) // nproc
        local = jnp.asarray(flat)[rank * shard:(rank + 1) * shard]
        sharding = NamedSharding(mesh, pspec)
        arrays = [jax.device_put(local, d)
                  for d in self._my_devices(mesh)]
        return jax.make_array_from_single_device_arrays(
            tuple(flat.shape), sharding, arrays)

    def _replicate(self, flat):
        """All-gather one (possibly process-spanning) sharded flat into
        a host-local full array (the flush collective)."""
        if getattr(flat, "is_fully_addressable", True):
            return jnp.asarray(flat)
        mesh = flat.sharding.mesh
        key = (tuple(flat.shape), str(flat.dtype), id(mesh))
        fn = self._gather_fn.get(key)
        if fn is None:
            rep = NamedSharding(mesh, PartitionSpec())
            fn = self._gather_fn[key] = jax.jit(lambda a: a + 0,
                                                out_shardings=rep)
        out = fn(flat)
        return jnp.asarray(out.addressable_data(0))

    # -- the program -----------------------------------------------------
    def _program_for(self, sig, lanes, args, nproc, mesh, zero1,
                     guard, leaves):
        cached = self._programs.get(sig)
        if cached is not None:
            return cached
        from ..compile.cache import enable_cache
        enable_cache()          # program build is a compile entry point
        statics = tuple((l.spec.fn, l.wd, l.hyper) for l in lanes)
        dp = NamedSharding(mesh, PartitionSpec("proc")) \
            if zero1 else None
        rep = NamedSharding(mesh, PartitionSpec()) \
            if nproc > 1 else None

        def core(w_flats, g_flats, state_flats, lrs, ts):
            if nproc > 1:
                # the gradient exchange: the same stacked sum the
                # bucketed kvstore allreduce jits, fused in-program so
                # XLA schedules it behind the update math
                g_flats = tuple(jnp.sum(g, axis=0) for g in g_flats)
            if guard:
                with _scope("mx.guard"):
                    ok = jnp.all(jnp.stack(
                        [jnp.isfinite(g).all() for g in g_flats]))
            else:
                ok = jnp.bool_(True)

            def update():
                outs_w, outs_s = [], []
                for (fn, wd, hyper), w, g, st, lr, t in zip(
                        statics, w_flats, g_flats, state_flats,
                        lrs, ts):
                    if dp is not None:
                        # ZeRO-1: constrain grads + state to the dp
                        # axis so the partitioner lowers the exchange
                        # as reduce-scatter, runs the update on the
                        # local 1/N shard, and all-gathers the params
                        g = lax.with_sharding_constraint(g, dp)
                        st = tuple(
                            lax.with_sharding_constraint(s, dp)
                            for s in st)
                    nw, ns = fn(w, g, st, lr, t, wd, hyper)
                    if rep is not None:
                        nw = lax.with_sharding_constraint(nw, rep)
                    outs_w.append(nw)
                    outs_s.append(tuple(ns))
                return tuple(outs_w), tuple(outs_s)

            def apply():
                with _scope("mx.optimizer"):
                    return update()

            if guard:
                # ONE lax.cond over the WHOLE step body (the PR-9
                # contract): the false branch passes every weight and
                # state flat through bit-identically
                new_w, new_s = lax.cond(
                    ok, apply,
                    lambda: (tuple(w_flats),
                             tuple(tuple(s) for s in state_flats)))
            else:
                new_w, new_s = apply()
            return new_w, new_s, ok

        buckets = tuple(l.bucket for l in lanes)
        # multi-precision lanes also hand back the weights cast down
        low = tuple(l.group[0].weight._data.dtype
                    if l.group[0].master is not None else None
                    for l in lanes)

        def from_leaves(masters, weights, grads, states, lrs, ts):
            """The same step with the program's boundary at the
            per-parameter leaves: the lane's own `Bucket` layout packs
            them into the flats `core` takes and slices its results
            back, all inside the trace."""
            w_flats, g_flats, state_flats = [], [], []
            with _scope("mx.optimizer"):
                for b, m, w, g, st in zip(buckets, masters, weights,
                                          grads, states):
                    w = b.pack(m or w)
                    g = b.pack(g)
                    if g.dtype != w.dtype:
                        # multi-precision: ONE fp32 cast of the whole
                        # flat, as _pack spells it
                        g = g.astype(w.dtype)
                    w_flats.append(w)
                    g_flats.append(g)
                    state_flats.append(
                        tuple(b.pack(s) for s in st))
            new_w, new_s, ok = core(w_flats, g_flats, state_flats,
                                    lrs, ts)
            with _scope("mx.optimizer"):
                new_w = tuple(tuple(b.unpack(f))
                              for b, f in zip(buckets, new_w))
                new_s = tuple(tuple(tuple(b.unpack(f)) for f in st)
                              for b, st in zip(buckets, new_s))
                cast = tuple(
                    () if dt is None else tuple(x.astype(dt) for x in w)
                    for dt, w in zip(low, new_w))
            return new_w, new_s, ok, cast

        program = from_leaves if leaves else core
        # the program's name in a device trace and in the program table
        program.__name__ = "fused_step_" + "_".join(
            sorted({l.spec.name for l in lanes}))
        # donated: masters or flats, and states; never gradients or
        # (on the leaves' boundary) user-visible weights: _leaf_args
        kw = {"donate_argnums": (0, 3) if leaves else (0, 2)}
        if leaves:
            devices = lanes[0].group[0].pack_w.devices()
            if len(devices) == 1:
                # say where the leaves live: left to be inferred, a
                # leaf that is committed to its device (step 1's
                # results are) and one that is not (a fresh
                # initializer's) lower to different modules, and step
                # 2 would build the program a second time
                kw["in_shardings"] = SingleDeviceSharding(
                    next(iter(devices)))
        if nproc > 1:
            state_out = tuple(
                tuple((dp if zero1 else rep) for _ in lane_states)
                for lane_states in args[2])
            kw["out_shardings"] = (tuple(rep for _ in lanes),
                                   state_out, rep)
        jitted = jax.jit(program, **kw)
        fn = self._aot_or_jit(sig, jitted, args, nproc, zero1,
                              guard, lanes)
        if len(self._programs) > 64:
            # membership/cohort churn: same bound as the layout-plan
            # and refusal caches — steady-state training holds one
            self._programs.clear()
            self._aot.clear()
        self._programs[sig] = fn
        return fn

    def _aot_or_jit(self, sig, jitted, args, nproc, zero1, guard,
                    lanes):
        """Try the PR-11 artifact store for this program signature;
        fall back to (and optionally export from) the jit.
        Multi-process (process-spanning mesh) programs never touch the
        store — a deserialized multi-device CPU executable can
        segfault jaxlib (compile/cache.py guard); the single-device
        flat programs here are the same class as the fused-update
        kernels, which round-trip safely."""
        store = _aot.default_store()
        if store is None or nproc > 1:
            return jitted
        extra = {
            "kind": "fused_step",
            "lanes": [[l.spec.name, repr(l.bucket.signature),
                       l.wd, [repr(h) for h in l.hyper]]
                      for l in lanes],
            # the stable bucket-layout plan signature: a layout change
            # re-fingerprints -> counted fallback, never a stale load
            "plan": self._updater._layout.plan_signature(
                [l.bucket for l in lanes]),
            "zero1": zero1, "guard": guard,
            # the avals of the program's own arguments (leaves or
            # flats): an artifact built for the other boundary misses
            "args": _aot.aval_signature(args),
        }
        name = "fused_step/%s" % _aot.fingerprint(extra)[:16]
        loaded = store.load_jit(name, extra)
        if loaded is None and _aot.export_enabled():
            try:
                avals = _aot.abstract(args)
                compiled = _aot.compile_fresh(jitted, avals)
                _aot.record_analyses(name, compiled)
                store.put(name, _aot.fingerprint(extra), compiled)
                loaded = compiled
            except Exception:   # noqa: BLE001 — capture is best-effort
                loaded = None
        if loaded is None:
            return jitted
        self._aot[sig] = loaded
        # a loaded executable still answers cost/memory analysis —
        # register under the program name so MFU uses measured FLOPs
        _aot.record_analyses(name, compiled=loaded)
        self._cost_name[sig] = name

        def call(*args):
            try:
                return loaded(*args)
            except (TypeError, ValueError):
                # aval refusal happens BEFORE execution, so the donated
                # flats are intact: latch this signature to JIT for
                # good and count the fallback
                self._aot[sig] = False
                self._programs[sig] = jitted
                _aot.FALLBACKS.inc(reason="dispatch")
                return jitted(*args)
        return call

    # -- the program's results: leaves, or flats to unpack eagerly --------
    @staticmethod
    def _assign_leaves(lanes, new_w, new_states, cast):
        """Hand the program's leaves to the NDArrays that own them:
        attribute writes only."""
        for lane, w, states, low in zip(lanes, new_w, new_states, cast):
            for j, e in enumerate(lane.group):
                if e.master is not None:
                    e.master._data = w[j]
                    e.weight._data = low[j]
                else:
                    e.weight._data = w[j]
                for s in range(lane.n_states):
                    e.state_leaves[s]._data = states[s][j]

    def _unpack(self, lanes, new_w, new_states, sig, nproc, zero1):
        from .bucketing import UNPACK_SECONDS
        t0 = time.perf_counter()
        lanes_meta, kept = [], []
        for lane, w_flat, state in zip(lanes, new_w, new_states):
            b, group = lane.bucket, lane.group
            if nproc > 1:
                w_flat = jnp.asarray(w_flat.addressable_data(0))
            # post-update corruption site (the SDC simulation), same
            # as the staged path's
            w_flat = corrupt_point("weight.post", w_flat)
            for e, w_sub in zip(group, b.unpack(w_flat)):
                if e.master is not None:
                    e.master._data = w_sub
                    e.weight._data = w_sub.astype(e.weight._data.dtype)
                else:
                    e.weight._data = w_sub
            if zero1:
                # sharded state flats are the authoritative copy,
                # carried (donated) into the next step; the per-key
                # NDArrays re-materialize at the flush boundary
                lanes_meta.append((b, [e.state_leaves for e in group],
                                   b.sizes))
                kept.append(tuple(state))
            else:
                for s in range(lane.n_states):
                    flat = state[s]
                    if nproc > 1:
                        flat = jnp.asarray(flat.addressable_data(0))
                    for e, s_sub in zip(group, b.unpack(flat)):
                        e.state_leaves[s]._data = s_sub
        if zero1:
            self._state_flats = {sig: (lanes_meta, kept)}
            _memory.set_bytes("trainer", "optimizer", "zero1_state",
                              self._carried_state_bytes())
        UNPACK_SECONDS.observe(time.perf_counter() - t0)


def _exchange_plan(kvstore):
    """(nproc, mesh) for the in-program gradient exchange, or
    (None, None) when the kvstore's semantics cannot be fused (a
    compressing store, one that applies the optimizer itself, an
    exotic type)."""
    if kvstore is None:
        return 1, None
    if getattr(kvstore, "_compression", None) is not None:
        return None, None     # compressed exchange: staged path
    if getattr(kvstore, "_updater", None) is not None:
        return None, None     # update_on_kvstore: a push IS the update
    from .kvstore_dist import DistKVStore
    if isinstance(kvstore, DistKVStore):
        if kvstore.num_workers <= 1:
            return 1, None
        return kvstore.num_workers, kvstore._proc_mesh()
    # local/device stores: the single-worker reduce is an identity
    # round-trip — safe to subsume
    if getattr(kvstore, "num_workers", 1) <= 1:
        return 1, None
    return None, None


def step(updater, indices, grads, weights, kvstore=None,
         ignore_stale_grad=False):
    """Module/Trainer entry, and the one place the update path is
    chosen: run the one-program exchange+update step over
    (indices, grads, weights) when what the step observes allows it
    (`FusedTrainStep._staged_by`, then what `_collect` finds in the key
    set). Returns True when it ran; False sends the caller to its
    staged path with nothing mutated."""
    from .fused_update import FusedUpdater
    if not isinstance(updater, FusedUpdater):
        return False        # a plain optimizer.Updater, a user's closure
    owner = updater._fused_step_owner or FusedTrainStep(updater)
    return owner.run(indices, grads, weights, kvstore=kvstore,
                     ignore_stale_grad=ignore_stale_grad)
