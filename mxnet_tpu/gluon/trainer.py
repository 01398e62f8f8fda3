"""Gluon Trainer: applies an Optimizer to a set of Parameters.

API parity with the reference Trainer (python/mxnet/gluon/trainer.py:
step :241, allreduce_grads :276, update :314, save/load_states :371).

TPU-native notes: in the reference, step() pushes each grad to KVStore
(multi-GPU reduce) and pulls it back, then updates per-device replicas.
Here parameters hold single (possibly mesh-sharded) arrays; the kvstore
push/pull is the cross-process psum when running under `tpu_dist`
(jax.distributed), and a no-op reduce in single-process mode — XLA
already summed the batch gradient. The optimizer update itself is a
jit-compiled fused kernel per parameter (optimizer.py).

Internally the sync strategy is resolved ONCE into two booleans
(_reduce_via_kv / _update_via_kv) by _resolve_sync(), and every
gradient walk goes through _trainable() — a different decomposition
from the reference's per-call branching.

Fused one-program step (docs/performance.md "Fused train step &
ZeRO-1", default on): `step()` runs gradient exchange + optimizer
update as ONE donated jit program (parallel/fused_step.py) — no
host-visible buffers or Python between the phases, recorded as a
single "step.launch" phase in telemetry. Whether it runs is
`fused_step.step`'s answer alone, from what the step observes
(optimizer class, kvstore, process count, the key set); a refusal
takes the staged bucketed path below (the bit-parity oracle).
`allreduce_grads()` / `update()` always take the staged halves: that
pair is how a caller, or a test, asks for them.
"""
from __future__ import annotations

from .. import optimizer as opt
from ..kvstore import create as _create_kvstore
from ..observability.telemetry import StepTimer
from ..observability.trace import trace_span
from ..parallel import fused_step as _fstep
from ..resilience import numerics as _numerics
from ..resilience.atomic import atomic_write
from ..resilience.preempt import at_step_boundary
from .parameter import ParameterDict, Parameter

__all__ = ["Trainer"]


def _normalize_params(params):
    """Accept dict/ParameterDict/list-of-Parameter; reject the rest
    with the reference's error wording."""
    if isinstance(params, (dict, ParameterDict)):
        params = list(params.values())
    if not isinstance(params, (list, tuple)):
        raise ValueError(
            "First argument must be a list or dict of Parameters, "
            "got %s." % (type(params)))
    for p in params:
        if not isinstance(p, Parameter):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                "got list of %s." % (type(p)))
    return list(params)


class Trainer:
    """Applies an Optimizer on a set of Parameters
    (reference: trainer.py:28)."""

    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        self._params = _normalize_params(params)
        self._param2idx = {p.name: i for i, p in enumerate(self._params)}
        self._compression_params = compression_params
        opt_kw = dict(optimizer_params or {})
        self._scale = float(opt_kw.get("rescale_grad", 1.0))
        self._kvstore_spec = (kvstore, update_on_kvstore)
        self._kvstore = None
        self._reduce_via_kv = False
        self._update_via_kv = False
        self._ready = False
        self._optimizer = self._make_optimizer(optimizer, opt_kw)
        self._updaters = [opt.get_updater(self._optimizer)]
        self._telemetry = StepTimer("gluon.trainer")
        # training numerics guard (default on, ISSUE 10): resolves the
        # fused update's in-graph skip flags at each step boundary,
        # drives the loss-scale schedule, and arms divergence rollback
        # when a checkpoint is attached (docs/fault_tolerance.md)
        self._numerics = (_numerics.NumericsGuard(source="gluon.trainer")
                          if _numerics.enabled() else None)
        self._scaler = None          # armed lazily via scale_loss()
        self._last_grads = None

    # -- construction ---------------------------------------------------
    def _make_optimizer(self, optimizer, opt_kw):
        param_dict = dict(enumerate(self._params))
        if isinstance(optimizer, opt.Optimizer):
            assert not opt_kw, \
                "optimizer_params must be None if optimizer is an " \
                "Optimizer instance"
            optimizer.param_dict = param_dict
            return optimizer
        return opt.create(optimizer, param_dict=param_dict, **opt_kw)

    def _resolve_sync(self):
        """Materialize the kvstore (if any) and decide, once, where
        reduction and updates happen. Runs lazily on first use so
        deferred-shape parameters can finish initializing first."""
        spec, on_kv = self._kvstore_spec
        if spec:
            self._kvstore = spec if not isinstance(spec, str) \
                else _create_kvstore(spec)
        if self._kvstore is not None:
            if self._compression_params:
                self._kvstore.set_gradient_compression(
                    self._compression_params)
            self._reduce_via_kv = True
            self._update_via_kv = bool(on_kv)
            if self._update_via_kv:
                self._kvstore.set_optimizer(self._optimizer)
            for i, param in enumerate(self._params):
                self._kvstore.init(i, param.data())
        self._ready = True

    def _ensure_ready(self):
        if not self._ready:
            self._resolve_sync()
            # live introspection plane (docs/observability.md): a
            # training rank binds /metricsz + /debugz when
            # MXTPU_METRICS_PORT is set — one env read, no socket
            # otherwise
            from ..observability import httpz as _httpz
            _httpz.maybe_start()
            self._register_param_bytes()

    def _register_param_bytes(self):
        """One-time HBM-ledger cell for the trainable set (runs at the
        same lazy boundary as _resolve_sync, when deferred shapes are
        materialized). ZeRO-1 optimizer-state bytes ride a separate
        cell owned by the fused step."""
        from ..observability import memory as _memory
        if not _memory.enabled():
            return
        try:
            nb = _memory.nbytes([p.data()._data
                                 for _i, p in self._trainable()])
        except Exception:   # a param still deferred: skip, not fatal
            return
        _memory.set_bytes("trainer", "trainer", "params", nb)

    def _trainable(self):
        """(slot, param) pairs that actually carry gradients."""
        return [(i, p) for i, p in enumerate(self._params)
                if p.grad_req != "null"]

    # -- public knobs ---------------------------------------------------
    @property
    def learning_rate(self):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning("Optimizer has to be defined before its "
                              "learning rate can be accessed.")
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        """Sets a new learning rate (reference: trainer.py:222)."""
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning("Optimizer has to be defined before its "
                              "learning rate is mutated.")
        self._optimizer.set_learning_rate(lr)

    @property
    def numerics(self):
        """The trainer's NumericsGuard (None with MXTPU_NUMERICS=0).
        Training loops feed the divergence watchdog through it
        (``trainer.numerics.note(loss=...)``) and arm rollback/replay
        (``attach_rollback`` / ``attach_replay``)."""
        return self._numerics

    def scale_loss(self, loss):
        """Dynamic loss scaling for fp16/bf16 lanes (GradScaler shape,
        docs/fault_tolerance.md): returns ``loss * scale`` for the
        backward pass and ARMS the scaler — from then on `step()`
        folds ``1/scale`` into rescale_grad (unscaling in the fused
        kernel, no extra pass) and the guard's overflow verdicts drive
        the halve-on-overflow / grow-after-`MXTPU_SCALE_WINDOW`
        schedule. Unscaled runs never arm it, so the default-on guard
        cannot change their numerics."""
        if self._scaler is None:
            self._scaler = _numerics.GradScaler()
            if self._numerics is not None:
                self._numerics.scaler = self._scaler
        return self._scaler.scale_loss(loss)

    @property
    def loss_scale(self):
        return self._scaler.scale if self._scaler is not None else 1.0

    # -- the step -------------------------------------------------------
    def step(self, batch_size, ignore_stale_grad=False):
        """One optimization step: reduce grads, then update params
        (reference: trainer.py:241). Where `fused_step.step` takes it,
        both phases run as ONE donated jit program — the gradient
        exchange and the fused update share an XLA computation, so the
        telemetry record carries a single "step.launch" phase and
        `train.step.dispatches` reads exactly 1. The spans are every
        trainer's (docs/observability.md "Step spans"): `step.prepare`
        to just before the compiled program is called, `step.launch`
        around the call (`allreduce` and `optimizer` on the staged
        path), `step.finish` from there to the return."""
        tel = self._telemetry
        tel.begin_step()
        with trace_span("step.prepare"):
            # step boundary: params/opt-state are consistent here, so a
            # pending SIGTERM checkpoints and stops BEFORE new work
            # starts (resilience/preempt.py)
            at_step_boundary()
            self._ensure_ready()
            self._optimizer.rescale_grad = self._rescale(batch_size)
            plan = self._fused_plan(ignore_stale_grad)
        if not self._fused_launch(plan, tel, ignore_stale_grad):
            with tel.phase("allreduce"):
                self._reduce()
            with tel.phase("optimizer"):
                self._apply_updates(ignore_stale_grad)
        with trace_span("step.finish"):
            self._numerics_boundary(tel)
            tel.end_step(batch_size=batch_size, close_root=False)
        tel.close_root()

    def _fused_plan(self, ignore_stale_grad):
        """What the one-program exchange+update step
        (parallel/fused_step.py) would run on: (slots, grads, weights,
        kvstore). Whether it runs is `fused_step.step`'s to say.

        ZeRO-1 note (docs/performance.md): with ``MXTPU_ZERO1=1`` in a
        multi-process run, `save_states`/`get_states` all-gathers the
        sharded optimizer state — a COLLECTIVE every rank must enter;
        a rank-0-only save_states would deadlock (save through
        `parallel.TrainerCheckpoint` or call it on every rank)."""
        pairs = self._trainable()
        if ignore_stale_grad:
            pairs = [(i, p) for i, p in pairs if p.grad()._fresh_grad]
        return ([i for i, _ in pairs], [p.grad() for _, p in pairs],
                [p.data() for _, p in pairs],
                self._kvstore if self._reduce_via_kv else None)

    def _fused_launch(self, plan, tel, ignore_stale_grad):
        """Offer the planned step to `fused_step.step`. Returns True
        when the one program ran; False falls back to the staged path
        with nothing mutated."""
        idxs, grads, weights, kv = plan
        with tel.phase("step.launch"):
            ran = _fstep.step(self._updaters[0], idxs, grads, weights,
                              kvstore=kv,
                              ignore_stale_grad=ignore_stale_grad)
        if not ran:
            # refused (microseconds: the rule is checked before
            # anything is collected): the staged record keeps its shape
            tel._phases.pop("step.launch", None)
            return False
        if self._numerics is not None:
            # kept for the boundary's SDC replay digest (the fused
            # program never donates its gradient arguments)
            self._last_grads = grads
        for g in grads:
            g._fresh_grad = False
        return True

    def _rescale(self, batch_size):
        """rescale_grad for this step: the caller's scale over the
        batch, divided by the loss scale when the scaler is armed (the
        unscale rides the fused update kernel for free)."""
        scale = self._scale / batch_size
        if self._scaler is not None and self._scaler.armed:
            scale *= self._scaler.unscale_factor()
        return scale

    def _numerics_boundary(self, tel=None):
        """Resolve this step's in-graph skip flags: metric/telemetry
        accounting, loss-scale schedule, SDC replay on first anomaly,
        divergence watchdog (may raise TrainingDiverged after
        rollback)."""
        if self._numerics is None:
            return
        grads, self._last_grads = self._last_grads, None
        if tel is not None:
            with tel.phase("numerics"):
                self._numerics.step_boundary(step=tel.step, grads=grads)
        else:
            self._numerics.step_boundary(grads=grads)

    def allreduce_grads(self):
        """Reduce gradients over devices/workers without updating
        (reference: trainer.py:276)."""
        self._ensure_ready()
        assert not (self._kvstore and self._update_via_kv), \
            "allreduce_grads() when parameters are updated on kvstore " \
            "is not supported."
        self._reduce()

    def update(self, batch_size, ignore_stale_grad=False):
        """Updates parameters from already-reduced gradients
        (reference: trainer.py:314)."""
        self._ensure_ready()
        assert not (self._kvstore and self._update_via_kv), \
            "update() when parameters are updated on kvstore is not " \
            "supported."
        self._optimizer.rescale_grad = self._rescale(batch_size)
        self._apply_updates(ignore_stale_grad)
        self._numerics_boundary()

    def _reduce(self):
        if not self._reduce_via_kv:
            return
        # one batched exchange for the whole gradient set: under
        # `tpu_dist` this is the bucketed fused allreduce
        # (parallel/bucketing.py) — a few large collectives issued in
        # priority order (-i: earlier params first, what the next
        # forward needs) instead of one per parameter
        pairs = self._trainable()
        if not pairs:
            return
        keys = [i for i, _ in pairs]
        grads = [p.list_grad() for _, p in pairs]
        prios = [-i for i in keys]
        self._kvstore.push_all(keys, grads, priorities=prios)
        if not self._update_via_kv:
            self._kvstore.pull_all(keys, grads, priorities=prios,
                                   ignore_sparse=False)

    def _apply_updates(self, ignore_stale_grad=False):
        if self._update_via_kv:
            pairs = self._trainable()
            if pairs:
                self._kvstore.pull_all(
                    [i for i, _ in pairs],
                    [p.list_data() for _, p in pairs],
                    priorities=[-i for i, _ in pairs])
            return
        pairs = self._trainable()
        if ignore_stale_grad:
            # the reference's _fresh_grad contract: only params whose
            # grad a backward pass wrote since the last update
            # participate (autograd sets the mark, the update consumes
            # it; zero_grad/manual writes don't refresh)
            pairs = [(i, p) for i, p in pairs if p.grad()._fresh_grad]
        if not pairs:
            return
        # ONE batched call over the whole trainable set: FusedUpdater
        # groups it into a handful of donated jit updates instead of
        # one dispatch per parameter (parallel/fused_update.py)
        idxs = [i for i, _ in pairs]
        grads = [p.grad() for _, p in pairs]
        weights = [p.data() for _, p in pairs]
        for updater in self._updaters:
            updater.update_all(idxs, grads, weights)
        if self._numerics is not None:
            # kept for the boundary's SDC replay digest (grads are not
            # donated — the arrays stay valid until the next backward)
            self._last_grads = grads
        for g in grads:
            g._fresh_grad = False

    # -- state io -------------------------------------------------------
    def save_states(self, fname):
        """Saves trainer (optimizer/updater) states
        (reference: trainer.py:371)."""
        assert self._optimizer is not None
        self._ensure_ready()
        if self._update_via_kv:
            self._kvstore.save_optimizer_states(fname,
                                                dump_optimizer=True)
            return
        with atomic_write(fname) as fout:
            fout.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        """Loads trainer states (reference: trainer.py:394)."""
        self._ensure_ready()
        if self._update_via_kv:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
            return
        with open(fname, "rb") as f:
            states = f.read()
        for updater in self._updaters:
            updater.set_states(states)
            updater.optimizer = self._updaters[0].optimizer
        self._optimizer = self._updaters[0].optimizer
