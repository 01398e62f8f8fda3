"""Preemption-safe training: SIGTERM/SIGINT -> checkpoint at the next
step boundary (docs/fault_tolerance.md).

TPU VMs are preempted with a SIGTERM and a grace window; the reference
framework would die mid-step and lose everything since the last manual
checkpoint. A `PreemptionGuard` turns the signal into a *request*: the
handler only sets a flag, and the training loops (gluon Trainer.step,
parallel.ShardedTrainer.step/step_many, module fit) call
`at_step_boundary()` between optimizer steps — the only moment the
params/opt-state/step-counter triple is consistent. There the guard
runs its synchronous save callback and raises `TrainingPreempted`
carrying the checkpointed step, so the relaunched job resumes exactly
where the preempted one stopped.

    with TrainerCheckpoint(dir) as ck, \
         PreemptionGuard.for_trainer(ck, trainer):
        for x, y in batches:
            trainer.step(x, y)       # SIGTERM => save + TrainingPreempted

Handlers are installed only while a guard is active and are restored on
exit; without a guard the signals keep their default behavior.
"""
from __future__ import annotations

import signal

from ..base import MXNetError
from .chaos import chaos_point

__all__ = ["TrainingPreempted", "PreemptionGuard", "at_step_boundary",
           "preemption_requested"]


class TrainingPreempted(MXNetError):
    """Raised at a step boundary after a preemption signal; `.step` is
    the step the final synchronous checkpoint captured (None when the
    guard had no save callback).

    `.exit_code` (75) is the gang exit-code contract
    (resilience/supervisor.py): a preempted worker exits 75 so a
    GangSupervisor can tell external eviction (stop — the host is
    going away) from a crash (restart) and from a lost peer (76)
    without parsing stderr."""

    exit_code = 75

    def __init__(self, msg, step=None):
        super().__init__(msg)
        self.step = step


_requested = {"sig": None}
_guards = []  # stack of active PreemptionGuards
_cold = {"boundaries": 0}


def _handler(signum, frame):
    if _requested["sig"] is not None:
        # a SECOND signal while the first is still pending means the
        # loop is not reaching a step boundary (wedged mid-step):
        # escalate immediately with the clean unwind that SIGINT-first
        # reaping ladders (bench.fence_child) rely on —
        # absorbing it would force them all the way to SIGKILL, which
        # leaves a device lease behind
        raise KeyboardInterrupt(
            "second %s while a preemption request was already pending"
            % signal.Signals(signum).name)
    # signal context: only set a flag; all real work happens at the
    # next step boundary on the training thread
    _requested["sig"] = signum


def preemption_requested():
    """True once a guarded SIGTERM/SIGINT arrived and the next step
    boundary has not consumed it yet."""
    return _requested["sig"] is not None


def at_step_boundary():
    """Called by the training loops between optimizer steps. No-op
    (one dict read) unless a PreemptionGuard is active and a signal
    arrived; then the innermost guard saves and raises.

    Also the `worker.kill` chaos site: `kind=kill` SIGKILLs this rank
    mid-run — the gang-supervision proof (a dead rank must yield fast
    peer detection, supervisor teardown, and a committed-checkpoint
    resume, docs/fault_tolerance.md).

    And the training-side cold-start marker: every loop (gluon
    Trainer, ShardedTrainer, module fit) passes here, so one counter
    check publishes the compile/cold-start record a supervised gang's
    downtime split reads (docs/compilation.md). It fires at the
    SECOND boundary, not the first — the boundary sits at the top of
    the step, so only the second one has the whole first step
    (forward/backward AND the fused-update kernel compiles) inside
    the measured window."""
    if _cold["boundaries"] < 2:
        _cold["boundaries"] += 1
        if _cold["boundaries"] == 2:
            from ..compile import coldstart as _coldstart
            _coldstart.mark_ready("train")
    chaos_point("worker.kill")
    sig = _requested["sig"]
    if sig is None or not _guards:
        return
    _requested["sig"] = None
    _guards[-1]._fire(sig)


class PreemptionGuard:
    """Scoped SIGTERM/SIGINT-to-checkpoint bridge.

    `save` is a zero-arg callable run synchronously at the boundary; it
    may return the step number it captured. `reraise=False` turns the
    guard into a cooperative flag (`guard.preempted`) for loops that
    prefer to break cleanly themselves."""

    def __init__(self, save=None, signals=(signal.SIGTERM, signal.SIGINT),
                 reraise=True):
        self._save = save
        self._signals = tuple(signals)
        self._old = {}
        self.reraise = reraise
        self.preempted = False
        self.saved_step = None

    @classmethod
    def for_trainer(cls, checkpoint, trainer, **kwargs):
        """Guard wiring a parallel.TrainerCheckpoint to a trainer with
        a `_step_count`: the boundary save is synchronous (wait=True) —
        an async save racing process exit is exactly the torn-write
        mode this layer exists to prevent."""
        def _save():
            step = int(getattr(trainer, "_step_count", 0))
            checkpoint.save(step, trainer, wait=True)
            return step
        return cls(save=_save, **kwargs)

    def __enter__(self):
        _requested["sig"] = None
        for sig in self._signals:
            try:
                self._old[sig] = signal.signal(sig, _handler)
            except ValueError:
                # not the main thread: signals cannot be trapped here;
                # at_step_boundary still works if another guard (or the
                # main thread) installed the handler
                pass
        _guards.append(self)
        return self

    def __exit__(self, *exc):
        _guards.remove(self)
        for sig, old in self._old.items():
            signal.signal(sig, old)
        self._old = {}
        return False

    def _fire(self, signum):
        self.preempted = True
        if self._save is not None:
            self.saved_step = self._save()
        if self.reraise:
            name = signal.Signals(signum).name
            suffix = "" if self.saved_step is None else \
                "; final checkpoint saved at step %d" % self.saved_step
            raise TrainingPreempted(
                "training preempted by %s at a step boundary%s"
                % (name, suffix), step=self.saved_step)
