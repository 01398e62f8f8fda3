"""Training numerics guard: in-graph anomaly detection + skip, dynamic
loss scaling, divergence rollback, and SDC replay
(docs/fault_tolerance.md "Training numerics guard").

PRs 1/7/8 made training survive process death, wedged devices, and gang
member loss — but a run can still die *numerically*: one NaN gradient
poisons the weights forever, a loss spike silently wastes the rest of
the job, and a silent-data-corruption (SDC) bit-flip is
indistinguishable from a bad hyperparameter. The reference ships only
host-side debug tools for this (`Monitor`,
`clip_global_norm(check_isfinite=True)`) which cost a device→host sync
per check; our fused, donated update path (PR 3/PR 4) is exactly the
place to make detection and recovery in-graph and effectively free.

Four layers, outermost first:

1. **In-graph detection + skip** (parallel/fused_update.py,
   parallel/data_parallel.py): one ``isfinite``-all reduce per packed
   fusion buffer rides inside the update jit, and the update becomes
   ``jnp.where(ok, new, old)`` over weights AND optimizer state — a
   poisoned step is skipped with bit-identical pre-step state
   preserved, no host round-trip in the decision. The per-group ``ok``
   flags land in this module's collector (`record_flag`) and are
   resolved at the next step boundary.
2. **Dynamic loss scaling** (`GradScaler`): the classic
   halve-on-overflow / grow-after-`MXTPU_SCALE_WINDOW`-clean-steps
   schedule for fp16/bf16 multi-precision lanes, driven by the same
   skip flags. Exposed through `gluon.Trainer.scale_loss` (the scaler
   arms only when the loss is actually scaled, so the default-on guard
   never changes an unscaled run's numerics).
3. **Divergence watchdog + rollback** (`DivergenceWatchdog`): a
   host-side rolling detector over per-step loss/grad-norm telemetry —
   a value is *bad* when non-finite, a spike vs. the rolling median,
   or the step was skipped. After `MXTPU_DIVERGE_PATIENCE` consecutive
   bad steps the guard rolls back: committed checkpoint steps newer
   than the last trustworthy one are dropped
   (`TrainerCheckpoint.drop_steps_after` — a bad observation at step S
   was computed from weights *written* at S-1, so the newest trusted
   checkpoint is S-2), the latest surviving committed step is
   restored, and a typed `TrainingDiverged` (exit code 77) is raised —
   which a `GangSupervisor` treats as restart-with-rollback, not a
   crash loop.
4. **SDC replay** (`attach_replay`): on the FIRST anomaly the guard
   deterministically re-runs the step from the (preserved) pre-step
   state via a caller-provided replay closure and compares gradient
   digests bit-for-bit. A bit-differing replay is hardware SDC (typed
   ``sdc_suspected`` event + `numerics.sdc.suspected{device=...}`
   naming the device to quarantine); a bit-identical one is a
   data/optimization problem (quarantine the shard/hyperparameters,
   not a chip).

``MXTPU_NUMERICS=0`` restores the unguarded kernels everywhere
(re-read per call on the host paths; read at trace time by the
compiled ShardedTrainer step).

Env knobs (docs/fault_tolerance.md):
  MXTPU_NUMERICS             guard on/off                      (1)
  MXTPU_SCALE_INIT           initial loss scale                (65536)
  MXTPU_SCALE_WINDOW         clean steps before the scale grows (200)
  MXTPU_DIVERGE_PATIENCE     consecutive bad steps before rollback (6)
  MXTPU_DIVERGE_FACTOR       spike threshold vs rolling median (10)
  MXTPU_DIVERGE_WINDOW       rolling-median window             (32)
  MXTPU_SDC_REPLAY           replay-classify the first anomaly (1)
"""
from __future__ import annotations

import hashlib
import sys
import threading
import time

import numpy as np

from ..base import MXNetError, getenv
from ..observability import registry as _obs
from ..observability import telemetry as _tele
from ..observability.trace import trace_span

__all__ = ["enabled", "sdc_replay_enabled", "record_flag", "drain_flags",
           "pending_flags", "reset_flags", "digest", "GradScaler",
           "DivergenceWatchdog", "TrainingDiverged", "NumericsGuard",
           "EXIT_DIVERGED"]

EXIT_DIVERGED = 77

SKIPPED = _obs.counter(
    "numerics.skipped_steps",
    "Training steps where at least one update group was skipped "
    "in-graph because its packed gradients were not finite")
ANOMALIES = _obs.counter(
    "numerics.anomalies",
    "Numeric anomalies observed (label kind: nonfinite / spike)")
LOSS_SCALE = _obs.gauge(
    "numerics.loss_scale",
    "Current dynamic loss scale (GradScaler; set only when armed)")
ROLLBACKS = _obs.counter(
    "numerics.rollbacks",
    "Divergence rollbacks performed (committed checkpoints dropped + "
    "restore + TrainingDiverged)")
SDC_SUSPECTED = _obs.counter(
    "numerics.sdc.suspected",
    "Anomalies whose deterministic replay produced bit-DIFFERENT "
    "gradients — suspected hardware SDC (label device)")

# marker lines are the chaos_run no-injection-detected evidence; cap
# them so a persistently-NaN run cannot flood stderr
_MAX_MARKERS = 8


def enabled():
    """MXTPU_NUMERICS gate, re-read per call (default on)."""
    return getenv("MXTPU_NUMERICS", True)


def sdc_replay_enabled():
    return getenv("MXTPU_SDC_REPLAY", True)


def _marker(guard, text):
    """Greppable stderr marker (`MXTPU_NUMERICS ...`):
    tools/chaos_run.py --nan-at-step proves its injection was actually
    detected by finding one of these in the child output."""
    if guard._markers >= _MAX_MARKERS:
        if guard._markers == _MAX_MARKERS:
            guard._markers += 1
            print("MXTPU_NUMERICS further markers suppressed",
                  file=sys.stderr, flush=True)
        return
    guard._markers += 1
    print("MXTPU_NUMERICS %s" % text, file=sys.stderr, flush=True)


# -- skip-flag collector -------------------------------------------------
# The in-graph guard leaves its verdicts as tiny device arrays (a 0-d
# bool per fused group / exchange bucket / compiled step / step_many
# window; 1-d vectors are tolerated and count element-wise). They are
# appended here WITHOUT a host read — the skip already happened
# in-graph — and resolved in one sweep at the next step boundary,
# when the values are long since computed.

_flags_lock = threading.Lock()
_flags = []          # [(flag, keys, where)]
_FLAG_CAP = 4096     # loops that never drain (bench windows) stay bounded
_carry = {"bad": 0, "total": 0, "skipped": 0}
_unguarded = [0]     # updates applied WITHOUT the in-graph guard since
#                      the last drain (per-key leftover lanes): they
#                      veto full_skip — the step provably was not
#                      wholly skipped, so SDC replay would be unsound

# flag provenance -> what a bad verdict MEANS:
#   "update"   fused-update group skipped in-graph (state preserved)
#   "step"     a WHOLE compiled step skipped with state preserved —
#              the ShardedTrainer one-program step, or the fused
#              exchange+update program behind gluon.Trainer /
#              Module.update (parallel/fused_step.py): one lax.cond
#              over the entire step body, one verdict per step
#   "exchange" allreduce bucket carried non-finite values (attribution
#              only — whether the apply was skipped is the update
#              flag's business)
#   "window"   a step_many window went bad (detection-only: the scan
#              body is unguarded, the weights WERE poisoned — the
#              guard is NEVER applied inside a lax.scan; see
#              data_parallel._make_step_body)
_PROTECTED = ("update", "step")


def record_flag(flag, keys=None, where="update"):
    """Record one in-graph ok verdict (device bool scalar or vector).
    Never blocks on the device; resolution happens at drain time."""
    with _flags_lock:
        _flags.append((flag, keys, where))
        if len(_flags) > _FLAG_CAP:
            old = _flags.pop(0)
            bad, total = _resolve(old[0])
            _carry["bad"] += bad
            _carry["total"] += total
            if old[2] in _PROTECTED:
                _carry["skipped"] += bad
    return flag


def note_unguarded(n=1):
    """Count updates that ran OUTSIDE the in-graph guard this step
    (per-key leftover lanes in FusedUpdater): they veto `full_skip` so
    a partially-unguarded step can never claim SDC-replay soundness."""
    with _flags_lock:
        _unguarded[0] += int(n)


def _resolve(flag):
    """(bad_count, total_count) of one recorded flag."""
    arr = np.asarray(flag)
    if arr.ndim == 0:
        return (0 if bool(arr) else 1), 1
    return int(np.size(arr) - np.count_nonzero(arr)), int(np.size(arr))


def drain_flags():
    """Resolve and clear every pending flag. Returns a dict:

    - ``bad`` / ``total``: raw flag counts across every provenance;
    - ``skipped_steps``: steps whose state was provably PRESERVED —
      only the protected wheres ("update"/"step") count; scalar flags
      collapse to at most one skipped step per drain (several groups
      of ONE step may fail together), vector flags count one per
      False entry;
    - ``anomalies``: deduplicated incident count — protected + window
      bads, plus exchange bads only when no protected flags rode the
      drain (with the fused update guarded, an exchange verdict is a
      second observation of the SAME NaNs, not a second anomaly;
      with the per-key fallback it is the only observation);
    - ``full_skip``: every protected flag bad, nothing unguarded, no
      detection-only window verdicts — the precondition that makes a
      deterministic SDC replay sound;
    - ``bad_keys`` / ``by_where`` / ``exchange_bad`` / ``unguarded``:
      diagnosis detail."""
    with _flags_lock:
        pending, _flags[:] = list(_flags), []
        carry = dict(_carry)
        _carry.update(bad=0, total=0, skipped=0)
        unguarded, _unguarded[0] = _unguarded[0], 0
    bad, total = carry["bad"], carry["total"]
    scalar_protected_bad = 0
    vector_skipped = carry["skipped"]
    bad_keys = []
    by_where = {}
    if pending:
        # reading the flags waits for the programs that wrote them: a
        # wait on the chip, so a `fence` (docs/observability.md)
        with trace_span("fence"):
            pending = [(np.asarray(flag), keys, where)
                       for flag, keys, where in pending]
    for flag, keys, where in pending:
        b, t = _resolve(flag)
        bad += b
        total += t
        wb, wt = by_where.get(where, (0, 0))
        by_where[where] = (wb + b, wt + t)
        if np.ndim(np.asarray(flag)) == 0:
            if b:
                if where in _PROTECTED:
                    scalar_protected_bad += 1
                if keys:
                    bad_keys.extend(list(keys)[:8])
        elif where in _PROTECTED:
            vector_skipped += b
    skipped = (1 if scalar_protected_bad else 0) + vector_skipped
    prot_bad = sum(by_where.get(w, (0, 0))[0] for w in _PROTECTED)
    prot_total = sum(by_where.get(w, (0, 0))[1] for w in _PROTECTED)
    window_bad = by_where.get("window", (0, 0))[0]
    exchange_bad = by_where.get("exchange", (0, 0))[0]
    anomalies = prot_bad + window_bad + \
        (exchange_bad if prot_total == 0 else 0)
    full_skip = (prot_total > 0 and prot_bad == prot_total
                 and unguarded == 0 and window_bad == 0)
    return {"bad": bad, "total": total, "skipped_steps": skipped,
            "anomalies": anomalies, "bad_keys": bad_keys,
            "by_where": by_where, "exchange_bad": exchange_bad,
            "unguarded": unguarded, "full_skip": full_skip}


def pending_flags():
    with _flags_lock:
        return len(_flags)


def reset_flags():
    """Drop pending flags (tests)."""
    with _flags_lock:
        _flags[:] = []
        _carry.update(bad=0, total=0, skipped=0)
        _unguarded[0] = 0


def digest(arrays):
    """Order-sensitive sha256 over the raw bytes (+shape/dtype) of a
    list of arrays (NDArray / jax / numpy). Forces a host read — used
    only on the anomaly path (SDC replay), never per step."""
    h = hashlib.sha256()
    for a in arrays:
        if hasattr(a, "_data"):           # NDArray
            a = a._data
        arr = np.asarray(a)
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _device_of(arrays):
    """Best-effort device name of the first array (the SDC suspect)."""
    for a in arrays or ():
        data = getattr(a, "_data", a)
        try:
            devs = getattr(data, "devices", None)
            if callable(devs):
                for d in devs():
                    return str(d)
        except Exception:
            pass
    return "unknown"


# -- dynamic loss scaling ------------------------------------------------
class GradScaler:
    """Dynamic loss scale with the classic GradScaler schedule: halve
    on overflow, double after `growth_interval` consecutive clean
    steps, clamped to [`min_scale`, `max_scale`].

    The scaler starts *disarmed*: `update()` is a no-op and the scale
    reads 1.0 until the first `scale_loss()` call arms it — so wiring
    a scaler into every Trainer (the guard default) cannot silently
    divide unscaled gradients. fp32-only runs simply never arm it."""

    def __init__(self, init_scale=None, growth_factor=2.0,
                 backoff_factor=0.5, growth_interval=None,
                 min_scale=1.0, max_scale=2.0 ** 24):
        self._scale = float(init_scale if init_scale is not None
                            else getenv("MXTPU_SCALE_INIT", 65536.0))
        self.growth_factor = float(growth_factor)
        self.backoff_factor = float(backoff_factor)
        self.growth_interval = int(
            growth_interval if growth_interval is not None
            else getenv("MXTPU_SCALE_WINDOW", 200))
        self.min_scale = float(min_scale)
        self.max_scale = float(max_scale)
        self.armed = False
        self.good_steps = 0
        self.overflows = 0

    @property
    def scale(self):
        return self._scale if self.armed else 1.0

    def scale_loss(self, loss):
        """Scale a loss value/array for backward; arms the scaler."""
        self.armed = True
        LOSS_SCALE.set(self._scale)
        return loss * self._scale

    def unscale_factor(self):
        """What the optimizer must fold into rescale_grad (1/scale)."""
        return 1.0 / self.scale

    def update(self, overflow):
        """Advance the schedule with one step's verdict."""
        if not self.armed:
            return self.scale
        if overflow:
            self.overflows += 1
            self.good_steps = 0
            self._scale = max(self.min_scale,
                              self._scale * self.backoff_factor)
        else:
            self.good_steps += 1
            if self.good_steps >= self.growth_interval:
                self.good_steps = 0
                self._scale = min(self.max_scale,
                                  self._scale * self.growth_factor)
        LOSS_SCALE.set(self._scale)
        return self._scale


# -- divergence watchdog -------------------------------------------------
class DivergenceWatchdog:
    """Rolling spike detector over the per-step telemetry value (loss
    or grad norm). A step is *bad* when its value is non-finite, when
    it exceeds `factor`× the rolling median of recent good values
    (after `min_history` good observations), or when the in-graph
    guard skipped it. `observe` returns True once `patience`
    consecutive bad steps accumulated — the divergence verdict."""

    def __init__(self, patience=None, factor=None, window=None,
                 min_history=5):
        self.patience = int(patience if patience is not None
                            else getenv("MXTPU_DIVERGE_PATIENCE", 6))
        self.factor = float(factor if factor is not None
                            else getenv("MXTPU_DIVERGE_FACTOR", 10.0))
        maxlen = int(window if window is not None
                     else getenv("MXTPU_DIVERGE_WINDOW", 32))
        from collections import deque
        self._window = deque(maxlen=max(1, maxlen))
        self.min_history = int(min_history)
        self.bad_streak = 0
        self.first_bad_step = None

    def median(self):
        if not self._window:
            return None
        vals = sorted(self._window)
        return vals[len(vals) // 2]

    def is_spike(self, value):
        if value is None:
            return False
        v = float(value)
        if not np.isfinite(v):
            return True
        med = self.median()
        if med is None or len(self._window) < self.min_history:
            return False
        return abs(v) > self.factor * max(abs(med), 1e-12)

    def observe(self, step, value=None, anomalous=False):
        bad = bool(anomalous) or self.is_spike(value)
        if bad:
            if self.bad_streak == 0:
                self.first_bad_step = step
            self.bad_streak += 1
            if not anomalous:
                # in-graph nonfinite anomalies were already counted by
                # the guard from the skip flags; only the watchdog's
                # own spike verdicts add here
                ANOMALIES.inc(kind="spike")
        else:
            self.bad_streak = 0
            self.first_bad_step = None
            if value is not None and np.isfinite(float(value)):
                self._window.append(abs(float(value)))
        return self.bad_streak >= self.patience

    def last_good_step(self):
        """The newest checkpoint step still above suspicion: a bad
        value observed at step S was computed from weights WRITTEN at
        step S-1, so the checkpoint of S-1 is suspect and S-2 is the
        newest trusted one."""
        if self.first_bad_step is None:
            return None
        return int(self.first_bad_step) - 2


class TrainingDiverged(MXNetError):
    """Raised by the numerics guard after `MXTPU_DIVERGE_PATIENCE`
    consecutive bad steps, AFTER rolling back: suspect committed
    checkpoints are already dropped and the last trusted one restored,
    so a supervised relaunch resumes from healthy state
    (restart-with-rollback, not a crash loop). `.exit_code` (77) is
    the gang exit-code contract (resilience/supervisor.py)."""

    exit_code = EXIT_DIVERGED

    def __init__(self, msg, step=None, restored_step=None,
                 first_bad_step=None):
        super().__init__(msg)
        self.step = step
        self.restored_step = restored_step
        self.first_bad_step = first_bad_step


# -- the guard -----------------------------------------------------------
class NumericsGuard:
    """Step-boundary orchestrator over the in-graph skip flags: metric
    + telemetry accounting, loss-scale schedule, SDC replay on the
    first anomaly, and the divergence watchdog → rollback →
    `TrainingDiverged` chain. One guard per training loop
    (gluon Trainer / Module fit own theirs); not thread-safe."""

    def __init__(self, source="train", scaler=None, watchdog=None):
        self.source = source
        self.scaler = scaler
        self.watchdog = watchdog or DivergenceWatchdog()
        self._rollback = None        # (TrainerCheckpoint, state holder)
        self._replay_fn = None
        self._replay_done = False
        self._pending_note = {}
        self._markers = 0
        self._step = 0
        self.last_report = None

    # -- wiring ---------------------------------------------------------
    def attach_rollback(self, checkpoint, state):
        """Arm divergence rollback: `checkpoint` is a
        parallel.TrainerCheckpoint, `state` the trainer-shaped object
        it saves/restores (params/aux/opt_state/step_count)."""
        self._rollback = (checkpoint, state)
        return self

    def attach_replay(self, fn):
        """Arm SDC replay: `fn()` must deterministically re-run the
        step's gradient computation from the (skip-preserved) pre-step
        state and return the recomputed gradient arrays. Re-attach per
        batch when the closure captures one; only the FIRST anomaly
        ever replays."""
        self._replay_fn = fn
        return self

    def note(self, loss=None, grad_norm=None):
        """Stash this step's telemetry value for the next
        `step_boundary` (training loops that own the loss call this;
        the boundary's own arguments win when both are given)."""
        if loss is not None:
            self._pending_note["loss"] = float(loss)
        if grad_norm is not None:
            self._pending_note["grad_norm"] = float(grad_norm)

    # -- the boundary ---------------------------------------------------
    def step_boundary(self, step=None, loss=None, grad_norm=None,
                      grads=None):
        """Resolve the step's in-graph flags and run the host-side
        state machine. Raises `TrainingDiverged` after rollback when
        the watchdog trips; otherwise returns a report dict."""
        if step is None:
            step = self._step
        self._step = int(step) + 1
        if loss is None:
            loss = self._pending_note.pop("loss", None)
        if grad_norm is None:
            grad_norm = self._pending_note.pop("grad_norm", None)
        self._pending_note.clear()
        resolved = drain_flags()
        any_bad = resolved["anomalies"] > 0
        verdict = None
        if any_bad:
            if resolved["skipped_steps"]:
                SKIPPED.inc(resolved["skipped_steps"])
            ANOMALIES.inc(resolved["anomalies"], kind="nonfinite")
            _tele.emit({"ts": time.time(), "source": "resilience",
                        "event": "numerics_skip", "step": int(step),
                        "step_time": 0.0,
                        "bad_groups": resolved["bad"],
                        "anomalies": resolved["anomalies"],
                        "skipped_steps": resolved["skipped_steps"],
                        "exchange_bad": resolved["exchange_bad"],
                        "unguarded": resolved["unguarded"],
                        "bad_keys": resolved["bad_keys"][:8],
                        "guard": self.source})
            _marker(self, "anomaly step=%d anomalies=%d skipped=%d "
                    "keys=%s"
                    % (step, resolved["anomalies"],
                       resolved["skipped_steps"],
                       resolved["bad_keys"][:4]))
            if (self._replay_fn is not None and not self._replay_done
                    and sdc_replay_enabled()
                    and resolved["full_skip"]):
                verdict = self._classify(step, grads)
        calibrating = (any_bad and self.scaler is not None
                       and self.scaler.armed
                       and self.scaler.scale > self.scaler.min_scale)
        if self.scaler is not None:
            self.scaler.update(any_bad)
        value = loss if loss is not None else grad_norm
        # an armed scaler that still has backoff room turns overflow
        # skips into ordinary scale calibration (the AMP warm-up
        # shape) — they must not count toward divergence, or a
        # too-high MXTPU_SCALE_INIT would roll back committed
        # checkpoints while merely finding its scale. Once the scale
        # is floored, skips are real anomalies again.
        if self.watchdog.observe(step, value,
                                 anomalous=any_bad and not calibrating):
            self._fire_rollback(step)
        report = {"step": int(step), "bad": resolved["bad"],
                  "anomalies": resolved["anomalies"],
                  "skipped_steps": resolved["skipped_steps"],
                  "sdc": verdict}
        self.last_report = report
        return report

    # -- SDC replay ------------------------------------------------------
    def _classify(self, step, grads):
        """Deterministic replay of the anomalous step's gradients:
        bit-identical → the anomaly replays (data/optimization);
        bit-different → the original computation was corrupted in
        flight (suspected hardware SDC; the device is named so the
        operator knows whether to quarantine a chip or a shard)."""
        self._replay_done = True
        if not grads:
            return None
        try:
            original = digest(grads)
            replayed = self._replay_fn()
            if replayed is None:
                # a closure that re-ran but returned nothing gives us
                # nothing to compare — abstain rather than fabricate a
                # "deterministic" verdict from digesting the originals
                # against themselves
                _marker(self, "sdc replay returned no arrays — "
                              "verdict abstained")
                return None
            replay_digest = digest(replayed)
        except Exception as err:  # noqa: BLE001 — a broken replay
            # closure must never take down training on top of the
            # anomaly it was meant to diagnose
            _marker(self, "sdc replay failed: %s" % err)
            return None
        if replay_digest == original:
            verdict, device = "deterministic", None
            ANOMALIES.inc(kind="deterministic")
        else:
            verdict = "sdc"
            device = _device_of(grads)
            SDC_SUSPECTED.inc(device=device)
        _tele.emit({"ts": time.time(), "source": "resilience",
                    "event": "sdc_suspected" if verdict == "sdc"
                    else "anomaly_deterministic",
                    "step": int(step), "step_time": 0.0,
                    "device": device, "guard": self.source})
        _marker(self, "sdc verdict=%s step=%d device=%s"
                % (verdict, step, device))
        return verdict

    # -- rollback --------------------------------------------------------
    def _fire_rollback(self, step):
        t0 = time.perf_counter()
        last_good = self.watchdog.last_good_step()
        restored, dropped = None, []
        if self._rollback is not None:
            ckpt, state = self._rollback
            if last_good is not None:
                dropped = ckpt.drop_steps_after(last_good)
            try:
                restored = ckpt.restore_latest(state)
            except MXNetError:
                restored = None      # nothing restorable: fresh start
        ROLLBACKS.inc()
        _tele.emit({"ts": time.time(), "source": "resilience",
                    "event": "numerics_rollback", "step": int(step),
                    "step_time": time.perf_counter() - t0,
                    "restored_step": restored,
                    "dropped_steps": [int(s) for s in dropped],
                    "guard": self.source})
        _marker(self, "rollback step=%d restored_step=%s dropped=%s"
                % (step, restored, [int(s) for s in dropped]))
        # streak state resets so a post-restart guard starts clean when
        # the raise is caught and training continues in-process
        self.watchdog.bad_streak = 0
        first_bad, self.watchdog.first_bad_step = \
            self.watchdog.first_bad_step, None
        rolled_back = self._rollback is not None
        err = TrainingDiverged(
            "training diverged: %d consecutive bad steps ending at "
            "step %d; %s (docs/fault_tolerance.md)"
            % (self.watchdog.patience, step,
               ("rolled back to committed checkpoint step %s (dropped "
                "%s) — exit code %d asks the supervisor for a "
                "restart-with-rollback"
                % (restored, [int(s) for s in dropped], EXIT_DIVERGED))
               if rolled_back else
               "no rollback target attached (attach_rollback) — "
               "surfacing as a plain crash"),
            step=step, restored_step=restored, first_bad_step=first_bad)
        if not rolled_back:
            # exit 77 is the supervisor's "worker already rolled back"
            # contract; claiming it WITHOUT having dropped the suspect
            # checkpoints would relaunch into the same diverged state
            # and mislabel every loop iteration as a rollback — a
            # guard with no checkpoint attached is an ordinary crash
            err.exit_code = 1
        raise err
