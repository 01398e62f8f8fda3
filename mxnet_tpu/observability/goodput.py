"""Goodput accounting: per-program FLOP costs -> per-step MFU.

The missing half of the telemetry plane: step records say how LONG a
step took, this module says how much USEFUL work it did. Per-program
costs are captured once at program registration — `record_cost(name,
compiled)` reads ``compiled.cost_analysis()`` (cached per program name,
i.e. per PR-11 fingerprint) with an analytic fallback for paths where
no Compiled object exists (the fused update's `update_cost` estimator,
bench's model-FLOP constant) — and every dispatch bumps the process
``goodput.flops`` counter by its program's cost. `StepTimer.end_step`
reads the per-step delta and derives

    mfu = step_flops / (step_time * peak_flops)

streamed as the ``step_flops`` / ``mfu`` record fields and the
``goodput.mfu`` gauge. Peak FLOPs comes from ``MXTPU_PEAK_FLOPS`` when
the operator knows the chip, else the table of published peaks keyed by
the device's ``device_kind``; a kind that is not in the table has no
peak and its MFU is None ("not measured"), never a default. The CPU
entry is deliberately modest so CI MFU reads a small nonzero number
instead of 0.0 or noise.

Compute/comm/host decomposition needs no new measurement: the step
record already carries allreduce/fused-update/data-wait seconds;
`tools/telemetry_report.py`'s goodput section divides them by
step_time. Gated by the same ``MXTPU_MEMLEDGER`` switch as the ledger
(one observability plane, one A/B knob).
"""
from __future__ import annotations

import os
import threading

from .registry import counter, gauge

__all__ = ["enabled", "peak_flops", "record_cost", "cost",
           "note_dispatch", "note_flops", "mfu_value", "costs_snapshot"]

FLOPS = counter("goodput.flops",
                "model FLOPs dispatched (per-program cost_analysis "
                "costs, analytic where no Compiled exists)")
DISPATCHES = counter("goodput.dispatches",
                     "dispatches that charged the goodput FLOP counter")
MFU = gauge("goodput.mfu",
            "last derived per-step model FLOPs utilization "
            "(label source)")

#: peak FLOP/s by `jax.devices()[0].device_kind` when MXTPU_PEAK_FLOPS
#: is unset: one TPU v5e chip in bf16 (Google Cloud documentation,
#: "TPU v5e": 197 TFLOP/s) / a deliberately modest CPU figure (≈ a few
#: AVX cores) so CPU-CI MFU is a meaningful nonzero signal
_KIND_PEAK = {"TPU v5 lite": 1.97e14, "cpu": 5.0e10}

_lock = threading.Lock()
_costs = {}   # program name -> {"flops": f, "bytes": b, "source": s}
_peak_cache = {"key": None, "value": None}


def enabled():
    """Same gate as the HBM ledger (memory.enabled): one knob turns
    the whole memory/goodput plane off for the overhead A/B."""
    return os.environ.get("MXTPU_MEMLEDGER", "1") not in ("0", "false")


def peak_flops():
    """Peak device FLOP/s for the MFU denominator: MXTPU_PEAK_FLOPS
    wins, else the published peak of the first device's kind, else
    None (a device with no published peak here is "not measured").
    Cached per env value."""
    env = os.environ.get("MXTPU_PEAK_FLOPS")
    if _peak_cache["key"] == (env,):
        return _peak_cache["value"]
    if env:
        value = float(env)
    else:
        import jax
        value = _KIND_PEAK.get(jax.devices()[0].device_kind)
    _peak_cache["key"], _peak_cache["value"] = (env,), value
    return value


def _analysis_flops(compiled):
    """(flops, bytes_accessed) from cost_analysis(), or (None, None).
    jax returns a flat dict (older versions a one-element list)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:   # noqa: BLE001 — backend without the analysis
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None, None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    try:
        flops = float(flops) if flops is not None else None
        nbytes = float(nbytes) if nbytes is not None else None
    except (TypeError, ValueError):
        return None, None
    if flops is not None and flops < 0:
        flops = None
    return flops, nbytes


def record_cost(name, compiled=None, flops=None, nbytes=None):
    """Register the per-dispatch cost of one program. Measured
    (`compiled.cost_analysis()`) wins over an analytic `flops=`
    estimate, which wins over nothing; re-registration with a weaker
    source never downgrades a measured entry. Returns the stored cost
    dict or None."""
    if not enabled():
        return None
    name = str(name)
    source = None
    if compiled is not None:
        measured, mbytes = _analysis_flops(compiled)
        if measured is not None:
            flops, nbytes, source = measured, mbytes, "measured"
    if source is None and flops is not None:
        source = "analytic"
    if source is None:
        return None
    entry = {"flops": float(flops),
             "bytes": float(nbytes) if nbytes is not None else None,
             "source": source}
    with _lock:
        old = _costs.get(name)
        if old is not None and old["source"] == "measured" \
                and source == "analytic":
            return old
        _costs[name] = entry
        if len(_costs) > 256:    # program-churn bound
            _costs.clear()
            _costs[name] = entry
    return entry


def cost(name):
    with _lock:
        return _costs.get(str(name))


def note_dispatch(name, n=1):
    """Charge one (or n) dispatches of a registered program to the
    FLOP counter — the per-step MFU numerator. Unregistered programs
    charge nothing (the gauge stays honest rather than guessing)."""
    if not enabled():
        return 0.0
    c = cost(name)
    if c is None or not c["flops"]:
        return 0.0
    total = c["flops"] * n
    FLOPS.inc(total)
    DISPATCHES.inc(n)
    return total


def note_flops(flops, n_dispatches=1):
    """Charge raw FLOPs directly (callers that know their model cost
    analytically — bench's fwd/bwd, an engine's per-batch estimate)."""
    if not enabled() or not flops or flops <= 0:
        return 0.0
    FLOPS.inc(float(flops))
    if n_dispatches:
        DISPATCHES.inc(n_dispatches)
    return float(flops)


def mfu_value(step_flops, step_time, source=None):
    """step_flops over the step's peak-FLOP envelope (not clamped: a
    reading above 1 says the cost model or the peak is wrong, and has
    to be seen); also sets the goodput.mfu gauge. Returns None on
    degenerate input or where the device's peak is not known."""
    if not step_flops or not step_time or step_time <= 0:
        return None
    peak = peak_flops()
    if not peak or peak <= 0:
        return None
    mfu = float(step_flops) / (float(step_time) * peak)
    if source is not None:
        MFU.set(mfu, source=source)
    else:
        MFU.set(mfu)
    return mfu


def costs_snapshot():
    """{program: cost dict} for /debugz."""
    with _lock:
        return {n: dict(v) for n, v in _costs.items()}


def _reset_for_tests():
    with _lock:
        _costs.clear()
    _peak_cache["key"] = _peak_cache["value"] = None
    MFU.reset()
