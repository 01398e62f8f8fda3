"""Device counters: numbers a compiled step computes on the chip,
published as gauges with no host sync inside a step.

An op declares an aux input as a counter (`ops.registry.Op.counters`):
a small float32 vector, one element a metric, which the op overwrites
every training step. The trainer's step program returns those vectors
beside its state, not donated, and hands them to `publish()`, which only
keeps the arrays. `drain()` reads them (the one host sync, paid by who
asks: a scrape, a report, the benchmark's reader) and sets the gauges,
one label set a counter variable:

  moe.assignments.held{var}     rows that landed on held experts, last step
  moe.load.max_over_mean{var}   largest expert's count over the mean count,
                                over all the layer's experts, last step
  linear_attention.chunks{var}  chunks the delta rule scanned, last step
"""
from __future__ import annotations

import threading

import numpy as np

from .registry import gauge

__all__ = ["publish", "drain"]

GAUGES = {g.name: g for g in (
    gauge(
        "moe.assignments.held",
        "Token-expert assignments that landed on the experts held here, in "
        "the last training step (label var: the layer's counter)."),
    gauge(
        "moe.load.max_over_mean",
        "Largest expert's count of assignments over the mean count, over "
        "ALL the layer's experts, in the last training step."),
    gauge(
        "linear_attention.chunks",
        "Chunks the gated delta rule scanned in the last training step."),
)}

_lock = threading.Lock()
_latest = {}                  # var name -> ((metric, ...), device array)


def publish(names_by_var, arrays):
    """Keep the step's counter arrays ({var: array}); never blocks."""
    with _lock:
        for var, arr in arrays.items():
            _latest[var] = (names_by_var[var], arr)


def drain():
    """Read what was published last and set the gauges. Returns
    {metric: {var: value}}."""
    with _lock:
        held = dict(_latest)
    out = {}
    for var, (names, arr) in held.items():
        for name, value in zip(names, np.asarray(arr).ravel()):
            cell = GAUGES.get(name) or gauge(name)
            cell.set(float(value), var=var)
            out.setdefault(name, {})[var] = float(value)
    return out
