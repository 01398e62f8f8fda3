"""Kernels, device trace: share of the device-busy time of the traced steps,
busiest chip, whose operation has no `mx.` scope at all: copies, slices, what
XLA adds. (`mx.guard` and `mx.cast` are scoped: printed as `other`.)"""
import program_trace


def read(run):
    dev = program_trace.analyse(run)["device"]
    if dev is None:
        return None
    total = sum(dev["ms"].values())
    return 100.0 * dev["ms"]["unscoped"] / total if total > 0 else None
