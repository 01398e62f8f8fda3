"""Kernels, device trace: device ms a traced step, busiest chip, of the
operations owned by the gated short convolution (its two projections, its
gates and its taps), forward and backward (a rematerialised layer's
recomputed forward too): the graph nodes of the op kind
`_contrib_short_conv`, through `program_trace`. Nothing to read (no trace,
no program table, no such op in the traced steps) reads as None."""
import program_trace

KIND = "_contrib_short_conv"


def read(run):
    dev = program_trace.analyse(run)["device"]
    traced = run.get("traced_steps")
    if dev is None or not traced:
        return None
    secs = [s for s, _m, _f, _c, kind in dev["joined"] if kind == KIND]
    return 1e3 * sum(secs) / traced if secs else None
