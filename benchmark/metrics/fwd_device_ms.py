"""Kernels, device trace: device ms a traced step, busiest chip, of the
operations under a graph node's scope (`mx.<op>.<node>`) with no `transpose(`
around it: the forward pass (in a Gluon step the backward program's recomputed
forward too)."""
import program_trace


def read(run):
    dev = program_trace.analyse(run)["device"]
    return None if dev is None else dev["ms"]["fwd"]
