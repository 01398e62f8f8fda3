"""Kernels, device trace: device ms a traced step, busiest chip, of the
operations under a graph node's scope with `transpose(` around it: the backward
pass."""
import program_trace


def read(run):
    dev = program_trace.analyse(run)["device"]
    return None if dev is None else dev["ms"]["bwd"]
