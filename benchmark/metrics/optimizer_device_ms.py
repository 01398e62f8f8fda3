"""Kernels, device trace: device ms a traced step, busiest chip, of the
operations under `mx.optimizer`: the update, with the numerics guard's selects
that XLA fuses into it."""
import program_trace


def read(run):
    dev = program_trace.analyse(run)["device"]
    return None if dev is None else dev["ms"]["optimizer"]
