"""Device, device trace: share of the idlest chip's idle time in the traced
window that a span of the program's other than the iteration's root covers,
the program's spans laid on the trace's clock by the last `fence`."""
import program_trace


def read(run):
    idle = program_trace.analyse(run)["idle"]
    if idle is None or idle["idle_s"] <= 0:
        return None
    return 100.0 * idle["owned_s"] / idle["idle_s"]
