"""Train step, program span: mean self time a step of `step.launch` (the call
of the compiled step program), over the steps of the window that the span ring
still holds."""
import program_trace


def read(run):
    host = program_trace.analyse(run)["host"]
    return None if host is None else host.get("step.launch")
