"""Train step, program span: mean self time a step of `step.prepare` (from the
entry of the trainer's `step()` to just before the compiled step program is
called), over the steps of the window that the span ring still holds."""
import program_trace


def read(run):
    host = program_trace.analyse(run)["host"]
    return None if host is None else host.get("step.prepare")
