"""Front end, program span: mean time a step inside `frontend.forward`
(`CachedOp.__call__`) and `frontend.backward` (`autograd.backward`), children
included, over the steps of the window that the span ring still holds."""
import program_trace


def read(run):
    return program_trace.analyse(run).get("frontend_ms")
