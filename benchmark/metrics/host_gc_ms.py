"""Front end, program span: ms a step in garbage collection, over the steps
of the window the span ring still holds: the `gc` spans (generations 1
and 2) of any thread that start among those steps, plus the step roots'
`gc0_ms` (generation 0). None from a program whose roots carry no `gc0`
(`benchmark/host_account.py`)."""
import host_account


def read(run):
    return host_account.analyse(run)["host_gc_ms"]
