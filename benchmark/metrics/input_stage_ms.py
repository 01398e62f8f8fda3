"""Input, program span: mean duration of `input.stage` a batch over the
window: how long the staging thread (or the consumer, in a script that stages
its own batches) is busy copying one host batch to the device."""
import program_trace


def read(run):
    return program_trace.analyse(run)["stage_ms"]
