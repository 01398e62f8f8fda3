"""Train step, program span: ms a step lost to stalls, over the steps of
the window the span ring still holds: the sum, over the step roots longer
than 1.2 times the median root, of each one's excess over the median,
divided by the steps held (`benchmark/host_account.py`, which prints each
stalled step and what covered it)."""
import host_account


def read(run):
    return host_account.analyse(run)["stall_ms"]
