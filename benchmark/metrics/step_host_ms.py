"""Front end, program span: mean ms a step of the step root's length less
the union of its `fence` and `input.wait` spans on the training thread,
over the steps of the window the span ring still holds: the host time a
step that waits on neither the chip nor the feed, the span-read twin of
`host_dispatch_ms` (`benchmark/host_account.py`)."""
import host_account


def read(run):
    return host_account.analyse(run)["step_host_ms"]
