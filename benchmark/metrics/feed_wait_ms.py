"""Input, program span: mean self ms a step of `input.wait` (the
prefetcher's `__next__` blocked on its queue) on the training thread,
over the steps of the window the span ring still holds: the span-read
twin of `input_wait_ms` (`benchmark/host_account.py`)."""
import host_account


def read(run):
    return host_account.analyse(run)["feed_wait_ms"]
