"""End to end: the 95th percentile, over ALL steps of the window, of the
host-clock time from one step's fetched loss to the next (the first step's
from the opening of the window)."""
import math


def read(run):
    ends = [run["t_open"]] + [s[3] for s in run["steps"]]
    gaps = sorted(b - a for a, b in zip(ends, ends[1:]))
    return 1e3 * gaps[max(0, math.ceil(0.95 * len(gaps)) - 1)]
