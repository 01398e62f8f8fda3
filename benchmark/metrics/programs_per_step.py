"""Front end, device trace: runs of compiled programs on chip 0 inside the
traced window, over the steps of that window."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices:
        return None
    n = len(tr.devices[0].programs(run["trace_window"]))
    return n / run["traced_steps"] if n else None
