"""Device, device trace: 1 - (union of device-op intervals over the traced
window), on the chip that idles most."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices:
        return None
    lo, hi = run["trace_window"]
    busy = min(d.busy_seconds((lo, hi)) for d in tr.devices)
    return 100.0 * (1.0 - busy / (hi - lo))
