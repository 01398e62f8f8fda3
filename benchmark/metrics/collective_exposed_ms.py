"""Collectives, device trace: per step, the time inside collective
operations during which no other operation runs on that chip; the worst
chip. Nothing to read on one chip."""


def read(run):
    tr = run["trace"]
    if tr is None or len(tr.devices) < 2:
        return None
    worst = max(d.exposed_collective_seconds(run["trace_window"])
                for d in tr.devices)
    return 1e3 * worst / run["traced_steps"]
