"""Kernels: the step program's share of its roofline, bounded by compute.
The model FLOPs of one step over the chips' bf16 peak is the least time a
step could take; over the device time of one step (the union of
device-op intervals inside the window on the busiest chip, over the steps
of the window). Idle time is out; what remains is how far the XLA kernels
of the step are from the compute roof."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices:
        return None
    busy = max(d.busy_seconds(run["trace_window"]) for d in tr.devices)
    if busy <= 0:
        return None
    least = run["flops_per_step"] / (run["peak"]["bf16_flops_per_s"]
                                     * run["chips"])
    return 100.0 * least * run["traced_steps"] / busy
