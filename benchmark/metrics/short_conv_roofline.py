"""Kernels, device trace: the least time one step's short-convolution
work could take on the chip (the larger of its FLOPs over the bf16 peak
and its least bytes over the memory's peak, `kernel_counts(...)
["short_conv"]` of `flops/<config>.py`, from the shapes, whatever
implements the op) over `short_conv_device_ms`. None where that reads
None or the configuration counts no such kernel."""
import harness


def read(run):
    ms = harness.load_file("metrics", "short_conv_device_ms").read(run)
    if not ms:
        return None
    flops = harness.load_file("flops", run["config"]["flops"])
    if not hasattr(flops, "kernel_counts"):
        return None
    counts = flops.kernel_counts(run["config"], run["batch"])
    if "short_conv" not in counts:
        return None
    ops, least_bytes = counts["short_conv"]
    peak = run["peak"]
    least = max(ops / (peak["bf16_flops_per_s"] * run["chips"]),
                least_bytes / (peak["hbm_bytes_per_s"] * run["chips"]))
    return 100.0 * least / (ms / 1e3)
