"""Kernels, device trace: the least time one step's linear attention work could
take on the chip (the larger of its FLOPs over the bf16 peak and its least
bytes over the memory's peak, counted by `flops/<config>.py` from the shapes,
whatever implements the kernel) over `linear_attention_device_ms`."""
import kernel_owner


def read(run):
    return kernel_owner.roofline_pct(run, "linear_attention")
