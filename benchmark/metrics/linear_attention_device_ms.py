"""Kernels, device trace: device ms a traced step, busiest chip, of the
operations owned by the gated delta rule, the causal convolution and the gated norm,
forward and backward (a rematerialised layer's recomputed forward too): the
graph nodes of those op kinds (`kernel_owner.KINDS`), through `program_trace`."""
import kernel_owner


def read(run):
    return kernel_owner.device_ms(run, "linear_attention")
