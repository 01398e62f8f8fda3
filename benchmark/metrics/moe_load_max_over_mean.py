"""Experts, program counter: the largest expert's count of assignments
over the mean count, over ALL the experts the router scores, in the last
step; the worst layer. The program computes it on the chip every step
(`moe.load.max_over_mean`, a device counter) and it is read here, once,
after the window. A program that keeps no such counter reads as None."""


def read(run):
    try:
        from mxnet_tpu.observability import device_counters
    except ImportError:
        return None
    loads = device_counters.drain().get("moe.load.max_over_mean")
    return max(loads.values()) if loads else None
