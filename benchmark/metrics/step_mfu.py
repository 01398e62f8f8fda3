"""Train step: the whole step's share of the chips' bf16 peak. Model FLOPs
of the window's steps (forward and backward from the layers' shapes, 2 a
multiply-add, nothing recomputed: `flops/<config>.py`) over the window's
time, first start to last fetched loss, times chips times the peak."""


def read(run):
    steps = run["steps"]
    seconds = steps[-1][3] - run["t_open"]
    peak = run["peak"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * run["flops_per_step"] * len(steps) / (seconds * peak)
