"""End to end: every sample of every step that completed in the window,
over the whole time of the window (first step's start to the last step's
fetched loss)."""


def read(run):
    steps = run["steps"]
    return len(steps) * run["batch"] / (steps[-1][3] - run["t_open"])
