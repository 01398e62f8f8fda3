"""End to end: process start to the opening of the window: imports, the
model and its weights, compiling or loading the programs, the first steps
and the warm-up."""


def read(run):
    return run["setup_s"]
