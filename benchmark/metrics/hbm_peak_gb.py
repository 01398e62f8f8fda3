"""Device, program counter: `memory_stats()` after the window on the
fullest chip: the peak of live buffers plus what the runtime set aside
for the loaded programs' temporaries."""


def read(run):
    return run["memory_peak_bytes"] / 1e9
