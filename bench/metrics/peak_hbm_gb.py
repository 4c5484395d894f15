"""Peak device memory in use after the window, on the fullest of the
cell's chips (the runtime's allocator, read on the host), in GB."""


def read(run):
    return run["memory_peak_bytes"] / 1e9
