"""Process start to the first timed call (s): backend start, the grid
made on the device, tracing, lowering, compiling (from the cache after
the first run) and the warm-up call."""


def read(run):
    return run.setup_s
