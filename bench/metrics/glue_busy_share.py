"""Share of the device's busy time (union of op intervals, summed over
the chips) spent in ops that are neither Mosaic kernels nor collectives:
the pads, slices, copies and fusions around the kernels (%)."""


def read(run):
    t = run.trace
    if t is None:
        return None
    busy = sum(t.busy_ns(d) for d in t.devices)
    if not busy:
        return None
    return 100.0 * sum(t.busy_ns(d, ("glue",)) for d in t.devices) / busy
