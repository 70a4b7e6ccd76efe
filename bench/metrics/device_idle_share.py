"""1 - (union of the device's op intervals / traced window), on the
most idle chip (%)."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * max(1.0 - t.busy_ns(d) / t.window_ns for d in t.devices)
