"""Share of the traced window in which a collective runs on a chip and
no kernel or glue op does: halo exchange the chip waits for, on the
chip where it is largest (%)."""
from bench import trace as _trace


def read(run):
    t = run.trace
    if t is None:
        return None
    exposed = [_trace.length(_trace.subtract(
        t.intervals(d, (_trace.COLLECTIVE,)),
        t.intervals(d, (_trace.KERNEL, _trace.GLUE)))) for d in t.devices]
    return 100.0 * max(exposed) / t.window_ns
