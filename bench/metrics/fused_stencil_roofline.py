"""Share of the HBM roofline the fused stencil kernels reach (%): the
algorithmic bytes of the window's calls (``bench.work``: one read and
one write of the grid per fused block) over the published HBM
bandwidth times the summed duration of the Mosaic kernel events in the
trace.  The kernels are found by op kind, not by name.  Bandwidth bound
only: no VPU float32 peak is published for the chip."""


def read(run):
    t = run.trace
    bandwidth = run.peaks.get("hbm_bytes_per_s")
    if t is None or not bandwidth:
        return None
    kernel_s = sum(t.summed_ns(d, "kernel") for d in t.devices) / 1e9
    if not kernel_s:
        return None
    moved = len(run.calls) * run.bytes_per_call
    return 100.0 * moved / (bandwidth * kernel_s)
