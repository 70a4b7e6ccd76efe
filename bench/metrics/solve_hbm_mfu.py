"""The whole solve's share of the chips' peak (%), with ``mfu`` in its
name for that role: the algorithmic bytes of the window's calls over
the published HBM bandwidth times chips times the window (host clock,
first dispatch to last result).  HBM bandwidth is the peak that bounds
a stencil's roofline, not FLOP/s.  It bounds the kernels' roofline
share from below whichever ops do the work."""


def read(run):
    bandwidth = run.peaks.get("hbm_bytes_per_s")
    if not bandwidth:
        return None
    span = run.calls[-1][2] - run.calls[0][0]
    moved = len(run.calls) * run.bytes_per_call
    return 100.0 * moved / (bandwidth * run.n_devices * span)
