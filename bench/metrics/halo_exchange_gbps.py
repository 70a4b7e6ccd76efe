"""Rate of the halo exchange (GB/s): the bytes the window's calls
exchange (``bench.mesh_work``: each chip's deep-halo box inside the
grid, outside its block, per fused block) over the summed duration of
the collective ops on all the chips.  ``None`` where the trace holds no
collective."""
from bench import mesh_work


def read(run):
    t = run.trace
    if t is None:
        return None
    collective_s = sum(t.summed_ns(d, "collective") for d in t.devices) / 1e9
    if not collective_s:
        return None
    moved = len(run.calls) * mesh_work.exchange_bytes_per_call(
        run.config, run.traffic)
    return moved / collective_s / 1e9
