"""Grid points times stencil steps completed in the window, per second
(Gpt/s): all the window's work over the time from the first call's
dispatch to the last call's result being ready (host clock)."""


def read(run):
    first, last = run.calls[0][0], run.calls[-1][2]
    return len(run.calls) * run.point_updates_per_call / (last - first) / 1e9
