"""The chip benchmark of the stencil engine.

``BENCHMARK.json`` at the repository root names the cells; everything
one configuration, traffic mix, reference, driver or metric needs sits
in a file of its own under this directory, found by its name:

    configs/<config>.json      grid, stencil, dtype, sweeps, boundary
    traffic/<mix>.json         driver name and its parameters
    limits/<cell>.json         the limit of each number compared
    references/<name>.py       plain jax.numpy step of one stencil
    drivers/<driver>.py        one kind of load (``timestep``)
    metrics/<metric>.py        one reader per metric
    peaks.json                 published peaks keyed by ``device_kind``

``run.py`` is the command; ``harness.py`` ties the pieces together and
``trace.py`` reduces the profiler trace.  This package imports nothing
of the program at import time.
"""
